"""Attention over the paged KV cache: the decode step's (one query a
slot) and the batched prefill's (a chunk a slot, :func:`chunk_attention`,
at the end of this file). Every function here sees one layout: a pool
``[pages, page_size, heads, head_dim]``, a ``page_table`` and
``positions``.

**Decode.** One query token per slot against that slot's cached
keys/values. The key axis is static (the cache's ``max_pages_per_slot *
page_size`` virtual axis); reachability is a mask
(``key_pos <= position``), never a shape — so the op compiles once and a
slot's result depends only on that slot's bytes (reductions run within a
slot; other slots' values cannot perturb the arithmetic, which is what
makes mid-stream eviction bit-invisible to its neighbors).

The softmax is computed in explicitly chunked form over the key axis:
``block_k`` cached rows per partial reduction, partials combined in a
static python loop. The chunk geometry is what :mod:`apex_tpu.tune` tunes
(kernel name ``decode_attention``): on TPU the XLA fusion streams one
``[block_k, head_dim]`` K/V tile at a time through VMEM, so the block size
is a real tile-geometry knob, with
:func:`~apex_tpu.ops.pallas.tiling.decode_attention_block` as the
committed heuristic. The decode step and the speculative verify scan's
body call this function with the same geometry, so those two stay
bit-identical. The batched prefill does not come through here: its
chunk's own keys never touch the cache's key axis, so it matches the
decode step to float32 rounding and not to the bit (docs/serving.md).

**A chunk lives inside one page** (``block_k`` divides ``page_size``),
so its fetch is one page gather plus a static in-page slice; scores,
masking, the max combine and the sum order do not depend on where the
pages lie. Two engines with different page sizes are therefore bit-exact
in fp32 on identical traces **at the same block_k** (tier-1 asserts:
several pages a slot against one page a slot). The *default* chunk
follows the page (the heuristic/tuner unit is ``page_size``), and a
different ``block_k`` reorders the partial sums by design (±1 ulp); pin
``block_k`` to compare page sizes bitwise.

All math fp32 (max-subtracted softmax; the row's own token is always
reachable, so the denominator is never empty); IO dtype preserved.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.pallas.tiling import decode_attention_block
from apex_tpu.serve.kv_cache import pad_last
from apex_tpu.tune.api import tuned_params

_f32 = jnp.float32
NEG_INF = jnp.float32(-1e30)


def resolve_block_k(max_len: int, heads: int, head_dim: int, dtype,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    page_size: Optional[int] = None,
                    tp_shards: int = 1) -> int:
    """The decode KV-chunk size: explicit value (validated), else the
    autotuned winner for this (max_len, page_size, heads, head_dim,
    tp_shards, dtype, chip), else the committed heuristic.

    The chunk must divide ``page_size`` (``None``: one page a slot,
    ``max_len``) so every chunk's rows live inside one page — the fetch
    is then a single page gather plus a static slice, and the geometry
    the autotuner times is the true streamed working set.

    ``tp_shards`` is the tensor-parallel mesh size the attention runs
    under (1 = single chip): a sharded engine passes its PER-SHARD head
    count as ``heads``, and the shard count is its own exact key axis —
    the per-shard working set that the tuner times is a different
    kernel instance than an unsharded engine with the same local head
    count (collective pressure and VMEM headroom differ), so winners
    never leak across mesh shapes.
    """
    ps = int(max_len if page_size is None else page_size)
    if ps <= 0 or max_len % ps:
        raise ValueError(
            f"page_size={ps} must be positive and divide the cache "
            f"max_len={max_len}")
    if block_k is not None:
        bk = int(block_k)
        if bk <= 0 or max_len % bk:
            raise ValueError(
                f"block_k={bk} must be positive and divide the cache "
                f"max_len={max_len} (the chunked softmax tiles the static "
                f"key axis exactly)")
        if ps % bk:
            raise ValueError(
                f"block_k={bk} must divide page_size={ps}: each "
                f"chunked-softmax tile must live inside one KV page "
                f"(pick a block_k that divides the page, or a page_size "
                f"that is a multiple of the tuned block)")
        return bk
    # max_len is keyed EXACTLY (not pow2-bucketed): it is a static,
    # layout-defining engine constant and the winner must divide it — a
    # bucketed key would warm entries that can never validate for
    # non-pow2 cache lengths. page_size is a geometry axis of the same
    # kind: a winner tuned for one page size cannot apply to another.
    p = tuned_params(
        "decode_attention",
        (("max_len", int(max_len)), ("page_size", ps), ("heads", heads),
         ("d", head_dim), ("tp_shards", int(tp_shards))),
        {"block_k": decode_attention_block(ps)},
        dtype=dtype, interpret=interpret,
        validate=lambda pr: (pr["block_k"] > 0
                             and ps % pr["block_k"] == 0))
    return int(p["block_k"])


def _combine_chunks(q: jax.Array, positions: jax.Array, L: int, bk: int,
                    scale: jnp.float32,
                    fetch: Callable[[int], Tuple[jax.Array, jax.Array]],
                    ) -> jax.Array:
    """The shared chunked-softmax core: ``fetch(i)`` returns chunk ``i``'s
    ``(k_rows, v_rows)`` as ``[b, block_k, heads, head_dim]``, a page
    gather. Everything numeric happens HERE, whatever the page size: each
    score's reduction runs over ``d`` (not ``L``), the global row max
    equals the max over chunk maxima bit-for-bit, and only the SUM order
    depends on ``block_k`` — identically in decode and verify.
    """
    b, h, d = q.shape
    q32 = q.astype(_f32)
    pos = positions.astype(jnp.int32)[:, None, None]
    nchunk = L // bk

    def chunk_scores(i):
        ks, vs = fetch(i)                 # ONE fetch per chunk: a second
        # call would trace the K and V gathers twice (and execute them
        # twice under interpret=True) just to rely on XLA CSE
        sc = jnp.einsum("bhd,bkhd->bhk", q32, ks.astype(_f32)) * scale
        kpos = jnp.arange(i * bk, (i + 1) * bk, dtype=jnp.int32)
        reach = kpos[None, None, :] <= pos
        return jnp.where(reach, sc, NEG_INF), reach, vs

    chunks = [chunk_scores(i) for i in range(nchunk)]      # static unroll
    m = chunks[0][0].max(axis=-1, keepdims=True)
    for sc, _, _ in chunks[1:]:
        m = jnp.maximum(m, sc.max(axis=-1, keepdims=True))

    num = jnp.zeros((b, h, d), _f32)
    den = jnp.zeros((b, h), _f32)
    for sc, reach, vs in chunks:
        e = jnp.where(reach, jnp.exp(sc - m), 0.0)         # [b, h, bk]
        den = den + jnp.sum(e, axis=-1)
        num = num + jnp.einsum("bhk,bkhd->bhd", e, vs.astype(_f32))
    return (num / den[..., None]).astype(q.dtype)


def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                    page_table: jax.Array, positions: jax.Array, *,
                    scale: Optional[float] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    k_scale: Optional[jax.Array] = None,
                    v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Single-token attention through the page table.

    ``q``: ``[num_slots, heads, head_dim]``; ``k_pool``/``v_pool``:
    ``[num_pages, page_size, heads, head_dim]`` (one layer of the paged
    pool); ``page_table``: ``[num_slots, max_pages_per_slot]`` int32;
    ``positions``: ``[num_slots]`` int32 over each slot's VIRTUAL key
    axis (page-table row laid flat). Chunk ``i`` of the virtual axis
    lives inside page ``page_table[:, (i * block_k) // page_size]``
    (``block_k`` divides ``page_size``), so the fetch is one page gather
    plus a static in-page slice — the working set per partial reduction
    is one ``[block_k, head_dim]`` tile (the premise the
    ``decode_attention`` autotuner times). Unmapped table entries point
    at the null page; its rows sit past every live position, so the
    reachability mask discards them.

    ``k_scale``/``v_scale`` (``[num_pages, page_size, heads]`` fp32, one
    layer of a ``kv_quant`` pool's scale planes) dequantize each fetched
    ``[block_k]`` tile to fp32 as it is read, through the SAME page
    gather as the payload — the scores/combine arithmetic never changes,
    and the scales ride the page table, so sharing/COW/eviction need no
    quant-aware code.
    """
    P, ps, h, d = k_pool.shape
    L = int(page_table.shape[1]) * ps
    bk = resolve_block_k(L, h, d, q.dtype, block_k, interpret,
                         page_size=ps)
    s = jnp.float32(scale if scale is not None else 1.0 / (d ** 0.5))

    def fetch(i):
        start = i * bk
        pages = page_table[:, start // ps]                 # [b]
        sl = slice(start % ps, start % ps + bk)            # static in-page
        ks, vs = k_pool[pages, sl], v_pool[pages, sl]
        if k_scale is not None:
            ks = ks.astype(_f32) * k_scale[pages, sl][..., None]
            vs = vs.astype(_f32) * v_scale[pages, sl][..., None]
        return ks, vs

    return _combine_chunks(q, positions, L, bk, s, fetch)


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, cache,
                    layer: int, start: jax.Array, *,
                    block_k: int) -> jax.Array:
    """Attention of a chunk of ``T`` consecutive tokens per slot — the
    batched prefill's attention.

    ``q``/``k``/``v``: ``[num_slots, T, heads, head_dim]``, the chunk's
    own queries, keys and values (``k``/``v`` as a read of the cache
    would return them: :func:`~apex_tpu.serve.kv_cache.write_rows`);
    ``start``: ``[num_slots]`` int32, the absolute position of each
    slot's first chunk token. The query at chunk position ``t`` of slot
    ``b`` attends

    (a) causally over the chunk's own keys ``0..t`` of that slot, taken
        from ``k``/``v`` and never through the cache: one plain
        ``[T, T]`` score block per slot and head, whose cost follows
        the chunk alone; and
    (b) over the cached positions ``< start[b]`` (a prompt's head that
        the prefix index served), fetched ``block_k`` rows at a time from
        layer ``layer`` of ``cache`` through the page table and folded
        into (a)'s running max, sum and weighted sum.

    (b) is a loop whose trip count is DATA: ``ceil(max(start) /
    block_k)`` chunks, so a call with no hit runs none of it and a call
    with one pays for the head it attends over, never for ``max_len`` or
    the pool. A slot with ``start == 0`` beside one with a hit has every
    fetched key masked, which changes none of its bits.

    All math fp32, the two products at ``HIGHEST`` precision (on a TPU
    the default would round the probabilities to bf16 before the weighted
    sum); one max-subtracted softmax over (a) and (b) together. A row's
    own token is always reachable, so the denominator is never empty.
    Reductions run within a slot and a head. Returns ``[num_slots, T,
    heads, head_dim]`` in ``q.dtype``.
    """
    b, t, h, d = q.shape
    ps = cache.page_size
    bk = resolve_block_k(cache.max_len, h, d, q.dtype, block_k,
                         page_size=ps)
    s = jnp.float32(1.0 / (d ** 0.5))
    hi = jax.lax.Precision.HIGHEST
    q32 = q.astype(_f32)

    def scores(ks, reach):
        """Every query against ``ks`` ``[b, k, h, d]``, unreachable keys
        at ``NEG_INF`` (``reach`` broadcasts to ``[b, h, t, k]``)."""
        sc = jnp.einsum("bqhd,bkhd->bhqk", q32, ks.astype(_f32),
                        precision=hi) * s
        return jnp.where(reach, sc, NEG_INF)

    def weigh(sc, vs, reach, m):
        """The softmax's sum and weighted values about the max ``m``."""
        e = jnp.where(reach, jnp.exp(sc - m[..., None]), 0.0)
        return (jnp.sum(e, axis=-1),
                jnp.einsum("bhqk,bkhd->bhqd", e, vs.astype(_f32),
                           precision=hi))

    idx = jnp.arange(t, dtype=jnp.int32)
    causal = (idx[None, :] <= idx[:, None])[None, None]    # [1, 1, q, k]
    sc = scores(k, causal)
    m = sc.max(axis=-1)                                    # [b, h, t]
    den, num = weigh(sc, v, causal, m)

    start = start.astype(jnp.int32)
    scales = cache.k_scale is not None

    def fetch(buf, r0):
        """Rows ``r0 .. r0 + block_k`` of every slot's key axis out of
        the STACKED buffer in one indexing op: slicing the layer out
        first would be loop-invariant, hoisted, and paid by every call."""
        pages = jax.lax.dynamic_index_in_dim(
            cache.page_table, r0 // ps, axis=1, keepdims=False)
        return jax.lax.dynamic_slice_in_dim(
            buf[layer, pages], r0 % ps, bk, axis=1)

    def body(i, carry):
        m, den, num = carry
        r0 = i * bk
        ks, vs = fetch(cache.k, r0), fetch(cache.v, r0)
        if scales:
            ks = ks.astype(_f32) * fetch(cache.k_scale, r0)[..., None]
            vs = vs.astype(_f32) * fetch(cache.v_scale, r0)[..., None]
        kpos = r0 + jnp.arange(bk, dtype=jnp.int32)
        reach = (kpos[None, :] < start[:, None])[:, None, None, :]
        sc = scores(ks, reach)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        keep = jnp.exp(m - m_new)
        d_den, d_num = weigh(sc, vs, reach, m_new)
        return (m_new, den * keep + d_den, num * keep[..., None] + d_num)

    m, den, num = jax.lax.fori_loop(
        0, (jnp.max(start) + bk - 1) // bk, body, (m, den, num))
    o = num / den[..., None]                               # [b, h, t, d]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q.dtype)


# ------------------------------------------ latent attention (MLA models)
#
# A latent-attention model caches one row a token a layer, ``[c_kv, k_r]``:
# the normalised key-value latent (``C`` wide) and the one rotated key
# (``R`` wide) that all heads share. Keys and values are ``c_kv`` times the
# up-projection ``W_kvb``; the two functions below are the two orders in
# which that product can be taken. One cache, two paths.


def latent_decode_attention(q_lat: jax.Array, q_rope: jax.Array,
                            cache, layer: int, positions: jax.Array, *,
                            scale: float) -> jax.Array:
    """One query a slot over the slot's latent pages, in the ABSORBED
    form: the key up-projection is folded into the query (``q_lat = q_nope
    W_kb^T``, by the caller), so a score is ``(q_lat . c_kv + q_rope .
    k_r) * scale`` straight off the cached rows, and the result is the
    probability-weighted LATENT ``[num_slots, heads, C]`` (float32), which
    the caller takes through the value up-projection. No key or value of
    any head is ever expanded for a cached token.

    ``q_lat [num_slots, heads, C]``, ``q_rope [num_slots, heads, R]``;
    ``cache`` a :class:`~apex_tpu.serve.kv_cache.PagedLatentCache` whose
    layer ``layer`` holds rows ``C + R`` wide in whole lanes (the query
    is widened with zeros to meet them); slot ``b`` attends over
    positions ``0 .. positions[b]``. The slot's whole virtual key axis is
    gathered through the page table (unmapped entries read the null page,
    behind the reachability mask); softmax in float32, the two products
    in the cache's dtype with float32 accumulation."""
    b, _, c = q_lat.shape
    rows = cache.rows[layer, cache.page_table]         # [b, pages, ps, row]
    rows = rows.reshape(b, -1, rows.shape[-1])
    q = pad_last(jnp.concatenate([q_lat, q_rope], axis=-1),
                  rows.shape[-1]).astype(rows.dtype)
    sc = jnp.einsum("bhw,bkw->bhk", q, rows,
                    preferred_element_type=_f32) * jnp.float32(scale)
    kpos = jnp.arange(rows.shape[1], dtype=jnp.int32)
    reach = kpos[None, None, :] <= positions.astype(jnp.int32)[:, None, None]
    sc = jnp.where(reach, sc, NEG_INF)
    e = jnp.where(reach, jnp.exp(sc - sc.max(axis=-1, keepdims=True)), 0.0)
    o = jnp.einsum("bhk,bkc->bhc", e.astype(rows.dtype), rows[..., :c],
                   preferred_element_type=_f32)
    return o / jnp.sum(e, axis=-1)[..., None]


def latent_chunk_attention(q_nope: jax.Array, q_rope: jax.Array,
                           k_nope: jax.Array, k_rope: jax.Array,
                           v: jax.Array, w_kb: jax.Array, w_vb: jax.Array,
                           cache, layer: int, start: jax.Array, *,
                           scale: float) -> jax.Array:
    """A chunk of ``T`` consecutive tokens a slot — the batched prefill of
    a latent-attention model. The query at chunk position ``t`` of slot
    ``b`` attends

    (a) causally over the chunk's own tokens in the PLAIN form: their
        keys ``[k_nope, k_rope]`` and values ``v`` expanded from the
        chunk's latent rows by the caller (``k_nope``/``v`` ``[b, T,
        heads, N]``/``[b, T, heads, V]``, ``k_rope [b, T, R]``: one
        rotated key for all heads), as a read of the cache would give
        them; and
    (b) over the cached positions ``< start[b]`` (a prompt's head that
        the prefix index served) in the absorbed form of
        :func:`latent_decode_attention`, one page of latent rows at a
        time, folded into (a)'s running max and sum. The loop's trip
        count is data, and the whole of (b), its absorbed queries
        included (``w_kb``/``w_vb`` ``[C, heads, N]``/``[C, heads, V]``:
        the two halves of the up-projection), sits behind a ``cond``: a
        call with no hit pays for none of it.

    One max-subtracted float32 softmax over (a) and (b) together; (a)'s
    products at ``HIGHEST`` precision as in :func:`chunk_attention`, (b)'s
    as the decode step takes them.
    Returns ``[b, T, heads, V]`` in ``q_nope.dtype``."""
    b, t, h, _ = q_nope.shape
    c = w_kb.shape[0]
    hi = jax.lax.Precision.HIGHEST
    s = jnp.float32(scale)
    qn, qr = q_nope.astype(_f32), q_rope.astype(_f32)

    idx = jnp.arange(t, dtype=jnp.int32)
    causal = (idx[None, :] <= idx[:, None])[None, None]    # [1, 1, q, k]
    sc = (jnp.einsum("bqhn,bkhn->bhqk", qn, k_nope.astype(_f32),
                     precision=hi)
          + jnp.einsum("bqhr,bkr->bhqk", qr, k_rope.astype(_f32),
                       precision=hi)) * s
    sc = jnp.where(causal, sc, NEG_INF)
    m = sc.max(axis=-1)                                    # [b, h, t]
    e = jnp.where(causal, jnp.exp(sc - m[..., None]), 0.0)
    den = jnp.sum(e, axis=-1)
    num = jnp.einsum("bhqk,bkhv->bhqv", e, v.astype(_f32), precision=hi)

    start = start.astype(jnp.int32)
    ps = cache.page_size

    def over_the_head(carry):
        # the decode step's numerics: absorbed queries and probabilities
        # in the cache's dtype, float32 accumulation
        dt = cache.rows.dtype
        q = pad_last(jnp.concatenate(
            [jnp.einsum("bqhn,chn->bqhc", q_nope, w_kb,
                        preferred_element_type=_f32).astype(dt),
             q_rope.astype(dt)], axis=-1),
            cache.rows.shape[-1])                          # [b, t, h, row]

        def body(i, carry):
            m, den, num, lat = carry
            pages = jax.lax.dynamic_index_in_dim(
                cache.page_table, i, axis=1, keepdims=False)
            rows = cache.rows[layer, pages]                # [b, ps, row]
            kpos = i * ps + jnp.arange(ps, dtype=jnp.int32)
            reach = (kpos[None, :] < start[:, None])[:, None, None, :]
            sc = jnp.where(reach, jnp.einsum(
                "bqhw,bkw->bhqk", q, rows,
                preferred_element_type=_f32) * s, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            keep = jnp.exp(m - m_new)
            e = jnp.where(reach, jnp.exp(sc - m_new[..., None]), 0.0)
            return (m_new, den * keep + jnp.sum(e, axis=-1),
                    num * keep[..., None],
                    lat * keep[..., None] + jnp.einsum(
                        "bhqk,bkc->bhqc", e.astype(dt), rows[..., :c],
                        preferred_element_type=_f32))

        m, den, num = carry
        m, den, num, lat = jax.lax.fori_loop(
            0, (jnp.max(start) + ps - 1) // ps, body,
            (m, den, num, jnp.zeros((b, h, t, c), _f32)))
        return m, den, num + jnp.einsum(
            "bhqc,chv->bhqv", lat.astype(dt), w_vb,
            preferred_element_type=_f32)

    _, den, num = jax.lax.cond(jnp.max(start) > 0, over_the_head,
                               lambda carry: carry, (m, den, num))
    o = num / den[..., None]                               # [b, h, t, V]
    return jnp.transpose(o, (0, 2, 1, 3)).astype(q_nope.dtype)
