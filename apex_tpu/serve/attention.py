"""Attention over the paged KV cache: the decode step's (one query a
slot) and the batched prefill's (a chunk a slot, :func:`chunk_attention`,
at the end of this file). Every function here sees one layout: a pool
``[pages, page_size, heads, head_dim]``, a ``page_table`` and
``positions``.

**Decode.** One query token per slot against that slot's cached
keys/values: a loop over the slot's virtual key axis (the cache's
``max_pages_per_slot * page_size`` rows) in chunks of ``block_k``, with
an online softmax's running max, denominator and weighted sum as its
carry (:func:`_fold_cached_chunks`, which the batched prefill's cached
head runs too).

*What is static*: every shape, ``block_k``, the carry; so the op compiles
once. *What is data*: the trip count, ``max over the slots the step
writes of positions // block_k + 1`` (:func:`attended_chunks`, worked
out once a forward and handed to every layer). A step pays for the
chunks its longest active slot can reach, never for ``max_len``;
reachability inside those chunks is a mask (``key_pos <= position``).
A slot's result depends only on that slot's bytes (reductions run within
a slot; a chunk wholly past a slot's position leaves its carry as it was
to the bit), which is what makes mid-stream eviction, and a long
neighbour's extra trips, bit-invisible to it; and the result does not
depend on the trip count once it covers the slot.

The chunk geometry is what :mod:`apex_tpu.tune` tunes (kernel name
``decode_attention``): on TPU a trip streams one ``[block_k, head_dim]``
K/V tile a slot and head through VMEM, so the block size is a real
tile-geometry knob, and it is the granularity of the trip count too,
with :func:`~apex_tpu.ops.pallas.tiling.decode_attention_block` as the
committed heuristic. The decode step and the speculative verify scan's
body call this function with the same geometry, so those two stay
bit-identical. The batched prefill does not come through here: its
chunk's own keys never touch the cache's key axis, so it matches the
decode step to float32 rounding and not to the bit (docs/serving.md).

**A chunk lives inside one page** (``block_k`` divides ``page_size``),
so its fetch is one page gather from the stacked pool plus an in-page
slice; scores, masking, the max and the sum order do not depend on where
the pages lie. Two engines with different page sizes are therefore
bit-exact in fp32 on identical traces **at the same block_k** (tier-1
asserts: several pages a slot against one page a slot), and so are the
ranks of a tensor-parallel engine (positions are replicated: every rank
runs the same trips). The *default* chunk follows the page (the
heuristic/tuner unit is ``page_size``), and a different ``block_k``
reorders the partial sums by design (±1 ulp); pin ``block_k`` to compare
page sizes bitwise. *Not promised*: the sum order of a two-pass softmax
(one max over the whole axis, then one sum). The running sums are
rescaled by ``exp(m - m_new)`` once a chunk: float32 rounding against
that form, not its bits.

All math fp32 (max-subtracted softmax; the row's own token is always
reachable, so the denominator is never empty); IO dtype preserved.
"""

from __future__ import annotations

from typing import Optional, Tuple

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


def attended_chunks(positions, mask, block_k: int, key_chunks: int,
                    xp=jnp):
    """The decode attention's trip count for one step: the chunks
    ``0 .. max(positions under mask) // block_k`` hold every key a slot
    the step writes can reach, and ``key_chunks`` (``max_len //
    block_k``) is all there are. A slot the mask leaves out does not
    lengthen the loop, whatever its position says (its output is
    discarded); with no slot at all the loop runs its one first chunk.
    ``xp=numpy`` is the engine's host mirror of what the program runs
    (``apex.decode_step``'s ``attended_chunks``): one spelling for both."""
    longest = xp.max(xp.where(mask, positions, 0))
    return xp.minimum(longest // block_k + 1, key_chunks)


def _fold_cached_chunks(q32: jax.Array, cache, layer, bk: int,
                        limit: jax.Array, trips: jax.Array,
                        carry: Tuple[jax.Array, jax.Array, jax.Array],
                        scale: jnp.float32, precision=None):
    """Fold the cached key chunks ``0 .. trips - 1`` of every slot into
    an online softmax's running ``(max, denominator, weighted sum)``:
    THE loop over cached keys, which the decode step (one query a slot)
    and the batched prefill's cached head (a chunk of queries a slot)
    both run.

    ``q32`` ``[b, t, heads, head_dim]`` float32; ``limit`` ``[b]``
    int32: slot ``b``'s queries reach the cached positions ``<
    limit[b]``; ``carry`` ``(m [b, h, t], den [b, h, t], num [b, h, t,
    d])`` float32; ``trips`` an int32 scalar, DATA; ``layer`` the pool's
    plane, a python int or, where the model loops over its layers, a
    traced int32 scalar (DATA too: one more coordinate of the fetch's
    gather). A trip fetches
    ``block_k`` rows a slot from layer ``layer`` of the STACKED pool in
    one indexing op (``buf[layer, pages]``: slicing the layer out first
    would be loop-invariant, hoisted, and paid by every call), scale
    planes the same way under ``kv_quant``, and widens them to float32.
    A chunk that lies wholly past ``limit[b]`` leaves slot ``b``'s carry
    as it was to the bit (``m_new == m``, ``keep == 1``, ``e == 0``), so
    the result does not depend on ``trips`` once it covers the slot, nor
    on what any other slot holds."""
    ps = cache.page_size
    scales = cache.k_scale is not None

    def fetch(buf, r0):
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
        reach = (kpos[None, :] < limit[:, None])[:, None, None, :]
        sc = jnp.where(reach, jnp.einsum(
            "bqhd,bkhd->bhqk", q32, ks.astype(_f32),
            precision=precision) * scale, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1))
        keep = jnp.exp(m - m_new)
        e = jnp.where(reach, jnp.exp(sc - m_new[..., None]), 0.0)
        d_den = jnp.sum(e, axis=-1)
        d_num = jnp.einsum("bhqk,bkhd->bhqd", e, vs.astype(_f32),
                           precision=precision)
        return (m_new, den * keep + d_den, num * keep[..., None] + d_num)

    return jax.lax.fori_loop(0, trips, body, carry)


def paged_attention(q: jax.Array, cache, layer, positions: jax.Array,
                    trips: Optional[jax.Array] = None, *,
                    block_k: Optional[int] = None) -> jax.Array:
    """Single-token attention through the page table: slot ``b``'s one
    query over that slot's cached positions ``0 .. positions[b]``.

    ``q``: ``[num_slots, heads, head_dim]`` (heads as the pool's head
    axis has them: :func:`~apex_tpu.serve.kv_cache.pad_heads`);
    ``cache``: a :class:`~apex_tpu.serve.kv_cache.PagedKVCache`, read at
    plane ``layer`` (static or traced); ``positions``: ``[num_slots]``
    int32 over each slot's VIRTUAL key axis (page-table row laid flat).
    Chunk ``i`` of that axis lives inside page ``page_table[:, (i * block_k) //
    page_size]`` (``block_k`` divides ``page_size``), so a trip's fetch
    is one page gather plus an in-page slice and its working set one
    ``[block_k, head_dim]`` tile a slot and head (what the
    ``decode_attention`` autotuner times).

    ``trips`` (int32 scalar, data): how many chunks the loop visits,
    worked out once a forward by :func:`attended_chunks` from the slots
    the step writes; ``None`` counts every slot. Unmapped table entries
    point at the null page; its rows sit past every live position, behind
    the reachability mask. Under ``kv_quant`` each fetched tile is
    dequantized to float32 as it is read, through the same page gather
    as the payload, so the scales ride the page table. Returns
    ``[num_slots, heads, head_dim]`` in ``q.dtype``."""
    b, h, d = q.shape
    L = cache.max_len
    bk = resolve_block_k(L, h, d, q.dtype, block_k,
                         page_size=cache.page_size)
    pos = positions.astype(jnp.int32)
    if trips is None:
        trips = attended_chunks(pos, True, bk, L // bk)
    _, den, num = _fold_cached_chunks(
        q.astype(_f32)[:, None], cache, layer, bk, pos + 1, trips,
        (jnp.full((b, h, 1), NEG_INF), jnp.zeros((b, h, 1), _f32),
         jnp.zeros((b, h, 1, d), _f32)), jnp.float32(1.0 / (d ** 0.5)))
    return (num / den[..., None])[:, :, 0].astype(q.dtype)


def chunk_attention(q: jax.Array, k: jax.Array, v: jax.Array, cache,
                    layer, start: jax.Array, *,
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
        plane ``layer`` of ``cache`` (static or traced) through the page
        table and folded
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
    bk = resolve_block_k(cache.max_len, h, d, q.dtype, block_k,
                         page_size=cache.page_size)
    s = jnp.float32(1.0 / (d ** 0.5))
    hi = jax.lax.Precision.HIGHEST
    q32 = q.astype(_f32)

    idx = jnp.arange(t, dtype=jnp.int32)
    causal = (idx[None, :] <= idx[:, None])[None, None]    # [1, 1, q, k]
    sc = jnp.where(causal, jnp.einsum(
        "bqhd,bkhd->bhqk", q32, k.astype(_f32), precision=hi) * s, NEG_INF)
    m = sc.max(axis=-1)                                    # [b, h, t]
    e = jnp.where(causal, jnp.exp(sc - m[..., None]), 0.0)
    den = jnp.sum(e, axis=-1)
    num = jnp.einsum("bhqk,bkhd->bhqd", e, v.astype(_f32), precision=hi)

    start = start.astype(jnp.int32)
    _, den, num = _fold_cached_chunks(
        q32, cache, layer, bk, start, (jnp.max(start) + bk - 1) // bk,
        (m, den, num), s, precision=hi)
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
