"""The static-shape KV cache: a paged block pool behind a page table.

Every array shape is fixed at engine build, and request admission,
completion and eviction only move *values*, so the jitted decode step
that takes the pytree compiles exactly once:

- :class:`PagedKVCache` — a shared block pool: ``k``/``v`` are
  ``[n_layer, num_pages, page_size, heads, head_dim]`` plus a per-slot
  page table ``[num_slots, max_pages_per_slot]`` of pool indices and
  per-slot ``lengths``. A slot's virtual key axis is its page-table row
  laid end to end; position ``p`` lives at ``(page_table[slot, p //
  page_size], p % page_size)``. Page indices are DATA (host-allocated in
  :mod:`apex_tpu.serve.paging`, threaded through the compiled call),
  never shapes — so paging multiplies resident requests per HBM byte
  without touching the one-compile invariant. Page 0 is the reserved
  null page: masked-off writes are routed there and unmapped table
  entries read its zeros (discarded by the attention reachability mask).
  How a slot's tokens lie in HBM is decided here and nowhere else: a
  per-slot reservation of ``max_len`` tokens is this pool with
  ``page_size == max_len`` (one page a slot, ``num_slots + 1`` pages),
  which is what an engine built without a ``page_size`` allocates.

- :class:`PagedLatentCache` — the paged pool of a latent-attention (MLA)
  model: ONE array ``rows`` ``[n_layer, num_pages, page_size, row]``
  (a token's normalised key-value latent and its one rotated key, shared
  by every head: no head axis, no second array) with the same
  ``page_table`` and ``lengths``, so the allocator, the prefix index and
  every length mutator below serve it unchanged.

- :class:`HybridCache` — the cache of a model whose layers are of two
  kinds: a latent pool with ONE PLANE A LATENT-ATTENTION LAYER (``rows``,
  as :class:`PagedLatentCache` has them) and, for every layer that keeps
  a recurrent state instead of rows a token, a FIXED-SIZE STATE A SLOT
  (``state`` float32 ``[state layers, num_slots, heads, key_dim,
  value_dim]`` and ``conv``, the short convolution's tail). The state has
  no page axis: it is indexed by slot, so a row view of this cache
  carries the slots' ids too (``slots``; :func:`slot_view`).

**A token's row is whole tiles** in both pools: the ``heads`` axis of
``k``/``v`` (and of their scale planes) is allocated as whole groups of
8 sublanes (:func:`padded_heads`: 25 heads lie in 32, the rest zeros:
:func:`pad_heads`; attention runs over all of them and drops the
padding's outputs), the latent row as whole 128-lane tiles
(:func:`pad_last`). That shape is the pool's device layout: see "the
resident pool" below.

All mutators are pure functions returning a new cache: that is true of
the functions and, inside the engine, false of the buffers. **The pool
is resident**: every engine program that takes a cache and returns one
is given it by donation, so its writes land in the buffers it was
handed and no call copies a pool. A donated cache is gone: whoever kept
a reference to one across an engine call holds deleted arrays. The
functions here donate nothing themselves (a test may
``jax.jit(paged_write_token)`` and keep its input). Masked writes
read-modify-write the existing token so an inactive slot's bytes are
untouched — slot isolation is structural, not best-effort.

Block-scale quantization (``EngineConfig(kv_quant=...)``) changes the
VALUES, never the structure of this contract: ``k``/``v`` hold codec
bytes (int8 / float8_e4m3fn) and two extra pytree fields
``k_scale``/``v_scale`` hold one fp32 scale per (token, head) — shaped
like the payload minus the head_dim axis, so scales ride every page
behaviour (prefix sharing, COW, eviction, export/import, tp head
sharding) through the exact same code paths as the payload. On an
unquantized cache both fields are ``None`` — an empty pytree node, so
an unquantized pytree has no leaf for them.
"""

from __future__ import annotations

import re
from typing import Any, Optional

import flax.struct
import jax
import jax.numpy as jnp

SUBLANES, LANES = 8, 128    # the tile of every TPU array layout


def padded_heads(heads: int, shards: int = 1) -> int:
    """The size of a K/V pool's head axis for ``heads`` heads split over
    ``shards`` tensor-parallel ranks: each rank's heads in whole groups
    of :data:`SUBLANES` (its real heads first)."""
    return shards * (-(-(heads // shards) // SUBLANES) * SUBLANES)


def pad_heads(tok: jax.Array, size: int) -> jax.Array:
    """``[..., heads, head_dim]`` zero-padded on the head axis to
    ``size``: a token, or a query, as the pool holds its heads. The
    padding attends over zeros to zeros; the caller drops it."""
    pad = size - tok.shape[-2]
    return jnp.pad(tok, [(0, 0)] * (tok.ndim - 2) + [(0, pad), (0, 0)])


def pad_last(x: jax.Array, size: int) -> jax.Array:
    """``x`` zero-padded on its last axis to ``size``: a token's scales
    beside its padded heads; a latent row as it lies in its pool (whole
    :data:`LANES` wide), and a query widened to meet it (the padding
    multiplies zeros, so no score moves)."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, size - x.shape[-1])])


# ``lengths`` is all the four below touch: they serve PagedKVCache and
# PagedLatentCache alike


def advance(cache, mask: jax.Array):
    """Bump ``lengths`` by one for masked slots (after a decode append)."""
    return cache.replace(
        lengths=cache.lengths + mask.astype(jnp.int32))


def reset_slots(cache, mask: jax.Array):
    """Zero masked slots' lengths — insertion prologue: the slot's stale
    bytes stay in place and are unreachable behind ``lengths``."""
    return cache.replace(
        lengths=jnp.where(mask, 0, cache.lengths).astype(jnp.int32))


def set_lengths(cache, mask: jax.Array, new_lengths: jax.Array):
    """Set masked slots' lengths (prefill epilogue: prompt lengths)."""
    return cache.replace(
        lengths=jnp.where(mask, new_lengths,
                          cache.lengths).astype(jnp.int32))


# A row view: the cache as a call over ``rows`` of its slots sees it.
# A forward reaches a slot's TOKENS only through ``lengths`` and
# ``page_table``, each indexed by the call's row (``write_rows``,
# ``write_latent``, ``attention.chunk_attention``,
# ``latent_chunk_attention``, ``_fold_cached_chunks``), so the two gathered
# at the slots' ids, beside the SAME pool arrays, are a cache of ``rows``
# slots to it: the small prefill program runs the unchanged forward over
# the slots it admits. A slot's recurrent STATE (:class:`HybridCache`) is
# indexed by the slot itself, so a view of that cache also carries the
# ids (``slots``), which :func:`read_state` and :func:`write_state` index
# by; the whole cache carries none (row ``i`` is slot ``i``).


def slot_view(cache, slots: jax.Array):
    """``cache`` with the ``lengths`` and page-table rows of ``slots``
    (``[rows]`` int32) in the slots' place. An id out of range (a padding
    row's) reads the last slot's; that row must write nothing."""
    at = jnp.clip(slots.astype(jnp.int32), 0, cache.num_slots - 1)
    view = cache.replace(lengths=cache.lengths[at],
                         page_table=cache.page_table[at])
    if isinstance(cache, HybridCache):
        # a view of a view: the ids of the rows' ids; a row out of range
        # stays out of range of the state's slot axis
        inside = (slots >= 0) & (slots < cache.num_slots)
        ids = slots.astype(jnp.int32) if cache.slots is None \
            else cache.slots[at]
        view = view.replace(
            slots=jnp.where(inside, ids, cache.state.shape[1]))
    return view


def close_view(cache, view, slots: jax.Array, mask: jax.Array,
               new_lengths: jax.Array):
    """The whole cache after a call over ``view = slot_view(cache,
    slots)``: the view's pool arrays (what the call wrote), ``cache``'s
    page table as it came, and ``cache``'s ``lengths`` with row ``i``'s
    ``new_lengths[i]`` at slot ``slots[i]`` where ``mask[i]``
    (:func:`set_lengths` through the view). The other rows are sent out
    of range and dropped, so no slot is written twice; the masked rows'
    ids must be distinct."""
    at = jnp.where(mask, slots.astype(jnp.int32), cache.num_slots)
    closed = view.replace(
        page_table=cache.page_table,
        lengths=cache.lengths.at[at].set(
            new_lengths.astype(jnp.int32), mode="drop"))
    if isinstance(cache, HybridCache):
        closed = closed.replace(slots=cache.slots)
    return closed


# host-callable eviction: ``lengths`` alone goes through a (mask-shaped)
# op, compiled once per slot count — freeing a slot between decode steps
# cannot recompile anything, and the pool's arrays never enter a program
# (a jitted pass-through of an undonated pool is a whole copy of it)
def evict_slots(cache, mask: jax.Array):
    """Free masked slots. Data is left in place; only ``lengths`` moves —
    the attention mask (``key_pos <= position``) makes the stale rows
    unreachable, and the next insert overwrites them. A slot's page
    *indices* are host bookkeeping, freed by the allocator. The
    returned cache holds the SAME pool arrays as ``cache``."""
    return reset_slots(cache, mask)


# ------------------------------------------------------- paged block pool


@flax.struct.dataclass
class PagedKVCache:
    """Pytree of the paged serving cache; see module docstring."""

    # k, v: [n_layer, num_pages, page_size, padded heads, head_dim]
    k: jax.Array
    v: jax.Array
    lengths: jax.Array     # [num_slots] int32 — tokens resident per slot
    page_table: jax.Array  # [num_slots, max_pages_per_slot] int32
    # per-(token, head) fp32 codec scales when kv_quant is armed:
    # [n_layer, num_pages, page_size, heads] — scales live IN the page
    # structure, so sharing/COW/eviction/migration move them with the
    # page for free; None when unquantized
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def n_layer(self) -> int:
        return self.k.shape[0]

    @property
    def num_pages(self) -> int:
        return self.k.shape[1]

    @property
    def page_size(self) -> int:
        return self.k.shape[2]

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def max_pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def max_len(self) -> int:
        """Per-slot virtual context bound (the page-table row laid flat)."""
        return self.page_size * self.max_pages_per_slot


def init_paged_cache(n_layer: int, num_slots: int, max_len: int,
                     page_size: int, num_pages: int, heads: int,
                     head_dim: int, dtype: Any = jnp.float32,
                     kv_quant: Optional[str] = None,
                     shards: int = 1) -> PagedKVCache:
    """Allocate an empty page pool. ``max_len`` (must be a multiple of
    ``page_size``) bounds every request's total context; ``num_pages``
    bounds the *pool* — sizing it below ``num_slots * max_len /
    page_size`` (+1 for the null page) is the point: mixed-length
    traffic shares the pool instead of each slot reserving ``max_len``.
    """
    if max_len % page_size:
        raise ValueError(
            f"page_size={page_size} must divide max_len={max_len} (a "
            f"slot's virtual key axis is whole pages laid end to end)")
    max_pages = max_len // page_size
    if num_pages < max_pages + 1:
        raise ValueError(
            f"num_pages={num_pages} cannot hold even one full-context "
            f"request: need max_len/page_size + 1 null page = "
            f"{max_pages + 1}")
    shape = (n_layer, num_pages, page_size, padded_heads(heads, shards),
             head_dim)
    lengths = jnp.zeros((num_slots,), jnp.int32)
    table = jnp.zeros((num_slots, max_pages), jnp.int32)
    if kv_quant is None:
        return PagedKVCache(k=jnp.zeros(shape, dtype),
                            v=jnp.zeros(shape, dtype),
                            lengths=lengths, page_table=table)
    from apex_tpu.quant.kv import kv_storage_dtype

    sdtype = kv_storage_dtype(kv_quant)
    return PagedKVCache(
        k=jnp.zeros(shape, sdtype), v=jnp.zeros(shape, sdtype),
        lengths=lengths, page_table=table,
        k_scale=jnp.zeros(shape[:-1], jnp.float32),
        v_scale=jnp.zeros(shape[:-1], jnp.float32))


@jax.named_scope("kv_write")
def paged_write_token(cache: PagedKVCache, layer, k_tok: jax.Array,
                      v_tok: jax.Array, positions: jax.Array,
                      mask: jax.Array,
                      codec: Optional[str] = None) -> PagedKVCache:
    """Append one token's K/V per slot at virtual position
    ``positions[slot]`` — physical page ``page_table[slot, pos //
    page_size]``, row ``pos % page_size`` — where ``mask[slot]``: the
    decode step's write.

    ``k_tok``/``v_tok``: ``[num_slots, heads, head_dim]``; ``positions``:
    ``[num_slots]`` int32; ``mask``: ``[num_slots]`` bool. ``layer`` is
    the pool's plane: a python int where the model unrolls its layers (the
    index is then static), a traced int32 scalar where it loops over them
    (``models/ouro.py``: the plane is then DATA, one more coordinate of the
    same gather and scatter). Shapes never change either way, so this is
    recompile-free under jit.

    Masked-off slots are routed to the null page (page 0) and write back
    its current row bit-for-bit: a stale page-table entry on an inactive
    slot can therefore never collide with a live slot's append inside
    the same scatter. Live slots' target pages are uniquely owned by
    construction (the host allocator never maps one writable page into
    two tables), so the scatter indices of real writes never alias.

    With ``codec`` the token is block-scale encoded (one scale per head)
    and codes + scales land through the same indices — the scale write
    obeys the identical slot-isolation contract as the payload write.
    """
    ps = cache.page_size
    pos = jnp.clip(positions.astype(jnp.int32), 0, cache.max_len - 1)
    rows = jnp.arange(cache.num_slots)
    pages = cache.page_table[rows, pos // ps]          # [B]
    pages = jnp.where(mask, pages, 0)
    offs = jnp.where(mask, pos % ps, 0)
    out = {}
    for name, tok in (("k", k_tok), ("v", v_tok)):
        scales = None
        if codec is not None:
            from apex_tpu.quant.kv import encode_kv

            tok, scales = encode_kv(codec, tok.astype(jnp.float32))
        buf = getattr(cache, name)                     # [L, P, S, H, d]
        cur = buf[layer, pages, offs]                  # [B, H, d]
        new = jnp.where(mask[:, None, None],
                        pad_heads(tok.astype(buf.dtype), buf.shape[-2]),
                        cur)
        out[name] = buf.at[layer, pages, offs].set(new)
        if scales is not None:
            sname = name + "_scale"
            sbuf = getattr(cache, sname)               # [L, P, S, H]
            scur = sbuf[layer, pages, offs]            # [B, H]
            snew = jnp.where(mask[:, None], pad_last(
                scales.astype(sbuf.dtype), sbuf.shape[-1]), scur)
            out[sname] = sbuf.at[layer, pages, offs].set(snew)
    return cache.replace(**out)


# ------------------------------------------------ a chunk of rows (prefill)


@jax.named_scope("kv_write")
def write_rows(cache, layer, k_rows: jax.Array, v_rows: jax.Array,
               positions: jax.Array, mask: jax.Array,
               codec: Optional[str] = None):
    """Append a chunk of tokens' K/V per slot in one masked scatter —
    the batched prefill's write.

    ``k_rows``/``v_rows``: ``[num_slots, T, heads, head_dim]``;
    ``positions``/``mask``: ``[num_slots, T]`` (int32 absolute position,
    bool; ``layer`` as :func:`paged_write_token` takes it: static or
    traced). Row ``(b, t)`` lands at ``positions[b, t]`` of slot ``b``,
    through the page table, where ``mask[b, t]``. A
    masked-off row (a slot this call does not admit, or a prompt's
    padding) is given an out-of-range page and DROPPED by the scatter:
    no byte of a decoding neighbour, of the null page, or of a row past
    the prompt is touched. Real rows never alias (one slot's positions
    are distinct, and the allocator maps no writable page twice).

    With ``codec`` each row is encoded exactly as
    :func:`paged_write_token` encodes it (one scale per token and head)
    and the scales take the same scatter. Returns ``(cache, k_read,
    v_read)``: the cache, and the rows as a later read of the cache
    returns them (fp32 decoded codes, or the rows in the cache's dtype;
    the head axis padded as the cache's is) — what the chunk's own
    attention attends over, so that prefill sees the values decode will
    see.
    """
    pos = positions.astype(jnp.int32)
    live = mask & (pos >= 0) & (pos < cache.max_len)
    slot = jnp.arange(pos.shape[0], dtype=jnp.int32)[:, None]
    ps = cache.page_size
    pages = cache.page_table[
        slot, jnp.clip(pos // ps, 0, cache.max_pages_per_slot - 1)]
    index = (layer, jnp.where(live, pages, cache.num_pages), pos % ps)
    out, read = {}, {}
    for name, rows in (("k", k_rows), ("v", v_rows)):
        buf = getattr(cache, name)
        if codec is None:
            rows = read[name] = pad_heads(rows.astype(buf.dtype),
                                          buf.shape[-2])
        else:
            from apex_tpu.quant.kv import decode_kv, encode_kv

            rows, scales = encode_kv(codec, rows.astype(jnp.float32))
            sbuf = getattr(cache, name + "_scale")
            rows = pad_heads(rows.astype(buf.dtype), buf.shape[-2])
            scales = pad_last(scales.astype(sbuf.dtype), sbuf.shape[-1])
            read[name] = decode_kv(rows, scales)
            out[name + "_scale"] = sbuf.at[index].set(scales, mode="drop")
        out[name] = buf.at[index].set(rows, mode="drop")
    return cache.replace(**out), read["k"], read["v"]


# ------------------------------------------------- tensor-parallel layout
#
# Tensor parallelism shards the pool's head axis: axis 3 of
# `[n_layer, num_pages, page_size, heads, head_dim]` (a rank's stretch of
# it holds its own heads first, then its padding). Everything
# host-indexed — `lengths`, the page table, page/slot indices — stays
# replicated data, which is why the allocator, prefix index, scheduler,
# and journal are mesh-agnostic: a page index addresses every rank's
# shard of that page simultaneously.


def tp_cache_specs(cache: PagedKVCache, axis: str = "tp") -> PagedKVCache:
    """``PartitionSpec`` pytree for a TP-sharded cache: ``k``/``v`` on
    the head axis, ``lengths`` and the page table replicated. Shaped
    like the cache pytree itself, so it serves as ``shard_map``
    in/out_specs and as the ``device_put`` placement recipe."""
    from jax.sharding import PartitionSpec as P

    kv = P(None, None, None, axis, None)
    # scale planes end on the head axis — scales shard with their pages
    # on the tp head axis by construction, not by a separate code path
    sc = None if cache.k_scale is None else P(None, None, None, axis)
    return PagedKVCache(k=kv, v=kv, lengths=P(), page_table=P(),
                        k_scale=sc, v_scale=sc)


def shard_cache(cache: PagedKVCache, mesh, axis: str = "tp") -> PagedKVCache:
    """Place a freshly-initialized cache onto the serving mesh per
    :func:`tp_cache_specs` (head-sharded K/V pools, replicated
    bookkeeping). The caller has checked that heads divide over the mesh
    axis and allocated the head axis for it (``init_paged_cache(...,
    shards=tp)``)."""
    from jax.sharding import NamedSharding

    # ONE spelling of the layout: the placement derives from the same
    # spec tree shard_map consumes, so the two can never drift
    return jax.tree_util.tree_map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        cache, tp_cache_specs(cache, axis))


def _token_arrays(cache) -> tuple:
    """Names of the cache's arrays that hold tokens (axis 1 is the
    page)."""
    if hasattr(cache, "rows"):
        return ("rows",)
    return ("k", "v") + (("k_scale", "v_scale")
                         if cache.k_scale is not None else ())


def _slot_arrays(cache) -> tuple:
    """Names of the cache's arrays that hold a fixed-size state a slot
    (axis 1 is the slot; no page axis): none but a
    :class:`HybridCache`'s."""
    return ("state", "conv") if isinstance(cache, HybridCache) else ()


def cache_bytes(cache) -> int:
    """Resident bytes of the cache's storage: the tokens' (scale planes
    included) and the per-slot states'."""
    return sum(int(getattr(cache, name).nbytes)
               for name in _token_arrays(cache) + _slot_arrays(cache))


# ------------------------------------------------------ the resident pool
#
# The TPU runtime's default layout for an array is the one that pads
# least, not the row-major one. 25 heads do not fill their sublane tiles,
# so ``bf16[L, 17, 64, 25, 64]`` lies as ``{4,2,3,1,0}`` (heads outside a
# page's rows), and a 576-wide latent row puts the PAGE index innermost
# (``bf16[L, 1089, 64, 576]{1,3,2,0}``). The serving programs index pages
# and rows and move whole token rows (a scatter wants its window minor),
# so XLA relaid each whole pool on the way into a program and again on
# the way out, and copied an undonated pool once more before an in-place
# scatter: four whole-pool copies a decode step, and donation alone
# removed none (PERF.md, PR 31).
#
# A layout asked for through ``jax.experimental.layout`` does not survive
# the persistent compile cache on this JAX (0.9.0: an array that a LOADED
# executable returns reports the default layout whatever its bytes are,
# and the next call refuses it), so the layout is not asked for: it is
# the SHAPE. The head axis is allocated in whole groups of
# :data:`SUBLANES` (:func:`padded_heads`: 25 -> 32) and the latent row in
# whole :data:`LANES` (576 -> 640): row-major then pads no more than any
# other order of the minor axes, the runtime breaks the tie for it, and
# its default IS the layout the programs work in. The bytes are those the
# row-major layout of the unpadded shape would take (it pads to the same
# tiles). That holds while no other axis makes a cheaper minor one: a
# ``page_size`` that is a multiple of 128 (one page a slot at ``max_len``
# 1024, say) pads nothing as the minor axis where row-major pads
# ``head_dim`` 64 to 128, and a page axis of 65 pads less than that too,
# so such pools are still relaid (the engine's ``pool_copies`` says what
# a compiled program got; PERF.md, PR 32). With the cache donated to
# every program that returns it, a write lands in the buffer it was
# handed and nothing is copied.


def pool_facts(compiled, cache) -> dict:
    """What a compiled program's own text says of the pool:
    ``pool_aliased_bytes``, the bytes of its arguments that alias a
    result (at least :func:`cache_bytes` once every pool array is written
    in place), and ``pool_copies``, the ``copy`` instructions whose shape
    is a whole pool array's (a per-slot state array counts as one), or a
    rank's whole shard of one (none, where the pool is resident)."""
    arrays = [getattr(cache, name)
              for name in _token_arrays(cache) + _slot_arrays(cache)]
    # a program over the serving mesh names a rank's shard of the array
    dims = {",".join(map(str, a.sharding.shard_shape(a.shape)))
            for a in arrays}
    copies = sum(m.group(1) in dims for m in re.finditer(
        r"\[([\d,]+)\](?:\{[^}]*\})? copy\(", compiled.as_text()))
    memory = compiled.memory_analysis()
    return {"pool_aliased_bytes": int(
                getattr(memory, "alias_size_in_bytes", 0) or 0),
            "pool_copies": copies}


# copy-on-write: page indices are traced scalars, so the engine's ONE
# jitted op (the pool donated, a page written in place) serves every
# pair — sharing a partially-used prefix page costs a page copy, never a
# recompile and never a copy of the pool
def copy_page(cache, src, dst):
    """Copy page ``src`` onto page ``dst`` across every layer and every
    paged array of the cache (K and V with their scale planes, or the
    latent rows) — the copy-on-write that gives a slot its own writable
    copy of a shared prefix page whose tail it must append into."""
    if _slot_arrays(cache):
        raise ValueError(
            "copy_page: a page of this cache is shared with nothing, "
            "because the slot's recurrent state at the page's boundary "
            "is not kept (the engine refuses prefix_cache for the model)")
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return cache.replace(**{
        name: getattr(cache, name).at[:, dst].set(getattr(cache, name)[:, src])
        for name in _token_arrays(cache)})


# page install: the page index is a traced scalar, the payload a
# fixed-shape array, so the engine's ONE jitted op lands every migrated
# page from another replica's pool with one scatter into the donated
# pool, never a recompile. The inverse of reading `cache.k[:, page]` out:
# the disaggregated prefill→decode handoff streams `[n_layer, page_size,
# heads (padded), head_dim]` payloads and this op parks them under a pool
# index the receiving allocator chose.
def install_page(cache: PagedKVCache, page, k_page: jax.Array,
                 v_page: jax.Array, k_scale_page=None,
                 v_scale_page=None) -> PagedKVCache:
    """Write a whole page's K/V payload into pool slot ``page`` across
    every layer. ``k_page``/``v_page``: ``[n_layer, page_size, heads,
    head_dim]`` with the pool's padded head axis; on a quantized cache
    the caller also supplies the page's scale planes ``[n_layer,
    page_size, heads]``. The caller
    owns ``page`` (freshly allocated, refcount held), so the scatter
    can never alias a live slot's append."""
    page = jnp.asarray(page, jnp.int32)
    out = cache.replace(
        k=cache.k.at[:, page].set(k_page.astype(cache.k.dtype)),
        v=cache.v.at[:, page].set(v_page.astype(cache.v.dtype)))
    if k_scale_page is not None:
        out = out.replace(
            k_scale=cache.k_scale.at[:, page].set(
                k_scale_page.astype(cache.k_scale.dtype)),
            v_scale=cache.v_scale.at[:, page].set(
                v_scale_page.astype(cache.v_scale.dtype)))
    return out


# ------------------------------------------- the latent pool (MLA models)


@flax.struct.dataclass
class PagedLatentCache:
    """Pytree of a latent-attention model's paged cache: one row a token
    a layer, ``[c_kv, k_rope]`` (for DeepSeek-V3 widths 512 + 64 = 576)
    padded with zeros to whole lanes (640: :func:`pad_last`), which
    every head reads. Page indices, the null page and ``lengths``
    mean what they mean in :class:`PagedKVCache`."""

    rows: jax.Array        # [n_layer, num_pages, page_size, row]
    lengths: jax.Array     # [num_slots] int32 — tokens resident per slot
    page_table: jax.Array  # [num_slots, max_pages_per_slot] int32

    @property
    def n_layer(self) -> int:
        return self.rows.shape[0]

    @property
    def num_pages(self) -> int:
        return self.rows.shape[1]

    @property
    def page_size(self) -> int:
        return self.rows.shape[2]

    @property
    def num_slots(self) -> int:
        return self.page_table.shape[0]

    @property
    def max_pages_per_slot(self) -> int:
        return self.page_table.shape[1]

    @property
    def max_len(self) -> int:
        return self.page_size * self.max_pages_per_slot


def init_paged_latent_cache(n_layer: int, num_slots: int, max_len: int,
                            page_size: int, num_pages: int, width: int,
                            dtype: Any = jnp.float32) -> PagedLatentCache:
    """Allocate an empty latent pool for rows ``width`` wide (stored
    whole lanes wide); the geometry rules are :func:`init_paged_cache`'s."""
    if max_len % page_size:
        raise ValueError(
            f"page_size={page_size} must divide max_len={max_len}")
    max_pages = max_len // page_size
    if num_pages < max_pages + 1:
        raise ValueError(
            f"num_pages={num_pages} cannot hold one full-context request "
            f"plus the null page (need >= {max_pages + 1})")
    return PagedLatentCache(
        rows=jnp.zeros((n_layer, num_pages, page_size,
                        -(-width // LANES) * LANES), dtype),
        lengths=jnp.zeros((num_slots,), jnp.int32),
        page_table=jnp.zeros((num_slots, max_pages), jnp.int32))


@jax.named_scope("kv_write")
def write_latent(cache: PagedLatentCache, layer: int, rows: jax.Array,
                 positions: jax.Array, mask: jax.Array) -> PagedLatentCache:
    """Append latent rows through the page table, one token a slot
    (``rows [num_slots, width]``, ``positions``/``mask`` ``[num_slots]``:
    the decode step) or a chunk a slot (``[num_slots, T, width]`` and
    ``[num_slots, T]``: the batched prefill), in one masked scatter. Row
    ``(b, t)`` lands at virtual position ``positions[b, t]`` of slot
    ``b``; a masked-off row is given an out-of-range page and DROPPED,
    as :func:`write_rows` drops it, so no byte of a neighbour, of the
    null page or of a row past the prompt is touched."""
    pos = positions.astype(jnp.int32)
    live = mask & (pos >= 0) & (pos < cache.max_len)
    slot = jnp.arange(pos.shape[0], dtype=jnp.int32).reshape(
        (-1,) + (1,) * (pos.ndim - 1))
    ps = cache.page_size
    pages = cache.page_table[
        slot, jnp.clip(pos // ps, 0, cache.max_pages_per_slot - 1)]
    index = (layer, jnp.where(live, pages, cache.num_pages), pos % ps)
    return cache.replace(rows=cache.rows.at[index].set(
        pad_last(rows.astype(cache.rows.dtype), cache.rows.shape[-1]),
        mode="drop"))


# ------------------------- pages beside a state a slot (hybrid models)


@flax.struct.dataclass
class HybridCache(PagedLatentCache):
    """Pytree of the cache of a model that mixes latent-attention layers
    with recurrent (linear-attention) ones. ``rows`` has one plane a
    LATENT layer; ``lengths`` and ``page_table`` mean what they mean in
    :class:`PagedLatentCache`, so the allocator and every length mutator
    serve it unchanged. Each recurrent layer keeps, a slot, a float32
    state and the last ``taps - 1`` inputs of its short convolution; both
    are overwritten in place a call and never grow with the context.

    ``slots`` is ``None`` on the whole cache (row ``i`` of a call is slot
    ``i``) and the rows' slot ids on a row view (:func:`slot_view`), where
    an id out of range names no slot: it reads the last one's state and
    its write is dropped."""

    # [state layers, num_slots, heads, key_dim, value_dim] float32
    state: jax.Array
    # [state layers, num_slots, (taps - 1) * channels]: step t - taps + 1
    # first, each step's channels together
    conv: jax.Array
    slots: Optional[jax.Array] = None


def init_hybrid_cache(planes: int, state_layers: int, num_slots: int,
                      max_len: int, page_size: int, num_pages: int,
                      width: int, heads: int, key_dim: int, value_dim: int,
                      conv_width: int,
                      dtype: Any = jnp.float32) -> HybridCache:
    """Allocate an empty hybrid cache: :func:`init_paged_latent_cache`'s
    pool with ``planes`` planes, and ``state_layers`` zero states and
    convolution tails (``conv_width = (taps - 1) * channels``) a slot."""
    pool = init_paged_latent_cache(planes, num_slots, max_len, page_size,
                                   num_pages, width, dtype)
    return HybridCache(
        rows=pool.rows, lengths=pool.lengths, page_table=pool.page_table,
        state=jnp.zeros((state_layers, num_slots, heads, key_dim,
                         value_dim), jnp.float32),
        conv=jnp.zeros((state_layers, num_slots, conv_width), dtype))


def read_state(cache: HybridCache, layer: int):
    """``(state [rows, heads, key_dim, value_dim], conv [rows,
    conv_width])`` of recurrent layer ``layer`` for the call's rows: the
    layer's whole slice where the cache is whole, the rows' slots
    gathered where it is a view."""
    if cache.slots is None:
        return cache.state[layer], cache.conv[layer]
    at = jnp.clip(cache.slots, 0, cache.state.shape[1] - 1)
    return cache.state[layer, at], cache.conv[layer, at]


def write_state(cache: HybridCache, layer: int, state: jax.Array,
                conv: jax.Array, mask: jax.Array) -> HybridCache:
    """Recurrent layer ``layer``'s state and convolution tail after the
    call, for the rows under ``mask [rows]``; every other slot's stay
    bit for bit (a masked-off row of the whole cache writes back what
    was there; one of a view is sent out of range and dropped, as
    :func:`write_latent` drops a row)."""
    conv = conv.astype(cache.conv.dtype)
    if cache.slots is None:
        return cache.replace(
            state=cache.state.at[layer].set(jnp.where(
                mask[:, None, None, None], state, cache.state[layer])),
            conv=cache.conv.at[layer].set(jnp.where(
                mask[:, None], conv, cache.conv[layer])))
    at = jnp.where(mask, cache.slots, cache.state.shape[1])
    return cache.replace(
        state=cache.state.at[layer, at].set(state, mode="drop"),
        conv=cache.conv.at[layer, at].set(conv, mode="drop"))
