"""Pallas TPU kernel for the megatron attention-score softmax family.

Reference: ``csrc/megatron/scaled_masked_softmax.h`` warp kernels (:106
unmasked, :211 arbitrary mask, scaled_upper_triang_masked_softmax.h:130
causal) and their backward chains (:106-207). Semantics preserved: scale
applied first, masked positions REPLACED with -10000.0, fully-masked rows
output zeros, math in fp32 regardless of IO dtype.

TPU design: one grid step owns a (block_rows, sk) row-complete tile resident
in VMEM, so the max / exp / sum / divide chain touches HBM exactly once per
element (read x, write y) — the XLA jnp lowering re-reads the input for each
reduction pass, which caps it at ~1/3 of HBM peak; this kernel removes those
extra passes. The backward needs only y and dy (masked positions have y == 0
so their dx is exactly 0 without consulting the mask — same trick as the
reference backward kernels, which also take no mask).

The mask is streamed block-wise with broadcast dims UNMATERIALIZED, matching
the reference's (b, 1, sq, sk) mask vs (b, h, sq, sk) scores convention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas.tpu import CompilerParams as _CompilerParams
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.tiling import softmax_block_rows
from apex_tpu.tune.api import pow2_bucket, tuned_params
from apex_tpu.utils.env import interpret_default
from apex_tpu.utils.tiling import round_up as _round_up

_f32 = jnp.float32
MASK_FILL = -10000.0
# largest row length the VMEM-resident tile supports (fp32 working set);
# beyond this the caller falls back to the XLA path (the "generic" variant
# has no length limit, like generic_scaled_masked_softmax.cpp:58-61)
MAX_PALLAS_COLS = 16384


def _pick_rows(skp: int, sq: int, itemsize: int = 4,
               has_mask: bool = False) -> int:
    """Row-block size from a per-grid-step VMEM budget covering EVERY
    streamed operand (in + out tiles double-buffered, mask tile, fp32
    temporaries) — shared heuristic (ops/pallas/tiling.py), also the
    autotuner's default candidate."""
    return softmax_block_rows(skp, sq, itemsize, has_mask)


def _block_rows(skp: int, sq: int, itemsize: int, has_mask: bool, dtype,
                interpret: bool, block_rows: int | None = None) -> int:
    """Row-block resolution: explicit arg > tuned cache entry > heuristic.
    Any 8-aligned block is grid-legal (sq pads up to a block multiple), so
    validation only checks alignment."""
    if block_rows is not None:
        return block_rows

    def ok(p):
        br = p["block_rows"]
        return isinstance(br, int) and br >= 8 and br % 8 == 0

    return tuned_params(
        "softmax",
        (("sk", skp), ("sq", pow2_bucket(sq)), ("mask", has_mask)),
        {"block_rows": _pick_rows(skp, sq, itemsize, has_mask)},
        dtype=dtype, interpret=interpret, validate=ok)["block_rows"]


def _softmax_rows_f32(x32):
    """Row softmax on a masked fp32 tile. Reciprocal-multiply (one divide
    per ROW, then a row-broadcast mul) instead of a per-element divide;
    fully-masked rows (max == fill) output zeros,
    scaled_masked_softmax.h:297. Shared by every forward kernel."""
    m = jnp.max(x32, axis=-1, keepdims=True)
    e = jnp.exp(x32 - m)
    s = jnp.sum(e, axis=-1, keepdims=True)
    return e * jnp.where(m <= MASK_FILL, 0.0, 1.0 / s)


def _sm_fwd_kernel(*refs, scale, causal, has_mask, sk_orig, br, skp):
    if has_mask:
        x_ref, m_ref, o_ref = refs
    else:
        x_ref, o_ref = refs
        m_ref = None
    qi = pl.program_id(1)
    x32 = x_ref[0].astype(_f32) * scale
    if has_mask:
        x32 = jnp.where(m_ref[0] != 0, MASK_FILL, x32)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (br, skp), 0) + qi * br
        cols = jax.lax.broadcasted_iota(jnp.int32, (br, skp), 1)
        x32 = jnp.where(cols > rows, MASK_FILL, x32)
    if skp != sk_orig:
        cols = jax.lax.broadcasted_iota(jnp.int32, (br, skp), 1)
        x32 = jnp.where(cols >= sk_orig, MASK_FILL, x32)
    o_ref[0] = _softmax_rows_f32(x32).astype(o_ref.dtype)


def _sm_bwd_kernel(y_ref, dy_ref, dx_ref, *, scale):
    y32 = y_ref[0].astype(_f32)
    dy32 = dy_ref[0].astype(_f32)
    c = jnp.sum(dy32 * y32, axis=-1, keepdims=True)
    dx_ref[0] = ((dy32 - c) * y32 * scale).astype(dx_ref.dtype)


def _sm_causal_chunked_kernel(x_ref, o_ref, xbuf, *, scale, sk_orig, br, bc,
                              skp, nc):
    """Causal forward with column-chunked fetch: chunk j of row block qi is
    DMA'd from HBM only when it intersects the lower triangle (the index
    map aliases above-diagonal chunks to the last needed one, and Mosaic
    skips the copy when the block index repeats) — on causal scores ~25%
    of the input bytes never leave HBM. Chunks are staged into a
    row-complete VMEM buffer; the softmax itself runs once per row block
    at the last chunk."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    limit = ((qi + 1) * br - 1) // bc  # last chunk touching the triangle

    @pl.when(j <= limit)
    def _stage():
        xbuf[:, pl.ds(j * bc, bc)] = x_ref[0].astype(_f32)

    @pl.when(j == nc - 1)
    def _softmax():
        rows = jax.lax.broadcasted_iota(jnp.int32, (br, skp), 0) + qi * br
        cols = jax.lax.broadcasted_iota(jnp.int32, (br, skp), 1)
        # one mask covers the diagonal straddle, the never-staged region
        # (whose xbuf content is stale garbage — replaced, not arithmetic,
        # so NaN/Inf there cannot leak), and key padding
        keep = (cols <= rows) & (cols < sk_orig)
        x32 = jnp.where(keep, xbuf[...] * scale, MASK_FILL)
        o_ref[0] = _softmax_rows_f32(x32).astype(o_ref.dtype)


def _softmax_fwd_causal_chunked(x3, *, scale, interpret,
                                block_rows=None, chunk_cols=None):
    B, sq, sk = x3.shape
    skp = _round_up(sk, 128)
    # largest chunk that still gives >= 2 chunks; with one row block or one
    # chunk nothing can ever be skipped — signal the caller to use the
    # plain row-complete kernel instead of paying the staging overhead.
    # 0 encodes "no usable chunk" (cache values must be ints, not None).
    defaults = {
        "block_rows": _pick_rows(skp, sq, x3.dtype.itemsize, False),
        "chunk_cols": next((c for c in (512, 256, 128)
                            if skp % c == 0 and skp > c), 0),
    }

    def ok(p):
        br, bc = p["block_rows"], p["chunk_cols"]
        return (isinstance(br, int) and isinstance(bc, int)
                and br >= 8 and br % 8 == 0 and bc > 0 and bc % 128 == 0
                and skp % bc == 0 and skp > bc)

    if block_rows is None and chunk_cols is None:
        tuned = tuned_params(
            "softmax_causal_chunked",
            (("sk", skp), ("sq", pow2_bucket(sq))),
            defaults, dtype=x3.dtype, interpret=interpret, validate=ok)
        br, bc = tuned["block_rows"], tuned["chunk_cols"]
    else:
        br = block_rows if block_rows is not None else \
            defaults["block_rows"]
        bc = chunk_cols if chunk_cols is not None else \
            defaults["chunk_cols"]
    sqp = _round_up(sq, br)
    if not bc or sqp // br < 2:
        return None
    nc = skp // bc
    xp = jnp.pad(x3, ((0, 0), (0, sqp - sq), (0, skp - sk)))

    def x_idx(b, i, j):
        limit = ((i + 1) * br - 1) // bc
        return (b, i, jnp.minimum(j, limit))

    out = pl.pallas_call(
        functools.partial(_sm_causal_chunked_kernel, scale=scale,
                          sk_orig=sk, br=br, bc=bc, skp=skp, nc=nc),
        grid=(B, sqp // br, nc),
        in_specs=[pl.BlockSpec((1, br, bc), x_idx,
                               memory_space=pltpu.VMEM)],
        # the output block ignores j: written once (at the last chunk) and
        # flushed when the row-block index advances
        out_specs=pl.BlockSpec((1, br, skp), lambda b, i, j: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, sqp, skp), x3.dtype),
        scratch_shapes=[pltpu.VMEM((br, skp), _f32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp)
    return out[:, :sq, :sk]


def softmax_fwd_pallas(x3, mask3, *, scale, causal, h=1, interpret=None,
                       block_rows=None, chunk_cols=None):
    """x3: (B, sq, sk) scores (B = b·h). mask3: None or (Bm, sqm, sk) with
    Bm in {1, B//h·? } — concretely Bm in {1, B // h} (the reference's
    per-batch mask shared across heads) or B; sqm in {1, sq}. 1/True =
    masked. ``block_rows``/``chunk_cols`` override the tuned/heuristic
    tile geometry (the autotuner's probe path)."""
    if interpret is None:
        interpret = interpret_default()
    B, sq, sk = x3.shape
    skp = _round_up(sk, 128)
    if causal and mask3 is None and skp >= 256 and sq >= 16:
        # chunked fetch pays only when >= 2 column chunks AND >= 2 row
        # blocks exist (so upper-triangle chunks can actually be skipped);
        # the helper returns None for degenerate shapes
        out = _softmax_fwd_causal_chunked(x3, scale=scale,
                                          interpret=interpret,
                                          block_rows=block_rows,
                                          chunk_cols=chunk_cols)
        if out is not None:
            return out
    br = _block_rows(skp, sq, x3.dtype.itemsize, mask3 is not None,
                     x3.dtype, interpret, block_rows)
    sqp = _round_up(sq, br)
    xp = jnp.pad(x3, ((0, 0), (0, sqp - sq), (0, skp - sk)))
    grid = (B, sqp // br)

    in_specs = [pl.BlockSpec((1, br, skp), lambda b, i: (b, i, 0),
                             memory_space=pltpu.VMEM)]
    operands = [xp]
    has_mask = mask3 is not None
    if has_mask:
        Bm, sqm, _ = mask3.shape
        mp = jnp.pad(mask3.astype(jnp.int32),
                     ((0, 0), (0, sqp - sq if sqm != 1 else 0),
                      (0, skp - sk)))
        full_q = sqm != 1
        if Bm == 1:
            bidx = lambda b: 0  # noqa: E731
        elif Bm == B:
            bidx = lambda b: b  # noqa: E731
        else:  # per-batch mask shared across h heads
            assert Bm * h == B, (Bm, h, B)
            bidx = lambda b: b // h  # noqa: E731
        in_specs.append(pl.BlockSpec(
            (1, br if full_q else 1, skp),
            lambda b, i: (bidx(b), i if full_q else 0, 0),
            memory_space=pltpu.VMEM))
        operands.append(mp)

    out = pl.pallas_call(
        functools.partial(_sm_fwd_kernel, scale=scale, causal=causal,
                          has_mask=has_mask, sk_orig=sk, br=br, skp=skp),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, br, skp), lambda b, i: (b, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, sqp, skp), x3.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)
    return out[:, :sq, :sk]


def softmax_bwd_pallas(y3, dy3, *, scale, interpret=None, block_rows=None):
    """dx for any variant: masked positions have y == 0 ⇒ dx == 0, so no
    mask input is needed (matches the reference backward kernels)."""
    if interpret is None:
        interpret = interpret_default()
    B, sq, sk = y3.shape
    skp = _round_up(sk, 128)
    br = _block_rows(skp, sq, y3.dtype.itemsize, False, y3.dtype,
                     interpret, block_rows)
    sqp = _round_up(sq, br)
    # padded cols have y == 0 ⇒ contribute nothing to the row sum
    yp = jnp.pad(y3, ((0, 0), (0, sqp - sq), (0, skp - sk)))
    dyp = jnp.pad(dy3, ((0, 0), (0, sqp - sq), (0, skp - sk)))
    spec = pl.BlockSpec((1, br, skp), lambda b, i: (b, i, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_sm_bwd_kernel, scale=scale),
        grid=(B, sqp // br),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, sqp, skp), y3.dtype),
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(yp, dyp)
    return out[:, :sq, :sk]
