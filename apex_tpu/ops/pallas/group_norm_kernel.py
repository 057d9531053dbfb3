"""Pallas TPU kernels for NHWC GroupNorm (+ fused SiLU) — the kernel-layer
equivalent of ``group_norm_cuda`` / ``group_norm_v2_cuda``
(apex/contrib/csrc/group_norm*: one-pass & two-pass NHWC algorithms across 27
per-channel-count instantiations; SURVEY §2.3).

TPU design: BOTH reference algorithms, selected like the reference selects
them (``group_norm.py:193-209`` keys one-pass on channels-per-group and SM
resources; here the analogous resource bound is the VMEM slab):

- **one-pass** (``_one_pass_kernel``): the whole (HW, C) sample slab lives
  in VMEM for one grid step — stats AND normalize+affine+SiLU happen on a
  single HBM read of x (1R + 1W total), halving traffic exactly where the
  reference's one-pass wins. Selected when the slab fits
  (:func:`one_pass_ok`).
- **two-pass** (``_stats_kernel`` + ``_apply_kernel``): per-(sample, group)
  sum/sumsq partials accumulated across HW tiles, then a second sweep
  normalizes (2R + 1W) — covers arbitrarily large HW.

ONE kernel pair covers every channel count — per-shape instantiation is the
Mosaic compiler's job. Stats fp32. The backward uses the saved (mean, rstd)
in one fused XLA chain (the dgamma/dbeta reductions are column sums XLA
already tiles well).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas.tpu import CompilerParams as _CompilerParams
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas.tiling import groupnorm_hw_block
from apex_tpu.tune.api import pow2_bucket, tuned_params
from apex_tpu.utils.env import interpret_default

_f32 = jnp.float32


def pallas_ok(n: int, hw: int, c: int) -> bool:
    """Shape guard: HW tiles need 8-sublane alignment."""
    return hw % 8 == 0


# one-pass slab budget: the (hw, c) block is double-buffered by Mosaic for
# BOTH x and y (4 windows) plus the in-kernel fp32 temporaries — a 2 MiB
# fp32 payload bounds the worst case (~10 MiB) under the ~16 MiB VMEM.
_ONE_PASS_SLAB_ELEMS = (2 * 1024 * 1024) // 4


def one_pass_ok(n: int, hw: int, c: int) -> bool:
    """TPU translation of the reference's one-pass eligibility rule
    (apex/contrib/group_norm/group_norm.py:193-209 picks one-pass by
    channels-per-group / SM capacity): one-pass needs the full per-sample
    (HW, C) slab resident so stats and apply share one read of x."""
    return pallas_ok(n, hw, c) and hw * c <= _ONE_PASS_SLAB_ELEMS


def _pick_hw_block(hw: int, c: int) -> int:
    # shared heuristic (ops/pallas/tiling.py), also the autotuner's
    # default candidate
    return groupnorm_hw_block(hw, c)


def _hw_block(hw: int, c: int, dtype, interpret: bool,
              hw_block: int | None = None) -> int:
    """HW-tile resolution: explicit arg > tuned cache entry > heuristic.
    The stats kernel accumulates per-group partials across HW tiles AND the
    grid floor-divides hw, so a block that does not tile ``hw`` exactly
    would silently drop the tail rows — explicit values are validated
    (ValueError), tuned entries rejected back to the heuristic."""
    def ok(p):
        blk = p["hw_block"]
        return (isinstance(blk, int) and blk >= 8 and blk % 8 == 0
                and hw % blk == 0)

    if hw_block is not None:
        if not ok({"hw_block": hw_block}):
            raise ValueError(
                f"group_norm hw_block={hw_block!r} invalid for hw={hw}: "
                f"must be a positive multiple of 8 that divides hw (the "
                f"two-pass grid floor-divides hw, so a non-divisor would "
                f"silently skip the HW tail)")
        return hw_block

    return tuned_params(
        "group_norm", (("hw", pow2_bucket(hw)), ("c", c)),
        {"hw_block": _pick_hw_block(hw, c)},
        dtype=dtype, interpret=interpret, validate=ok)["hw_block"]


def _make_sel(c: int, g: int):
    """(C, G) one-hot group-selector matrix (contiguous groups)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (c, g), 0) // (c // g)
            == jax.lax.broadcasted_iota(jnp.int32, (c, g), 1)).astype(_f32)


def _append_wb(in_specs, args, weight, bias, c, wspec):
    """Append the optional affine operands (shared by both drivers)."""
    if weight is not None:
        in_specs.append(wspec)
        args.append(weight.reshape(1, c))
    if bias is not None:
        in_specs.append(wspec)
        args.append(bias.reshape(1, c))


def _split_wb(refs, n_head: int, has_w: bool, has_b: bool):
    """Split *refs laid out as [head..., w?, b?, tail...] →
    (head_refs, w_ref, b_ref, tail_refs) — the single unpacking convention
    for both drivers' kernels."""
    head = refs[:n_head]
    idx = n_head
    w_ref = b_ref = None
    if has_w:
        w_ref = refs[idx]
        idx += 1
    if has_b:
        b_ref = refs[idx]
        idx += 1
    return head, w_ref, b_ref, refs[idx:]


def _stats_kernel(x_ref, sel_ref, sum_ref, sq_ref):
    """Per-group partials via an MXU matmul with the (C, G) group-selector —
    no lane-dim reshapes (Mosaic-unfriendly)."""
    h = pl.program_id(1)

    @pl.when(h == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        sq_ref[...] = jnp.zeros_like(sq_ref)

    x = x_ref[0].astype(_f32)                     # (hwb, C)
    sel = sel_ref[...]                            # (C, G) one-hot
    csum = jnp.sum(x, axis=0, keepdims=True)      # (1, C)
    csq = jnp.sum(x * x, axis=0, keepdims=True)
    # HIGHEST: keep full fp32 operand mantissas on the MXU — these are
    # large per-channel sums and default (bf16-operand) precision would put
    # ~1e-3 relative error into the group statistics
    sum_ref[...] += jnp.dot(csum, sel, preferred_element_type=_f32,
                            precision=jax.lax.Precision.HIGHEST)[None]
    sq_ref[...] += jnp.dot(csq, sel, preferred_element_type=_f32,
                           precision=jax.lax.Precision.HIGHEST)[None]


def _apply_kernel(x_ref, mean_ref, rstd_ref, w_ref, b_ref, y_ref, *,
                  act: str):
    x = x_ref[0].astype(_f32)                     # (hwb, C)
    y = (x - mean_ref[0]) * rstd_ref[0]
    if w_ref is not None:
        y = y * w_ref[...].astype(_f32)
    if b_ref is not None:
        y = y + b_ref[...].astype(_f32)
    if act == "silu":
        y = y * jax.nn.sigmoid(y)
    y_ref[0] = y.astype(y_ref.dtype)


def _one_pass_kernel(x_ref, sel_ref, selt_ref, w_ref, b_ref,
                     y_ref, mean_ref, rstd_ref, *, act: str, eps: float,
                     cnt: float):
    """Whole-sample slab: stats + normalize + affine + activation on ONE
    read of x (the reference's one-pass structure,
    group_norm_nhwc_one_pass_*.cu)."""
    x = x_ref[0].astype(_f32)                     # (hw, C)
    sel = sel_ref[...]                            # (C, G) one-hot
    csum = jnp.sum(x, axis=0, keepdims=True)      # (1, C)
    csq = jnp.sum(x * x, axis=0, keepdims=True)
    # HIGHEST precision — same rationale as _stats_kernel
    gsum = jnp.dot(csum, sel, preferred_element_type=_f32,
                   precision=jax.lax.Precision.HIGHEST)      # (1, G)
    gsq = jnp.dot(csq, sel, preferred_element_type=_f32,
                  precision=jax.lax.Precision.HIGHEST)
    mean = gsum / cnt
    var = gsq / cnt - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    mean_ref[0] = mean
    rstd_ref[0] = rstd
    selt = selt_ref[...]                          # (G, C) one-hot
    # HIGHEST: default (bf16-operand) precision would round the fp32 group
    # stats to ~2^-9 relative before normalization (same hazard as the
    # stats dots above)
    mean_c = jnp.dot(mean, selt, preferred_element_type=_f32,
                     precision=jax.lax.Precision.HIGHEST)     # (1, C)
    rstd_c = jnp.dot(rstd, selt, preferred_element_type=_f32,
                     precision=jax.lax.Precision.HIGHEST)
    y = (x - mean_c) * rstd_c
    if w_ref is not None:
        y = y * w_ref[...].astype(_f32)
    if b_ref is not None:
        y = y + b_ref[...].astype(_f32)
    if act == "silu":
        y = y * jax.nn.sigmoid(y)
    y_ref[0] = y.astype(y_ref.dtype)


def _group_norm_one_pass(x3, n, hw, c, g, weight, bias, eps, act,
                         interpret):
    sel = _make_sel(c, g)
    xspec = pl.BlockSpec((1, hw, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    gspec = pl.BlockSpec((1, 1, g), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    wspec = pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM)
    in_specs = [xspec,
                pl.BlockSpec((c, g), lambda i: (0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((g, c), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)]
    args = [x3, sel, sel.T]
    _append_wb(in_specs, args, weight, bias, c, wspec)

    def kernel(*refs):
        (x_ref, s_ref, st_ref), w_ref, b_ref, tail = _split_wb(
            refs, 3, weight is not None, bias is not None)
        y_ref, m_ref, r_ref = tail
        _one_pass_kernel(x_ref, s_ref, st_ref, w_ref, b_ref,
                         y_ref, m_ref, r_ref, act=act, eps=eps,
                         cnt=float(hw * (c // g)))

    y, mean, rstd = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=[xspec, gspec, gspec],
        out_shape=[jax.ShapeDtypeStruct((n, hw, c), x3.dtype),
                   jax.ShapeDtypeStruct((n, 1, g), _f32),
                   jax.ShapeDtypeStruct((n, 1, g), _f32)],
        compiler_params=_CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)
    return y, mean[:, 0], rstd[:, 0]


def group_norm_nhwc_pallas(x: jax.Array, num_groups: int,
                           weight: Optional[jax.Array] = None,
                           bias: Optional[jax.Array] = None,
                           eps: float = 1e-5, act: str = "",
                           interpret: Optional[bool] = None,
                           algo: str = "auto",
                           hw_block: Optional[int] = None):
    """Forward: returns (y, mean, rstd) with mean/rstd (N, G) fp32.

    ``algo``: "auto" (one-pass when the sample slab fits VMEM — the
    reference's selection rule translated, group_norm.py:193-209),
    "one_pass", or "two_pass". ``hw_block`` overrides the tuned/heuristic
    two-pass HW tile (the autotuner's probe path)."""
    if interpret is None:
        interpret = interpret_default()
    n, h, w, c = x.shape
    if algo == "auto":
        algo = "one_pass" if one_pass_ok(n, h * w, c) else "two_pass"
    elif algo not in ("one_pass", "two_pass"):
        raise ValueError(f"algo must be auto|one_pass|two_pass, got {algo!r}")
    if algo == "one_pass":
        g = num_groups
        y, mean, rstd = _group_norm_one_pass(
            x.reshape(n, h * w, c), n, h * w, c, g, weight, bias, eps, act,
            interpret)
        return y.reshape(n, h, w, c), mean, rstd
    g = num_groups
    hw = h * w
    x3 = x.reshape(n, hw, c)
    hwb = _hw_block(hw, c, x.dtype, interpret, hw_block)
    grid = (n, hw // hwb)

    xspec = pl.BlockSpec((1, hwb, c), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM)
    gspec = pl.BlockSpec((1, 1, g), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    selspec = pl.BlockSpec((c, g), lambda i, j: (0, 0),
                           memory_space=pltpu.VMEM)
    cpg = c // g
    sel = _make_sel(c, g)

    sums, sqs = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[xspec, selspec],
        out_specs=[gspec, gspec],
        out_shape=[jax.ShapeDtypeStruct((n, 1, g), _f32),
                   jax.ShapeDtypeStruct((n, 1, g), _f32)],
        interpret=interpret,
    )(x3, sel)
    cnt = _f32(hw * (c // g))
    mean = sums[:, 0] / cnt                                    # (N, G)
    var = sqs[:, 0] / cnt - mean * mean
    rstd = jax.lax.rsqrt(var + eps)

    mean_c = jnp.repeat(mean, cpg, axis=1).reshape(n, 1, c)
    rstd_c = jnp.repeat(rstd, cpg, axis=1).reshape(n, 1, c)

    cspec = pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    in_specs = [xspec, cspec, cspec]
    args = [x3, mean_c, rstd_c]
    wspec = pl.BlockSpec((1, c), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM)
    _append_wb(in_specs, args, weight, bias, c, wspec)

    def kernel(*refs):
        (x_ref, m_ref, r_ref), w_ref, b_ref, tail = _split_wb(
            refs, 3, weight is not None, bias is not None)
        _apply_kernel(x_ref, m_ref, r_ref, w_ref, b_ref, tail[0], act=act)

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((n, hw, c), x.dtype),
        interpret=interpret,
    )(*args)
    return y.reshape(n, h, w, c), mean, rstd
