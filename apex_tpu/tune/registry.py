"""Tunable-kernel registry: how each Pallas kernel is searched.

One :class:`KernelSpec` per kernel entry point declares:

- ``shape_key(shape)`` — the bucketed cache-key pairs, computed EXACTLY the
  way the kernel's ``tuned_params()`` call site computes them (same
  padding, same bucketing) so warmed entries are found at run time;
- ``defaults(shape)`` — today's heuristic choice (from
  ``ops/pallas/tiling.py``, the shared source of truth);
- ``candidates(shape)`` — the geometries the search times, always
  including the default so the heuristic can win;
- ``build(shape, dtype, params)`` — a ``(step_fn, state, consts)`` triple
  for :func:`apex_tpu.utils.benchtime.timed_steps` that exercises the real
  kernel at that geometry (compiled on TPU; interpret elsewhere, which is
  only meaningful as a smoke test).

Kernel modules are imported lazily inside ``build`` so importing the tune
package never drags the kernel zoo (and cannot create an import cycle:
the kernels import ``apex_tpu.tune.api``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Sequence, Tuple

from apex_tpu.ops.pallas.tiling import (groupnorm_hw_block, norm_block_rows,
                                        round_up, softmax_block_rows)
from apex_tpu.tune.api import pow2_bucket

ShapeKey = Tuple[Tuple[str, Any], ...]


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    shape_key: Callable[[Dict[str, Any]], ShapeKey]
    defaults: Callable[[Dict[str, Any]], Dict[str, Any]]
    candidates: Callable[[Dict[str, Any]], List[Dict[str, Any]]]
    build: Callable[..., Tuple[Callable, Any, Tuple]]
    default_shapes: Tuple[Dict[str, Any], ...] = ()
    # kernels whose lookup is keyed dtype=None (the flat optimizers: the
    # streaming block depends on row count, not element type, and the
    # master-weight fp32 variant must share bf16-warmed entries)
    dtype_agnostic: bool = False


def _row_block_candidates(limit: int, ceiling: int = 2048,
                          floor: int = 8) -> List[int]:
    out = []
    br = floor
    while br <= min(limit, ceiling):
        out.append(br)
        br *= 2
    return out or [floor]


# ----------------------------------------------------------- layer_norm


def _ln_padded_rows(shape):
    return round_up(int(shape["rows"]), 8)


def _ln_shape_key(shape) -> ShapeKey:
    return (("rows", pow2_bucket(_ln_padded_rows(shape))),
            ("hidden", int(shape["hidden"])))


def _ln_defaults(shape):
    return {"block_rows": norm_block_rows(_ln_padded_rows(shape),
                                          int(shape["hidden"]))}


def _ln_candidates(shape):
    from apex_tpu.ops.pallas.tiling import NORM_VMEM_BUDGET

    rows, hidden = _ln_padded_rows(shape), int(shape["hidden"])
    cands = []
    for br in _row_block_candidates(rows, ceiling=1024):
        # the winner is consulted by ln_bwd_pallas too (dy + saved + dx
        # streams, MORE resident tiles than the forward) — blocks must
        # tile rows exactly AND keep the slab inside the same VMEM budget
        # the heuristic honors, so a fwd-timed winner cannot OOM the bwd
        if rows % br == 0 and br * hidden * 4 <= NORM_VMEM_BUDGET:
            cands.append({"block_rows": br})
    default = _ln_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _ln_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.layer_norm_kernel import ln_fwd_pallas

    rows, hidden = int(shape["rows"]), int(shape["hidden"])
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden), dtype)
    g = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)
    br = params["block_rows"]

    def step(i, x, g, b):
        y, _, _ = ln_fwd_pallas(x, g, b, eps=1e-5, rms=False,
                                interpret=interpret, block_rows=br)
        return y.astype(x.dtype)

    return step, x, (g, b)


# -------------------------------------------------------------- softmax


def _sm_skp(shape):
    return round_up(int(shape["sk"]), 128)


def _sm_shape_key(shape) -> ShapeKey:
    return (("sk", _sm_skp(shape)),
            ("sq", pow2_bucket(int(shape["sq"]))),
            ("mask", bool(shape.get("mask", False))))


def _sm_defaults(shape):
    return {"block_rows": softmax_block_rows(
        _sm_skp(shape), int(shape["sq"]), int(shape.get("itemsize", 2)),
        bool(shape.get("mask", False)))}


def _sm_candidates(shape):
    from apex_tpu.ops.pallas.tiling import SOFTMAX_VMEM_BUDGET

    skp, sq = _sm_skp(shape), int(shape["sq"])
    itemsize = int(shape.get("itemsize", 2))
    # the winner is also consulted by softmax_bwd_pallas, which streams
    # THREE row-complete tiles (y, dy, dx) double-buffered plus fp32
    # temporaries — bound candidates by that footprint (≈6·itemsize+12
    # bytes/elt), and keep the heuristic's 512-row cap
    cands = [{"block_rows": br}
             for br in _row_block_candidates(round_up(sq, 8), ceiling=512)
             if skp * br * (6 * itemsize + 12) <= SOFTMAX_VMEM_BUDGET]
    default = _sm_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _sm_build(shape, dtype, params, interpret=None):
    import jax

    from apex_tpu.ops.pallas.softmax_kernel import softmax_fwd_pallas

    B, sq, sk = int(shape.get("B", 8)), int(shape["sq"]), int(shape["sk"])
    x = jax.random.normal(jax.random.PRNGKey(0), (B, sq, sk), dtype) * 0.1
    br = params["block_rows"]

    def step(i, x):
        # softmax output is a stable input distribution; chain directly
        return softmax_fwd_pallas(x, None, scale=1.0, causal=False,
                                  interpret=interpret,
                                  block_rows=br).astype(x.dtype)

    return step, x, ()


# ------------------------------------------- softmax (causal, chunked)


def _smc_shape_key(shape) -> ShapeKey:
    return (("sk", _sm_skp(shape)), ("sq", pow2_bucket(int(shape["sq"]))))


def _smc_defaults(shape):
    skp = _sm_skp(shape)
    return {
        "block_rows": softmax_block_rows(skp, int(shape["sq"]),
                                         int(shape.get("itemsize", 2)),
                                         False),
        "chunk_cols": next((c for c in (512, 256, 128)
                            if skp % c == 0 and skp > c), 0),
    }


def _smc_candidates(shape):
    from apex_tpu.ops.pallas.tiling import SOFTMAX_VMEM_BUDGET

    skp, sq = _sm_skp(shape), int(shape["sq"])
    itemsize = int(shape.get("itemsize", 2))
    chunks = [c for c in (1024, 512, 256, 128) if skp % c == 0 and skp > c]
    # dominant residents: the (br, skp) fp32 staging scratch plus the
    # double-buffered in/out tiles
    cands = [{"block_rows": br, "chunk_cols": bc}
             for br in _row_block_candidates(round_up(sq, 8), ceiling=512,
                                             floor=32)
             for bc in chunks
             if skp * br * (4 + 4 * itemsize) <= SOFTMAX_VMEM_BUDGET]
    default = _smc_defaults(shape)
    if default["chunk_cols"] and default not in cands:
        cands.append(default)
    return cands


def _smc_build(shape, dtype, params, interpret=None):
    import jax

    from apex_tpu.ops.pallas.softmax_kernel import softmax_fwd_pallas

    B, sq, sk = int(shape.get("B", 8)), int(shape["sq"]), int(shape["sk"])
    x = jax.random.normal(jax.random.PRNGKey(0), (B, sq, sk), dtype) * 0.1

    def step(i, x):
        return softmax_fwd_pallas(
            x, None, scale=1.0, causal=True, interpret=interpret,
            block_rows=params["block_rows"],
            chunk_cols=params["chunk_cols"]).astype(x.dtype)

    return step, x, ()


# ----------------------------------------------------------- group_norm


def _gn_shape_key(shape) -> ShapeKey:
    return (("hw", pow2_bucket(int(shape["hw"]))),
            ("c", int(shape["c"])))


def _gn_defaults(shape):
    return {"hw_block": groupnorm_hw_block(int(shape["hw"]),
                                           int(shape["c"]))}


def _gn_candidates(shape):
    from apex_tpu.ops.pallas.tiling import NORM_VMEM_BUDGET

    hw, c = int(shape["hw"]), int(shape["c"])
    cands = []
    for blk in _row_block_candidates(hw, ceiling=4096):
        # same slab budget as the heuristic: the stats+apply pair streams
        # multiple (blk, c) tiles double-buffered
        if hw % blk == 0 and blk * c * 4 <= NORM_VMEM_BUDGET:
            cands.append({"hw_block": blk})
    default = _gn_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _gn_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.group_norm_kernel import group_norm_nhwc_pallas

    n = int(shape.get("n", 2))
    hw, c, g = int(shape["hw"]), int(shape["c"]), int(shape.get("groups", 8))
    h = int(hw ** 0.5)
    while hw % h:
        h -= 1
    w = hw // h
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, c), dtype)
    weight = jnp.ones((c,), jnp.float32)
    blk = params["hw_block"]

    def step(i, x, weight):
        y, _, _ = group_norm_nhwc_pallas(x, g, weight, None,
                                         interpret=interpret,
                                         algo="two_pass", hw_block=blk)
        return y.astype(x.dtype)

    return step, x, (weight,)


# ------------------------------------------------------ flash_attention


def _fa_shape_key(shape) -> ShapeKey:
    return (("sq", pow2_bucket(int(shape["sq"]))),
            ("sk", pow2_bucket(int(shape["sk"]))),
            ("d", int(shape["d"])),
            ("causal", bool(shape.get("causal", True))))


def _fa_defaults(shape):
    from apex_tpu.ops.pallas.flash_attention import (DEFAULT_BLOCK_K,
                                                     DEFAULT_BLOCK_Q)

    return {"block_q": DEFAULT_BLOCK_Q, "block_k": DEFAULT_BLOCK_K}


# the on-chip sweep set of tools/tune_flash.py, minus the configs whose
# BACKWARD exceeds v5e VMEM (proven deviceless via tools/flash_blocks_aot)
_FA_BLOCKS = ((128, 512), (128, 1024), (128, 2048), (256, 256), (256, 512),
              (256, 1024), (256, 2048), (512, 512), (512, 1024),
              (512, 2048), (1024, 512), (2048, 512))


def _fa_candidates(shape):
    sq, sk = int(shape["sq"]), int(shape["sk"])
    cands = [{"block_q": bq, "block_k": bk} for bq, bk in _FA_BLOCKS
             if bq <= sq and bk <= sk]
    default = _fa_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _fa_build(shape, dtype, params, interpret=None):
    import jax

    from apex_tpu.ops.pallas.flash_attention import flash_attention_fwd

    b, h = int(shape.get("b", 4)), int(shape.get("h", 16))
    sq, sk, d = int(shape["sq"]), int(shape["sk"]), int(shape["d"])
    causal = bool(shape.get("causal", True))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), dtype) * 0.2
    k = jax.random.normal(ks[1], (b, h, sk, d), dtype) * 0.2
    v = jax.random.normal(ks[2], (b, h, sk, d), dtype) * 0.2
    scale = 1.0 / (d ** 0.5)
    bq, bk = params["block_q"], params["block_k"]

    def step(i, q, k, v):
        o, _ = flash_attention_fwd(q, k, v, scale=scale, causal=causal,
                                   block_q=bq, block_k=bk,
                                   interpret=interpret)
        return o.astype(q.dtype)

    return step, q, (k, v)


# ----------------------------------------------------- decode_attention


def _da_shape_key(shape) -> ShapeKey:
    # max_len keyed exactly (layout-defining static engine constant; the
    # winner must divide it) — matches serve.attention.resolve_block_k.
    # page_size is a second exact geometry axis (absent: one page a
    # slot, max_len): a chunk must live inside one page, so a winner
    # tuned at one page size cannot apply to another — CODE_VERSIONS
    # bumped to 2 when this axis landed so v1 entries invalidate, and to
    # 4 when its value 0 (a slot-contiguous layout) went.
    # tp_shards (1 = single chip) is a third: a tensor-parallel engine
    # runs this kernel per mesh rank with `heads` = its PER-SHARD head
    # count, and a winner timed unsharded must not apply to a sharded
    # instance (or vice versa) — CODE_VERSIONS bumped to 3 with it so v2
    # entries invalidate cleanly.
    return (("max_len", int(shape["max_len"])),
            ("page_size", _da_unit(shape)),
            ("heads", int(shape["heads"])),
            ("d", int(shape["d"])),
            ("tp_shards", int(shape.get("tp_shards", 1))))


def _da_unit(shape) -> int:
    """The span a chunk must divide: the page."""
    return int(shape.get("page_size") or shape["max_len"])


def _da_defaults(shape):
    from apex_tpu.ops.pallas.tiling import decode_attention_block

    return {"block_k": decode_attention_block(_da_unit(shape))}


def _da_candidates(shape):
    unit = _da_unit(shape)
    cands = [{"block_k": bk} for bk in (128, 256, 512, 1024, 2048)
             if bk <= unit and unit % bk == 0]
    default = _da_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _da_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    b = int(shape.get("b", 8))
    L, h, d = (int(shape["max_len"]), int(shape["heads"]),
               int(shape["d"]))
    ps = _da_unit(shape)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    bk = params["block_k"]

    # time the page-table gather path of a busy pool (every slot's table
    # maps distinct live pages) at contexts a serving step sees, not at
    # full slots alone: the loop's trip count follows the longest slot,
    # so the slots are spread from an eighth of max_len to half of it (a
    # smaller chunk then saves masked rows and pays trips, as in serving)
    from apex_tpu.serve.attention import paged_attention
    from apex_tpu.serve.kv_cache import init_paged_cache

    positions = jnp.linspace(L // 8, L // 2, b).astype(jnp.int32)
    mp = L // ps
    P = b * mp + 1                             # +1: the reserved null page
    pool = init_paged_cache(1, b, L, ps, P, h, d, dtype)
    cache = pool.replace(
        k=jax.random.normal(ks[1], pool.k.shape, dtype) * 0.2,
        v=jax.random.normal(ks[2], pool.v.shape, dtype) * 0.2,
        page_table=jnp.arange(1, P, dtype=jnp.int32).reshape(b, mp))
    # the queries meet the pool's head axis, allocated in whole tiles
    q = jax.random.normal(ks[0], (b, pool.k.shape[-2], d), dtype) * 0.2

    def step(i, q, cache):
        return paged_attention(q, cache, 0, positions, block_k=bk)

    return step, q, (cache,)


# ------------------------------------------------------ flat optimizers


def _flat_shape_key(shape) -> ShapeKey:
    rows = int(shape["numel"]) // 128
    return (("rows", pow2_bucket(rows)),)


def _flat_defaults(shape):
    from apex_tpu.ops.pallas.fused_adam_kernel import _pick_block_rows

    return {"block_rows": _pick_block_rows(int(shape["numel"]) // 128)}


def _flat_candidates(shape):
    rows = int(shape["numel"]) // 128
    cands = [{"block_rows": br}
             for br in _row_block_candidates(rows, ceiling=2048, floor=64)]
    default = _flat_defaults(shape)
    if default not in cands:
        cands.append(default)
    return cands


def _adam_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_adam_kernel import fused_adam_flat

    n = int(shape["numel"])
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    br = params["block_rows"]

    def step(i, st, g):
        p, m, v = st
        return tuple(fused_adam_flat(p, g, m, v, lr=1e-3, step=i + 1,
                                     block_rows=br, interpret=interpret))

    return step, (p, m, v), (g,)


def _lamb_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_opt_kernels import fused_lamb_flat

    n = int(shape["numel"])
    rows = n // 128
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    # one-tensor buffer: every row belongs to segment 0
    row_ids = jnp.zeros((rows,), jnp.int32)
    br = params["block_rows"]

    def step(i, st, g, row_ids):
        p, m, v = st
        p, m, v, _ = fused_lamb_flat(p, g, m, v, row_ids, num_tensors=1,
                                     lr=1e-3, step=i + 1, block_rows=br,
                                     interpret=interpret)
        return (p, m, v)

    return step, (p, m, v), (g, row_ids)


def _novograd_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_opt_kernels import fused_novograd_flat

    n = int(shape["numel"])
    rows = n // 128
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    m = jnp.zeros((n,), jnp.float32)
    vt = jnp.zeros((1,), jnp.float32)  # per-tensor 2nd-moment norm state
    row_ids = jnp.zeros((rows,), jnp.int32)
    br = params["block_rows"]

    def step(i, st, g, row_ids):
        p, m, vt = st
        return tuple(fused_novograd_flat(
            p, g, m, vt, row_ids, num_tensors=1, lr=1e-3, step=i + 1,
            block_rows=br, interpret=interpret))

    return step, (p, m, vt), (g, row_ids)


def _adagrad_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_opt_kernels import fused_adagrad_flat

    n = int(shape["numel"])
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    h = jnp.zeros((n,), jnp.float32)
    br = params["block_rows"]

    def step(i, st, g):
        p, h = st
        return tuple(fused_adagrad_flat(p, g, h, lr=1e-3, block_rows=br,
                                        interpret=interpret))

    return step, (p, h), (g,)


def _sgd_build(shape, dtype, params, interpret=None):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops.pallas.fused_sgd_kernel import fused_sgd_flat

    n = int(shape["numel"])
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), dtype) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), dtype)
    buf = jnp.zeros((n,), jnp.float32)
    br = params["block_rows"]

    def step(i, st, g):
        p, buf = st
        return tuple(fused_sgd_flat(p, g, buf, lr=1e-3, momentum=0.9,
                                    block_rows=br, interpret=interpret))

    return step, (p, buf), (g,)


SPECS: Dict[str, KernelSpec] = {}


def _register(spec: KernelSpec) -> None:
    SPECS[spec.name] = spec


_register(KernelSpec(
    "layer_norm", _ln_shape_key, _ln_defaults, _ln_candidates, _ln_build,
    default_shapes=({"rows": 8192, "hidden": 4096},)))
_register(KernelSpec(
    "softmax", _sm_shape_key, _sm_defaults, _sm_candidates, _sm_build,
    default_shapes=({"B": 128, "sq": 1024, "sk": 1024},)))
_register(KernelSpec(
    "softmax_causal_chunked", _smc_shape_key, _smc_defaults,
    _smc_candidates, _smc_build,
    default_shapes=({"B": 128, "sq": 1024, "sk": 1024},)))
_register(KernelSpec(
    "group_norm", _gn_shape_key, _gn_defaults, _gn_candidates, _gn_build,
    default_shapes=({"n": 2, "hw": 4096, "c": 256, "groups": 32},)))
_register(KernelSpec(
    "flash_attention", _fa_shape_key, _fa_defaults, _fa_candidates,
    _fa_build,
    default_shapes=({"b": 4, "h": 16, "sq": 2048, "sk": 2048, "d": 64,
                     "causal": True},)))
_register(KernelSpec(
    "decode_attention", _da_shape_key, _da_defaults, _da_candidates,
    _da_build,
    default_shapes=({"b": 8, "max_len": 2048, "page_size": 256,
                     "heads": 16, "d": 64},)))
_register(KernelSpec(
    "fused_adam", _flat_shape_key, _flat_defaults, _flat_candidates,
    _adam_build, default_shapes=({"numel": 134_217_728},),
    dtype_agnostic=True))
_register(KernelSpec(
    "fused_sgd", _flat_shape_key, _flat_defaults, _flat_candidates,
    _sgd_build, default_shapes=({"numel": 134_217_728},),
    dtype_agnostic=True))
_register(KernelSpec(
    "fused_lamb", _flat_shape_key, _flat_defaults, _flat_candidates,
    _lamb_build, default_shapes=({"numel": 134_217_728},),
    dtype_agnostic=True))
_register(KernelSpec(
    "fused_novograd", _flat_shape_key, _flat_defaults, _flat_candidates,
    _novograd_build, default_shapes=({"numel": 134_217_728},),
    dtype_agnostic=True))
_register(KernelSpec(
    "fused_adagrad", _flat_shape_key, _flat_defaults, _flat_candidates,
    _adagrad_build, default_shapes=({"numel": 134_217_728},),
    dtype_agnostic=True))


def spec(kernel: str) -> KernelSpec:
    try:
        return SPECS[kernel]
    except KeyError:
        raise KeyError(
            f"unknown tunable kernel {kernel!r}; known: "
            f"{sorted(SPECS)}") from None


def kernels() -> Sequence[str]:
    return sorted(SPECS)
