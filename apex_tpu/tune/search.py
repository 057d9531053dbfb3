"""Autotune search: time real compiled kernel calls per candidate tile
geometry and persist the winner.

Timing rides :func:`apex_tpu.utils.benchtime.timed_steps` — K chained
steps inside one jitted ``fori_loop`` with a data-dependent host fetch —
the same methodology as ``bench.py`` (see docs/performance.md). On a
CPU host the kernels run in interpret mode, which only exercises the
machinery (the CLI smoke test); real tuning needs the chip.
``APEX_TPU_FORCE_COMPILED`` is NOT a
tuning path: under it ``tuned_params`` deliberately skips the cache
(deviceless AOT has no trustworthy device identity), so entries warmed
that way would be dead on arrival.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

from apex_tpu.tune import registry
from apex_tpu.tune.api import record_tuned
from apex_tpu.tune.cache import cache_key, code_version, device_key
from apex_tpu.utils.logging import publish_event


def autotune_kernel(kernel: str, shape: Dict[str, Any], dtype=None, *,
                    iters: int = 10, floor_s: Optional[float] = None,
                    max_candidates: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    save: bool = True) -> Dict[str, Any]:
    """Search the candidate geometries for ``kernel`` at ``shape`` and
    store the fastest in the tune cache.

    Returns a result record ``{kernel, key, best, best_ms, default,
    default_ms, candidates: [...]}``. Candidates that fail to compile or
    run are recorded with an ``error`` and skipped — a geometry that
    exceeds VMEM must not kill the warm-up sweep.
    """
    import jax.numpy as jnp

    from apex_tpu.utils.benchtime import measure_fetch_floor, timed_steps

    spec = registry.spec(kernel)
    if dtype is None:
        dtype = jnp.bfloat16
    dtype = jnp.dtype(dtype)
    if floor_s is None:
        floor_s = measure_fetch_floor()
    # the softmax-family heuristics are itemsize-dependent; derive it from
    # the ACTUAL dtype unless the workload pinned it, so the registry's
    # "default" candidate is exactly what the kernel call site would pick
    shape = dict(shape)
    shape.setdefault("itemsize", dtype.itemsize)
    # flat optimizers key dtype=None: one entry serves bf16 params, fp32
    # master weights, and every other element type (same row streaming)
    key_dtype = None if spec.dtype_agnostic else dtype
    defaults = spec.defaults(shape)
    cands = spec.candidates(shape)
    if max_candidates is not None:
        max_candidates = max(1, max_candidates)
    if max_candidates is not None and len(cands) > max_candidates:
        # keep the default in the truncated sweep: the heuristic must
        # always be allowed to win
        kept = cands[:max_candidates]
        if defaults not in kept:
            kept[-1] = defaults
        cands = kept

    rows: List[Dict[str, Any]] = []
    best: Optional[Dict[str, Any]] = None
    default_ms: Optional[float] = None
    for params in cands:
        row: Dict[str, Any] = {"params": dict(params)}
        try:
            t0 = time.perf_counter()
            step, state, consts = spec.build(shape, dtype, params,
                                             interpret=interpret)
            ms = timed_steps(step, state, iters=iters, consts=consts,
                             floor_s=floor_s, donate=False)
            row["ms"] = round(ms, 4)
            row["wall_s"] = round(time.perf_counter() - t0, 2)
        except Exception as e:  # VMEM blowout / Mosaic reject: skip
            row["error"] = f"{type(e).__name__}: {e}"
            rows.append(row)
            continue
        rows.append(row)
        if params == defaults:
            default_ms = row["ms"]
        if best is None or row["ms"] < best["ms"]:
            best = row

    # one allocator sample per kernel sweep (hbm_snapshot on the bus):
    # tuning is an AOT point — a candidate geometry that balloons HBM
    # shows up in the run's memory accounting, not just its timing.
    # Silent off-TPU (CPU backends report no allocator stats).
    from apex_tpu.monitor.memory import sample_device_memory

    sample_device_memory(f"tune:{kernel}", candidates=len(rows))

    result: Dict[str, Any] = {
        "kernel": kernel,
        "shape": dict(shape),
        "dtype": str(dtype.name),
        "device": device_key(),
        "default": defaults,
        "default_ms": default_ms,
        "candidates": rows,
    }
    if best is None:
        result["error"] = "no candidate completed"
        result["key"] = cache_key(kernel, spec.shape_key(shape), key_dtype,
                                  device_key(), code_version(kernel))
        publish_event("kernel_autotune_failed", kernel=kernel,
                      key=result["key"], emit=False)
        return result

    result["best"] = best["params"]
    result["best_ms"] = best["ms"]
    if default_ms and default_ms > 0:
        result["speedup_vs_default"] = round(default_ms / best["ms"], 3)
    result["key"] = record_tuned(
        kernel, spec.shape_key(shape), best["params"], dtype=key_dtype,
        meta={"ms": best["ms"], "default_ms": default_ms,
              "iters": iters, "shape": dict(shape)},
        save=save)
    return result


def warm_cache(workload: List[Dict[str, Any]], *, iters: int = 10,
               max_candidates: Optional[int] = None,
               interpret: Optional[bool] = None) -> List[Dict[str, Any]]:
    """Run :func:`autotune_kernel` for every ``{kernel, shape, dtype?}``
    entry of a workload spec; returns the result records. The cache file
    is saved after each kernel (a mid-sweep crash keeps earlier wins)."""
    results = []
    for entry in workload:
        results.append(autotune_kernel(
            entry["kernel"], entry["shape"], entry.get("dtype"),
            iters=iters, max_candidates=max_candidates,
            interpret=interpret, save=True))
    return results
