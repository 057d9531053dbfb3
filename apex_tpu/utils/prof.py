"""Tracing / profiling — the framework's observability layer.

Reference status (SURVEY §5): apex has no first-class tracing subsystem —
ad-hoc NVTX ranges (``torch.cuda.nvtx``) and ``cudaProfilerStart`` in tests,
``--prof`` iteration caps in examples. The TPU framework makes it first-class:

- ``profile(logdir)``: context manager over ``jax.profiler`` producing a
  TensorBoard-loadable device trace (the nsys/nvtx equivalent).
- ``annotate(name, **attrs)``: named trace ranges (the NVTX
  ``range_push/pop`` analog) that show up in the trace viewer.
- ``StepTimer``: the examples' AverageMeter, with proper device sync.
"""

from __future__ import annotations

import contextlib
import time
from typing import Optional

import jax


# logdir of the live profile() region, if any — jax.profiler raises an
# opaque internal error on nested start_trace; we fail with context first
_active_profile: Optional[str] = None


@contextlib.contextmanager
def profile(logdir: str = "/tmp/apex_tpu_trace"):
    """Capture a device trace for the enclosed region (≈ nsys profile).

    Not reentrant (one device trace per process at a time): a nested call
    raises ``RuntimeError`` naming the already-active logdir instead of
    jax's opaque "trace already started" internals.
    """
    global _active_profile
    if _active_profile is not None:
        raise RuntimeError(
            f"profile() is not reentrant: a device trace is already being "
            f"captured to {_active_profile!r} — close it before opening "
            f"another (use annotate() for nested named ranges)")
    jax.profiler.start_trace(logdir)
    _active_profile = logdir
    try:
        yield logdir
    finally:
        _active_profile = None
        jax.profiler.stop_trace()


# bound on annotate()'s first call, not at import: monitor/__init__ imports
# telemetry, which imports this module
_get_tracer = None


def annotate(name: str, **attrs):
    """Named range inside a trace (≈ nvtx.range_push/pop).

    Always opens a ``jax.profiler.TraceAnnotation`` carrying ``attrs``
    (visible in the device-trace viewer, on the host plane of the same
    ``.xplane.pb`` as the device's operations; with no profiler session
    running it costs about half a microsecond). When the process span
    tracer is enabled (:func:`apex_tpu.monitor.trace.set_tracer`, or
    ``Telemetry(trace_jsonl=...)``), the range opens a span in the
    trace tree instead, with ``attrs`` attached, and that span enters
    the annotation itself — host annotations and the span timeline stay
    in lockstep because they are the same call.

    ``attrs`` are values the host already holds (ints, short strings):
    an annotation takes them when it opens, so an attribute rides the
    first range that opens after its value is known.
    """
    global _get_tracer
    if _get_tracer is None:
        from apex_tpu.monitor.trace import get_tracer as _get_tracer
    tracer = _get_tracer()
    if tracer.enabled:
        return tracer.span(name, **attrs)
    return jax.profiler.TraceAnnotation(name, **attrs)


class StepTimer:
    """Average/last step timing with device synchronization (the examples'
    AverageMeter; ``block`` forces completion like cudaDeviceSynchronize).

    Context-manager form times the enclosed region::

        timer = StepTimer()
        with timer:                      # start()/stop() around the body
            out = step(state)
            timer.block(out)             # sync on `out` at exit: honest
                                         # wall clock on an async runtime

    The explicit ``start()``/``stop(block_on=...)`` pair remains for loops
    that want manual control.
    """

    def __init__(self):
        self.reset()

    def __enter__(self) -> "StepTimer":
        self.start()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        block_on, self._block_on = self._block_on, None
        if exc_type is not None:
            # aborted step: recording its partial duration would silently
            # skew avg/total low — drop the window instead
            self._t0 = None
            return
        self.stop(block_on=block_on)

    def block(self, block_on) -> "StepTimer":
        """Arm the enclosing ``with`` block to ``block_until_ready`` on
        ``block_on`` when it exits."""
        self._block_on = block_on
        return self

    def reset(self):
        self.count = 0
        self.total = 0.0
        self.last = 0.0
        self._t0 = None
        self._block_on = None

    def start(self):
        self._t0 = time.perf_counter()

    def stop(self, block_on=None):
        if self._t0 is None:
            raise RuntimeError(
                "StepTimer.stop() called before start() (or after reset()) "
                "— call start() at the top of the step")
        if block_on is not None:
            jax.block_until_ready(block_on)
        self.last = time.perf_counter() - self._t0
        self.total += self.last
        self.count += 1
        return self.last

    @property
    def avg(self) -> float:
        return self.total / max(self.count, 1)


def _costs_module():
    """``apex_tpu.monitor.costs`` WITHOUT triggering the monitor package
    ``__init__`` (which imports telemetry → this module: a cycle, and
    ``apex_tpu/__init__`` imports utils before monitor). The module is
    import-time stdlib-only by contract, so a direct by-path load is
    cheap; registered under its canonical name so the package import
    later reuses this instance instead of making a second copy."""
    import importlib.util
    import os
    import sys

    mod = sys.modules.get("apex_tpu.monitor.costs")
    if mod is None:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "monitor", "costs.py")
        spec = importlib.util.spec_from_file_location(
            "apex_tpu.monitor.costs", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["apex_tpu.monitor.costs"] = mod
        spec.loader.exec_module(mod)
    return mod


# chip peaks for roofline reporting (bf16 TFLOPs, HBM GB/s) — derived
# from the ledger's chip-spec table (monitor/costs.py owns the numbers;
# the "cpu" fallback entry is non-gating there and excluded here, where
# peaks always mean real silicon)
CHIP_PEAKS = {
    chip: {"hbm_gbps": spec["hbm_gbps"], "tflops": spec["tflops"]}
    for chip, spec in _costs_module().CHIP_SPECS.items() if spec["gating"]
}

# device_kind substrings → CHIP_PEAKS generation, most specific first
# (``"v5"`` alone is the v5p kind string "TPU v5"; the lite parts say so)
_KIND_TO_GEN = (
    ("v5e", "v5e"), ("v5 lite", "v5e"), ("v5litepod", "v5e"),
    ("v6e", "v6e"), ("v6 lite", "v6e"), ("trillium", "v6e"),
    ("v5p", "v5p"), ("v5", "v5p"),
)


def detect_chip(devices=None) -> Optional[str]:
    """Map the attached TPU's ``device_kind`` to a :data:`CHIP_PEAKS` key.

    Returns ``None`` off-TPU. A TPU whose kind is not in the table is a
    ``ValueError`` — a new generation must be added with its published
    peaks, never measured against another chip's. ``devices`` is
    injectable for tests; defaults to ``jax.devices()`` (a backend that
    cannot initialize raises).
    """
    if devices is None:
        devices = jax.devices()
    if not devices or getattr(devices[0], "platform", None) != "tpu":
        return None
    kind = str(getattr(devices[0], "device_kind", "")).lower()
    for pat, gen in _KIND_TO_GEN:
        if pat in kind:
            return gen
    raise ValueError(
        f"unknown TPU device_kind {kind!r}: add the generation and its "
        f"published peaks to apex_tpu.monitor.costs.CHIP_SPECS and "
        f"apex_tpu.utils.prof._KIND_TO_GEN")


def chip_peaks(chip: Optional[str] = None) -> dict:
    """``{"chip", "tflops", "hbm_gbps"}`` for ``chip`` (default: the
    attached TPU's generation). Off-TPU with no explicit ``chip`` the
    v5e row is the stated projection target; a name that is not in the
    table is a ``ValueError``."""
    chip = chip or detect_chip() or "v5e"
    if chip not in CHIP_PEAKS:
        raise ValueError(f"unknown chip {chip!r}; known: "
                         f"{sorted(CHIP_PEAKS)}")
    return {"chip": chip, **CHIP_PEAKS[chip]}


def roofline(fn, *args, chip: str | None = None,
             measured_ms: float | None = None) -> dict:
    """Compile ``fn(*args)`` and report XLA's own cost model against the
    chip roofline — the first-class version of the analysis the reference
    does ad hoc with nvprof (SURVEY §5 tracing row).

    Returns ``{flops, bytes, t_mxu_ms, t_hbm_ms, bound, ideal_ms}`` plus,
    when ``measured_ms`` is given, ``achieved_frac`` (ideal/measured —
    how close the step runs to its own roofline) and the per-resource
    fractions. ``chip`` defaults to the generation auto-detected from
    ``jax.devices()[0].device_kind`` (:func:`chip_peaks`; off-TPU the
    projection target is v5e).

    Caveat on ``bytes``: XLA's "bytes accessed" counts every operand's
    bytes per op, including VMEM-resident reuse that never touches HBM,
    so ``t_hbm_ms`` is an UPPER bound on memory time and fusion-heavy
    programs (conv nets) can legitimately run faster than ``ideal_ms`` —
    ``achieved_frac > 1`` means "beat the operand-byte model", not an
    error (observed: ResNet-50 b128 measures 55 ms vs a 79 ms
    operand-byte bound). ``t_mxu_ms`` has no such slack; ``mxu_frac`` is
    the trustworthy utilization number for compute-bound steps.
    """
    peaks = chip_peaks(chip)
    chip = peaks["chip"]
    compiled = jax.jit(fn).lower(*args).compile()
    rec = _costs_module().xla_cost_record(compiled) or {}
    flops = rec.get("flops", 0.0)
    nbytes = rec.get("bytes_accessed", 0.0)
    t_mxu = flops / (peaks["tflops"] * 1e12) * 1e3
    t_hbm = nbytes / (peaks["hbm_gbps"] * 1e9) * 1e3
    out = {"chip": chip, "flops": flops, "bytes": nbytes,
           "t_mxu_ms": t_mxu, "t_hbm_ms": t_hbm,
           "bound": "mxu" if t_mxu > t_hbm else "hbm",
           "ideal_ms": max(t_mxu, t_hbm)}
    if measured_ms is not None and measured_ms > 0:
        out["measured_ms"] = measured_ms
        out["achieved_frac"] = out["ideal_ms"] / measured_ms
        out["mxu_frac"] = t_mxu / measured_ms
        out["hbm_frac"] = t_hbm / measured_ms
    return out
