"""Unified telemetry sink — metrics rows, event mirror, trace spans.

One object merges the pieces the seed already carried in fragments:

- per-step metric rows ride :class:`~apex_tpu.utils.logging.MetricLogger`
  (device arrays buffered, ONE batched host sync at flush) to JSONL and/or
  console;
- every ``structured_warning``/``publish_event`` record in the process —
  checkpoint retries, overflow storms, preemption — is mirrored into the
  same JSONL via the event bus, so the run log is one stream;
- :meth:`Telemetry.span` opens a named trace range (``prof.annotate``, the
  NVTX analog, visible in the device trace) AND emits a wall-clock span
  event, so host-side phases line up with the profiler timeline;
- per-step ``step_ms`` / ``tokens_per_s`` / ``mfu`` are derived host-side
  from loop wall clock and the XLA cost model
  (:func:`~apex_tpu.monitor.metrics.step_flops`,
  ``prof.chip_peaks``) — nothing extra crosses the
  host-device boundary.

Multihost: by default only process 0 writes (``rank_zero_only=True``);
other ranks keep timing/goodput accounting but emit nothing.

Row schema (metric rows; ``None``-valued fields are simply absent):
``{step, t, loss, grad_norm, param_norm, update_norm, found_inf,
loss_scale, step_ms, tokens_per_s, mfu, ...extras}``. Event rows carry an
``"event"`` key instead of ``"step"``. See docs/observability.md.
"""

from __future__ import annotations

import contextlib
import json
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

from apex_tpu.monitor.goodput import GoodputLedger
from apex_tpu.monitor.metrics import TrainMetrics, step_flops
from apex_tpu.utils.logging import (MetricLogger, publish_event,
                                    subscribe_events)
from apex_tpu.utils.prof import annotate, chip_peaks

# the keys every instrumented train loop's rows must carry (the bench
# regression gate and the schema smoke test validate against this)
PERF_ROW_KEYS = ("step", "loss", "grad_norm", "loss_scale", "step_ms",
                 "tokens_per_s", "mfu")


def validate_row(row: Dict[str, Any],
                 require: Iterable[str] = PERF_ROW_KEYS) -> Dict[str, Any]:
    """Validate one metric row against the telemetry schema.

    Raises ``ValueError`` naming the offending key; returns the row so the
    call composes. Event rows (``"event"`` key) are rejected — filter them
    out first (:func:`read_jsonl` does).
    """
    if not isinstance(row, dict):
        raise ValueError(f"telemetry row is {type(row).__name__}, not dict")
    if "event" in row:
        raise ValueError(f"event row passed as metric row: {row!r}")
    for key in require:
        if key not in row:
            raise ValueError(f"telemetry row missing {key!r}: {row!r}")
    for key, val in row.items():
        if not isinstance(val, (int, float, bool, str, type(None))):
            raise ValueError(
                f"telemetry row field {key!r} is non-scalar "
                f"{type(val).__name__} (device arrays must be flushed)")
    if not isinstance(row.get("step"), int):
        raise ValueError(f"telemetry row 'step' not an int: {row!r}")
    return row


def read_jsonl(path: str) -> Tuple[List[Dict[str, Any]],
                                   List[Dict[str, Any]]]:
    """Parse a telemetry JSONL file into ``(metric_rows, event_rows)``."""
    metrics: List[Dict[str, Any]] = []
    events: List[Dict[str, Any]] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            (events if "event" in rec else metrics).append(rec)
    return metrics, events


class Telemetry:
    """The one observability object a training loop needs.

    Typical wiring (see bench_cli._telemetry_bench for the full pattern)::

        tel = Telemetry("run.jsonl", tokens_per_step=B * S).calibrate(
            step, state, batch)                  # MFU from the cost model
        for i in range(steps):
            state, tm = step(i, state, batch)    # ONE jitted call
            skipped = bool(tm.found_inf)         # the loop's one host sync
            tel.log_step(i, metrics=tm, skipped=skipped)
        tel.close()
        print(tel.summary())

    ``log_step`` never syncs: metric values stay device arrays until the
    batched flush. ``step_ms`` is wall clock between successive
    ``log_step`` calls (honest as long as the loop consumes something
    data-dependent per step — the ``found_inf`` fetch above).
    """

    def __init__(self, jsonl_path: Optional[str] = None, *,
                 console_every: int = 0, stream=None,
                 tokens_per_step: Optional[float] = None,
                 flops_per_step: Optional[float] = None,
                 chip: Optional[str] = None,
                 rank_zero_only: bool = True,
                 goodput: bool = True,
                 mirror_events: bool = True,
                 flush_every: int = 50,
                 trace_jsonl: Optional[str] = None,
                 registry=None):
        if rank_zero_only:
            import jax

            self.enabled = jax.process_index() == 0
        else:
            self.enabled = True
        self.jsonl_path = jsonl_path if self.enabled else None
        # span-tree tracing (monitor.trace): trace_jsonl enables the
        # process tracer for this run and streams completed spans as a
        # Perfetto/Chrome-trace JSON; close() restores the previous tracer
        self.tracer = None
        self._trace_writer = None
        self._prev_tracer = None
        if trace_jsonl and self.enabled:
            from apex_tpu.monitor.trace import (ChromeTraceWriter, Tracer,
                                                set_tracer)

            self.tracer = Tracer(enabled=True)
            self._prev_tracer = set_tracer(self.tracer)
            self._trace_writer = ChromeTraceWriter(trace_jsonl)
        if self.jsonl_path:
            # per-RUN sink: truncate any previous capture — mixed-run rows
            # would silently skew check_regression's medians
            open(self.jsonl_path, "w").close()
        self.flush_every = flush_every
        self._rows_since_flush = 0
        self.tokens_per_step = tokens_per_step
        self.flops_per_step = flops_per_step
        self.chip = chip
        self._peak = None
        self._last_t: Optional[float] = None
        self.logger = MetricLogger(self.jsonl_path,
                                   print_every=console_every, stream=stream)
        self.ledger: Optional[GoodputLedger] = (
            GoodputLedger().attach() if goodput else None)
        # live-metrics registry (monitor.export): the training-side seam
        # of the serving SLO layer — step-time lands in a mergeable
        # histogram so per-rank training snapshots aggregate exactly like
        # serving ranks do (tools/metrics_merge.py); all ranks record
        # (fleet view sums), only rank 0 writes files
        self.registry = registry
        if registry is not None:
            self._m_steps = registry.counter(
                "train_steps_total", "train steps recorded")
            self._m_skipped = registry.counter(
                "train_skipped_steps_total",
                "steps lost to overflow skips")
            self._m_step_hist = registry.histogram(
                "train_step_seconds", "wall clock per train step")
        self._unsubscribe = None
        if mirror_events and self.jsonl_path:
            self._unsubscribe = subscribe_events(self._on_event)

    # ---- cost model -----------------------------------------------------
    def calibrate(self, fn, *args,
                  tokens_per_step: Optional[float] = None) -> "Telemetry":
        """Set ``flops_per_step`` from the XLA cost model of ``fn(*args)``
        (the compiled step function — already-jitted callables reuse their
        lowering). Inherits roofline's operand-byte caveats; see
        docs/observability.md. Also captures the step's STATIC memory
        reservation (``compiled.memory_analysis()``) as an
        ``hbm_snapshot`` event — the bench's AOT point for the memory
        accounting layer (monitor.memory)."""
        from apex_tpu.monitor.metrics import compile_for_analysis

        # ONE lower+compile serves both the cost model and the memory
        # analysis (step_flops without it would compile a second copy)
        compiled = compile_for_analysis(fn, *args)
        self.flops_per_step = step_flops(fn, *args, compiled=compiled)
        if compiled is not None:
            from apex_tpu.monitor.memory import publish_compiled_memory

            publish_compiled_memory("calibrated_step", compiled)
        if tokens_per_step is not None:
            self.tokens_per_step = tokens_per_step
        return self

    def _peak_flops(self) -> float:
        if self._peak is None:
            self._peak = chip_peaks(self.chip)["tflops"] * 1e12
        return self._peak

    # ---- per-step path --------------------------------------------------
    def start(self) -> "Telemetry":
        """Open the timing window for the first step (otherwise the first
        ``log_step`` row has no ``step_ms``)."""
        self._last_t = time.perf_counter()
        return self

    def log_step(self, step: int, metrics: Optional[TrainMetrics] = None, *,
                 loss: Any = None, tokens: Optional[float] = None,
                 step_ms: Optional[float] = None, skipped: bool = False,
                 **extra: Any) -> None:
        """Record one step. Device arrays in ``metrics``/``loss``/``extra``
        are buffered as-is (no sync) and batch-fetched at flush."""
        now = time.perf_counter()
        if step_ms is None and self._last_t is not None:
            step_ms = (now - self._last_t) * 1e3
        self._last_t = now

        fields: Dict[str, Any] = metrics.to_dict() if metrics is not None \
            else {}
        if loss is not None:
            fields["loss"] = loss
        fields.update(extra)
        if step_ms is not None:
            fields["step_ms"] = round(step_ms, 3)
            step_s = step_ms / 1e3
            n_tokens = tokens if tokens is not None else self.tokens_per_step
            if n_tokens is not None and step_s > 0:
                fields["tokens_per_s"] = round(n_tokens / step_s, 1)
            if self.flops_per_step is not None and step_s > 0:
                fields["mfu"] = round(
                    self.flops_per_step / step_s / self._peak_flops(), 6)
        if self.ledger is not None:
            # no timing window yet (first row before start()): count the
            # step/skip with zero seconds rather than dropping it
            self.ledger.record_step(step_ms / 1e3 if step_ms else 0.0,
                                    productive=not skipped)
        if self.registry is not None:
            self._m_steps.inc()
            if skipped:
                self._m_skipped.inc()
            if step_ms is not None:
                self._m_step_hist.record(step_ms / 1e3)
        if self.enabled:
            self.logger.log(step, **fields)
            self._rows_since_flush += 1
            # bound the buffer (and the JSONL's staleness): a crash must
            # not take a long run's whole metric history with it
            if self.flush_every and \
                    self._rows_since_flush >= self.flush_every:
                self.flush()

    # ---- spans + events -------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """Named range: a device-trace annotation (shows in the profiler
        timeline) plus a wall-clock span event on the bus (mirrored into
        the JSONL)."""
        t0 = time.perf_counter()
        with annotate(name):
            yield
        publish_event("span", name=name,
                      ms=round((time.perf_counter() - t0) * 1e3, 3))

    def event(self, name: str, **fields: Any) -> Dict[str, Any]:
        """Publish a structured info event on the process bus (lands in
        this sink's JSONL via the mirror, and in any attached ledger)."""
        return publish_event(name, emit=False, **fields)

    def _on_event(self, rec: Dict[str, Any]) -> None:
        # the mirror: every bus record becomes one JSONL line alongside the
        # metric rows (append-per-event; events are low-rate by design).
        # span_open/span_close are the exception — they are per-span and
        # belong in the dedicated Chrome-trace file, not the metric log
        if rec.get("event") in ("span_open", "span_close"):
            return
        with open(self.jsonl_path, "a") as f:
            f.write(json.dumps(rec, sort_keys=True, default=float) + "\n")

    # ---- lifecycle ------------------------------------------------------
    def flush(self) -> None:
        self.logger.flush()
        self._rows_since_flush = 0

    def summary(self) -> Dict[str, Any]:
        """Flush, then return running means plus the goodput ledger."""
        out: Dict[str, Any] = {"metrics": self.logger.summary()}
        if self.ledger is not None:
            out["goodput"] = self.ledger.summary()
        return out

    def close(self) -> None:
        self.flush()
        if self._unsubscribe is not None:
            self._unsubscribe()
            self._unsubscribe = None
        if self.ledger is not None:
            self.ledger.detach()
        if self._trace_writer is not None:
            self._trace_writer.close()
            self._trace_writer = None
        if self._prev_tracer is not None:
            from apex_tpu.monitor.trace import set_tracer

            set_tracer(self._prev_tracer)
            self._prev_tracer = None

    def __enter__(self) -> "Telemetry":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()
