"""TPU-native inference engine — static-shape KV cache, one-jit decode,
continuous batching.

Serving throughput on TPU is won by keeping the compiled graph stable
(TokenWeave, arXiv:2505.11329; operation-fusion serving, arXiv:2502.17728):
XLA rewards a single jitted decode step over fixed-shape buffers, and
punishes anything that changes shapes mid-stream with a recompile that
costs more than the tokens it produces. This package is built around that
one invariant:

- :mod:`~apex_tpu.serve.kv_cache` — the static-shape KV cache pytree: a
  paged pool (``[n_layer, num_pages, page_size, heads, head_dim]``), a
  per-slot page table and a per-slot length vector; one page a slot by
  default. Appends and evictions are pure, jittable, mask-driven ops:
  batch membership changes (a request finishes, another backfills its
  slot) never change a shape and therefore never trigger a recompile.
- :mod:`~apex_tpu.serve.engine` — the AOT-lowered batched ``prefill``
  (one forward a call over the rows it admits: ``[rows, bucket]``
  where the batch fits the bucket's small program, else ``[num_slots,
  bucket]``) and the ONE jitted ``decode_step``; incremental decode matches prefill to fp32 rounding,
  and slots are arithmetically isolated from each other.
- :mod:`~apex_tpu.serve.scheduler` — continuous batching: an admission
  queue, slot assignment, per-request EOS/max-token termination, eviction
  and backfill between decode steps, with TTFT/latency/throughput
  accounting and ``serve_*`` events on the telemetry bus.
- :mod:`~apex_tpu.serve.resilience` — production failure semantics:
  bounded-queue admission with pluggable load shedding
  (:class:`AdmissionController`), graceful degradation under sustained
  overload, the per-tick :class:`TickJournal`, and the
  :class:`ServeSupervisor` warm-restart loop (a fatal tick exception
  rolls back to the last journaled tick; every submitted request reaches
  exactly one terminal status). Per-request deadlines live on
  :class:`Request` (``deadline_ms``) and are swept every tick.
- :mod:`~apex_tpu.serve.fleet` — :class:`FleetController`: the control
  plane above N engine replicas (thread-backed so CPU tier-1 fakes a
  pod) — heartbeat replica health (:class:`ReplicaRegistry`),
  least-loaded + burn-rate-aware routing with bounded retry and hedged
  dispatch, failover re-dispatch off dead replicas (exactly-once
  terminal status by request id), and drain/rolling restart that never
  drops admitting capacity below N-1.
- :mod:`~apex_tpu.serve.metrics` — :class:`ServeMetrics`: live per-tenant
  accounting (bounded-cardinality counters, TTFT/latency histograms,
  occupancy gauges) into an :class:`apex_tpu.monitor.export.MetricsRegistry`
  plus per-tick SLO burn-rate evaluation — the layer
  ``--metrics-port``/``--metrics-snapshot`` scrape and merge.
- :mod:`~apex_tpu.serve.tp` — tensor-parallel serving: shard params and
  the KV pool on the HEAD axis over a ``NamedSharding`` mesh and lower
  the one decode step (and each prefill bucket) under ``shard_map`` —
  one compile per mesh shape, with per-layer collectives overlapped
  TokenWeave-style (``tp_sync="overlap"``) or relaxed
  (``tp_sync="relaxed"``), and the default exact mode bit-identical in
  fp32 to the single-chip engine at equal ``block_k``.
- :mod:`~apex_tpu.serve.model` — the model seam: what a model gives the
  engine (its cache constructor, its token forward, the modes it
  refuses); GPT-2 and DeepSeek-V3 behind it. :mod:`~apex_tpu.serve.moe`
  is the serving expert layer of the latter: a rank's share of an
  expert-parallel layer, no token dropped.
- :mod:`~apex_tpu.serve.cli` — ``apex-tpu-serve``: load a model config,
  run a scripted or stdin request stream, print per-request stats.

See docs/serving.md for the architecture, the slot lifecycle, and the
overload/failure contracts.
"""

from apex_tpu.serve.engine import Engine, EngineConfig  # noqa: F401
from apex_tpu.serve.fleet import (EngineReplica,  # noqa: F401
                                  FleetController, FleetStats,
                                  FleetTraceHarness, ReplicaRegistry)
from apex_tpu.serve.kv_cache import (PagedKVCache,  # noqa: F401
                                     evict_slots, init_paged_cache,
                                     paged_write_token)
from apex_tpu.serve.metrics import ServeMetrics  # noqa: F401
from apex_tpu.serve.resilience import (SHED_POLICIES,  # noqa: F401
                                       AdmissionController,
                                       ServeSupervisor, TickJournal)
from apex_tpu.serve.scheduler import (Request, ServeScheduler,  # noqa: F401
                                      ServeStats)

__all__ = [
    "Engine", "EngineConfig", "PagedKVCache", "init_paged_cache",
    "paged_write_token", "evict_slots", "Request", "ServeScheduler",
    "ServeStats",
    "AdmissionController", "TickJournal", "ServeSupervisor",
    "SHED_POLICIES", "ServeMetrics",
    "FleetController", "EngineReplica", "ReplicaRegistry", "FleetStats",
    "FleetTraceHarness",
]
