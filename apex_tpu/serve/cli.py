"""Console entry point (``apex-tpu-serve``) — run a request stream
through the serving engine and print per-request stats.

Two request sources:

- scripted (default): ``--requests N`` seeded random prompts — the
  repeatable smoke/bench workload;
- ``--stdin``: one request per line, whitespace- or comma-separated token
  ids (the engine speaks token ids; tokenization lives with the caller).

Per request, one JSON line: ``{request_id, state, finish_reason,
prompt_tokens, new_tokens, generated, ttft_s, latency_s, tokens_per_s}``
(load-shed requests additionally carry ``"retriable": true`` — a healthy
or less-loaded replica can serve them); the final line is the aggregate
summary (tokens/s, p50/p99 per-step latency, TTFT, plus the SLO fields
``rejected`` / ``deadline_exceeded`` / ``shed_rate`` / ``restarts``) with
a ``device`` block — platform, ``device_kind``, device count and whether
Pallas ran interpreted — so no record outlives the device it ran on.
``serve_*`` lifecycle events ride the telemetry bus —
``--telemetry-jsonl PATH`` mirrors them (and nothing else crosses the
host boundary per step beyond the sampled tokens).

Production failure semantics (docs/serving.md "Overload and failure
semantics"): ``--deadline-ms`` bounds per-request latency,
``--max-queue`` + ``--shed-policy`` bound the backlog with explicit
rejection, ``--max-restarts N`` arms the tick journal + warm-restart
supervisor so a fatal tick exception recovers instead of killing every
in-flight request.

Paged KV pool (docs/serving.md "Paged KV pool and prefix caching"):
``--page-size N`` cuts the KV pool's pages from a slot's whole
``max_len`` (the default: one page a slot) to N tokens, shared by all
slots (``--num-pages`` sizes the pool; default = every slot's whole
context, size it smaller to overcommit), and
``--prefix-cache`` shares read-only prompt-prefix pages across requests
so a repeated system prompt is prefilled once. The summary's
``prefix_hit_rate`` / ``peak_resident_tokens`` report what the pool
bought; decode still compiles exactly once (``decode_compiles``).

Tensor-parallel decode (docs/serving.md "Tensor-parallel decode"):
``--tp N`` shards the ONE engine — params and the KV pool on the head
axis — over an N-device ``NamedSharding`` mesh and lowers decode plus
each prefill bucket under ``shard_map``; the default ``--tp-sync exact``
mode is bit-identical to the single-chip engine (fp32, equal block_k),
``overlap``/``relaxed`` trade ulps/accuracy for fewer or hidden
collectives. One compile per mesh shape (``decode_compiles`` stays 1);
with ``--metrics-snapshot PATH`` each rank's shard-local view lands at
``PATH.tpK`` and the ``tools/metrics_merge.py`` fold at ``PATH.tp``.
``--tp`` composes with ``--replicas N`` as a **fleet of meshes**: each
replica owns its own N-device ``NamedSharding`` mesh (one compile per
mesh shape; per-rank metrics fold through the same merge). ``--tp-sync``
without a mesh is still refused as inert.

Live metrics and SLOs (docs/observability.md "Live metrics, SLOs, and
fleet aggregation"): ``--metrics-port`` serves Prometheus text at
``/metrics`` + a mergeable JSON snapshot at ``/metrics.json`` while the
scheduler runs, ``--metrics-snapshot PATH`` commits the snapshot
atomically at exit (the per-rank artifact ``tools/metrics_merge.py``
folds into one fleet view), ``--tenants N`` labels the scripted workload
round-robin so the per-tenant breakdown is visible, and ``--slo
NAME=VALUE`` (repeatable) arms burn-rate tracked objectives whose
breach/recovery transitions publish ``serve_slo_breach`` /
``serve_slo_recovered`` bus events.

Serving fleet (docs/serving.md "Fleet failover and draining"):
``--replicas N`` (N >= 2) runs N thread-backed engine replicas under a
:class:`~apex_tpu.serve.fleet.FleetController` — heartbeat replica
health (``--heartbeat-ms``), least-loaded routing with failover
re-dispatch off dead replicas, optional hedged dispatch
(``--hedge-ms``: a request with no terminal status after that long
fires one copy on a second replica, first terminal wins), and
``--drain-on SIGTERM`` (on SIGTERM: stop admitting, shed still-queued
requests as retriable rejections — a healthy fleet can serve them —
finish in-flight ones, exit cleanly). The summary gains ``failovers`` /
``hedge_fired`` / ``migrations``; ``--metrics-snapshot PATH`` writes one
mergeable snapshot PER replica (``PATH.rK``) plus the
``tools/metrics_merge.py`` fleet view at ``PATH`` itself.

Fleet request journeys (docs/observability.md "Fleet request
journeys"): with ``--replicas N``, ``--trace-jsonl PATH`` opens ONE
cross-replica trace per request (``fleet_queue → attempt[replica=k] →
retry/backoff → hedge → failover → terminal``, with each replica's
``queue/prefill/decode`` spans nested under its attempt) — the fleet
plane streams to ``PATH``, each replica to ``PATH.rK``, and
``tools/trace_explain.py`` merges them into per-request latency
attribution that reconciles exactly with the summary and the goodput
ledger. ``--trace-sample RATE`` head-samples the happy path
deterministically (seeded) while tail capture promotes every
bad-outcome journey in full; ``--metrics-port`` serves the merged fleet
view at ``/metrics`` plus per-replica registries at ``/metrics/rK``;
``--flight-recorder PATH`` arms one recorder per replica (``PATH.rK``,
auto-dump on that replica's death or suspect escalation with its
registry row and open spans) plus a fleet-plane recorder at ``PATH``.
Only ``--max-restarts`` remains single-scheduler wiring (exit 2 with
``--replicas > 1``), as are the fleet knobs with ``--replicas 1`` —
never silent no-ops; ``--trace-sample`` without ``--trace-jsonl`` is
equally inert and refused.

Disaggregated prefill/decode (docs/serving.md "Disaggregated
prefill/decode"): ``--roles P:D`` splits the fleet into P dedicated
prefill replicas and D decode replicas (``--replicas``, if given, must
equal P+D). Prefill replicas run the bucketed prefill and stream the
committed prompt pages into a decode replica's pool; every migrated
page is certified on arrival against the prompt's own chain hashes — a
corrupt or torn transfer refuses the handoff and the decode replica
re-prefills locally, bit-exact. Requires ``--page-size`` +
``--prefix-cache`` (pages move through the prefix index).
``--autoscale`` arms the SLO-driven control loop (needs ``--slo`` —
the burn rate is its up signal) scaling the decode pool between
``--min-replicas`` and ``--max-replicas`` by rolling drain / warm
restart; both bounds are inert (exit 2) without it.

Example::

    apex-tpu-serve --config tiny --requests 4 --max-new-tokens 8 \
        --temperature 0 --seed 0
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

import numpy as np


def _parse_roles(spec):
    """``"P:D"`` -> ``(P, D)`` with both >= 1, else None (bad spec or
    no spec — the caller owns the usage error)."""
    if spec is None:
        return None
    p, sep, d = str(spec).partition(":")
    if not sep:
        return None
    try:
        roles = (int(p), int(d))
    except ValueError:
        return None
    return roles if roles[0] >= 1 and roles[1] >= 1 else None


def _parse_line(line: str) -> List[int]:
    toks = line.replace(",", " ").split()
    return [int(t) for t in toks]


def _run_fleet(args, cfg, max_len: int, prompts, slo) -> int:
    """The ``--replicas N`` path: N thread-backed engine replicas under
    a :class:`~apex_tpu.serve.fleet.FleetController`. ``slo`` (one
    parsed tracker, or None) donates its objective DECLARATIONS — each
    replica gets its own tracker instance so burn windows never alias
    across replicas (the burn is the per-replica routing signal).

    Fleet observability (PR 13): ``--trace-jsonl`` opens one
    cross-replica journey per request (fleet file at PATH, one
    Chrome-trace per replica at PATH.rK; ``--trace-sample`` head-samples
    the happy path while tail capture promotes every bad outcome);
    ``--metrics-port`` serves the merged fleet view at ``/metrics`` and
    each replica at ``/metrics/rK``; ``--flight-recorder`` arms one
    recorder per replica (auto-dump on that replica's death/suspect
    transition, with its registry row as context) plus a fleet-level
    recorder guarding the control loop."""
    import signal as signal_mod

    from apex_tpu.serve.disagg import Autoscaler, DisaggController
    from apex_tpu.serve.engine import (Engine, EngineConfig,
                                       init_gpt2_params)
    from apex_tpu.serve.fleet import (EngineReplica, FleetController,
                                      FleetTraceHarness)
    from apex_tpu.serve.model import serving_model
    from apex_tpu.serve.scheduler import Request
    from apex_tpu.utils.env import device_block

    roles = _parse_roles(args.roles)
    if roles:
        # pK prefill the prompts and stream pages; dK decode the streams
        replica_specs = [(f"p{i}", "prefill") for i in range(roles[0])] \
            + [(f"d{i}", "decode") for i in range(roles[1])]
    else:
        replica_specs = [(f"r{i}", "unified")
                         for i in range(args.replicas)]
    replica_ids = [rid for rid, _ in replica_specs]
    want_metrics = bool(args.metrics_snapshot) or slo is not None \
        or args.metrics_port is not None
    metrics_meta = registries = exporter = None
    if want_metrics:
        from apex_tpu.monitor.export import MetricsRegistry
        from apex_tpu.utils.env import capture_provenance

        metrics_meta = capture_provenance()
        registries = {rid: MetricsRegistry() for rid in replica_ids}
        if args.metrics_port is not None:
            # bound BEFORE the engines pay for params + compiles (the
            # PR-10 contract): an unbindable port must fail in
            # milliseconds with exit 2, never after trace time
            from apex_tpu.monitor.export import FleetMetricsExporter

            try:
                exporter = FleetMetricsExporter(
                    registries, port=args.metrics_port,
                    meta=metrics_meta).start()
            except OSError as e:
                print(f"apex-tpu-serve: cannot bind --metrics-port "
                      f"{args.metrics_port}: {e}", file=sys.stderr)
                return 2
            print(f"apex-tpu-serve: fleet metrics at {exporter.url} "
                  f"(merged; per-replica at /metrics/rK)",
                  file=sys.stderr)

    harness = None
    if args.trace_jsonl:
        harness = FleetTraceHarness(
            args.trace_jsonl, replica_ids,
            sample_rate=1.0 if args.trace_sample is None
            else args.trace_sample,
            sample_seed=args.seed)

    # held as every replica's engine holds it, so the replicas (and a
    # cold spawn) share one copy
    params = serving_model(cfg).serving_weights(
        init_gpt2_params(cfg, seed=args.seed))
    # fleet of meshes: with --tp >= 2 EVERY replica shards its own
    # engine over its own NamedSharding mesh (one compile per mesh
    # shape; per-rank metrics fold through the same snapshot merge)
    engine_cfg = EngineConfig(num_slots=args.num_slots, max_len=max_len,
                              temperature=args.temperature,
                              top_k=args.top_k, page_size=args.page_size,
                              num_pages=args.num_pages,
                              prefix_cache=args.prefix_cache,
                              tp=args.tp, tp_sync=args.tp_sync,
                              spec_draft_len=args.spec_draft_len or 0,
                              decode_policy=args.decode_policy,
                              kv_quant=args.kv_quant)
    handles = []
    for i, (rid, role) in enumerate(replica_specs):
        try:
            engine = Engine(cfg, params, engine_cfg, seed=args.seed)
        except ValueError as e:
            print(f"apex-tpu-serve: {e}", file=sys.stderr)
            if exporter is not None:
                exporter.stop()
            if harness is not None:
                harness.close()
            return 2
        admission = metrics = None
        if args.max_queue is not None:
            from apex_tpu.serve.resilience import AdmissionController

            admission = AdmissionController(max_queue=args.max_queue,
                                            shed_policy=args.shed_policy)
        if want_metrics:
            from apex_tpu.monitor.slo import SLOTracker
            from apex_tpu.serve.metrics import ServeMetrics

            tracker = SLOTracker(slo.objectives) \
                if slo is not None else None
            metrics = ServeMetrics(registry=registries[rid], slo=tracker)
        handles.append(EngineReplica(
            rid, engine, role=role, admission=admission,
            metrics=metrics,
            tracer=harness.tracer_for(rid) if harness is not None
            else None))
    # ALWAYS pre-compile in fleet mode (--aot is implied): a prefill or
    # decode compiling inside a worker's first tick blocks that
    # replica's heartbeats for the whole trace time — seconds — which
    # the registry can only read as a death, triggering a spurious
    # fleet-wide failover before any request is served. Startup pays
    # every trace; the heartbeat window only ever measures serving.
    # EVERY reachable pow2 bucket is warmed, not just the prompt
    # lengths': a prefix-cache hit prefills only the unshared tail,
    # which lands on a smaller bucket (the bench warms identically)
    top = max(len(p) for p in prompts)
    buckets, b = [], 1
    while b < top:
        buckets.append(b)
        b *= 2
    buckets.append(top)
    for h in handles:
        h.engine.aot_compile(buckets)
    tel = None
    if args.telemetry_jsonl:
        from apex_tpu.monitor import Telemetry

        tel = Telemetry(args.telemetry_jsonl)
    # CPU-tolerant death budget (heartbeat_ms * dead_misses = 2s at the
    # default interval): the XLA CPU client serializes executions, so a
    # contended decode tick — during which the worker cannot beat — can
    # stall far past a tight window; fabricated deaths would duplicate
    # work via failover on a perfectly healthy fleet. Operators trade
    # detection latency via --heartbeat-ms (the budget scales with it).
    # DisaggController degrades to the base router with no prefill
    # replicas, so it also carries the autoscaler hook for unified
    # fleets; the plain FleetController path stays byte-identical when
    # neither feature is armed
    fleet_cls = DisaggController if (roles or args.autoscale) \
        else FleetController
    fleet = fleet_cls(
        handles,
        heartbeat_ms=50.0 if args.heartbeat_ms is None
        else args.heartbeat_ms,
        suspect_misses=20, dead_misses=40, hedge_ms=args.hedge_ms,
        tracer=harness.fleet_tracer if harness is not None else None)
    if args.autoscale:
        scale_role = "decode" if roles else "unified"
        decode_n = roles[1] if roles else args.replicas
        spawn_seq = [len(replica_specs)]

        def _spawn():
            # cold spawn: a fresh engine on the shared params, warmed
            # over the same buckets (the warm-restart standby path is
            # preferred by the autoscaler and never reaches here).
            # Spawned replicas serve without a per-replica metrics
            # registry: the merged snapshot covers the starting fleet.
            idx = spawn_seq[0]
            spawn_seq[0] += 1
            eng = Engine(cfg, params, engine_cfg, seed=args.seed)
            eng.aot_compile(buckets)
            return EngineReplica(f"{'d' if roles else 'r'}{idx}", eng,
                                 role=scale_role)

        fleet.autoscaler = Autoscaler(
            fleet, role=scale_role,
            min_replicas=1 if args.min_replicas is None
            else args.min_replicas,
            max_replicas=decode_n if args.max_replicas is None
            else args.max_replicas,
            factory=_spawn)
    recorders = []
    fleet_flight = None
    if args.flight_recorder:
        from apex_tpu.serve.fleet import attach_fleet_recorders

        # one recorder per replica (PATH.rK: auto-dump scoped to THAT
        # replica's death/suspect transition, with its registry row)
        # plus the fleet-plane recorder, returned last — ONE wiring
        # shared with apex-tpu-bench
        recorders = attach_fleet_recorders(fleet, args.flight_recorder,
                                           harness)
        fleet_flight = recorders[-1]
    if args.drain_on == "SIGTERM":
        # stop admitting, shed the queued backlog retriable, finish
        # in-flight, exit cleanly — the rolling-deployment contract
        # (fleet.begin_drain is one flag write; safe at signal depth,
        # the control thread's next pump does the shedding)
        signal_mod.signal(signal_mod.SIGTERM,
                          lambda *_: fleet.begin_drain())
    for i, toks in enumerate(prompts):
        tenant = f"tenant-{i % args.tenants}" if args.tenants > 0 else None
        fleet.submit(Request(request_id=f"req-{i}", tokens=toks,
                             max_new_tokens=args.max_new_tokens,
                             eos_id=args.eos_id,
                             deadline_ms=args.deadline_ms,
                             tenant=tenant))
    try:
        import contextlib

        # liveness bound scaled to the workload: a large --requests run
        # is long, not wedged. A fatal control-loop exception leaves the
        # fleet-plane postmortem before propagating.
        with (fleet_flight.guard("fleet") if fleet_flight is not None
              else contextlib.nullcontext()):
            stats = fleet.run(max_wall_s=max(60.0, 2.0 * len(prompts)))
    finally:
        if exporter is not None:
            exporter.stop()
        if want_metrics and args.metrics_snapshot:
            # one mergeable snapshot PER replica (PATH.rK — what a real
            # fleet's ranks each write) plus the metrics_merge fleet
            # view at PATH itself, all atomic; provenance meta rides
            # each so the device-mismatch gate still sees it
            from apex_tpu.monitor.export import (atomic_write_json,
                                                 merge_snapshots)

            docs = []
            for i, h in enumerate(handles):
                doc = h.metrics.registry.snapshot(
                    meta={**(metrics_meta or {}),
                          "replica": h.replica_id})
                atomic_write_json(f"{args.metrics_snapshot}.r{i}", doc)
                docs.append(doc)
            atomic_write_json(args.metrics_snapshot,
                              merge_snapshots(docs))
        for fr in recorders:
            fr.detach()
        if harness is not None:
            # finalize PATH + every PATH.rK into strict JSON
            harness.close()
        if tel is not None:
            tel.close()
    for rec in stats.requests:
        print(json.dumps(rec, sort_keys=True))
    final = {"summary": stats.summary(),
             "decode_compiles": [h.engine.decode_traces
                                 for h in handles],
             "prefill_compiles": [h.engine.prefill_traces
                                  for h in handles],
             "device": device_block()}
    if harness is not None:
        # sampling provenance: how many journeys streamed, how many the
        # tail capture promoted, how many happy-path ones were dropped
        final["trace"] = harness.stats()
    print(json.dumps(final, sort_keys=True))
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="apex-tpu-serve",
        description="run a scripted or stdin token-id request stream "
                    "through the apex_tpu.serve engine")
    ap.add_argument("--config", default="tiny",
                    choices=["tiny", "small", "xl"],
                    help="GPT2Config preset (default tiny)")
    ap.add_argument("--dtype", default="fp32",
                    choices=["fp32", "bf16"],
                    help="compute dtype (fp32 default: bit-exact decode)")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64,
                    help="per-slot context bound (prompt + generated)")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request latency budget from submit; expired "
                         "requests (queued or running) terminate with "
                         "finish_reason=deadline")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission backlog; overflow is shed "
                         "per --shed-policy as a terminal, retriable "
                         "rejection (default: unbounded)")
    ap.add_argument("--shed-policy", default="reject-newest",
                    choices=["reject-newest", "shed-oldest", "priority"],
                    help="who pays when the queue is full")
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="warm restarts to attempt after a fatal tick "
                         "exception (tick journal + recovery; 0 = fail "
                         "fast, the pre-PR-8 behavior)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (must divide --max-len; "
                         "the tuned decode block_k must divide it). "
                         "Default: --max-len, one page a slot")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="pool capacity in pages incl. the reserved null "
                         "page (default: every slot's whole context; "
                         "smaller overcommits — the point of paging). "
                         "Needs --page-size")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share read-only prompt-prefix pages across "
                         "requests (hash-indexed, page-granular; needs "
                         "--page-size)")
    ap.add_argument("--requests", type=int, default=4,
                    help="scripted request count (ignored with --stdin)")
    ap.add_argument("--prompt-len", type=int, default=8,
                    help="scripted prompt length")
    ap.add_argument("--tenants", type=int, default=0,
                    help="label scripted requests round-robin across N "
                         "tenants (tenant-0..tenant-N-1) so the live "
                         "metrics carry a per-tenant breakdown "
                         "(0 = unlabeled, the 'default' tenant; "
                         "incompatible with --stdin)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve live Prometheus-text /metrics + JSON "
                         "/metrics.json from this port while the "
                         "scheduler runs (0 = ephemeral; the bound URL "
                         "prints to stderr)")
    ap.add_argument("--metrics-snapshot", default=None,
                    help="commit an atomic mergeable metrics snapshot "
                         "JSON here at exit — the per-rank artifact "
                         "tools/metrics_merge.py folds into a fleet view")
    ap.add_argument("--slo", action="append", default=None,
                    metavar="NAME=VALUE",
                    help="arm a live SLO objective (repeatable): "
                         "ttft_p99_ms=50 (threshold ms), "
                         "deadline_miss_frac=0.05 / shed_frac=0.1 "
                         "(error budgets); breaches publish "
                         "serve_slo_breach on the event bus")
    ap.add_argument("--slo-window", default=None, metavar="SHORT:LONG",
                    help="burn-rate window spans in seconds "
                         "(default 60:300)")
    ap.add_argument("--replicas", type=int, default=None,
                    help="run N thread-backed engine replicas under the "
                         "fleet controller (heartbeat health, failover "
                         "re-dispatch, hedging; default 1 = the single "
                         "scheduler path)")
    ap.add_argument("--hedge-ms", type=float, default=None,
                    help="hedged dispatch: a request with no terminal "
                         "status after this many ms fires one copy on a "
                         "second replica; first terminal wins, the loser "
                         "is aborted (needs --replicas >= 2)")
    ap.add_argument("--heartbeat-ms", type=float, default=None,
                    help="replica heartbeat interval; a replica silent "
                         "for 20 intervals is suspect, 40 is dead and "
                         "its requests fail over (default 50 -> a 2s "
                         "death budget, sized so a contended decode "
                         "tick never reads as a death; needs "
                         "--replicas >= 2)")
    ap.add_argument("--drain-on", default=None, choices=["SIGTERM"],
                    help="on this signal, stop admitting new work, shed "
                         "still-queued requests as retriable "
                         "rejections, and finish in-flight ones before "
                         "exiting cleanly (needs --replicas >= 2)")
    ap.add_argument("--roles", default=None, metavar="P:D",
                    help="disaggregate the fleet: P dedicated prefill "
                         "replicas streaming certified KV pages into D "
                         "decode replicas (needs --page-size + "
                         "--prefix-cache; --replicas, if given, must "
                         "equal P+D)")
    ap.add_argument("--autoscale", action="store_true",
                    help="SLO-driven decode autoscaling: scale up on "
                         "burn rate / page pressure, rolling-drain down "
                         "when quiet (needs --slo and a fleet)")
    ap.add_argument("--min-replicas", type=int, default=None,
                    help="autoscaler floor for the scaled role "
                         "(default 1; needs --autoscale)")
    ap.add_argument("--max-replicas", type=int, default=None,
                    help="autoscaler ceiling for the scaled role "
                         "(default: the starting count; needs "
                         "--autoscale)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel mesh size: shard params + the "
                         "KV pool on the head axis over N devices and "
                         "run decode/prefill under shard_map (must "
                         "divide the model's n_head; default 1 = single "
                         "chip; docs/serving.md 'Tensor-parallel "
                         "decode')")
    ap.add_argument("--tp-sync", default="exact",
                    choices=["exact", "overlap", "relaxed"],
                    help="per-layer cross-rank sync with --tp >= 2: "
                         "exact (all-gather concatenation — "
                         "bit-identical to the single-chip engine, the "
                         "default), overlap (TokenWeave split psums "
                         "interleaved with norm/residual compute), "
                         "relaxed (ONE deferred all-reduce per layer; "
                         "opt-in approximation)")
    ap.add_argument("--spec-draft-len", type=int, default=None,
                    metavar="K",
                    help="speculative decoding: host n-gram drafter "
                         "proposes K tokens per active slot and one "
                         "compiled verify step (a K+1-position prefill "
                         "at decode width) scores them — exact "
                         "acceptance, greedy streams bit-identical to "
                         "the one-token engine (docs/serving.md "
                         "'Speculative decoding and the decode-policy "
                         "zoo')")
    ap.add_argument("--decode-policy", default=None, metavar="POLICY",
                    help="per-request sampling policy seam: greedy | "
                         "top_p[=P] | min_p[=M] | spec(POLICY), optional "
                         "',t=T' temperature suffix; policy knobs ride "
                         "the compiled calls as data, so mixing "
                         "policies in one batch never retraces "
                         "(beam-like policies are refused — no exact "
                         "per-token acceptance test exists)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["int8", "mxfp8"],
                    help="block-scale KV-cache quantization "
                         "(apex_tpu.quant, docs/quantization.md): store "
                         "K/V as codec bytes with one fp32 scale per "
                         "(token, head); needs --dtype fp32 (the "
                         "quality gate's reference engine) and is "
                         "refused with --spec-draft-len (exact "
                         "acceptance oracle vs tolerance-gated cache)")
    ap.add_argument("--stdin", action="store_true",
                    help="read one token-id request per input line")
    ap.add_argument("--aot", action="store_true",
                    help="AOT-compile decode + the prompt bucket before "
                         "serving (startup pays the trace, not traffic)")
    ap.add_argument("--telemetry-jsonl", default=None,
                    help="mirror serve_* bus events into this JSONL")
    ap.add_argument("--trace-jsonl", default=None,
                    help="write per-request span traces (queue/prefill/"
                         "decode/complete) as Perfetto-loadable "
                         "Chrome-trace JSON; with --replicas N the "
                         "fleet journey lands here and each replica's "
                         "trace at PATH.rK (tools/trace_explain.py "
                         "merges + reconciles them)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    metavar="RATE",
                    help="deterministic head sampling over request "
                         "journeys (seeded by --seed): only RATE of "
                         "happy-path journeys reach the trace file, "
                         "while every bad-outcome journey (deadline/"
                         "evict/reject/failover/hedge, or terminal "
                         "inside an SLO breach) is promoted in full — "
                         "the slow tail is always captured (needs "
                         "--trace-jsonl; default: trace everything)")
    ap.add_argument("--flight-recorder", default=None,
                    help="crash-time flight-recorder dump path: on "
                         "preemption, watchdog escalation, or a fatal "
                         "scheduler error, the last events + open spans "
                         "+ memory snapshot land here atomically")
    args = ap.parse_args(argv)

    from apex_tpu.utils.env import device_block, enable_compile_cache

    enable_compile_cache()

    import jax.numpy as jnp

    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.serve.engine import (Engine, EngineConfig,
                                       init_gpt2_params)
    from apex_tpu.serve.scheduler import Request, ServeScheduler

    cfg = getattr(GPT2Config, args.config)()
    if args.dtype == "fp32":
        import dataclasses

        cfg = dataclasses.replace(cfg, compute_dtype=jnp.float32)
    max_len = min(args.max_len, cfg.n_positions)
    if max_len < args.max_len:
        print(f"apex-tpu-serve: --max-len {args.max_len} clamped to the "
              f"model's n_positions={max_len}", file=sys.stderr)

    # tensor-parallel flag matrix, BEFORE any params/compile work
    # (PR-10 precedent: inert/contradictory combinations are loud usage
    # errors, never silent no-ops)
    if args.tp < 1:
        print(f"apex-tpu-serve: --tp {args.tp} must be >= 1",
              file=sys.stderr)
        return 2
    if cfg.n_head % args.tp:
        print(f"apex-tpu-serve: --tp {args.tp} must divide the model's "
              f"n_head={cfg.n_head} (the serving mesh shards whole "
              f"heads)", file=sys.stderr)
        return 2
    if args.tp_sync != "exact" and args.tp == 1:
        print(f"apex-tpu-serve: --tp-sync {args.tp_sync} relaxes "
              f"cross-rank synchronization; it needs --tp >= 2 (a "
              f"single chip has no collectives to overlap or relax)",
              file=sys.stderr)
        return 2

    # speculative-decoding flag matrix, BEFORE any params/compile work
    # (same PR-10 precedent): a draft width that cannot draft and a
    # policy the acceptance oracle cannot verify are usage errors
    if args.spec_draft_len is not None and args.spec_draft_len < 1:
        print(f"apex-tpu-serve: --spec-draft-len {args.spec_draft_len} "
              f"must be >= 1 (it is the drafter's proposal width; omit "
              f"the flag for one-token decode)", file=sys.stderr)
        return 2
    spec_k = args.spec_draft_len or 0
    if args.decode_policy is not None:
        from apex_tpu.serve.spec import parse_policy
        try:
            parse_policy(args.decode_policy, spec_draft_len=spec_k)
        except ValueError as e:
            print(f"apex-tpu-serve: --decode-policy: {e}",
                  file=sys.stderr)
            return 2

    # KV-quantization flag matrix, BEFORE any params/compile work (same
    # PR-10 precedent; argparse choices already refuse unknown codecs)
    if args.kv_quant is not None:
        if args.dtype != "fp32":
            print(f"apex-tpu-serve: --kv-quant {args.kv_quant} needs "
                  f"--dtype fp32: the quantization quality gate is "
                  f"calibrated against the fp32 engine as the exact "
                  f"reference", file=sys.stderr)
            return 2
        if spec_k:
            print(f"apex-tpu-serve: --kv-quant {args.kv_quant} is "
                  f"incompatible with --spec-draft-len {spec_k}: the "
                  f"speculative acceptance oracle is bit-exact, the "
                  f"quantized cache is tolerance-gated (drop one)",
                  file=sys.stderr)
            return 2
        from apex_tpu.quant.kv import check_kv_codec
        try:
            check_kv_codec(args.kv_quant)
        except ValueError as e:
            print(f"apex-tpu-serve: --kv-quant: {e}", file=sys.stderr)
            return 2

    # disaggregation / autoscaler flag matrix, BEFORE any params or
    # compile work (PR-10 precedent: inert or contradictory combinations
    # are loud usage errors in milliseconds, never silent no-ops)
    roles = _parse_roles(args.roles)
    if args.roles is not None:
        if roles is None:
            print(f"apex-tpu-serve: --roles {args.roles!r}: want P:D "
                  f"positive integers (P prefill replicas, D decode "
                  f"replicas, e.g. 1:2)", file=sys.stderr)
            return 2
        if args.replicas is not None and args.replicas != sum(roles):
            print(f"apex-tpu-serve: --roles {args.roles} is a "
                  f"{sum(roles)}-replica fleet; --replicas "
                  f"{args.replicas} contradicts it (drop one)",
                  file=sys.stderr)
            return 2
        if not args.page_size or not args.prefix_cache:
            print("apex-tpu-serve: --roles streams prompt pages "
                  "through the prefix index; it needs --page-size and "
                  "--prefix-cache", file=sys.stderr)
            return 2
        args.replicas = sum(roles)
    elif args.replicas is None:
        args.replicas = 1
    if (args.min_replicas is not None or args.max_replicas is not None) \
            and not args.autoscale:
        print("apex-tpu-serve: --min-replicas/--max-replicas bound the "
              "autoscaler; they need --autoscale", file=sys.stderr)
        return 2
    if args.autoscale:
        if args.replicas < 2:
            print("apex-tpu-serve: --autoscale scales a FLEET; it needs "
                  "--replicas >= 2 (or --roles)", file=sys.stderr)
            return 2
        if not args.slo:
            print("apex-tpu-serve: --autoscale scales on SLO burn rate; "
                  "give it at least one --slo NAME=VALUE objective",
                  file=sys.stderr)
            return 2
        mn = 1 if args.min_replicas is None else args.min_replicas
        decode_n = roles[1] if roles else args.replicas
        mx = decode_n if args.max_replicas is None else args.max_replicas
        if not 1 <= mn <= mx:
            print(f"apex-tpu-serve: need 1 <= --min-replicas <= "
                  f"--max-replicas, got {mn} / {mx}", file=sys.stderr)
            return 2

    # fleet flag matrix, BEFORE any params/compile work: an inert or
    # contradictory combination is a usage error that must fail in
    # milliseconds (PR-10 precedent), never a silent no-op
    if args.replicas < 1:
        print(f"apex-tpu-serve: --replicas {args.replicas} must be >= 1",
              file=sys.stderr)
        return 2
    if args.replicas == 1:
        inert = [(args.hedge_ms is not None, "--hedge-ms"),
                 (args.heartbeat_ms is not None, "--heartbeat-ms"),
                 (args.drain_on is not None, "--drain-on")]
        bad = [flag for cond, flag in inert if cond]
        if bad:
            print(f"apex-tpu-serve: {bad[0]} is fleet routing; it needs "
                  f"--replicas >= 2 (one replica has nowhere to hedge, "
                  f"fail over, or drain to)", file=sys.stderr)
            return 2
    else:
        if args.heartbeat_ms is not None and args.heartbeat_ms <= 0:
            # `or 50.0` would silently replace an explicit 0 with the
            # default — the exact silent-no-op class this matrix exists
            # to refuse
            print(f"apex-tpu-serve: --heartbeat-ms "
                  f"{args.heartbeat_ms:g} must be > 0", file=sys.stderr)
            return 2
        # --trace-jsonl / --flight-recorder / --metrics-port are fleet
        # citizens since PR 13 (cross-replica journeys, per-replica
        # postmortems, the merged pull endpoint); only the warm-restart
        # supervisor still wires exactly ONE scheduler
        if args.max_restarts > 0:
            print(f"apex-tpu-serve: --max-restarts cannot apply with "
                  f"--replicas {args.replicas}: the per-replica "
                  f"warm-restart supervisor wires ONE scheduler; the "
                  f"fleet recovers by failover re-dispatch",
                  file=sys.stderr)
            return 2

    # trace sampling is a property OF the trace file: without
    # --trace-jsonl there is nothing to sample (and silently ignoring
    # the rate would leave the user believing tail capture is armed)
    if args.trace_sample is not None:
        if not args.trace_jsonl:
            print("apex-tpu-serve: --trace-sample needs --trace-jsonl "
                  "(it decides which journeys reach that file)",
                  file=sys.stderr)
            return 2
        if not 0.0 < args.trace_sample <= 1.0:
            print(f"apex-tpu-serve: --trace-sample {args.trace_sample:g} "
                  f"must be in (0, 1] (1 = trace everything)",
                  file=sys.stderr)
            return 2

    if args.tenants > 0 and args.stdin:
        # before the stdin read: stdin lines carry no tenant identity to
        # label — silently dropping the flag would leave every series
        # under "default" while the user believes the per-tenant
        # breakdown is armed
        print("apex-tpu-serve: --tenants labels the SCRIPTED workload; "
              "it cannot apply to --stdin requests", file=sys.stderr)
        return 2

    # validate the request stream BEFORE paying for params + compiles: a
    # malformed stdin line must fail in milliseconds, not after trace time
    if args.stdin:
        try:
            prompts = [p for p in (_parse_line(l) for l in sys.stdin)
                       if p]
        except ValueError as e:
            print(f"apex-tpu-serve: request lines must be whitespace- or "
                  f"comma-separated integer token ids ({e})",
                  file=sys.stderr)
            return 2
    else:
        rng = np.random.RandomState(args.seed)
        plen = max(1, min(args.prompt_len, max_len - 1))
        prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, plen)]
                   for _ in range(args.requests)]
    if not prompts:
        print("apex-tpu-serve: no requests", file=sys.stderr)
        return 2
    bad = [i for i, p in enumerate(prompts)
           if max(p) >= cfg.vocab_size or min(p) < 0]
    if bad:
        print(f"apex-tpu-serve: request {bad[0]} has token ids outside "
              f"vocab [0, {cfg.vocab_size})", file=sys.stderr)
        return 2
    long = [i for i, p in enumerate(prompts) if len(p) >= max_len]
    if long:
        print(f"apex-tpu-serve: request {long[0]} has "
              f"{len(prompts[long[0]])} tokens — no room to generate "
              f"under max_len={max_len}", file=sys.stderr)
        return 2

    # SLO specs are usage input: a typo'd objective must fail before the
    # engine pays for params + compiles
    slo = None
    if args.slo_window and not args.slo:
        # silently ignoring a window spec would leave the user believing
        # burn-rate tracking is configured — same usage-error contract as
        # every other inapplicable flag combination here
        print("apex-tpu-serve: --slo-window needs at least one --slo "
              "NAME=VALUE objective to apply to", file=sys.stderr)
        return 2
    if args.slo:
        from apex_tpu.monitor.slo import SLOTracker, parse_slo_specs

        slo_kw = {}
        if args.slo_window:
            short, _, long_ = args.slo_window.partition(":")
            try:
                slo_kw = {"short_window_s": float(short),
                          "long_window_s": float(long_)}
            except ValueError:
                print(f"apex-tpu-serve: --slo-window {args.slo_window!r}: "
                      f"want SHORT:LONG seconds (e.g. 30:150)",
                      file=sys.stderr)
                return 2
        try:
            slo = SLOTracker(parse_slo_specs(args.slo, **slo_kw))
        except ValueError as e:
            print(f"apex-tpu-serve: {e}", file=sys.stderr)
            return 2

    if args.replicas > 1:
        # every usage check above already ran: the fleet path pays for
        # params/compiles only once the request stream and SLO specs
        # are known-good
        return _run_fleet(args, cfg, max_len, prompts, slo)

    # live metrics: any of the three flags arms the per-tenant registry.
    # The pull endpoint binds BEFORE the engine pays for params +
    # compiles — an unbindable port is a usage error that must fail in
    # milliseconds with exit 2, not a raw traceback after trace time
    metrics = exporter = metrics_meta = None
    if (args.metrics_port is not None or args.metrics_snapshot
            or slo is not None):
        from apex_tpu.serve.metrics import ServeMetrics
        from apex_tpu.utils.env import capture_provenance

        metrics = ServeMetrics(slo=slo)
        # provenance rides the snapshot meta (same as apex-tpu-bench):
        # check_regression's device-mismatch guard reads it, so a
        # CPU-smoke serve snapshot can never silently gate real-chip
        # numbers
        metrics_meta = capture_provenance()
        if args.metrics_port is not None:
            from apex_tpu.monitor.export import MetricsExporter

            try:
                exporter = MetricsExporter(
                    metrics.registry, port=args.metrics_port,
                    snapshot_path=args.metrics_snapshot,
                    meta=metrics_meta).start()
            except OSError as e:
                print(f"apex-tpu-serve: cannot bind --metrics-port "
                      f"{args.metrics_port}: {e}", file=sys.stderr)
                return 2
            print(f"apex-tpu-serve: metrics at {exporter.url}",
                  file=sys.stderr)

    try:
        engine = Engine(
            cfg, init_gpt2_params(cfg, seed=args.seed),
            EngineConfig(num_slots=args.num_slots, max_len=max_len,
                         temperature=args.temperature, top_k=args.top_k,
                         page_size=args.page_size,
                         num_pages=args.num_pages,
                         prefix_cache=args.prefix_cache,
                         tp=args.tp, tp_sync=args.tp_sync,
                         spec_draft_len=args.spec_draft_len or 0,
                         decode_policy=args.decode_policy,
                         kv_quant=args.kv_quant),
            seed=args.seed)
    except ValueError as e:
        # bad pool geometry (page_size vs max_len/block_k, undersized
        # num_pages, prefix-cache without pages) and an undersized
        # device pool for --tp are usage errors, not crashes: the
        # engine's message says exactly what to fix
        print(f"apex-tpu-serve: {e}", file=sys.stderr)
        return 2

    # one Telemetry owns the whole observability lifecycle: event mirror
    # (--telemetry-jsonl), span tracer install/restore + Chrome-trace
    # export (--trace-jsonl) — same wiring as apex-tpu-bench. With
    # --trace-sample, the Chrome-trace export routes through the
    # tail-capture router instead (head sampling + bad-outcome
    # promotion); without it, today's stream-everything path is
    # untouched (rate=1 IS that behavior)
    tel = flight = mem = router = None
    if args.trace_jsonl and args.trace_sample is not None:
        from apex_tpu.monitor.trace import (ChromeTraceWriter,
                                            TailCaptureRouter, Tracer)

        tracer = Tracer()
        router = TailCaptureRouter(
            {"": ChromeTraceWriter(args.trace_jsonl, subscribe=False)},
            sample_rate=args.trace_sample, sample_seed=args.seed)
        if args.telemetry_jsonl:
            from apex_tpu.monitor import Telemetry

            tel = Telemetry(args.telemetry_jsonl)
    else:
        if args.telemetry_jsonl or args.trace_jsonl:
            from apex_tpu.monitor import Telemetry

            tel = Telemetry(args.telemetry_jsonl,
                            trace_jsonl=args.trace_jsonl)
        tracer = tel.tracer if tel is not None else None
    if args.trace_jsonl:
        from apex_tpu.monitor.memory import MemoryAccountant

        # sampled every 16 decode ticks: an allocator read per tick would
        # tax the decode hot path for a slowly-moving number
        mem = MemoryAccountant(every=16)
    if args.flight_recorder:
        from apex_tpu.monitor.flight import FlightRecorder

        flight = FlightRecorder(args.flight_recorder,
                                tracer=tracer).attach()

    if args.aot:
        # after the observability wiring: the AOT compiles publish their
        # static hbm_snapshot, which the sinks above must see
        engine.aot_compile([max(len(p) for p in prompts)])

    admission = journal = None
    if args.max_queue is not None:
        from apex_tpu.serve.resilience import AdmissionController

        admission = AdmissionController(max_queue=args.max_queue,
                                        shed_policy=args.shed_policy)
    if args.max_restarts > 0:
        from apex_tpu.serve.resilience import TickJournal

        journal = TickJournal()
    sched = ServeScheduler(engine, tracer=tracer, flight_recorder=flight,
                           memory_accountant=mem, admission=admission,
                           journal=journal, metrics=metrics)
    for i, toks in enumerate(prompts):
        # --tenants with --stdin already exited 2 above
        tenant = f"tenant-{i % args.tenants}" if args.tenants > 0 else None
        sched.submit(Request(request_id=f"req-{i}", tokens=toks,
                             max_new_tokens=args.max_new_tokens,
                             eos_id=args.eos_id,
                             deadline_ms=args.deadline_ms,
                             tenant=tenant))
    try:
        if journal is not None:
            from apex_tpu.serve.resilience import ServeSupervisor

            stats = ServeSupervisor(
                sched, max_restarts=args.max_restarts).run()
        else:
            stats = sched.run()
    finally:
        if exporter is not None:
            # stop() also commits the atomic snapshot file when
            # --metrics-snapshot rode along with the port
            exporter.stop()
        elif metrics is not None and args.metrics_snapshot:
            from apex_tpu.monitor.export import write_snapshot

            write_snapshot(metrics.registry, args.metrics_snapshot,
                           meta=metrics_meta)
        if args.metrics_snapshot and engine.tp > 1:
            # one mergeable snapshot PER TP RANK (PATH.tpK — the file a
            # real multi-host rank would write itself) plus the
            # metrics_merge fleet view at PATH.tp: the PR-10 seam used
            # for its designed purpose. The scheduler-level serving
            # registry above stays the per-request truth; the rank
            # files carry the shard-local view (local KV bytes, local
            # heads, collective traffic) that sums to the engine totals
            from apex_tpu.monitor.export import (atomic_write_json,
                                                 merge_snapshots)

            docs = engine.tp_rank_snapshots(meta=metrics_meta)
            for r, doc in enumerate(docs):
                atomic_write_json(f"{args.metrics_snapshot}.tp{r}", doc)
            atomic_write_json(f"{args.metrics_snapshot}.tp",
                              merge_snapshots(docs))
        if flight is not None:
            flight.detach()
        if router is not None:
            router.close()
        if tel is not None:
            tel.close()

    for rec in stats.requests:
        print(json.dumps(rec, sort_keys=True))
    # the device rides the record: a run that fell onto the CPU (a busy
    # chip under an unpinned platform) must never read as a chip run
    final = {"summary": stats.summary(),
             "decode_compiles": engine.decode_traces,
             "prefill_compiles": engine.prefill_traces,
             "device": device_block()}
    if engine.tp > 1:
        # mesh provenance + the per-step collective contract: one
        # compile per MESH SHAPE is the invariant decode_compiles
        # witnesses above
        final["tp"] = {"tp": engine.tp, "sync": args.tp_sync,
                       "collectives_per_decode_step":
                           engine.tp_collectives_per_step()}
    if router is not None:
        final["trace"] = {"sample_rate": router.sampler.rate,
                          "sample_seed": router.sampler.seed,
                          **router.stats()}
    if metrics is not None:
        # live totals + SLO state ride the same final line the exact
        # summary does: the two views must reconcile (tier-1 asserts)
        final["metrics"] = metrics.summary()
    print(json.dumps(final, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
