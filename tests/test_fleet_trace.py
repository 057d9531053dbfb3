"""Fleet request journeys tier-1 (ISSUE 13): cross-replica tracing,
tail-capture sampling, jax-free latency attribution.

THE invariants under test:

- **one journey per request** — the PR-11 chaos schedule (kill +
  partition + straggle) with tracing armed yields exactly one fleet
  trace per submitted request, failover/hedge spans reconcile with the
  fleet summary counters and the goodput ledger's timed causes
  (bit-for-bit on the rounded attr values), and ``decode_traces`` delta
  is 0 on every survivor with tracing + metrics + flight recorder all
  armed;
- **tail capture** — at ``--trace-sample 0.1`` every bad-outcome
  request's full journey is promoted into the trace file while the
  happy path holds to the deterministic seeded sample;
- **jax-free attribution** — ``tools/trace_explain.py`` merges the
  fleet + per-replica files and passes its reconciliation in a
  subprocess where importing jax raises.

Engines are compiled once per module and shared via ``Engine.reset()``
(the test_serve_fleet pattern); trace-counter assertions use
before/after deltas.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor import journey as journey_mod
from apex_tpu.monitor.flight import FlightRecorder
from apex_tpu.monitor.goodput import STALL_EVENTS, GoodputLedger
from apex_tpu.monitor.trace import (ChromeTraceWriter, TailCaptureRouter,
                                    TraceSampler, Tracer)
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.fleet import (EngineReplica, FleetController,
                                  FleetTraceHarness, ReplicaRegistry)
from apex_tpu.serve.metrics import ServeMetrics
from apex_tpu.serve.resilience import AdmissionController
from apex_tpu.serve.scheduler import Request, ServeScheduler
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session (see test_serve_resilience for the history)
from apex_tpu.utils.logging import publish_event, subscribe_events

pytestmark = [pytest.mark.serve, pytest.mark.trace]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = GPT2Config(vocab_size=61, n_positions=32, n_embd=16, n_layer=1,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


@pytest.fixture(scope="module")
def engines(params):
    """Three 2-slot greedy engines sharing ONE param pytree, pre-warmed
    (a prefill compiling inside a worker tick reads as a death)."""
    return [Engine(CFG, params,
                   EngineConfig(num_slots=2, max_len=32, temperature=0.0),
                   seed=0).aot_compile([8])
            for _ in range(3)]


def _tokens(n, seed=7, vocab=61):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


def _requests(n=6, max_new=4, **kw):
    return [Request(request_id=f"r{i}", tokens=_tokens(4 + i % 3, seed=i),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _journey_trace_ids(records):
    """Trace ids that have a journey ROOT present in the capture."""
    return {str(r["trace_id"]) for r in records
            if r.get("parent_id") is None
            and str(r["trace_id"]).startswith("journey:")}


# ----------------------------------------------------------------- units

def test_sampler_deterministic_and_bounded():
    s1 = TraceSampler(0.3, seed=42)
    s2 = TraceSampler(0.3, seed=42)
    keys = [f"journey:r{i}" for i in range(500)]
    assert [s1.sampled(k) for k in keys] == [s2.sampled(k) for k in keys]
    frac = sum(s1.sampled(k) for k in keys) / len(keys)
    assert 0.15 < frac < 0.45       # seeded hash, roughly the rate
    assert TraceSampler(1.0).sampled("anything")
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="rate"):
            TraceSampler(bad)


def test_timed_cause_map_matches_goodput_schema():
    """journey.py cannot import goodput (jax-free by design) so it
    carries its own copy of the serve timed-cause map — THIS assertion
    is what keeps the two from drifting."""
    serve = {k: v for k, v in STALL_EVENTS.items()
             if k.startswith("serve_")}
    assert journey_mod.SERVE_TIMED_CAUSES == serve


def test_tail_capture_router_promotes_and_drops(tmp_path):
    """Unit, no fleet: an unsampled journey buffers in its ring; a bad
    terminal promotes it (serve_trace_promoted published, spans in the
    file), a happy terminal drops it, and a sampled journey streams."""
    path = str(tmp_path / "router.json")
    tracer = Tracer()
    # rate tiny: neither unit journey is head-sampled (asserted)
    router = TailCaptureRouter(
        {"": ChromeTraceWriter(path, subscribe=False)},
        sample_rate=1e-9, sample_seed=0, ring_spans=8)
    promoted = []
    unsub = subscribe_events(
        lambda r: promoted.append(r)
        if r.get("event") == "serve_trace_promoted" else None)
    try:
        assert not router.sampler.sampled("request:u1")
        assert not router.sampler.sampled("request:u2")
        for rid, ev in (("u1", "serve_request_completed"),
                        ("u2", "serve_deadline_exceeded")):
            root = tracer.begin("request", trace_id=f"request:{rid}",
                                t0=0.0, request_id=rid)
            child = tracer.begin("decode", parent=root, t0=0.0)
            tracer.end(child, t1=0.5)
            tracer.end(root, t1=1.0)
            publish_event(ev, request_id=rid, seconds=0.0,
                          emit=False)
    finally:
        unsub()
        router.close()
    stats = router.stats()
    assert stats == {"sampled": 0, "promoted": 1, "dropped": 1}
    assert len(promoted) == 1 and promoted[0]["request_id"] == "u2"
    recs = journey_mod.load_trace_files([path])
    tids = {r["trace_id"] for r in recs}
    assert tids == {"request:u2"}, "the happy journey leaked (or the "\
        "bad one was dropped)"
    assert len(recs) == 2           # its FULL ring: decode + root


def test_reject_at_submit_journey_is_promotable(engines, tmp_path):
    """Review regression: a submit-time admission rejection is a BAD
    outcome — its trace root must open BEFORE the verdict, or the
    journey has zero spans and tail capture has nothing to promote
    (the file would silently miss exactly the requests being shed).
    Scheduler + admission are bound at collection time like every other
    import here — a function-local import would re-bind them to a fresh
    bus after a sys.modules purge and the router would never hear
    the rejection."""
    path = str(tmp_path / "reject.json")
    tracer = Tracer()
    router = TailCaptureRouter(
        {"": ChromeTraceWriter(path, subscribe=False)},
        sample_rate=1e-9, sample_seed=0)
    try:
        sched = ServeScheduler(
            engines[0].reset(), tracer=tracer,
            admission=AdmissionController(max_queue=1,
                                          shed_policy="reject-newest"))
        assert sched.submit(Request(request_id="keep",
                                    tokens=_tokens(4),
                                    max_new_tokens=2))
        assert sched.submit(Request(request_id="shed-me",
                                    tokens=_tokens(4, seed=9),
                                    max_new_tokens=2)) is False
        sched.run()
    finally:
        router.close()
    recs = journey_mod.load_trace_files([path])
    tids = {r["trace_id"] for r in recs}
    assert "request:shed-me" in tids, \
        "the rejected-at-submit journey never reached the trace file"
    shed = [r for r in recs if r["trace_id"] == "request:shed-me"]
    assert {"request", "reject"} <= {r["name"] for r in shed}
    assert router.stats()["promoted"] >= 1


def test_flight_recorder_replica_death_postmortem(tmp_path):
    """A serve_replica_dead record auto-dumps the per-replica recorder —
    scoped by trigger_filter to ITS replica, with the registry row as
    context — while the peer replica's recorder stays quiet."""
    t = [0.0]
    reg = ReplicaRegistry(0.05, suspect_misses=2, dead_misses=4,
                          clock=lambda: t[0])
    reg.register("a")
    reg.register("b")
    recorders = {}
    for rid in ("a", "b"):
        recorders[rid] = FlightRecorder(
            str(tmp_path / f"flight.{rid}.json"),
            trigger_filter=lambda rec, rid=rid:
            rec.get("replica") in (None, rid),
            context_fn=lambda rid=rid: reg.row(rid)).attach()
    try:
        t[0] = 0.30                  # replica "a" and "b" both silent...
        reg.heartbeat("b")           # ...but b beat just in time
        reg.sweep()                  # a -> dead (one event, replica="a")
    finally:
        for fr in recorders.values():
            fr.detach()
    assert os.path.exists(recorders["a"].path)
    assert not os.path.exists(recorders["b"].path), \
        "a peer's death must not dump every replica's recorder"
    d = json.load(open(recorders["a"].path))
    assert d["reason"] == "serve_replica_dead"
    assert d["context"]["replica"] == "a"
    assert d["context"]["state"] == "dead"
    assert any(r.get("event") == "serve_replica_dead"
               for r in d["events"])


def test_fleet_metrics_exporter_merged_and_per_replica_routes():
    import urllib.request

    from apex_tpu.monitor.export import (FleetMetricsExporter,
                                         MetricsRegistry)

    regs = {"r0": MetricsRegistry(), "r1": MetricsRegistry()}
    regs["r0"].counter("serve_requests_completed_total").inc(3)
    regs["r1"].counter("serve_requests_completed_total").inc(4)
    exp = FleetMetricsExporter(regs, port=0,
                               meta={"device_kind": "cpu"}).start()
    try:
        base = f"http://127.0.0.1:{exp.port}"

        def get(path):
            return urllib.request.urlopen(base + path, timeout=5).read()

        merged = json.loads(get("/metrics.json"))
        total = sum(s["value"] for s in merged["metrics"]
                    ["serve_requests_completed_total"]["series"])
        assert total == 7
        assert merged["meta"]["merged_from"] == 2
        r0 = json.loads(get("/metrics/r0.json"))
        assert r0["meta"]["replica"] == "r0"
        assert sum(s["value"] for s in r0["metrics"]
                   ["serve_requests_completed_total"]["series"]) == 3
        text = get("/metrics").decode()
        assert "serve_requests_completed_total" in text
        assert "serve_requests" in get("/metrics/r1").decode()
        with pytest.raises(urllib.error.HTTPError):
            get("/metrics/nope")
    finally:
        exp.stop()


def test_lockfree_progress_snapshot_semantics(engines):
    """The (load, done_count) probe is a published snapshot, not a live
    query: a direct scheduler mutation is invisible until someone
    publishes — which every controller-side mutation path and every
    worker tick does."""
    h = EngineReplica("rep0", engines[0].reset())
    assert h.load() == 0 and h.done_count == 0
    h.scheduler.submit(Request(request_id="x", tokens=_tokens(4),
                               max_new_tokens=2))
    assert h.load() == 0, "a snapshot, not a live read"
    h.publish_progress()
    assert h.load() == 1 and h.done_count == 0
    assert h.scheduler.progress() == (1, 0)


# ----------------------------------------- journeys reconcile (no fault)

def test_fleet_journeys_reconcile_no_fault(engines, tmp_path):
    """Every request is exactly one journey; the replica's
    queue/prefill/decode spans nest under the fleet attempt span in the
    SAME trace; attribution reconciles exactly with the summary + the
    ledger's timed causes; and decode compiles exactly once per replica
    with tracing + metrics + flight recorder ALL armed."""
    path = str(tmp_path / "trace.json")
    harness = FleetTraceHarness(path, ["rep0", "rep1"], sample_rate=1.0)
    handles = [EngineReplica(f"rep{i}", e.reset(),
                             metrics=ServeMetrics(),
                             tracer=harness.tracer_for(f"rep{i}"))
               for i, e in enumerate(engines[:2])]
    recorders = [FlightRecorder(str(tmp_path / f"fl.rep{i}.json"),
                                tracer=harness.tracer_for(f"rep{i}")
                                ).attach()
                 for i in range(2)]
    traces = [e.decode_traces for e in engines[:2]]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            tracer=harness.fleet_tracer)
    events = []
    unsub = subscribe_events(
        lambda r: events.append(r) if "event" in r else None)
    try:
        for r in _requests():
            fleet.submit(r)
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
        for fr in recorders:
            fr.detach()
        harness.close()
    assert [e.decode_traces for e in engines[:2]] == traces, \
        "tracing+metrics+flight must add ZERO compiles"

    records = journey_mod.load_trace_files(harness.paths)
    summary = stats.summary()
    assert _journey_trace_ids(records) == \
        {f"journey:r{i}" for i in range(6)}
    # the replica-side request root is a CHILD of the fleet attempt span
    by_trace = journey_mod.spans_by_trace(records)
    for tid, spans in by_trace.items():
        if not tid.startswith("journey:"):
            continue
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        att, = by_name["attempt"]
        req_root, = by_name["request"]
        assert req_root["parent_id"] == att["span_id"]
        assert {"queue", "prefill", "decode", "complete",
                "terminal", "fleet_queue"} <= set(by_name)
        # every span of the journey shares the one trace id — that IS
        # the cross-replica propagation contract
        assert {s["trace_id"] for s in spans} == {tid}
    journeys = journey_mod.attribute_journeys(records)
    causes, counts = journey_mod.ledger_causes(events)
    problems = journey_mod.reconcile(journeys, records, summary=summary,
                                     causes=causes, counts=counts)
    assert problems == []
    # the exact record values rode the spans: ttfts match bit-for-bit
    got = sorted(j["ttft_s"] for j in journeys)
    want = sorted(r["ttft_s"] for r in stats.requests)
    assert got == want
    assert harness.stats()["sampled"] == 6
    assert harness.stats()["promoted"] == 0


# --------------------------------------------- THE chaos smoke, traced

@pytest.mark.fault
def test_fleet_chaos_journeys_reconcile(engines, tmp_path):
    """ISSUE 13 acceptance: the PR-11 chaos schedule (kill + partition
    + straggle) with tracing + metrics + per-replica flight recorders
    ALL armed yields exactly one fleet trace per submitted request,
    failover/hedge spans reconcile with the fleet summary counters and
    the ledger's timed causes, decode_traces delta is 0 on every
    replica, and the dead replicas' postmortems auto-dumped."""
    inj = (FaultInjector(seed=0)
           .kill_replica("rep1", at_tick=3)
           .partition_replica("rep2", at_tick=4)
           .straggler_replica("rep0", 0.01, at_tick=2, ticks=3))
    path = str(tmp_path / "chaos.json")
    ids = ["rep0", "rep1", "rep2"]
    harness = FleetTraceHarness(path, ids, sample_rate=1.0)
    handles = [EngineReplica(rid, e.reset(), metrics=ServeMetrics(),
                             tracer=harness.tracer_for(rid))
               for rid, e in zip(ids, engines)]
    traces = [e.decode_traces for e in engines]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=50, dead_misses=200,
                            hedge_ms=150.0, fault_injector=inj,
                            tracer=harness.fleet_tracer)
    recorders = [FlightRecorder(
        str(tmp_path / f"fl.{rid}.json"),
        tracer=harness.tracer_for(rid),
        trigger_filter=lambda rec, rid=rid:
        rec.get("replica") in (None, rid),
        context_fn=lambda rid=rid: fleet.registry.row(rid)).attach()
        for rid in ids]
    events = []
    unsub = subscribe_events(
        lambda r: events.append(r) if "event" in r else None)
    try:
        for r in _requests():
            fleet.submit(r)
        with GoodputLedger() as led:
            stats = fleet.run(max_wall_s=45)
    finally:
        unsub()
        for fr in recorders:
            fr.detach()
        harness.close()
    assert [e.decode_traces for e in engines] == traces, \
        "a replica retraced decode under chaos with tracing + metrics " \
        "+ flight recorders armed"
    # the killed and the partitioned replica each left a postmortem
    # whose context row says dead
    for rid in ("rep1", "rep2"):
        d = json.load(open(tmp_path / f"fl.{rid}.json"))
        assert d["reason"] in ("serve_replica_dead",
                               "serve_replica_suspect")
        assert d["context"]["replica"] == rid
    summary = stats.summary()
    assert summary["replica_dead"] == 2

    records = journey_mod.load_trace_files(harness.paths)
    assert _journey_trace_ids(records) == \
        {f"journey:r{i}" for i in range(6)}, \
        "want exactly one journey per submitted request"
    journeys = journey_mod.attribute_journeys(records)
    causes, counts = journey_mod.ledger_causes(events)
    problems = journey_mod.reconcile(journeys, records, summary=summary,
                                     causes=causes, counts=counts)
    assert problems == [], problems
    # the span attrs and the ledger folded the SAME rounded seconds
    g = led.summary()
    span_failover = sum(
        float((s.get("attrs") or {}).get("seconds", 0.0))
        for s in records if s["name"] == "failover")
    assert span_failover == pytest.approx(
        g["lost_by_cause"].get("serve_failover", 0.0), abs=1e-9)
    assert sum(j["failovers"] for j in journeys) == summary["failovers"]
    assert sum(j["hedged"] for j in journeys) == summary["hedge_fired"]


# ------------------------------------------------------- tail capture

def test_tail_capture_promotes_every_bad_outcome_at_low_rate(
        engines, tmp_path):
    """ISSUE 13 acceptance: at --trace-sample 0.1 tail capture records
    100% of bad-outcome requests (a queued deadline storm) while the
    happy path holds to the deterministic seeded sample."""
    path = str(tmp_path / "sampled.json")
    ids = ["rep0", "rep1"]
    harness = FleetTraceHarness(path, ids, sample_rate=0.1,
                                sample_seed=3)
    handles = [EngineReplica(rid, e.reset(),
                             tracer=harness.tracer_for(rid))
               for rid, e in zip(ids, engines)]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            tracer=harness.fleet_tracer)
    happy = [f"h{i}" for i in range(8)]
    bad = [f"b{i}" for i in range(4)]
    promoted_events = []
    unsub = subscribe_events(
        lambda r: promoted_events.append(r)
        if r.get("event") == "serve_trace_promoted" else None)
    try:
        for i, rid in enumerate(happy):
            fleet.submit(Request(request_id=rid,
                                 tokens=_tokens(4, seed=i),
                                 max_new_tokens=3))
        for i, rid in enumerate(bad):
            # an impossible deadline: the first tick's sweep expires it
            # (finish_reason "deadline" — a bad outcome by contract)
            fleet.submit(Request(request_id=rid,
                                 tokens=_tokens(4, seed=40 + i),
                                 max_new_tokens=3, deadline_ms=0.01))
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
        harness.close()
    by_state = {r["request_id"]: r for r in stats.requests}
    assert all(by_state[rid]["finish_reason"] == "deadline"
               for rid in bad)
    assert all(by_state[rid]["state"] == "completed" for rid in happy)

    captured = _journey_trace_ids(
        journey_mod.load_trace_files(harness.paths))
    sampler = harness.router.sampler
    sampled_happy = {f"journey:{rid}" for rid in happy
                     if sampler.sampled(f"journey:{rid}")}
    # every bad-outcome journey is captured — sampled or promoted —
    # and the happy path is EXACTLY the deterministic head sample
    assert captured == sampled_happy | {f"journey:{rid}"
                                        for rid in bad}, captured
    want_promoted = sum(not sampler.sampled(f"journey:{rid}")
                        for rid in bad)
    assert harness.stats()["promoted"] == want_promoted
    assert len(promoted_events) == want_promoted
    assert harness.stats()["dropped"] == len(happy) - len(sampled_happy)
    assert want_promoted >= 1, "schedule produced nothing to promote"
    assert len(sampled_happy) < len(happy), \
        "every happy journey sampled: the sample rate did nothing"


# -------------------------------------------- trace_explain, jax-free

def test_trace_explain_reconciles_in_jax_free_subprocess(
        engines, tmp_path):
    """ISSUE 13 acceptance: tools/trace_explain.py runs with no jax
    importable (a poisoned jax shim raises on import), reconciles a
    traced fleet capture (exit 0), and FAILS loudly (exit 1) when the
    summary is doctored — the reconciliation IS the test."""
    path = str(tmp_path / "ex.json")
    ids = ["rep0", "rep1"]
    harness = FleetTraceHarness(path, ids, sample_rate=1.0)
    handles = [EngineReplica(rid, e.reset(),
                             tracer=harness.tracer_for(rid))
               for rid, e in zip(ids, engines)]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            tracer=harness.fleet_tracer)
    events = []
    unsub = subscribe_events(
        lambda r: events.append(r) if "event" in r else None)
    try:
        for r in _requests(4, max_new=3):
            fleet.submit(r)
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
        harness.close()
    events_path = str(tmp_path / "events.jsonl")
    with open(events_path, "w") as f:
        for rec in events:
            f.write(json.dumps(rec, default=str) + "\n")
    summary_path = str(tmp_path / "summary.json")
    json.dump({"summary": stats.summary(),
               "trace": harness.stats()}, open(summary_path, "w"))
    shim = tmp_path / "nojax"
    shim.mkdir()
    (shim / "jax.py").write_text(
        'raise ImportError("jax must not be imported by trace_explain")')
    env = dict(os.environ)
    env["PYTHONPATH"] = str(shim)

    def explain(summary_file):
        return subprocess.run(
            [sys.executable,
             os.path.join(ROOT, "tools", "trace_explain.py"),
             *harness.paths, "--events", events_path,
             "--summary", summary_file,
             "--perfetto", str(tmp_path / "merged.json")],
            capture_output=True, text=True, env=env)

    proc = explain(summary_path)
    assert proc.returncode == 0, proc.stderr
    assert "reconciled" in proc.stderr
    assert "dominant=" in proc.stdout
    merged = json.load(open(tmp_path / "merged.json"))
    tracks = {e["args"]["name"] for e in merged if e.get("ph") == "M"}
    assert tracks == {"fleet", "rep0", "rep1"}

    # doctor the summary: one phantom failover -> exit 1, named mismatch
    doctored = {"summary": {**stats.summary(),
                            "failovers": stats.summary()["failovers"] + 1},
                "trace": harness.stats()}
    doctored_path = str(tmp_path / "doctored.json")
    json.dump(doctored, open(doctored_path, "w"))
    proc = explain(doctored_path)
    assert proc.returncode == 1
    assert "MISMATCH" in proc.stderr
