"""Serving fleet tier-1: replica health, failover re-dispatch, hedged
requests, rolling drain, and the fleet chaos invariant.

THE invariant under test (ISSUE 11 acceptance): under a seeded
kill + partition + straggler schedule across >= 3 thread-backed
replicas, **every submitted request reaches exactly one terminal status
fleet-wide**, completed greedy outputs equal the no-fault fleet's token
for token (routing and failover never change greedy content — the
replicas share params, and a failed-over request's batched re-prefill
of prompt plus generated matches the stream it continues to float32
rounding, ``tests/test_serve.py``: ``BORDER``, the same argmax on these
seeds), and no surviving replica recompiles (``decode_traces`` delta 0).

Engines are compiled once per module and shared across tests via
``Engine.reset()``; trace-counter assertions use before/after deltas.
The fleet model: a *crashed* replica's unharvested results died with
its memory; a *partitioned* replica keeps decoding but nothing crosses
to the router until the partition heals — and then its duplicates must
lose the first-terminal-wins race, never double-complete.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor.goodput import GoodputLedger
from apex_tpu.monitor.slo import SLObjective, SLOTracker
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.fleet import (REPLICA_DEAD, REPLICA_HEALTHY,
                                  REPLICA_SUSPECT, EngineReplica,
                                  FleetController, ReplicaRegistry)
from apex_tpu.serve.metrics import ServeMetrics
from apex_tpu.serve.scheduler import Request, ServeScheduler
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session (see test_serve_resilience for the history)
from apex_tpu.utils.logging import subscribe_events

pytestmark = [pytest.mark.serve, pytest.mark.fault]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# deliberately tiny (1 layer, 16-wide): the fleet compiles one decode +
# one prefill bucket PER replica, and three replicas' worth of compile
# time is the fixture cost every test below shares
CFG = GPT2Config(vocab_size=61, n_positions=32, n_embd=16, n_layer=1,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


@pytest.fixture(scope="module")
def engines(params):
    """Three 2-slot greedy engines sharing ONE param pytree (identical
    weights — the fleet bit-exactness precondition); tests reset().
    Pre-warmed: a prefill compiling INSIDE a worker tick blocks
    heartbeats long enough to read as a death, which is realistic but
    not what these tests schedule — startup pays the trace, the PR-5
    serving contract."""
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


def _handles(engines, n=3, **kw):
    return [EngineReplica(f"rep{i}", e.reset(), **kw)
            for i, e in enumerate(engines[:n])]


def _assert_exactly_one_terminal_fleetwide(stats, expected_ids):
    recs = stats.requests
    ids = [r["request_id"] for r in recs]
    assert sorted(ids) == sorted(expected_ids), \
        (sorted(set(expected_ids) - set(ids)),
         sorted(set(ids) - set(expected_ids)))
    assert len(ids) == len(set(ids)), "a request settled twice"
    for r in recs:
        assert r["state"] in ("completed", "evicted", "rejected"), r


# -------------------------------------------------- registry health model

def test_registry_escalates_suspect_then_dead():
    """Heartbeat misses escalate watchdog-style: suspect at 2 silent
    intervals, dead at 4 — one event per transition, dead absorbing."""
    t = [0.0]
    reg = ReplicaRegistry(0.05, suspect_misses=2, dead_misses=4,
                          clock=lambda: t[0])
    reg.register("a")
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r) if str(r.get("event", "")).startswith(
            "serve_replica_") else None)
    try:
        t[0] = 0.05
        assert reg.sweep() == [] and reg.state("a") == REPLICA_HEALTHY
        t[0] = 0.11                    # 2.2 missed intervals
        trans = reg.sweep()
        assert [x["new"] for x in trans] == [REPLICA_SUSPECT]
        assert reg.state("a") == REPLICA_SUSPECT
        assert reg.sweep() == []       # no re-announcement
        t[0] = 0.21                    # 4.2 missed intervals
        trans = reg.sweep()
        assert [x["new"] for x in trans] == [REPLICA_DEAD]
        assert reg.sweep() == []       # dead is absorbing
    finally:
        unsub()
    assert [e["event"] for e in seen] == ["serve_replica_suspect",
                                         "serve_replica_dead"]


def test_registry_beat_heals_suspect_never_dead():
    """A beat heals a suspect back to healthy; a dead replica's beats
    (a healed partition) do NOT revive it — its requests were already
    re-dispatched, and quiet re-admission is the double-complete door."""
    t = [0.0]
    reg = ReplicaRegistry(0.05, suspect_misses=2, dead_misses=4,
                          clock=lambda: t[0])
    reg.register("a")
    t[0] = 0.11
    reg.sweep()
    assert reg.state("a") == REPLICA_SUSPECT
    reg.heartbeat("a")
    assert reg.state("a") == REPLICA_HEALTHY
    t[0] = 0.50
    reg.sweep()
    assert reg.state("a") == REPLICA_DEAD
    reg.heartbeat("a")
    assert reg.state("a") == REPLICA_DEAD, \
        "a healed partition must rejoin via restart_replica, not a beat"


def test_registry_validation(engines):
    with pytest.raises(ValueError, match="heartbeat_s"):
        ReplicaRegistry(0.0)
    with pytest.raises(ValueError, match="suspect_misses"):
        ReplicaRegistry(0.05, suspect_misses=4, dead_misses=2)
    with pytest.raises(ValueError, match="replica"):
        FleetController([])
    with pytest.raises(ValueError, match="hedge"):
        FleetController(_handles(engines, n=1), hedge_ms=10.0)


# ------------------------------------------------------- no-fault fleet

def test_fleet_matches_single_scheduler_oracle(engines):
    """Routing across replicas never changes greedy content: the fleet's
    completed outputs are bit-identical to ONE scheduler serving the
    same requests (shared params + slot isolation: the same programs on
    the same rows, no border crossed), and the attempt counters equal
    the fleet record set when nothing fails."""
    sched = ServeScheduler(engines[0].reset())
    for r in _requests():
        sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in sched.run().requests}

    # generous death budget: a no-fault run must never see a spurious
    # death — under 3-thread CPU contention a decode tick can stall
    # past a tight heartbeat window (the XLA CPU client serializes
    # executions), which is exactly what dead_misses is FOR
    fleet = FleetController(_handles(engines), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    for r in _requests():
        fleet.submit(r)
    stats = fleet.run(max_wall_s=30)
    got = {r["request_id"]: r["generated"] for r in stats.requests}
    assert got == base
    s = stats.summary()
    assert s["completed"] == 6 and s["failovers"] == 0
    assert s["attempts"] == {"submitted": 6, "completed": 6,
                             "evicted": 0, "deadline_exceeded": 0,
                             "rejected": 0}
    assert s["replica_dead"] == 0


def test_fleet_refuses_duplicate_ids_and_drain_sheds_queued(engines):
    """begin_drain (the SIGTERM contract): new submits refused, and a
    pre-drain request that never reached a slot is shed as a terminal
    RETRIABLE rejection — never served after the drain, never silently
    dropped."""
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    assert fleet.submit(Request(request_id="x", tokens=_tokens(4)))
    with pytest.raises(ValueError, match="exactly-once"):
        fleet.submit(Request(request_id="x", tokens=_tokens(4)))
    fleet.begin_drain()
    assert fleet.submit(Request(request_id="y",
                                tokens=_tokens(4))) is False
    # x is still QUEUED (no workers have run): the drain sweep sheds it
    fleet.pump()
    rec, = fleet.stats().requests
    assert rec["request_id"] == "x" and rec["state"] == "rejected"
    assert rec["finish_reason"] == "draining" and rec["retriable"]
    # the replica-side queue emptied without a replica-side terminal
    assert all(h.load() == 0 for h in fleet.handles)
    assert all(h.scheduler.done_since(0)[0] == [] for h in fleet.handles)
    stats = fleet.run(max_wall_s=30)     # settles instantly: all terminal
    assert [r["request_id"] for r in stats.requests] == ["x"]


def test_drain_wait_false_cannot_wedge_draining(engines):
    """Review regression: drain(wait=False) on a BUSY replica must not
    leave it draining forever — any later pump marks it drained the
    moment its last in-flight request leaves, and restart_replica then
    accepts it."""
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    for r in _requests(4, max_new=4):
        fleet.submit(r)
    fleet.start()
    drained = []
    unsub = subscribe_events(
        lambda r: drained.append(r)
        if r.get("event") == "serve_replica_drained" else None)
    try:
        fleet.drain("rep0", wait=False)  # rep0 is busy: stays draining
        stats = fleet.run(max_wall_s=30)  # run() pumps; rep0 idles out
    finally:
        unsub()
    assert all(r["state"] == "completed" for r in stats.requests)
    assert fleet.registry.state("rep0") == "drained"
    assert len(drained) == 1
    fleet.restart_replica("rep0")
    assert fleet.registry.state("rep0") == REPLICA_HEALTHY


# ------------------------------------------------------ THE chaos smoke

def test_fleet_chaos_smoke(engines):
    """ISSUE 11 acceptance: one seeded schedule combining a replica
    kill, a network partition, and a straggler across 3 replicas.
    Every submitted request reaches exactly one terminal status
    fleet-wide, completed greedy outputs equal the no-fault fleet's
    token for token (a failover re-prefills across the border: module
    docstring), and no surviving replica recompiles."""
    fleet = FleetController(_handles(engines), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    for r in _requests():
        fleet.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in fleet.run(max_wall_s=30).requests}
    traces = [e.decode_traces for e in engines]

    # the killed and partitioned replicas stop beating ENTIRELY, so
    # their deaths are certain at any budget — the generous dead_misses
    # only protects the straggling survivor from a spurious death under
    # CPU-contention tick stalls (which would leave nobody admitting)
    inj = (FaultInjector(seed=0)
           .kill_replica("rep1", at_tick=3)
           .partition_replica("rep2", at_tick=4)
           .straggler_replica("rep0", 0.01, at_tick=2, ticks=3))
    fleet = FleetController(_handles(engines), heartbeat_ms=25,
                            suspect_misses=50, dead_misses=200,
                            hedge_ms=150.0, fault_injector=inj)
    for r in _requests():
        fleet.submit(r)
    with GoodputLedger() as led:
        stats = fleet.run(max_wall_s=45)

    assert [e.decode_traces for e in engines] == traces, \
        "a surviving replica retraced decode across the chaos schedule"
    _assert_exactly_one_terminal_fleetwide(
        stats, [f"r{i}" for i in range(6)])
    got = {r["request_id"]: r for r in stats.requests}
    for rid, gen in base.items():
        assert got[rid]["state"] == "completed"
        assert got[rid]["generated"] == gen, \
            f"{rid} drifted across kill+partition+straggler"
    s = stats.summary()
    assert s["replica_dead"] == 2          # the kill and the partition
    assert s["failovers"] >= 1
    g = led.summary()
    assert g["events"]["serve_replica_dead"] == 2
    assert g["events"].get("serve_failover", 0) == s["failovers"]
    # the failover span is a timed loss cause on the ledger
    assert g["lost_by_cause"].get("serve_failover", 0.0) >= 0.0


# ---------------------------------------------------------------- hedging

def test_hedge_fires_exactly_once_and_first_terminal_wins(engines):
    """A straggling primary trips the hedge: exactly one
    serve_hedge_fired, the fast replica's completion wins, the loser is
    aborted replica-side, and the fleet records exactly one terminal
    status. (Heartbeat thresholds are generous so the straggler is slow,
    not dead — hedging is the remedy under test, not failover.)"""
    inj = FaultInjector(seed=0).straggler_replica("rep0", 0.05,
                                                  at_tick=1, ticks=60)
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            hedge_ms=40.0, fault_injector=inj)
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("event") == "serve_hedge_fired" else None)
    try:
        fleet.submit(Request(request_id="h0", tokens=_tokens(5),
                             max_new_tokens=4))
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
    assert len(seen) == 1
    assert seen[0]["primary"] == "rep0" and seen[0]["hedge"] == "rep1"
    s = stats.summary()
    assert s["hedge_fired"] == 1 and s["requests"] == 1
    rec, = stats.requests
    # the WINNER is a race by design (first terminal wins — usually the
    # fast replica, but the straggler can still land first): assert the
    # contract, not the racer. Under greedy decoding either copy's
    # output is bit-identical, so the race never changes content.
    assert rec["state"] == "completed"
    assert rec["replica"] in ("rep0", "rep1")
    # the loser's abort is an attempt-level eviction, never a second
    # fleet record
    assert s["attempts"]["submitted"] == 2


# --------------------------------------------- partition heal / dedup

def test_partition_heal_never_double_completes(engines):
    """A partitioned replica keeps decoding while the router declares it
    dead and fails over. When the partition heals, its duplicate
    completions surface at harvest — and must lose first-terminal-wins:
    one record per request, and the healed replica stays out of the
    routing pool until an explicit restart."""
    import time

    inj = FaultInjector(seed=0).partition_replica("rep0", at_tick=2)
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25,
                            suspect_misses=50, dead_misses=200,
                            fault_injector=inj)
    for r in _requests(3, max_new=6):
        fleet.submit(r)
    fleet.start()
    t0 = time.perf_counter()
    while not fleet.all_terminal():
        fleet.pump()
        assert time.perf_counter() - t0 < 30, "fleet wedged"
        time.sleep(0.002)
    rep0 = fleet.handles[0]
    # the partitioned replica finished (some of) its copies in the dark
    t0 = time.perf_counter()
    while not any(r.state == "completed"
                  for r in rep0.scheduler.done_since(0)[0]):
        assert time.perf_counter() - t0 < 30, \
            "partitioned replica never completed its dark copies"
        time.sleep(0.002)
    dark = sum(r.state == "completed"
               for r in rep0.scheduler.done_since(0)[0])
    inj.heal_replica("rep0")
    t0 = time.perf_counter()
    while rep0.partitioned:
        assert time.perf_counter() - t0 < 10
        time.sleep(0.002)
    for _ in range(5):
        fleet.pump()               # harvest the healed replica's backlog
    fleet.stop()
    stats = fleet.stats()
    _assert_exactly_one_terminal_fleetwide(stats, ["r0", "r1", "r2"])
    assert all(r["state"] == "completed" for r in stats.requests)
    assert dark >= 1
    # duplicates existed fleet-wide (dark copies + survivor re-runs)...
    assert stats.attempts["completed"] >= 3 + dark - \
        sum(r["replica"] == "rep0" for r in stats.requests)
    # ...and the healed replica is still dead to the router
    assert fleet.registry.state("rep0") == REPLICA_DEAD
    assert fleet._route().replica_id == "rep1"
    assert stats.summary()["replica_dead"] == 1


# ------------------------------------------------- drain / rolling restart

def test_drain_migrates_queued_without_terminal_records(engines):
    """Drain before the workers ever run: still-queued requests migrate
    to peers through pop_queued — no terminal record anywhere, the
    drained replica empties, and the fleet still completes everything
    after a restart."""
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r) if r.get("event") in
        ("serve_failover", "serve_replica_drained",
         "serve_replica_restarted") else None)
    try:
        for r in _requests(4, max_new=3):
            fleet.submit(r)
        rep0, rep1 = fleet.handles
        assert rep0.load() == 2 and rep1.load() == 2
        migrated = fleet.drain("rep0", wait=False)
        assert migrated == 2
        assert rep0.load() == 0 and rep1.load() == 4
        # migration is NOT a terminal status on either side
        assert rep0.scheduler.done_since(0)[0] == []
        drains = [e for e in seen if e["event"] == "serve_failover"]
        assert len(drains) == 2
        assert all(e["cause"] == "drain" and e["to_replica"] == "rep1"
                   for e in drains)
        assert [e["event"] for e in seen if "replica" in e.get(
            "event", "")] or True
        fleet.restart_replica("rep0")
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
    assert all(r["state"] == "completed" for r in stats.requests)
    assert stats.summary()["migrations"] == 2
    assert [e["event"] for e in seen
            if e["event"].startswith("serve_replica_")] == \
        ["serve_replica_drained", "serve_replica_restarted"]


def test_rolling_restart_keeps_capacity_and_loses_nothing(engines):
    """ISSUE 11 acceptance: rolling drain keeps >= N-1 replicas
    admitting at all times and loses zero in-flight requests — queued
    ones migrate, running ones finish, every replica restarts exactly
    once with zero recompiles."""
    fleet = FleetController(_handles(engines), heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    for r in _requests(9, max_new=6):
        fleet.submit(r)
    fleet.start()
    traces = [e.decode_traces for e in engines]
    result = fleet.rolling_restart(max_wall_s=30)
    stats = fleet.run(max_wall_s=30)
    assert result["restarted"] == 3
    assert result["min_admitting"] >= 2, \
        "capacity dropped below N-1 during the rolling restart"
    _assert_exactly_one_terminal_fleetwide(
        stats, [f"r{i}" for i in range(9)])
    assert all(r["state"] == "completed" for r in stats.requests), \
        "rolling restart lost an in-flight request"
    s = stats.summary()
    assert s["replica_restarted"] == 3 and s["replica_dead"] == 0
    assert [e.decode_traces for e in engines] == traces, \
        "a clean restart must keep every compiled artifact"


def test_restart_requires_drained_or_dead(engines):
    fleet = FleetController(_handles(engines, n=2), heartbeat_ms=25)
    with pytest.raises(ValueError, match="drain"):
        fleet.restart_replica("rep0")


def test_hedge_copy_rejection_never_settles_live_request(engines):
    """Review regression: one hedge copy shed by admission control must
    NOT become the request's fleet-terminal status (nor abort the other
    copy a healthy replica is actively serving) — the live copy IS the
    retry. Driven clock-injected with no workers, so the race is
    deterministic."""
    from apex_tpu.serve.resilience import AdmissionController

    t = [0.0]
    handles = [EngineReplica("rep0", engines[0].reset(),
                             admission=AdmissionController(
                                 max_queue=1, shed_policy="shed-oldest")),
               EngineReplica("rep1", engines[1].reset())]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            hedge_ms=100.0, clock=lambda: t[0])
    fleet.submit(Request(request_id="h0", tokens=_tokens(4),
                         max_new_tokens=3))        # queues on rep0
    t[0] = 0.2
    fleet.pump()                                   # hedge fires to rep1
    assert fleet.hedges_fired == 1
    # a later submit sheds h0's rep0 copy (shed-oldest, queue full)
    fleet.submit(Request(request_id="filler", tokens=_tokens(4, seed=9),
                         max_new_tokens=3))
    done, _ = handles[0].scheduler.done_since(0)
    assert [r.request_id for r in done] == ["h0"]  # the shed copy
    fleet.pump()                                   # harvests the shed
    freq = fleet._requests["h0"]
    assert freq.record is None, \
        "a shed hedge copy settled a request rep1 is still serving"
    assert "rep1" in freq.attempts                 # live copy untouched
    assert handles[1].scheduler.load() == 1
    assert fleet.retries == 0                      # dropped, not retried


# ------------------------------------------------- burn-rate shed routing

def test_burn_rate_sheds_routing(engines):
    """PR-10 burn rates as a routing signal: a replica whose SLO
    short-window burn is at/above the shed factor receives new load
    only when every alternative burns too."""
    def tracker(clock):
        return SLOTracker([SLObjective.shed_frac(0.1, min_events=4)],
                          clock=clock)

    t = [1000.0]
    clock = lambda: t[0]                                     # noqa: E731
    mets = [ServeMetrics(slo=tracker(clock)) for _ in range(2)]
    handles = [EngineReplica(f"rep{i}", e.reset(), metrics=m)
               for i, (e, m) in enumerate(zip(engines, mets))]
    fleet = FleetController(handles, heartbeat_ms=25,
                            shed_burn_factor=2.0)
    assert fleet._route().replica_id == "rep0"   # equal: index tiebreak
    for _ in range(8):
        mets[0].slo.observe("shed", bad=True, t=t[0])
    mets[0].slo.evaluate(now=t[0])
    assert handles[0].burn_short_max() >= 2.0
    assert fleet._route().replica_id == "rep1", \
        "a budget-burning replica must shed new load"
    # both burning: routing still works (shedding everywhere beats
    # serving nowhere)
    for _ in range(8):
        mets[1].slo.observe("shed", bad=True, t=t[0])
    mets[1].slo.evaluate(now=t[0])
    assert fleet._route() is not None


# ------------------------------------------------ fleet metrics merge

def test_merged_replica_snapshots_reconcile_with_fleet_summary(
        engines, tmp_path):
    """ISSUE 11 acceptance: per-replica ServeMetrics snapshots fold
    through tools/metrics_merge.py into one fleet view whose counters
    reconcile EXACTLY with the fleet summary's attempt-level section —
    family by family, including the hedge loser's eviction."""
    inj = FaultInjector(seed=0).straggler_replica("rep0", 0.05,
                                                  at_tick=1, ticks=60)
    handles = [EngineReplica(f"rep{i}", e.reset(),
                             metrics=ServeMetrics())
               for i, e in enumerate(engines[:2])]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000,
                            hedge_ms=40.0, fault_injector=inj)
    for r in _requests(5, max_new=3):
        fleet.submit(r)
    stats = fleet.run(max_wall_s=30)
    s = stats.summary()
    assert s["hedge_fired"] >= 1       # at least one duplicate attempt

    from apex_tpu.monitor.export import write_snapshot

    paths = []
    for i, h in enumerate(handles):
        p = str(tmp_path / f"rank{i}.json")
        write_snapshot(h.metrics.registry, p, meta={"replica": i})
        paths.append(p)
    merged_path = str(tmp_path / "fleet.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "metrics_merge.py"),
         *paths, "-o", merged_path],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    merged = json.load(open(merged_path))

    def total(name):
        fam = merged["metrics"].get(name, {"series": []})
        return sum(x["value"] for x in fam["series"])

    want = s["attempts"]
    assert total("serve_requests_submitted_total") == want["submitted"]
    assert total("serve_requests_completed_total") == want["completed"]
    assert total("serve_requests_evicted_total") == want["evicted"]
    assert total("serve_requests_rejected_total") == want["rejected"]
    assert total("serve_deadline_exceeded_total") == \
        want["deadline_exceeded"]
    # the duplicate attempts are visible: more attempts than requests
    assert want["submitted"] > s["requests"] - 1 + s["hedge_fired"] - 1


# --------------------------------------------------- gate direction hints

def test_fleet_counters_gate_lower_is_better():
    """A 0 -> N failover/hedge/replica-death storm must gate as a
    regression, never a win (and never be skipped off a zero
    baseline)."""
    sys.path.insert(0, ROOT)
    try:
        from tools.check_regression import compare, lower_is_better
    finally:
        sys.path.remove(ROOT)
    for name in ("failovers", "serve_decode.failovers", "hedge_fired",
                 "replica_dead"):
        assert lower_is_better(name), name
    results, _ = compare({"failovers": (3.0, None)},
                         {"failovers": (0.0, None)}, tolerance=0.10)
    assert results[0]["regressed"] is True
    results, _ = compare({"failovers": (0.0, None)},
                         {"failovers": (0.0, None)}, tolerance=0.10)
    assert results[0]["regressed"] is False


# --------------------------------------------------------------- the CLI

def test_fleet_cli_usage_errors():
    """Inert or contradictory fleet flag combinations are clean exit-2
    usage errors BEFORE any compile (milliseconds, not trace time).
    PR 13 lifted the PR-11 restrictions: --trace-jsonl /
    --flight-recorder / --metrics-port are now fleet citizens, so only
    the still-genuinely-inert combos stay refused — --max-restarts (the
    one-scheduler supervisor) and --trace-sample with no trace file to
    sample into."""
    from apex_tpu.serve.cli import main

    for argv in (["--hedge-ms", "20"],
                 ["--heartbeat-ms", "20"],
                 ["--drain-on", "SIGTERM"],
                 ["--replicas", "0"],
                 ["--replicas", "2", "--heartbeat-ms", "0"],
                 ["--replicas", "2", "--max-restarts", "1"],
                 ["--trace-sample", "0.5"],
                 ["--replicas", "2", "--trace-sample", "0.5"],
                 ["--trace-sample", "1.5", "--trace-jsonl", "t.json"],
                 ["--trace-sample", "0", "--trace-jsonl", "t.json"]):
        assert main(argv) == 2, argv


def test_bench_fleet_usage_errors():
    from apex_tpu.bench_cli import _serve_bench

    for kw in ({"hedge_ms": 5.0}, {"heartbeat_ms": 5.0},
               {"replicas": 0},
               {"replicas": 2, "heartbeat_ms": 0.0},
               {"trace_sample": 0.5},                  # no --trace-jsonl
               {"trace_sample": 2.0, "trace_jsonl": "t.json"}):
        with pytest.raises(SystemExit, match="apex-tpu-bench"):
            _serve_bench(2, 2, None, **kw)


@pytest.mark.slow
def test_fleet_cli_end_to_end(capsys, tmp_path):
    """In-process --replicas e2e: per-request records, the fleet summary
    with failovers/hedge_fired/migrations, one decode compile per
    replica, and per-replica + merged snapshots on disk. Rides slow
    (the PR-5 CLI-subprocess precedent): it compiles two fresh
    tiny-preset engines, and the tier-1 budget is carried by the six
    mandated fleet tests above — the exit-2 usage matrices stay
    tier-1."""
    from apex_tpu.serve.cli import main

    snap = str(tmp_path / "fleet_snap.json")
    trace = str(tmp_path / "fleet_trace.json")
    rc = main(["--config", "tiny", "--replicas", "2", "--requests", "4",
               "--prompt-len", "4", "--max-new-tokens", "3",
               "--num-slots", "2", "--max-len", "32",
               "--temperature", "0", "--heartbeat-ms", "250",
               "--hedge-ms", "5000", "--metrics-snapshot", snap,
               "--trace-jsonl", trace, "--trace-sample", "1.0"])
    captured = capsys.readouterr()
    assert rc == 0, captured.err      # the usage message names the cause
    lines = [json.loads(l) for l in
             captured.out.strip().splitlines()]
    recs, final = lines[:-1], lines[-1]
    assert len(recs) == 4
    assert all(r["state"] == "completed" for r in recs)
    assert all(r["replica"] in ("r0", "r1") for r in recs)
    s = final["summary"]
    assert s["failovers"] == 0 and s["hedge_fired"] == 0
    assert s["migrations"] == 0 and s["replicas"] == 2
    assert final["decode_compiles"] == [1, 1], \
        "fleet tracing must add zero compiles"
    # PR 13: the journey files landed (fleet plane + one per replica)
    # and the sampling provenance rode the final line
    assert final["trace"]["sampled"] == 4
    assert final["trace"]["promoted"] == 0
    for p in (trace, trace + ".r0", trace + ".r1"):
        assert os.path.exists(p), p
    # one mergeable snapshot per replica + the merged fleet view, and
    # the merged counters reconcile with the attempts section
    assert os.path.exists(snap + ".r0") and os.path.exists(snap + ".r1")
    merged = json.load(open(snap))
    got = sum(x["value"] for x in
              merged["metrics"]["serve_requests_submitted_total"]
              ["series"])
    assert got == s["attempts"]["submitted"]


@pytest.mark.slow
def test_bench_fleet_entry(capsys):
    """--serve --replicas bench: the serve_decode entry carries the
    fleet resilience counters and the workload provenance records
    replicas/hedge_ms/heartbeat_ms (never gated across incomparable
    configs). Slow for the same reason as the CLI e2e: two more fresh
    engine compiles."""
    from apex_tpu.bench_cli import _serve_bench

    _serve_bench(4, 2, None, replicas=2, hedge_ms=5000.0,
                 heartbeat_ms=25.0)
    doc = json.loads(capsys.readouterr().out)
    e = doc["serve_decode"]
    assert e["value"] > 0
    for k in ("failovers", "hedge_fired", "replica_dead", "migrations"):
        assert e[k] == 0, k
    w = e["workload"]
    assert w["replicas"] == 2
    assert w["hedge_ms"] == 5000.0 and w["heartbeat_ms"] == 25.0
