"""Disaggregated prefill/decode tier-1: chain-hash-certified page
streaming, exactly-once across the handoff, the drain-flush gate, the
SLO-driven autoscaler under seeded diurnal traffic, and fleet-of-meshes
(tp x replicas) bit-exactness.

THE invariant under test (ISSUE 16 acceptance): under a seeded schedule
mixing kill-prefill + corrupt-page-in-flight + stall-handoff, every
greedy completion equals the non-disaggregated fleet's token for token
(a refused or lost handoff degrades to a local cold prefill, the very
program the non-disaggregated fleet runs: bit-exact; a handoff that
lands is a prefix hit, whose tail sums its softmax over cached pages
and chunk in another order than a cold prefill: logits to float32
rounding, ``tests/test_serve.py``: ``BORDER``, the same argmax on these
seeds; exported page BYTES equal local ones), every request settles
exactly once
fleet-wide, and no surviving replica recompiles (``decode_traces``
delta 0).

Engines are compiled once per module and shared via ``Engine.reset()``;
the autoscaler test runs fully clock-injected (no worker threads), so
its diurnal day replays deterministically.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor.goodput import GoodputLedger
from apex_tpu.monitor.slo import SLObjective, SLOTracker
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.disagg import (Autoscaler, DisaggController,
                                   DiurnalTraffic)
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.fleet import (REPLICA_DRAINED, REPLICA_DRAINING,
                                  EngineReplica, FleetController)
from apex_tpu.serve.metrics import ServeMetrics
from apex_tpu.serve.resilience import AdmissionController
from apex_tpu.serve.scheduler import Request, ServeScheduler
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session (see test_serve_resilience for the history)
from apex_tpu.utils.logging import subscribe_events

pytestmark = [pytest.mark.serve, pytest.mark.fault]

CFG = GPT2Config(vocab_size=61, n_positions=32, n_embd=16, n_layer=1,
                 n_head=2, compute_dtype=jnp.float32)
PAGE = 4


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


@pytest.fixture(scope="module")
def engines(params):
    """Four 2-slot greedy PAGED engines sharing one param pytree (the
    fleet bit-exactness precondition) — enough for 1 prefill + 2 decode
    + 1 oracle; tests reset()."""
    return [Engine(CFG, params,
                   EngineConfig(num_slots=2, max_len=32, temperature=0.0,
                                page_size=PAGE, num_pages=24,
                                prefix_cache=True),
                   seed=0).aot_compile([4, 8])
            for _ in range(4)]


@pytest.fixture(scope="module")
def tp_engines(params):
    """Two tp=2 replicas, each owning its OWN serving mesh — the
    fleet-of-meshes configuration PR 15 left mutually exclusive."""
    return [Engine(CFG, params,
                   EngineConfig(num_slots=2, max_len=32,
                                temperature=0.0, tp=2),
                   seed=0).aot_compile([8])
            for _ in range(2)]


def _tokens(n, seed=7, vocab=61):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


def _requests(n=6, max_new=4, **kw):
    # lens 6..8: every prompt spans >= 1 full page (handoff-eligible),
    # len 8 spans two — the chain has a link to break
    return [Request(request_id=f"r{i}", tokens=_tokens(6 + i % 3, seed=i),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _oracle(engine, reqs):
    """Greedy outputs from a plain single-engine scheduler — the
    bit-exactness reference every disaggregated path must match."""
    sched = ServeScheduler(engine.reset())
    for r in reqs:
        sched.submit(Request(request_id=r.request_id,
                             tokens=list(r.tokens),
                             max_new_tokens=r.max_new_tokens))
    sched.run(max_steps=2_000)
    done, _ = sched.done_since(0)
    return {q.request_id: q.record()["generated"] for q in done}


def _disagg_handles(engines, prefills=1, decodes=2):
    hs = [EngineReplica(f"p{i}", engines[i].reset(), role="prefill")
          for i in range(prefills)]
    hs += [EngineReplica(f"d{i}", engines[prefills + i].reset(),
                         role="decode")
           for i in range(decodes)]
    return hs


def _assert_exactly_one_terminal_fleetwide(stats, expected_ids):
    recs = stats.requests
    ids = [r["request_id"] for r in recs]
    assert sorted(ids) == sorted(expected_ids), \
        (sorted(set(expected_ids) - set(ids)),
         sorted(set(ids) - set(expected_ids)))
    assert len(ids) == len(set(ids)), "a request settled twice"
    for r in recs:
        assert r["state"] in ("completed", "evicted", "rejected"), r


# ---------------------------------------------- page export/import seam

def test_export_import_matches_and_duplicate_idempotent(engines):
    """The transport seam under the handoff: committed pages exported
    from one engine install into another, admission finds them as
    prefix hits, greedy output equals the cold oracle's token for token
    (the tail after a hit against a cold prefill: float32 rounding, the
    module docstring) — and re-importing the same stream is a no-op
    (duplicate-stream exactly-once)."""
    prompt = _tokens(8, seed=3)
    a, b = engines[0].reset(), engines[1].reset()
    sa = ServeScheduler(a)
    sa.submit(Request(request_id="seed", tokens=list(prompt),
                      max_new_tokens=1))
    sa.run(max_steps=50)

    payloads = sa.export_prefix_pages(list(prompt))
    assert len(payloads) == 2              # 8 tokens / page_size 4
    for p in payloads:
        assert set(p) >= {"chain_hash", "k", "v", "digest"}

    sb = ServeScheduler(b)
    first = sb.import_prefix_pages(payloads)
    assert first["installed"] == 2 and first["duplicate"] == 0
    again = sb.import_prefix_pages(payloads)
    assert again["installed"] == 0 and again["duplicate"] == 2, \
        "a duplicate stream must be absorbed, not double-installed"

    traces = b.decode_traces
    sb.submit(Request(request_id="real", tokens=list(prompt),
                      max_new_tokens=4))
    sb.run(max_steps=50)
    done, _ = sb.done_since(0)
    rec, = [q.record() for q in done]
    assert sb.prefix_hits >= 1, "migrated pages were not reused"
    assert b.decode_traces == traces, "imported pages forced a retrace"
    assert rec["generated"] == _oracle(engines[2], [Request(
        request_id="real", tokens=list(prompt), max_new_tokens=4)])["real"]


# ------------------------------------------- corruption: refuse + fallback

def test_single_bit_flip_refused_then_bit_exact_fallback(engines):
    """ISSUE 16 satellite: one flipped bit in an in-flight K payload is
    caught by the payload digest, the receiver refuses the chain
    (exactly one ``serve_handoff_refused``), installs nothing, and the
    request completes bit-exactly via local re-prefill."""
    req = Request(request_id="c0", tokens=_tokens(8, seed=11),
                  max_new_tokens=4)
    oracle = _oracle(engines[2], [req])

    inj = FaultInjector(seed=0).corrupt_page_in_flight(nth=1)
    fleet = DisaggController(
        _disagg_handles(engines, prefills=1, decodes=1),
        heartbeat_ms=25, suspect_misses=5_000, dead_misses=10_000,
        fault_injector=inj)
    refusals = []
    unsub = subscribe_events(
        lambda r: refusals.append(r)
        if r.get("event") == "serve_handoff_refused" else None)
    try:
        fleet.submit(Request(request_id="c0", tokens=list(req.tokens),
                             max_new_tokens=4))
        with GoodputLedger() as led:
            stats = fleet.run(max_wall_s=30)
    finally:
        unsub()

    rec, = stats.requests
    assert rec["state"] == "completed"
    assert rec["generated"] == oracle["c0"], \
        "refusal fallback drifted from the no-disagg oracle"
    assert stats.handoffs == 1 and stats.handoffs_refused == 1
    assert stats.handoffs_delivered == 0
    assert stats.pages_migrated == 0, \
        "a refused chain must truncate BEFORE the corrupt page"
    assert len(refusals) == 1
    assert refusals[0]["reason"] == "digest"
    assert refusals[0]["page_index"] == 0
    g = led.summary()
    assert g["events"]["serve_handoff_refused"] == 1
    assert g["events"].get("serve_page_migrated", 0) == 0


def test_torn_chain_truncates_but_keeps_certified_prefix(engines):
    """Corruption mid-chain: pages before the break install (certified
    individually), the tail is refused, decode re-prefills only the
    uncovered suffix — still bit-exact."""
    req = Request(request_id="t0", tokens=_tokens(8, seed=13),
                  max_new_tokens=4)
    oracle = _oracle(engines[2], [req])

    inj = FaultInjector(seed=0).corrupt_page_in_flight(nth=2)
    fleet = DisaggController(
        _disagg_handles(engines, prefills=1, decodes=1),
        heartbeat_ms=25, suspect_misses=5_000, dead_misses=10_000,
        fault_injector=inj)
    fleet.submit(Request(request_id="t0", tokens=list(req.tokens),
                         max_new_tokens=4))
    stats = fleet.run(max_wall_s=30)
    rec, = stats.requests
    assert rec["state"] == "completed"
    assert rec["generated"] == oracle["t0"]
    assert stats.handoffs_refused == 1
    assert stats.pages_migrated == 1, \
        "the certified prefix of a torn chain should still land"


# ------------------------------------------------- headline chaos smoke

def test_disagg_chaos_bit_exact_exactly_once_no_recompiles(engines):
    """ISSUE 16 acceptance: a seeded schedule mixing a prefill-replica
    kill, an in-flight page corruption, and a stalled handoff against a
    1-prefill + 2-decode fleet. Greedy completions stay bit-identical
    to the same requests on a non-disaggregated fleet, every request
    settles exactly once, no surviving replica recompiles, and the
    handoff ledger reconciles with the goodput ledger event-for-event."""
    reqs = _requests()
    base_handles = [EngineReplica(f"u{i}", engines[1 + i].reset(),
                                  role="unified") for i in range(2)]
    base_fleet = DisaggController(base_handles, heartbeat_ms=25,
                                  suspect_misses=5_000,
                                  dead_misses=10_000)
    assert base_fleet.disagg is False      # degrades to the base router
    for r in _requests():
        base_fleet.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_fleet.run(max_wall_s=30).requests}

    handles = _disagg_handles(engines)
    traces = [h.engine.decode_traces for h in handles]
    inj = (FaultInjector(seed=0)
           .kill_prefill_replica("p0", at_tick=3)
           .corrupt_page_in_flight(nth=2)
           .stall_handoff(0.02, at_handoff=1))
    fleet = DisaggController(handles, heartbeat_ms=25,
                             suspect_misses=50, dead_misses=200,
                             hedge_ms=150.0, fault_injector=inj)
    for r in reqs:
        fleet.submit(r)
    with GoodputLedger() as led:
        stats = fleet.run(max_wall_s=45)

    assert handles[0].crashed, "the seeded prefill kill never fired"
    assert [h.engine.decode_traces for h in handles] == traces, \
        "a replica retraced decode across the disaggregation chaos"
    _assert_exactly_one_terminal_fleetwide(
        stats, [f"r{i}" for i in range(6)])
    got = {r["request_id"]: r for r in stats.requests}
    for rid, gen in base.items():
        assert got[rid]["state"] == "completed"
        assert got[rid]["generated"] == gen, \
            f"{rid} drifted across kill+corrupt+stall"
    # every begun handoff resolves exactly once, through exactly one door
    assert stats.handoffs >= 1
    assert (stats.handoffs_delivered + stats.handoffs_refused
            + stats.handoffs_abandoned) == stats.handoffs
    g = led.summary()
    assert g["events"].get("serve_page_migrated", 0) == \
        stats.pages_migrated
    assert g["events"].get("serve_handoff_refused", 0) == \
        stats.handoffs_refused
    assert g["events"].get("serve_handoff_wait", 0) == stats.handoffs, \
        "a handoff resolved without charging its wait (or twice)"
    s = stats.summary()
    assert s["prefill_jobs"] == stats.handoffs
    # the clone accounting note on DisaggStats: real completions =
    # attempts completed - prefill jobs completed
    assert s["attempts"]["completed"] >= len(
        [r for r in stats.requests if r["state"] == "completed"])


# ----------------------------------------------- drain flushes handoffs

def test_draining_prefill_flushes_inflight_handoffs_before_drained(
        engines):
    """ISSUE 16 bugfix regression: a draining prefill replica holding a
    committed-but-undelivered handoff must flush it (pages land, the
    real request dispatches) BEFORE ``serve_replica_drained`` — never
    report drained with pages still in flight. Clock-free and
    worker-free, so the interleaving is exact."""
    prompt = _tokens(8, seed=17)
    oracle = _oracle(engines[2], [Request(
        request_id="f0", tokens=list(prompt), max_new_tokens=3)])

    inj = FaultInjector(seed=0).stall_handoff(60.0, at_handoff=1)
    handles = _disagg_handles(engines, prefills=1, decodes=1)
    p0, d0 = handles
    fleet = DisaggController(handles, heartbeat_ms=25,
                             suspect_misses=5_000, dead_misses=10_000,
                             fault_injector=inj)
    order = []
    unsub = subscribe_events(
        lambda r: order.append(r["event"])
        if r.get("event") in ("serve_page_migrated",
                              "serve_replica_drained") else None)
    try:
        fleet.submit(Request(request_id="f0", tokens=list(prompt),
                             max_new_tokens=3))
        for _ in range(10):                 # commit the clone prefill
            p0.scheduler.step()
        p0.publish_progress()
        fleet.pump()                        # commit seen; stalled 60s
        assert p0.pending_handoffs == 1
        assert fleet.handoffs_delivered == 0

        fleet.drain("p0", wait=False)
        assert fleet.registry.state("p0") == REPLICA_DRAINING, \
            "drained with a committed handoff still in flight"
        fleet.pump()                        # DRAINING overrides the stall
        assert fleet.handoffs_delivered == 1
        assert fleet.pages_migrated == 2
        assert p0.pending_handoffs == 0
        assert fleet.registry.state("p0") == REPLICA_DRAINED
        assert "serve_page_migrated" in order \
            and "serve_replica_drained" in order
        assert order.index("serve_page_migrated") \
            < order.index("serve_replica_drained"), \
            "drained was announced before the flush landed"

        for _ in range(20):                 # finish the real request
            d0.scheduler.step()
        d0.publish_progress()
        fleet.pump()
        rec = fleet._requests["f0"].record
        assert rec is not None and rec["state"] == "completed"
        assert rec["generated"] == oracle["f0"]
        assert d0.scheduler.prefix_hits >= 1, \
            "the flushed pages were not what decode admitted from"
    finally:
        unsub()


# ------------------------------------------------------- autoscaler e2e

def test_autoscaler_diurnal_scale_up_down_without_flapping(engines):
    """ISSUE 16 acceptance: one clock-injected diurnal day (trough ->
    peak -> trough) against an SLO-armed decode pool. The peak burns
    the shed budget -> at least one scale-up; the falling edge recovers
    -> at least one scale-down; capacity never leaves
    [min_replicas, max_replicas]; hysteresis + cooldown bound total
    actions; burn ends recovered."""
    t = [1_000.0]
    clock = lambda: t[0]                                     # noqa: E731

    def tracker():
        return SLOTracker([SLObjective.shed_frac(
            0.1, min_events=4, short_window_s=20.0,
            long_window_s=100.0)], clock=clock)

    def handle(rid, engine):
        return EngineReplica(
            rid, engine.reset(), role="decode",
            admission=AdmissionController(max_queue=2),
            metrics=ServeMetrics(slo=tracker()))

    fleet = DisaggController([handle("d0", engines[0])],
                             heartbeat_ms=25, suspect_misses=10**9,
                             dead_misses=2 * 10**9, clock=clock)
    spawned = []

    def factory():
        h = handle(f"d{1 + len(spawned)}", engines[1 + len(spawned)])
        spawned.append(h.replica_id)
        return h

    scaler = Autoscaler(fleet, role="decode", min_replicas=1,
                        max_replicas=2, factory=factory, up_burn=1.0,
                        down_burn=0.25, evals=2, cooldown_s=10.0,
                        clock=clock)
    fleet.autoscaler = scaler               # pump() ticks it

    day_s = 240.0
    # peak ~1 rps against ~0.66 rps of single-replica service below
    mean_rps = 0.625
    traffic = DiurnalTraffic(
        day_s=day_s, seed=3, prompt_lens=(4,), max_new_tokens=4,
        vocab=CFG.vocab_size, clock=clock,
        capacity_scale=mean_rps / (2_000_000 * 8.0 / 86400.0))
    traffic.start(t[0])

    active_trace, burn_trace, first_up_t = [], [], None
    for _ in range(int(day_s / 2.0)):
        t[0] += 2.0
        for r in traffic.due(t[0]):
            fleet.submit(r)
        for h in fleet.handles:             # bounded service per tick
            if not h.crashed:
                h.scheduler.step()
                h.publish_progress()
                h.metrics.slo.evaluate(now=t[0])
        fleet.pump()
        active_trace.append(len(scaler.active()))
        burn_trace.append(scaler.signals()["burn"])
        if scaler.scale_ups and first_up_t is None:
            first_up_t = t[0]

    assert traffic.emitted >= 100, "the diurnal day produced no load"
    assert scaler.scale_ups >= 1, \
        f"peak never scaled up (max burn {max(burn_trace):.2f})"
    assert scaler.scale_downs >= 1, \
        f"trough never scaled down (min burn {min(burn_trace):.2f})"
    assert min(active_trace) >= 1, "capacity fell below min_replicas"
    assert max(active_trace) <= 2, "capacity exceeded max_replicas"
    assert scaler.scale_ups + scaler.scale_downs <= 6, \
        f"flapping: {scaler.scale_ups} ups / {scaler.scale_downs} downs"
    assert max(burn_trace) >= scaler.up_burn     # pressure was real
    assert burn_trace[-1] < scaler.up_burn, \
        "burn never recovered after scaling"


def test_autoscaler_warm_restart_prefers_drained_standby(engines):
    """A scale-up with a DRAINED standby warm-restarts it instead of
    cold-spawning — zero recompiles, no factory call."""
    t = [0.0]
    clock = lambda: t[0]                                     # noqa: E731
    mets = [ServeMetrics(slo=SLOTracker(
        [SLObjective.shed_frac(0.1, min_events=4)], clock=clock))
        for _ in range(2)]
    handles = [EngineReplica(f"d{i}", engines[i].reset(), role="decode",
                             metrics=m)
               for i, m in enumerate(mets)]
    fleet = DisaggController(handles, heartbeat_ms=25,
                             suspect_misses=5_000, dead_misses=10_000,
                             clock=clock)
    calls = []
    scaler = Autoscaler(fleet, role="decode", min_replicas=1,
                        max_replicas=2,
                        factory=lambda: calls.append(1),
                        evals=1, cooldown_s=0.0, clock=clock)
    fleet.drain("d1", wait=False)
    fleet.pump()                            # idle replica drains at once
    assert fleet.registry.state("d1") == REPLICA_DRAINED
    traces = handles[1].engine.decode_traces

    for _ in range(8):
        mets[0].slo.observe("shed", bad=True, t=t[0])
    mets[0].slo.evaluate(now=t[0])
    assert scaler.tick() == "up"
    assert fleet.registry.state("d1") == "healthy"
    assert calls == [], "cold-spawned despite a warm standby"
    assert handles[1].engine.decode_traces == traces, \
        "a warm restart must keep every compiled artifact"
    assert scaler.scale_ups == 1 and scaler.spawned == 0


def test_autoscaler_and_controller_validation(engines, params):
    fleet = DisaggController(
        [EngineReplica("d0", engines[0].reset(), role="decode")],
        heartbeat_ms=25, suspect_misses=5_000, dead_misses=10_000)
    with pytest.raises(ValueError, match="role"):
        Autoscaler(fleet, role="router")
    with pytest.raises(ValueError, match="min_replicas"):
        Autoscaler(fleet, min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="down_burn"):
        Autoscaler(fleet, up_burn=0.5, down_burn=0.5)
    with pytest.raises(ValueError, match="free_frac"):
        Autoscaler(fleet, up_free_frac=0.6, down_free_frac=0.5)
    # a fleet of only prefill replicas serves nobody
    with pytest.raises(ValueError, match="serves nobody"):
        DisaggController(
            [EngineReplica("p0", engines[0].reset(), role="prefill")],
            heartbeat_ms=25)
    # disaggregation without a prefix index has nothing to stream through
    slot_engine = Engine(CFG, params,
                         EngineConfig(num_slots=2, max_len=32,
                                      temperature=0.0), seed=0)
    with pytest.raises(ValueError, match="prefix"):
        DisaggController(
            [EngineReplica("p0", engines[0].reset(), role="prefill"),
             EngineReplica("d0", slot_engine, role="decode")],
            heartbeat_ms=25)


# ------------------------------------------------------ diurnal traffic

def test_diurnal_traffic_seeded_curve_and_volume():
    def stream(seed):
        tr = DiurnalTraffic(day_s=100.0, seed=seed, prompt_lens=(4, 6),
                            capacity_scale=2.0 / (2_000_000 * 8.0
                                                  / 86400.0),
                            clock=lambda: 0.0).start(0.0)
        out = []
        for i in range(1, 101):
            out.extend((r.request_id, tuple(r.tokens))
                       for r in tr.due(float(i)))
        return tr, out

    tr1, s1 = stream(5)
    _, s2 = stream(5)
    _, s3 = stream(6)
    assert s1 == s2, "same seed + same clock readings must replay"
    assert s1 != s3
    # sinusoid: trough at phase 0, peak at half-day, ratio as configured
    assert math.isclose(tr1.rate_at(50.0) / tr1.rate_at(100.0), 4.0,
                        rel_tol=1e-6)
    # volume integrates to mean_rps * day_s (2 rps * 100 s) +- residue
    assert abs(len(s1) - 200) <= 4
    with pytest.raises(RuntimeError, match="start"):
        DiurnalTraffic().due(1.0)
    with pytest.raises(ValueError, match="peak_to_trough"):
        DiurnalTraffic(peak_to_trough=0.5)


# --------------------------------------------------- fleet of meshes

def test_fleet_of_meshes_tp_replicas_bit_exact(engines, tp_engines):
    """PR 15's open edge: tp=2 composed with replicas=2. Each replica
    owns its own serving mesh, compiles once, and the fleet's greedy
    outputs match the single-chip oracle bit-for-bit."""
    for e in tp_engines:
        assert e.mesh is not None and e.mesh.shape["tp"] == 2
    reqs = [Request(request_id=f"m{i}", tokens=_tokens(8, seed=20 + i),
                    max_new_tokens=4) for i in range(3)]
    oracle = _oracle(engines[0], reqs)      # tp=1 single-chip reference

    handles = [EngineReplica(f"r{i}", e.reset(), role="unified")
               for i, e in enumerate(tp_engines)]
    traces = [e.decode_traces for e in tp_engines]
    fleet = FleetController(handles, heartbeat_ms=25,
                            suspect_misses=5_000, dead_misses=10_000)
    for r in reqs:
        fleet.submit(Request(request_id=r.request_id,
                             tokens=list(r.tokens), max_new_tokens=4))
    stats = fleet.run(max_wall_s=30)
    _assert_exactly_one_terminal_fleetwide(stats, [r.request_id
                                                   for r in reqs])
    for rec in stats.requests:
        assert rec["state"] == "completed"
        assert rec["generated"] == oracle[rec["request_id"]], \
            f"{rec['request_id']} drifted on the sharded fleet"
    assert [e.decode_traces for e in tp_engines] == traces, \
        "a tp replica recompiled decode under fleet serving"


# ------------------------------------------- regression-gate semantics

def test_check_regression_handoff_counters_and_disagg_provenance():
    """ISSUE 16 satellite: refusal/autoscale counters are
    lower-is-better (0 -> N regresses even against a zero baseline),
    and a disaggregated suite entry is refused against a unified
    baseline instead of being numerically compared."""
    from tools.check_regression import compare, incomparable_entries

    rows, _ = compare({"serve_decode.handoff_refused": (3.0, None)},
                      {"serve_decode.handoff_refused": (0.0, None)}, 0.1)
    row, = rows
    assert row["direction"] == "lower"
    assert row["regressed"] and row["ratio"] == float("inf")
    rows, _ = compare({"serve_decode.autoscale_actions": (5.0, None)},
                      {"serve_decode.autoscale_actions": (0.0, None)},
                      0.1)
    assert rows[0]["regressed"], "autoscale churn growth must regress"
    rows, _ = compare({"serve_decode.handoff_refused": (0.0, None)},
                      {"serve_decode.handoff_refused": (0.0, None)}, 0.1)
    assert not rows[0]["regressed"]

    wl = {"tp": 1, "tp_sync": None, "disagg": True, "roles": "1:2",
          "diurnal": False}
    cur = {"serve_decode": {"value": 10.0, "workload": dict(wl)}}
    base = {"serve_decode": {"value": 10.0,
                             "workload": dict(wl, disagg=False,
                                              roles=None)}}
    assert incomparable_entries(cur, base) == {
        "serve_decode": "workload.disagg=True vs baseline "
                        "workload.disagg=False"}
    base_roles = {"serve_decode": {"value": 10.0,
                                   "workload": dict(wl, roles="2:1")}}
    assert incomparable_entries(cur, base_roles) == {
        "serve_decode": "workload.roles=1:2 vs baseline "
                        "workload.roles=2:1"}
    # a legacy baseline without the axis means its default (unified):
    # refused against a disagg run, comparable against a unified one
    legacy = {"serve_decode": {"value": 10.0, "workload": {"tp": 1}}}
    assert "serve_decode" in incomparable_entries(cur, legacy)
    unified = {"serve_decode": {
        "value": 10.0, "workload": dict(wl, disagg=False, roles=None)}}
    assert incomparable_entries(unified, legacy) == {}
    diurnal = {"serve_decode": {"value": 10.0,
                                "workload": dict(wl, disagg=False,
                                                 roles=None,
                                                 diurnal=True)}}
    assert "diurnal" in incomparable_entries(diurnal, legacy).get(
        "serve_decode", "")


# --------------------------------------------------------- CLI matrix

def test_serve_cli_disagg_flag_matrix():
    """Contradictory disaggregation/autoscale flag combinations exit 2
    with a diagnostic, before any engine is built."""
    from apex_tpu.serve.cli import main as serve_main

    bad = [
        ["--roles", "1:2"],                          # needs paging
        ["--roles", "0:2", "--page-size", "4", "--prefix-cache"],
        ["--roles", "x:y", "--page-size", "4", "--prefix-cache"],
        ["--roles", "1:1", "--replicas", "3",
         "--page-size", "4", "--prefix-cache"],      # 3 != 1+1
        ["--roles", "1:1", "--replicas", "1",
         "--page-size", "4", "--prefix-cache"],
        ["--autoscale", "--replicas", "2"],          # needs --slo
        ["--min-replicas", "2"],                     # needs --autoscale
        ["--autoscale", "--replicas", "2",
         "--slo", "ttft_p99_ms=500", "--min-replicas", "3",
         "--max-replicas", "2"],
    ]
    for argv in bad:
        assert serve_main(argv) == 2, argv


def test_bench_cli_disagg_flag_matrix(monkeypatch):
    import sys

    from apex_tpu.bench_cli import _serve_bench
    from apex_tpu.bench_cli import main as bench_main

    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, roles="1:1")                 # needs --disagg
    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, disagg=True)                 # needs paging
    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, disagg=True, page_size=4, prefix_cache=True,
                     replicas=1)
    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, disagg=True, page_size=4, prefix_cache=True,
                     roles="1:0")
    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, disagg=True, page_size=4, prefix_cache=True,
                     roles="2:2", replicas=3)
    with pytest.raises(SystemExit, match="apex-tpu-bench"):
        _serve_bench(4, diurnal=True)                # needs a fleet
    monkeypatch.setattr(sys, "argv", ["apex-tpu-bench", "--disagg"])
    with pytest.raises(SystemExit):
        bench_main()                                 # needs --serve
