"""Serving resilience tier-1: deadlines, admission control / load
shedding, graceful degradation, and crash-recovering warm restart.

THE chaos invariant under test (ISSUE 8 acceptance): under any seeded
``FaultInjector`` schedule — decode-step crashes, latency spikes, queue
storms, deadlines, bounded queues — **every submitted request reaches
exactly one terminal status** (completed / evicted / aborted / rejected /
deadline-exceeded), no request is ever silently lost, surviving slots'
greedy outputs equal an uncrashed run's token for token, and
``Engine.decode_traces`` does not grow across a ``recover()`` (the
compiled executables are reused, never retraced).

Across the prefill/decode border: recovery re-prefills prompt plus
generated-so-far in ONE batched forward where the unbroken stream
decoded them one row at a time; the same mathematics in another order
of float32 sums, so the continued stream's logits match the unbroken
one's to rounding (``tests/test_serve.py``: ``BORDER``) and its tokens
are equal on these seeds. The assertions below compare tokens.

Engines are compiled once per geometry and shared across tests via
``Engine.reset()`` (the PR-5 contract); trace-counter assertions use
before/after deltas so sharing stays airtight.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor.goodput import GoodputLedger
from apex_tpu.resilience.fault_injection import (FaultInjector,
                                                 SimulatedCrash)
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.resilience import (SHED_POLICIES, AdmissionController,
                                       ServeSupervisor, TickJournal)
from apex_tpu.serve.scheduler import (TERMINAL_STATES, Request,
                                      ServeScheduler)
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session, and a function-local re-import after that
# would subscribe to a FRESH bus while the (old) scheduler module keeps
# publishing to the original one
from apex_tpu.utils.logging import subscribe_events

pytestmark = [pytest.mark.serve, pytest.mark.fault]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


@pytest.fixture(scope="module")
def greedy2(params):
    """Shared greedy 2-slot engine; tests reset() it — compiled once."""
    return Engine(CFG, params,
                  EngineConfig(num_slots=2, max_len=32, temperature=0.0),
                  seed=0)


def _tokens(n, seed=7, vocab=97):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


def _requests(n=4, max_new=6, **kw):
    return [Request(request_id=f"r{i}", tokens=_tokens(5, seed=i),
                    max_new_tokens=max_new, **kw) for i in range(n)]


def _assert_exactly_one_terminal(sched, expected_ids):
    """The chaos invariant: every submitted id has exactly one record,
    every record is terminal, nothing extra, nothing in flight."""
    recs = sched.stats().requests
    ids = [r["request_id"] for r in recs]
    assert sorted(ids) == sorted(expected_ids), \
        (sorted(set(expected_ids) - set(ids)),
         sorted(set(ids) - set(expected_ids)))
    assert len(ids) == len(set(ids)), "a request was accounted twice"
    for r in recs:
        assert r["state"] in TERMINAL_STATES, r
    assert not sched.queue and all(s is None for s in sched.slots)


# ------------------------------------------------------------- deadlines

def test_deadline_expires_queued_and_running(greedy2):
    """A latency spike pushes a running request past its budget; a
    queued-but-never-admitted request times out too. Both land as
    terminal deadline records with the lost time charged to the ledger."""
    inj = FaultInjector(seed=0).latency_spike(1, 0.25)
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj)
    sched.submit(Request(request_id="slow", tokens=_tokens(5),
                         max_new_tokens=20))
    sched.submit(Request(request_id="tight", tokens=_tokens(5, seed=1),
                         max_new_tokens=20, deadline_ms=100.0))
    sched.submit(Request(request_id="waiting", tokens=_tokens(5, seed=2),
                         max_new_tokens=4, deadline_ms=50.0))
    with GoodputLedger() as led:
        stats = sched.run()
    recs = {r["request_id"]: r for r in stats.requests}
    assert recs["slow"]["state"] == "completed"
    for rid in ("tight", "waiting"):
        assert recs[rid]["state"] == "evicted"
        assert recs[rid]["finish_reason"] == "deadline"
    g = led.summary()
    assert g["events"]["serve_deadline_exceeded"] == 2
    # the whole submit-to-expiry span is a counted loss cause
    assert g["lost_by_cause"]["serve_deadline_exceeded"] > 0.1
    s = stats.summary()
    assert s["deadline_exceeded"] == 2 and s["completed"] == 1
    _assert_exactly_one_terminal(sched, ["slow", "tight", "waiting"])


def test_generous_deadline_never_fires(greedy2):
    sched = ServeScheduler(greedy2.reset())
    for r in _requests(3, deadline_ms=60_000.0):
        sched.submit(r)
    stats = sched.run()
    assert all(r["state"] == "completed" for r in stats.requests)
    assert stats.summary()["deadline_exceeded"] == 0


# ----------------------------------------------------- admission control

def test_reject_newest_bounds_the_backlog(greedy2):
    adm = AdmissionController(max_queue=2, shed_policy="reject-newest")
    sched = ServeScheduler(greedy2.reset(), admission=adm)
    with GoodputLedger() as led:
        verdicts = [sched.submit(r) for r in _requests(5)]
        stats = sched.run()
    assert verdicts == [True, True, False, False, False]
    recs = {r["request_id"]: r for r in stats.requests}
    for rid in ("r2", "r3", "r4"):
        assert recs[rid]["state"] == "rejected"
        assert recs[rid]["finish_reason"] == "queue_full"
        assert recs[rid]["retriable"] is True
    assert recs["r0"]["state"] == recs["r1"]["state"] == "completed"
    g = led.summary()
    assert g["events"]["serve_request_rejected"] == 3
    assert "serve_rejected" in g["lost_by_cause"]
    assert stats.summary()["shed_rate"] == pytest.approx(3 / 5)
    _assert_exactly_one_terminal(sched, [f"r{i}" for i in range(5)])


def test_shed_oldest_evicts_the_longest_waiter(greedy2):
    adm = AdmissionController(max_queue=2, shed_policy="shed-oldest")
    sched = ServeScheduler(greedy2.reset(), admission=adm)
    verdicts = [sched.submit(r) for r in _requests(4)]
    assert verdicts == [True, True, True, True]   # newest always admitted
    stats = sched.run()
    recs = {r["request_id"]: r for r in stats.requests}
    # r0/r1 (oldest queued) were shed to make room for r2/r3
    for rid in ("r0", "r1"):
        assert recs[rid]["state"] == "rejected"
        assert recs[rid]["finish_reason"] == "shed"
    for rid in ("r2", "r3"):
        assert recs[rid]["state"] == "completed"


def test_priority_sheds_strictly_lower_priority_only(greedy2):
    adm = AdmissionController(max_queue=2, shed_policy="priority")
    sched = ServeScheduler(greedy2.reset(), admission=adm)
    lo = Request(request_id="lo", tokens=_tokens(5), max_new_tokens=3,
                 priority=0)
    mid = Request(request_id="mid", tokens=_tokens(5, seed=1),
                  max_new_tokens=3, priority=1)
    hi = Request(request_id="hi", tokens=_tokens(5, seed=2),
                 max_new_tokens=3, priority=2)
    peer = Request(request_id="peer", tokens=_tokens(5, seed=3),
                   max_new_tokens=3, priority=0)
    assert sched.submit(lo) and sched.submit(mid)
    assert sched.submit(hi)             # sheds lo (lowest priority)
    assert lo.state == "rejected" and lo.finish_reason == "shed"
    assert not sched.submit(peer)       # no strictly-lower victim left
    assert peer.finish_reason == "priority"
    stats = sched.run()
    recs = {r["request_id"]: r for r in stats.requests}
    assert recs["mid"]["state"] == recs["hi"]["state"] == "completed"
    _assert_exactly_one_terminal(sched, ["lo", "mid", "hi", "peer"])


def test_shed_policy_validation():
    with pytest.raises(ValueError, match="shed_policy"):
        AdmissionController(max_queue=1, shed_policy="drop-table")
    with pytest.raises(ValueError, match="max_queue"):
        AdmissionController(max_queue=0)
    assert set(SHED_POLICIES) == {"reject-newest", "shed-oldest",
                                  "priority"}


# -------------------------------------------------- graceful degradation

def test_degraded_mode_clamps_admitted_budgets(greedy2):
    """A queue storm holding the backlog at the high watermark flips
    degraded mode; requests admitted while degraded get their token
    budget clamped; the mode clears once the queue drains — both
    transitions on the bus. (``sustain_ticks=1`` here so the clear is
    observable before the drained loop goes idle; the sustained-overload
    hysteresis is unit-tested below.)"""
    adm = AdmissionController(max_queue=8, queue_high=2, sustain_ticks=1,
                              degraded_max_new_tokens=1)
    inj = FaultInjector(seed=3).queue_storm(0, 6, prompt_len=4,
                                            max_new_tokens=8)
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                           admission=adm)
    sched.submit(Request(request_id="warm", tokens=_tokens(4),
                         max_new_tokens=8))
    with GoodputLedger() as led:
        stats = sched.run()
    g = led.summary()
    assert g["events"]["serve_degraded_mode"] == 2   # entered + cleared
    recs = {r["request_id"]: r for r in stats.requests}
    # requests admitted under degradation finished after ONE token (the
    # clamp); the backlog pressure is what drove it there
    clamped = [r for r in recs.values()
               if r["state"] == "completed" and r["new_tokens"] == 1]
    assert clamped, "no request was ever clamped"
    assert not adm.degraded                          # cleared at drain
    _assert_exactly_one_terminal(
        sched, ["warm"] + [f"storm-{i}" for i in range(6)])


def test_degraded_mode_requires_sustained_overload():
    """The hysteresis contract: a one-tick spike never flips the mode in
    either direction — only ``sustain_ticks`` CONSECUTIVE overloaded
    (resp. calm) ticks do."""
    adm = AdmissionController(max_queue=8, queue_high=4, sustain_ticks=3,
                              degraded_max_new_tokens=2)
    assert adm.on_tick(5) is None and adm.on_tick(5) is None
    assert adm.on_tick(0) is None          # spike broken: counter resets
    assert adm.on_tick(5) is None and adm.on_tick(5) is None
    assert adm.on_tick(5) is True and adm.degraded
    assert adm.clamp(16) == 2
    assert adm.on_tick(0) is None and adm.on_tick(0) is None
    assert adm.on_tick(5) is None          # calm streak broken
    assert adm.degraded
    assert adm.on_tick(0) is None and adm.on_tick(0) is None
    assert adm.on_tick(0) is False and not adm.degraded
    assert adm.clamp(16) == 16


def test_hbm_pressure_counts_as_overload():
    adm = AdmissionController(degraded_max_new_tokens=2, sustain_ticks=1,
                              hbm_frac_high=0.9)
    assert not adm.overloaded(queue_depth=0)
    adm.note_hbm({"bytes_in_use": 95, "bytes_limit": 100})
    assert adm.overloaded(queue_depth=0)
    assert adm.on_tick(0) is True and adm.degraded
    assert adm.clamp(16) == 2
    adm.note_hbm({"bytes_in_use": 10, "bytes_limit": 100})
    assert adm.on_tick(0) is False and not adm.degraded
    assert adm.clamp(16) == 16


def test_pool_low_watermark_counts_as_overload(paged2):
    """PR-9 satellite: a drained paged-KV free list is an overload
    signal like queue depth and HBM pressure — and the scheduler feeds
    ``Engine.free_page_frac`` to the controller each tick, so sustained
    pool pressure clamps admitted budgets end-to-end."""
    adm = AdmissionController(degraded_max_new_tokens=2, sustain_ticks=1,
                              pool_frac_low=0.10)
    assert not adm.overloaded(queue_depth=0)
    adm.note_pool(0.05)                    # below the low watermark
    assert adm.overloaded(queue_depth=0)
    assert adm.on_tick(0) is True and adm.degraded
    assert adm.clamp(16) == 2
    adm.note_pool(0.8)
    assert adm.on_tick(0) is False and not adm.degraded
    adm.note_pool(None)                    # no signal: state unchanged
    assert not adm.overloaded(queue_depth=0)

    # end-to-end: a drained overcommitted pool degrades admitted budgets.
    # free_page_frac counts free + LRU-evictable index pages (a completed
    # request's cached pages are allocatable on demand — PR 16), so the
    # watermark sits above the in-use-dominated fraction r1 pins (3/8
    # pages held while it decodes), not the raw free-list level.
    # r0 finishes fast and frees its slot while long-running r1 keeps
    # holding pages, so r2 is admitted INTO the drained-pool window and
    # gets the clamp
    eng = paged2.reset()
    sysp = _tokens(8, seed=99)
    adm = AdmissionController(degraded_max_new_tokens=3, sustain_ticks=1,
                              pool_frac_low=0.70)
    sched = ServeScheduler(eng, admission=adm)
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("event") == "serve_degraded_mode" else None)
    try:
        for rid, tail, max_new in (("r0", 3, 2), ("r1", 4, 8),
                                   ("r2", 5, 8)):
            sched.submit(Request(request_id=rid,
                                 tokens=sysp + _tokens(tail, seed=ord(
                                     rid[-1])),
                                 max_new_tokens=max_new))
        stats = sched.run()
    finally:
        unsub()
    assert any(e["entered"] for e in seen)
    recs = {r["request_id"]: r for r in stats.requests}
    assert all(r["state"] == "completed" for r in recs.values())
    assert recs["r1"]["new_tokens"] == 8      # pre-overload budget kept
    assert recs["r2"]["new_tokens"] == 3, \
        "the degraded-window admission should have been clamped to 3"


# ------------------------------------------------ warm restart / chaos

def _run_supervised(eng, injector, requests, *, max_restarts=2,
                    journal=None):
    sched = ServeScheduler(eng, fault_injector=injector,
                           journal=journal or TickJournal())
    for r in requests:
        sched.submit(r)
    stats = ServeSupervisor(sched, max_restarts=max_restarts,
                            sleep=lambda s: None).run()
    return sched, stats


def test_crash_recover_drain_smoke(greedy2):
    """THE tier-1 chaos acceptance: one schedule combining a decode-step
    crash, a latency spike, and a queue storm. Every submitted request
    (initial + storm) reaches exactly one terminal status, surviving
    requests' greedy outputs equal an uncrashed run's token for token
    (across the border: module docstring), and decode compiles exactly
    zero additional times across the recovery."""
    base_sched = ServeScheduler(greedy2.reset())
    for r in _requests(4):
        base_sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_sched.run().requests}
    traces_before = greedy2.decode_traces

    inj = (FaultInjector(seed=0)
           .crash_on_decode_step(2)
           .latency_spike(4, 0.02)
           .queue_storm(3, 3, prompt_len=4, max_new_tokens=2))
    sched, stats = _run_supervised(greedy2.reset(), inj, _requests(4))
    assert greedy2.decode_traces == traces_before, \
        "recover() must reuse the compiled decode executable"
    assert stats.restarts == 1
    _assert_exactly_one_terminal(
        sched, [f"r{i}" for i in range(4)] + [f"storm-{i}"
                                              for i in range(3)])
    recs = {r["request_id"]: r for r in stats.requests}
    for rid, gen in base.items():
        assert recs[rid]["state"] == "completed"
        assert recs[rid]["generated"] == gen, \
            f"{rid} drifted across the warm restart"


def test_warm_restart_determinism_greedy(greedy2):
    """Crash at every early tick in turn: greedy outputs always equal the
    uncrashed run — recovery's batched re-prefill matches the unbroken
    decode stream to float32 rounding (the module docstring's border),
    which on these seeds is the same argmax at every token, and the
    journal rollback replays the torn tick identically."""
    base_sched = ServeScheduler(greedy2.reset())
    for r in _requests(3):
        base_sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_sched.run().requests}
    for crash_at in (0, 1, 4):
        inj = FaultInjector(seed=0).crash_on_decode_step(crash_at)
        sched, stats = _run_supervised(greedy2.reset(), inj, _requests(3))
        assert stats.restarts == 1, crash_at
        got = {r["request_id"]: r["generated"] for r in stats.requests}
        assert got == base, f"crash at step {crash_at} changed outputs"


def test_warm_restart_replays_sampled_stream(params):
    """The PRNG key path is journaled and restored: a temperature>0
    stream continues token for token across a crash — the strictest
    form of 'surviving slots stay identical': the re-prefilled logits
    match to float32 rounding (the module docstring's border) and the
    restored key draws the same tokens from them on these seeds."""
    eng = Engine(CFG, params,
                 EngineConfig(num_slots=2, max_len=32, temperature=0.8,
                              top_k=5), seed=0)
    base_sched = ServeScheduler(eng)
    for r in _requests(2, max_new=8):
        base_sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_sched.run().requests}
    inj = FaultInjector(seed=0).crash_on_decode_step(3)
    sched, stats = _run_supervised(eng.reset(0), inj,
                                   _requests(2, max_new=8))
    assert stats.restarts == 1
    got = {r["request_id"]: r["generated"] for r in stats.requests}
    assert got == base, "sampled stream diverged across the restart"


def test_post_snapshot_admission_survives_crash(greedy2):
    """Review regression: a request submitted AND admitted inside the
    crashing tick (a storm arrival taking a free slot) exists in neither
    the snapshot's queue nor its slots nor the live queue — recover()
    must roll it back to queued, not forget it."""
    base_sched = ServeScheduler(greedy2.reset())
    base_sched.submit(Request(request_id="r0", tokens=_tokens(5, seed=0),
                              max_new_tokens=8))
    base = base_sched.run().requests[0]["generated"]

    inj = (FaultInjector(seed=0)
           .queue_storm(2, 2, prompt_len=4, max_new_tokens=3)
           .crash_on_decode_step(2))
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                           journal=TickJournal())
    # one long request on a 2-slot engine: a slot stays free for the
    # storm arrival to be admitted in the very tick that crashes
    sched.submit(Request(request_id="r0", tokens=_tokens(5, seed=0),
                         max_new_tokens=8))
    stats = ServeSupervisor(sched, max_restarts=2,
                            sleep=lambda s: None).run()
    assert stats.restarts == 1
    _assert_exactly_one_terminal(sched, ["r0", "storm-0", "storm-1"])
    recs = {r["request_id"]: r for r in stats.requests}
    assert all(r["state"] == "completed" for r in recs.values())
    assert recs["r0"]["generated"] == base


def test_failed_recovery_still_drains(greedy2, monkeypatch):
    """Review regression: when recover() itself raises (the likeliest
    production shape — the re-prefill hits the same dead runtime), the
    supervisor must still drain every live request to a terminal status
    before propagating."""
    inj = FaultInjector(seed=0).crash_on_decode_step(2)
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                           journal=TickJournal())
    for r in _requests(4):
        sched.submit(r)

    def broken_recover(error=None):
        raise RuntimeError("re-prefill hit the dead runtime too")

    monkeypatch.setattr(sched, "recover", broken_recover)
    with pytest.raises(RuntimeError, match="dead runtime"):
        ServeSupervisor(sched, max_restarts=2,
                        sleep=lambda s: None).run()
    _assert_exactly_one_terminal(sched, [f"r{i}" for i in range(4)])
    assert {r["finish_reason"] for r in sched.stats().requests} == \
        {"engine_failure"}


def test_restart_budget_exhausted_drains_and_rejects(greedy2):
    """When recovery keeps failing, the supervisor stops pretending:
    every still-live request is drained to a terminal status (queued →
    rejected-retriable, in-flight → evicted), the engine is never
    touched again, and the fatal error propagates."""
    inj = FaultInjector(seed=0).crash_on_decode_step(2, times=5)
    sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                           journal=TickJournal())
    for r in _requests(4):
        sched.submit(r)
    with GoodputLedger() as led:
        with pytest.raises(SimulatedCrash):
            ServeSupervisor(sched, max_restarts=1,
                            sleep=lambda s: None).run()
    assert sched.restarts == 1
    _assert_exactly_one_terminal(sched, [f"r{i}" for i in range(4)])
    recs = {r["request_id"]: r for r in sched.stats().requests}
    assert {r["finish_reason"] for r in recs.values()} == \
        {"engine_failure"}
    queued = [r for r in recs.values() if r["state"] == "rejected"]
    inflight = [r for r in recs.values() if r["state"] == "evicted"]
    assert queued and inflight
    assert all(r["retriable"] for r in queued)
    assert led.summary()["events"]["serve_engine_restart"] == 1


def test_supervisor_requires_a_journal(greedy2):
    with pytest.raises(ValueError, match="journal"):
        ServeSupervisor(ServeScheduler(greedy2.reset()))


def test_recover_without_snapshot_refuses(greedy2):
    sched = ServeScheduler(greedy2.reset(), journal=TickJournal())
    with pytest.raises(RuntimeError, match="snapshot"):
        sched.recover()


# ----------------------------------------------------------- the journal

def test_journal_persists_atomically(tmp_path, greedy2):
    """The on-disk journal commits via .tmp + os.replace (APX004): after
    a run the file is one complete JSON document with the schema the
    recovery/postmortem tooling expects, and no .tmp straggler remains."""
    path = str(tmp_path / "serve_journal.json")
    sched = ServeScheduler(greedy2.reset(),
                           journal=TickJournal(path, every=1))
    for r in _requests(3):
        sched.submit(r)
    sched.run()
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")
    doc = json.loads(open(path).read())
    assert doc["schema"] == 1
    assert set(doc) >= {"decode_steps", "decode_tokens", "engine",
                        "slots", "queued"}
    assert set(doc["engine"]) == {"rng", "last_tokens", "lengths"}
    # object refs never leak into the serialized view
    assert all(e is None or set(e) == {"request_id", "prompt",
                                       "generated"}
               for e in doc["slots"])


def test_journal_cadence_bounds_disk_writes(tmp_path, greedy2):
    calls = []
    journal = TickJournal(str(tmp_path / "j.json"), every=4)
    orig = journal.save
    journal.save = lambda *a, **k: (calls.append(1), orig(*a, **k))[1]
    sched = ServeScheduler(greedy2.reset(), journal=journal)
    for r in _requests(2):
        sched.submit(r)
    sched.run()
    assert journal.ticks_recorded > len(calls) >= 1


def test_restore_sampling_state_integrity_check(greedy2):
    eng = greedy2.reset()
    eng.prefill({0: _tokens(5)})
    state = eng.sampling_state()
    eng.reset()
    with pytest.raises(ValueError, match="integrity"):
        eng.restore_sampling_state(state, slots=[0])  # nothing re-prefilled


# --------------------------------------------------------- queued aborts

def test_queued_abort_charges_queue_wait(greedy2):
    """Satellite regression: aborting a still-queued request publishes
    its wasted queue time (before PR 8 the wait silently vanished from
    the ledger)."""
    waits = []
    unsub = subscribe_events(
        lambda r: waits.append(r) if r.get("event") == "serve_queue_wait"
        and r.get("request_id") == "r2" else None)
    try:
        inj = FaultInjector(seed=0).abort_request("r2", at_step=1)
        sched = ServeScheduler(greedy2.reset(), fault_injector=inj)
        for r in _requests(3):
            sched.submit(r)
        sched.run()
    finally:
        unsub()
    assert len(waits) == 1 and waits[0]["seconds"] >= 0.0


# --------------------------------------------------------------- the CLI

def test_serve_cli_resilience_flags(capsys):
    """In-process CLI e2e: --max-queue shedding surfaces retriable
    rejections per request, and the summary carries the SLO fields."""
    from apex_tpu.serve.cli import main

    rc = main(["--config", "tiny", "--requests", "4", "--prompt-len", "4",
               "--max-new-tokens", "3", "--num-slots", "2",
               "--max-len", "32", "--temperature", "0",
               "--max-queue", "2", "--shed-policy", "reject-newest",
               "--max-restarts", "1", "--deadline-ms", "60000"])
    assert rc == 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    recs, summary = lines[:-1], lines[-1]
    assert len(recs) == 4
    rejected = [r for r in recs if r["state"] == "rejected"]
    assert len(rejected) == 2
    assert all(r["retriable"] is True for r in rejected)
    s = summary["summary"]
    assert s["rejected"] == 2 and s["shed_rate"] == pytest.approx(0.5)
    assert s["deadline_exceeded"] == 0 and s["restarts"] == 0
    assert summary["decode_compiles"] == 1


# ------------------------------------------ warm restart under paging

@pytest.fixture(scope="module")
def paged2(params):
    """Shared 2-slot paged+prefix greedy engine for the paging
    resilience tests; reset() keeps the compile."""
    return Engine(CFG, params,
                  EngineConfig(num_slots=2, max_len=32, temperature=0.0,
                               page_size=8, prefix_cache=True), seed=0)


def _prefix_requests(n=4, max_new=6):
    """Mixed requests sharing one full-page system prefix, so shared
    read-only pages are resident (and index-pinned) at crash time."""
    sysp = _tokens(8, seed=99)
    return [Request(request_id=f"r{i}",
                    tokens=sysp + _tokens(3 + i % 3, seed=i),
                    max_new_tokens=max_new) for i in range(n)]


def test_chaos_smoke_under_paging(paged2):
    """ISSUE 9 acceptance: THE PR-8 chaos smoke re-run on a paged engine
    with shared prefix pages — decode-step crash + latency spike + queue
    storm. Every submitted request reaches exactly one terminal status,
    surviving greedy outputs equal the uncrashed paged run's token for
    token, and decode_traces delta is 0 across the recovery."""
    base_sched = ServeScheduler(paged2.reset())
    for r in _prefix_requests(4):
        base_sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_sched.run().requests}
    traces_before = paged2.decode_traces

    inj = (FaultInjector(seed=0)
           .crash_on_decode_step(2)
           .latency_spike(4, 0.02)
           .queue_storm(3, 3, prompt_len=4, max_new_tokens=2))
    sched, stats = _run_supervised(paged2.reset(), inj,
                                   _prefix_requests(4))
    assert paged2.decode_traces == traces_before, \
        "paged recover() must reuse the compiled decode executable"
    assert stats.restarts == 1
    _assert_exactly_one_terminal(
        sched, [f"r{i}" for i in range(4)] + [f"storm-{i}"
                                              for i in range(3)])
    recs = {r["request_id"]: r for r in stats.requests}
    for rid, gen in base.items():
        assert recs[rid]["state"] == "completed"
        assert recs[rid]["generated"] == gen, \
            f"{rid} drifted across the paged warm restart"


def test_warm_restart_paged_determinism_and_journal(paged2):
    """Crash at every early tick in turn: the paged engine's greedy
    outputs always equal the uncrashed run (recovery re-prefill through
    shared pages matches to float32 rounding: the module docstring's
    border), and the journal payload records the page
    accounting — tables, refcounts, prefix-index size — for the
    postmortem."""
    base_sched = ServeScheduler(paged2.reset())
    for r in _prefix_requests(3):
        base_sched.submit(r)
    base = {r["request_id"]: r["generated"]
            for r in base_sched.run().requests}
    journal = None
    for crash_at in (0, 1, 4):
        journal = TickJournal()
        inj = FaultInjector(seed=0).crash_on_decode_step(crash_at)
        sched, stats = _run_supervised(paged2.reset(), inj,
                                       _prefix_requests(3),
                                       journal=journal)
        assert stats.restarts == 1, crash_at
        got = {r["request_id"]: r["generated"] for r in stats.requests}
        assert got == base, \
            f"paged crash at step {crash_at} changed outputs"
    payload = journal.to_payload()
    pg = payload["paging"]
    assert pg["page_size"] == 8
    assert len(pg["refcounts"]) == pg["num_pages"]
    assert len(pg["page_table"]) == 2           # [num_slots][max_pages]
    assert all(len(row) == 4 for row in pg["page_table"])


def test_default_engine_journals_its_one_page_a_slot(greedy2):
    """An engine built with no ``page_size`` has the one pool every
    engine has, a ``max_len`` page a slot, and its journal says so: the
    'paging' section carries that geometry, every page back in the free
    list once both requests completed."""
    sched = ServeScheduler(greedy2.reset(), journal=TickJournal())
    for r in _requests(2, max_new=2):
        sched.submit(r)
    sched.run()
    pg = sched.journal.to_payload()["paging"]
    assert pg["page_size"] == greedy2.max_len == greedy2.page_size
    assert pg["num_pages"] == 3 and pg["free_pages"] == 2   # + null page
    assert pg["page_table"] == [[0], [0]] and pg["prefix_entries"] == 0
    assert greedy2.free_page_frac == 1.0


def test_paged_recovery_reprefills_only_unshared_pages(paged2):
    """recover() keeps the pool bytes and the prefix index (shared pages
    are read-only — the crash cannot have torn them): each surviving
    slot's recovery re-prefill HITS the index for its prompt pages and
    scans only the generated tail — proven by the hit counters, and only
    the original prompt ever enters the index (generated-token pages
    must not pin it)."""
    inj = FaultInjector(seed=0).crash_on_decode_step(3)
    sched, stats = _run_supervised(paged2.reset(), inj,
                                   _prefix_requests(2, max_new=8))
    assert stats.restarts == 1
    recs = {r["request_id"]: r for r in stats.requests}
    assert all(r["state"] == "completed" for r in recs.values())
    # the cold admission batch can't hit (inserts land post-batch), so
    # both hits are the recovery re-prefills riding the surviving index
    assert paged2.prefix_hits == 2
    assert paged2.prefix_hit_tokens == 16       # one 8-token page each
    # index holds ONLY prompt-page hashes: prompts are 11/12 tokens ->
    # one full page each, deduped to the single shared sysp chunk
    assert len(paged2.prefix) == 1


# ------------------------------------------------------- the slow sweep

@pytest.mark.slow
def test_chaos_schedule_sweep(greedy2):
    """Seeded fault-schedule sweep: crashes at different ticks, latency
    spikes, queue storms, deadlines, and bounded queues in combination.
    The invariant holds for every schedule, and any request that
    completes under two different schedules produced prefix-consistent
    greedy output (degradation may clamp lengths; greedy content never
    drifts)."""
    by_prompt = {}
    for seed in range(4):
        rng = np.random.RandomState(seed)
        inj = FaultInjector(seed=seed)
        crash_at = int(rng.randint(0, 5))
        inj.crash_on_decode_step(crash_at)
        if seed % 2:
            inj.latency_spike(int(rng.randint(0, 6)), 0.03)
        storm_n = int(rng.randint(2, 5))
        inj.queue_storm(int(rng.randint(1, 4)), storm_n, prompt_len=4,
                        max_new_tokens=3)
        adm = AdmissionController(max_queue=6,
                                  shed_policy=SHED_POLICIES[seed % 3],
                                  degraded_max_new_tokens=2,
                                  queue_high=3, sustain_ticks=2)
        reqs = [Request(request_id=f"r{i}", tokens=_tokens(5, seed=i),
                        max_new_tokens=5,
                        deadline_ms=5_000.0 if i % 2 else None,
                        priority=i % 3)
                for i in range(5)]
        sched = ServeScheduler(greedy2.reset(), fault_injector=inj,
                               admission=adm, journal=TickJournal())
        for r in reqs:
            sched.submit(r)
        stats = ServeSupervisor(sched, max_restarts=3,
                                sleep=lambda s: None).run()
        assert stats.restarts >= 1
        expected = [f"r{i}" for i in range(5)] + \
            [f"storm-{i}" for i in range(storm_n)]
        _assert_exactly_one_terminal(sched, expected)
        for rec in stats.requests:
            if rec["state"] != "completed":
                continue
            key = tuple(CFG.vocab_size * 0 + t for t in (
                reqs[int(rec["request_id"][1:])].tokens
                if rec["request_id"].startswith("r") else []))
            if not key:
                continue
            gen, prev = rec["generated"], by_prompt.get(key)
            if prev is not None:
                n = min(len(gen), len(prev))
                assert gen[:n] == prev[:n], \
                    f"{rec['request_id']} drifted across schedules"
            if prev is None or len(gen) > len(prev):
                by_prompt[key] = gen
    assert by_prompt, "no request ever completed across the sweep"
