"""Span-tree tracing, HBM accounting, flight recorder (marker: ``trace``).

The acceptance claims under test:

- spans form a correct tree (shared ``trace_id``, parent links), carry
  exact caller-stamped durations, and ride the event bus; a DISABLED
  tracer publishes nothing and yields ``None`` spans (zero overhead);
- Chrome-trace export is loadable JSON — including the unterminated
  array a crashed run leaves (what Perfetto tolerates);
- ``prof.annotate`` mirrors into the span tracer; ``profile()`` refuses
  to nest; ``StepTimer`` works as a context manager;
- ``MemoryAccountant``/static ``memory_analysis`` publish
  ``hbm_snapshot`` events that the goodput ledger folds into its summary;
- the flight recorder's ring stays bounded under a FaultInjector
  overflow storm, dumps atomically with the documented schema, keeps the
  previous dump when a dump itself dies mid-write, and auto-dumps on
  preemption and watchdog escalation — the postmortem acceptance path.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.amp.grad_scaler import DynamicGradScaler
from apex_tpu.monitor import GoodputLedger, MemoryAccountant, Tracer
from apex_tpu.monitor.flight import FlightRecorder, thread_stacks
from apex_tpu.monitor.memory import (publish_compiled_memory,
                                     sample_device_memory)
from apex_tpu.monitor.trace import (ChromeTraceWriter, get_tracer,
                                    read_chrome_trace, set_tracer,
                                    spans_by_trace)
from apex_tpu.resilience import FaultInjector, resilient_step
from apex_tpu.resilience.distributed import CollectiveWatchdog
from apex_tpu.resilience.preemption import PreemptionGuard
from apex_tpu.utils import prof
from apex_tpu.utils.logging import publish_event, subscribe_events

pytestmark = pytest.mark.trace


@pytest.fixture
def bus():
    recs = []
    unsub = subscribe_events(recs.append)
    yield recs
    unsub()


class _FakeHBMDev:
    """Injectable device with allocator stats (CPU backends report none)."""

    def __init__(self, bytes_in_use=1000, peak=2000):
        self._stats = {"bytes_in_use": bytes_in_use,
                       "peak_bytes_in_use": peak, "bytes_limit": 10_000}

    def memory_stats(self):
        return dict(self._stats)


# ------------------------------------------------------------- span tree

def test_span_tree_parenting_and_ids(bus):
    tr = Tracer()
    with tr.span("root", a=1) as root:
        with tr.span("child") as child:
            assert tr.current() is child
        assert tr.current() is root
    recs = tr.completed_records()
    assert [r["name"] for r in recs] == ["child", "root"]
    child_rec, root_rec = recs
    assert child_rec["trace_id"] == root_rec["trace_id"]
    assert child_rec["parent_id"] == root_rec["span_id"]
    assert root_rec["parent_id"] is None
    assert root_rec["attrs"] == {"a": 1}
    # both transitions rode the bus, in open/close order
    names = [(r["event"], r["name"]) for r in bus
             if r.get("event", "").startswith("span_")]
    assert names == [("span_open", "root"), ("span_open", "child"),
                     ("span_close", "child"), ("span_close", "root")]


def test_manual_spans_use_caller_stamps():
    """Lifecycle spans (serve requests) reuse the instrumented component's
    own clock reads — durations are exact, not approximate."""
    tr = Tracer()
    s = tr.begin("queue", trace_id="request:r0", t0=100.0)
    assert s.trace_id == "request:r0"
    tr.end(s, t1=100.25, queue_wait_s=0.25)
    rec = tr.completed_records()[0]
    assert rec["dur_ms"] == pytest.approx(250.0)
    assert rec["attrs"]["queue_wait_s"] == 0.25
    # end is idempotent: a second close cannot rewrite the record
    tr.end(s, t1=999.0)
    assert tr.completed_records()[0]["t1"] == pytest.approx(100.25)


def test_disabled_tracer_is_inert(bus):
    tr = Tracer(enabled=False)
    with tr.span("x") as s:
        assert s is None
    assert tr.begin("y") is None
    tr.end(None)  # must be a safe no-op: call sites carry no guards
    assert not tr.completed_records() and not tr.open_spans()
    assert not [r for r in bus if r.get("event", "").startswith("span_")]


def test_span_exception_marks_status_error():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("doomed"):
            raise ValueError("boom")
    rec = tr.completed_records()[0]
    assert rec["status"] == "error" and rec["t1"] >= rec["t0"]
    assert not tr.open_spans()


# ------------------------------------------------------- chrome export

def test_chrome_trace_writer_roundtrip(tmp_path):
    path = str(tmp_path / "trace.json")
    tr = Tracer()
    with ChromeTraceWriter(path):
        with tr.trace("req-a"):
            with tr.span("prefill"):
                pass
        with tr.trace("req-b"):
            pass
    events = read_chrome_trace(path)
    assert json.load(open(path)) == events  # close() left strict JSON
    xs = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"req-a", "prefill", "req-b"}
    for e in xs:
        assert e["dur"] >= 0 and "ts" in e and e["pid"] == os.getpid()
    # one tid track per trace, each named by a metadata event
    metas = [e for e in events if e.get("ph") == "M"]
    assert len({e["tid"] for e in xs}) == 2 and len(metas) == 2


def test_chrome_trace_tolerates_crashed_file(tmp_path):
    """A run killed mid-stream leaves an unterminated array — it must
    still parse (Perfetto does; so does our reader)."""
    path = str(tmp_path / "crash.json")
    w = ChromeTraceWriter(path)
    tr = Tracer()
    with tr.trace("only"):
        pass
    w._f.flush()          # simulate death: no close(), no "]"
    w._unsubscribe()
    events = read_chrome_trace(path)
    assert [e["name"] for e in events if e.get("ph") == "X"] == ["only"]
    w.close()


def test_spans_by_trace_groups():
    tr = Tracer()
    with tr.trace("a"):
        pass
    with tr.trace("b"):
        pass
    groups = spans_by_trace(tr.completed_records())
    assert len(groups) == 2
    for spans in groups.values():
        assert len(spans) == 1


# ------------------------------------------------- prof.py satellites

def test_annotate_mirrors_to_enabled_tracer():
    # annotate resolves the trace module BY NAME at call time, so this
    # test must too (a sys.modules purge can split identities)
    import importlib

    prof_mod = importlib.import_module("apex_tpu.utils.prof")
    trace_mod = importlib.import_module("apex_tpu.monitor.trace")
    tr = trace_mod.Tracer()
    prev = trace_mod.set_tracer(tr)
    try:
        with prof_mod.annotate("phase", step=3):
            pass
    finally:
        trace_mod.set_tracer(prev)
    rec = tr.completed_records()[0]
    assert rec["name"] == "phase" and rec["attrs"] == {"step": 3}
    # with the default (disabled) tracer, annotate is the raw jax range
    assert trace_mod.get_tracer().enabled is False
    with prof_mod.annotate("plain"):
        pass  # no tracer side effects
    assert len(tr.completed_records()) == 1


def test_annotate_attrs_reach_the_profiler_with_the_tracer_disabled(
        tmp_path):
    """No tracer installed, a profiler session running: the range lands
    on the host plane of the session's ``.xplane.pb`` with its attributes
    as the event's stats — what the benchmark's readers take."""
    import glob

    from jax.profiler import ProfileData

    assert get_tracer().enabled is False
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with prof.annotate("apex.test.outer", active=4, resident=300):
            with prof.annotate("apex.test.inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    found = {e.name: (e, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for e in line.events
             if e.name.startswith("apex.test.")}
    assert set(found) == {"apex.test.outer", "apex.test.inner"}
    outer, stats = found["apex.test.outer"]
    inner, none = found["apex.test.inner"]
    assert stats == {"active": 4, "resident": 300} and none == {}
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns \
        <= outer.start_ns + outer.duration_ns


def test_annotate_nests_under_the_ambient_parent_with_a_tracer():
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        with prof.annotate("apex.test.outer", queued=2) as outer:
            with prof.annotate("apex.test.inner") as inner:
                assert tr.current() is inner
            with prof.annotate("apex.test.next", bucket=8):
                pass
    finally:
        set_tracer(prev)
    recs = {r["name"]: r for r in tr.completed_records()}
    assert list(recs) == ["apex.test.inner", "apex.test.next",
                          "apex.test.outer"]
    assert recs["apex.test.outer"]["parent_id"] is None
    assert recs["apex.test.outer"]["attrs"] == {"queued": 2}
    for child in ("apex.test.inner", "apex.test.next"):
        assert recs[child]["parent_id"] == outer.span_id
        assert recs[child]["trace_id"] == outer.trace_id
        assert recs["apex.test.outer"]["t0"] <= recs[child]["t0"] \
            <= recs[child]["t1"] <= recs["apex.test.outer"]["t1"]
    assert recs["apex.test.next"]["attrs"] == {"bucket": 8}
    assert not tr.open_spans()


def test_profile_rejects_nesting(monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda logdir: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    with prof.profile("/tmp/outer"):
        with pytest.raises(RuntimeError, match="not reentrant"):
            with prof.profile("/tmp/inner"):
                pass
    # the guard resets: a fresh capture works after the region closes
    with prof.profile("/tmp/again"):
        pass


def test_steptimer_context_manager():
    t = prof.StepTimer()
    with t:
        x = jnp.ones((4,)) * 2
        t.block(x)     # sync on the output at exit
    assert t.count == 1 and t.last >= 0.0
    with t:
        pass           # un-armed: plain wall clock
    assert t.count == 2
    assert t._block_on is None
    # an aborted step records nothing (a partial duration would skew avg)
    with pytest.raises(ValueError):
        with t:
            raise ValueError("step died")
    assert t.count == 2


# ------------------------------------------------------ hbm accounting

def test_memory_accountant_samples_and_cadence(bus):
    mem = MemoryAccountant(device=_FakeHBMDev(), every=2)
    assert mem.tick("t") is None          # 1st tick skipped (every=2)
    assert mem.tick("t") is not None      # 2nd publishes
    assert mem.samples == 1 and mem.peak_bytes_in_use == 2000
    snaps = [r for r in bus if r.get("event") == "hbm_snapshot"]
    assert len(snaps) == 1
    assert snaps[0]["kind"] == "sampled" and snaps[0]["bytes_in_use"] == 1000


def test_memory_accountant_silent_without_stats(bus):
    class NoStats:
        def memory_stats(self):
            return None

    mem = MemoryAccountant(device=NoStats())
    assert mem.sample("t") is None        # silence, never fake zeros
    assert not [r for r in bus if r.get("event") == "hbm_snapshot"]


def test_static_memory_analysis_published(bus):
    compiled = jax.jit(lambda x: x * 2).lower(
        jnp.ones((8, 8), jnp.float32)).compile()
    rec = publish_compiled_memory("unit", compiled, note="test")
    assert rec is not None
    assert rec["reserved_bytes"] == rec["argument_size_in_bytes"] + \
        rec["output_size_in_bytes"] + rec["temp_size_in_bytes"]
    snap = [r for r in bus if r.get("event") == "hbm_snapshot"][0]
    assert snap["kind"] == "static" and snap["name"] == "unit"
    assert snap["note"] == "test"


def test_ledger_summarizes_hbm():
    with GoodputLedger() as led:
        sample_device_memory("t", device=_FakeHBMDev(peak=4096))
        compiled = jax.jit(lambda x: x + 1).lower(
            jnp.ones((4,), jnp.float32)).compile()
        publish_compiled_memory("unit", compiled)
    hbm = led.summary()["hbm"]
    assert hbm["samples"] == 2
    assert hbm["peak_bytes_in_use"] == 4096
    assert hbm["static_peak_bytes"] > 0
    # runs with no snapshots keep the summary key-compatible with PR-2
    assert "hbm" not in GoodputLedger().summary()


# ----------------------------------------------------- flight recorder

def test_flight_ring_bounded_under_overflow_storm(tmp_path):
    """FaultInjector NaN burst through a traced resilient_step with the
    recorder attached: every step adds span + overflow records, the ring
    holds exactly ``capacity``, and the dump counts the drops."""
    inj = FaultInjector(seed=1).nan_burst(start=0, length=6)
    scaler = DynamicGradScaler(init_scale=2.0 ** 8, growth_interval=1000)
    tracer = Tracer()
    path = str(tmp_path / "storm_flight.json")
    fr = FlightRecorder(path, capacity=8, tracer=tracer).attach()

    def train_step(params, sstate, grads):
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                     grads)
        from apex_tpu.multi_tensor.functional import tree_check_finite
        return new, tree_check_finite(grads), jnp.float32(1.0)

    step = resilient_step(train_step, scaler, tracer=tracer,
                          max_consecutive_overflows=3)
    params = {"w": jnp.ones((4,))}
    sstate = scaler.init()
    grads = {"w": jnp.full((4,), 0.5)}
    for i in range(6):
        params, sstate, _inf, _loss = step(params, sstate,
                                           inj.poison_grads(grads, i))
    fr.detach()
    assert step.skipped_steps == 6
    assert len(fr.events) == 8                 # the bound held
    assert fr.total_events > 8
    d = json.load(open(fr.dump("test")))
    assert d["dropped_events"] == d["total_events"] - len(d["events"])

    # one trace per train step: root + forward_backward + unscale children
    roots = [r for r in tracer.completed_records()
             if r["name"] == "train_step"]
    assert len(roots) == 6
    by_trace = spans_by_trace(tracer.completed_records())
    for root in roots:
        names = {s["name"] for s in by_trace[root["trace_id"]]}
        assert names == {"train_step", "forward_backward",
                         "unscale_grad_norm"}


def test_flight_dump_schema_and_atomicity(tmp_path, monkeypatch):
    import sys

    # resolve the module BACKING the class: a sys.modules purge can
    # leave a reimported apex_tpu.monitor.flight coexisting with the
    # collection-time one these tests hold — patch the one in use
    flight_mod = sys.modules[FlightRecorder.__module__]

    path = str(tmp_path / "flight.json")
    tracer = Tracer()
    fr = FlightRecorder(path, capacity=16, tracer=tracer).attach()
    sample_device_memory("t", device=_FakeHBMDev())
    publish_event("serve_decode_step", seconds=0.001, active=1)
    open_span = tracer.begin("decode", trace_id="request:r9")
    fr.dump("manual")
    fr.detach()

    d = json.load(open(path))
    for key in ("schema", "reason", "t", "pid", "capacity", "total_events",
                "dropped_events", "events", "open_spans", "hbm_snapshot",
                "thread_stacks"):
        assert key in d, key
    assert d["reason"] == "manual" and d["schema"] == 1
    assert d["hbm_snapshot"]["bytes_in_use"] == 1000
    assert [s["name"] for s in d["open_spans"]] == ["decode"]
    assert any("test_flight_dump" in "".join(frames)
               for frames in d["thread_stacks"].values())
    assert not os.path.exists(path + ".tmp")   # staging was replaced away

    # a dump that dies mid-write must leave the PREVIOUS dump intact
    def boom(*a, **k):
        raise OSError("disk full")

    monkeypatch.setattr(flight_mod.json, "dump", boom)
    with pytest.raises(OSError):
        fr.dump("second")
    assert json.load(open(path))["reason"] == "manual"
    tracer.end(open_span)


def test_flight_guard_dumps_on_fatal_exception(tmp_path):
    """The one death with no bus record: guard() (used by
    ServeScheduler.run) dumps and re-raises the original error."""
    path = str(tmp_path / "exc_flight.json")
    fr = FlightRecorder(path, capacity=8).attach()
    publish_event("serve_decode_step", seconds=0.001, active=1)
    with pytest.raises(RuntimeError, match="engine died"):
        with fr.guard("serve"):
            raise RuntimeError("engine died")
    fr.detach()
    d = json.load(open(path))
    assert d["reason"] == "exception:RuntimeError:serve"
    assert any(r.get("event") == "serve_decode_step" for r in d["events"])


def test_flight_auto_dump_on_preemption(tmp_path):
    """The postmortem acceptance path: a preemption request leaves a dump
    with the open spans, last-N events, and the hbm snapshot — with zero
    wiring beyond attach() (the trigger record rides the bus)."""
    path = str(tmp_path / "preempt_flight.json")
    tracer = Tracer()
    fr = FlightRecorder(path, capacity=32, tracer=tracer).attach()
    sample_device_memory("t", device=_FakeHBMDev(peak=7777))
    span = tracer.begin("decode", trace_id="request:r1")
    guard = PreemptionGuard()            # no handlers needed for the test
    guard.request_stop()
    assert guard.should_stop()           # announce -> preemption_requested
    fr.detach()
    d = json.load(open(path))
    assert d["reason"] == "preemption_requested"
    assert [s["name"] for s in d["open_spans"]] == ["decode"]
    assert d["hbm_snapshot"]["peak_bytes_in_use"] == 7777
    assert any(r.get("event") == "preemption_requested"
               for r in d["events"])
    tracer.end(span)


def test_flight_auto_dump_on_watchdog_escalation(tmp_path, capsys):
    path = str(tmp_path / "stall_flight.json")
    fr = FlightRecorder(path, capacity=32).attach()
    wd = CollectiveWatchdog(timeout_s=0.02, escalate="dump")
    with wd:
        with wd.watch("allreduce:grads"):
            deadline = time.time() + 2.0
            while not os.path.exists(path) and time.time() < deadline:
                time.sleep(0.005)
    fr.detach()
    d = json.load(open(path))
    assert d["reason"] == "collective_stall"
    stall = [r for r in d["events"]
             if r.get("event") == "collective_stall"][0]
    assert stall["name"] == "allreduce:grads" and stall["escalate"] == "dump"
    # the watchdog's stderr stack dump shares the flight formatting
    assert "thread stacks" in capsys.readouterr().err


def test_thread_stacks_sees_all_threads():
    import threading

    done = threading.Event()
    started = threading.Event()

    def worker():
        started.set()
        done.wait(5.0)

    t = threading.Thread(target=worker, name="flight-test-worker",
                         daemon=True)
    t.start()
    started.wait(5.0)
    try:
        stacks = thread_stacks()
    finally:
        done.set()
        t.join(5.0)
    assert any("flight-test-worker" in label for label in stacks)
    assert all(isinstance(frames, list) for frames in stacks.values())
