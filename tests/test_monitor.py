"""Telemetry pipeline tests (marker: ``monitor``).

Covers the observability contract end to end: in-graph ``TrainMetrics``
stay in-graph (no host callbacks traced into the step, the step remains
ONE jitted call), the JSONL schema round-trips, the goodput ledger's
arithmetic holds under injected overflow storms, the bench regression gate
passes/fails correctly, and ``apex-tpu-bench --telemetry-jsonl`` emits
schema-valid rows on CPU.
"""

import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.amp.grad_scaler import DynamicGradScaler
from apex_tpu.monitor import (GoodputLedger, Telemetry, TrainMetrics,
                              collect_metrics, read_jsonl, validate_row)
from apex_tpu.monitor.telemetry import PERF_ROW_KEYS
from apex_tpu.resilience import FaultInjector, resilient_step
from apex_tpu.utils.logging import (MetricLogger, publish_event,
                                    structured_warning, subscribe_events)
from apex_tpu.utils.prof import StepTimer, detect_chip, roofline

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.monitor


def _params():
    return {"w": jnp.full((4, 4), 2.0), "b": jnp.ones((8,), jnp.bfloat16)}


# ------------------------------------------------------------ in-graph

def test_collect_metrics_values_under_jit():
    params = _params()
    grads = jax.tree_util.tree_map(lambda x: jnp.ones_like(x) * 0.5, params)

    @jax.jit
    def step(params, grads):
        return collect_metrics(grads=grads, params=params,
                               loss=jnp.float32(2.5), loss_scale=8.0)

    tm = step(params, grads)
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    np.testing.assert_allclose(float(tm.grad_norm),
                               math.sqrt(n * 0.25), rtol=1e-5)
    np.testing.assert_allclose(float(tm.param_norm),
                               math.sqrt(16 * 4.0 + 8 * 1.0), rtol=1e-2)
    assert float(tm.loss) == 2.5
    assert float(tm.loss_scale) == 8.0
    assert not bool(tm.found_inf)
    assert tm.update_norm is None  # not collected -> absent, still a pytree


def test_collect_metrics_traces_no_host_callbacks():
    """The acceptance guarantee: metric collection adds no host syncs —
    the jaxpr of a collecting step contains no callback primitives and the
    whole step stays ONE jitted call that returns the metrics."""
    params = _params()
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    def step(params, grads):
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params, grads)
        tm = collect_metrics(grads=grads, params=new, loss_scale=1.0)
        return new, tm

    jaxpr = str(jax.make_jaxpr(step)(params, grads))
    assert "callback" not in jaxpr  # covers pure_callback/io_callback/debug
    jitted = jax.jit(step)
    new, tm = jitted(params, grads)  # one call yields params AND metrics
    assert isinstance(tm, TrainMetrics)
    assert isinstance(tm.grad_norm, jax.Array)


def test_found_inf_detects_nan():
    grads = {"w": jnp.array([1.0, jnp.nan])}
    tm = jax.jit(lambda g: collect_metrics(grads=g))(grads)
    assert bool(tm.found_inf)


def test_scaler_unscale_and_norm_fused():
    scaler = DynamicGradScaler(init_scale=4.0)
    state = scaler.init()
    grads = {"w": jnp.full((8,), 4.0)}
    out, gnorm, found_inf = scaler.unscale_and_norm(grads, state)
    np.testing.assert_allclose(np.asarray(out["w"]), np.full((8,), 1.0))
    np.testing.assert_allclose(float(gnorm), math.sqrt(8.0), rtol=1e-6)
    assert not bool(found_inf)


# ------------------------------------------------------------ telemetry

def test_telemetry_jsonl_schema_roundtrip(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry(path, tokens_per_step=256.0, flops_per_step=1e9,
                    chip="v5e").start()
    params = _params()
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    tm = jax.jit(lambda p, g: collect_metrics(
        grads=g, params=p, loss=jnp.float32(1.0), loss_scale=1.0))(
            params, grads)
    for i in range(3):
        tel.log_step(i, metrics=tm)
    tel.close()
    rows, events = read_jsonl(path)
    assert len(rows) == 3 and not events
    for row in rows:
        validate_row(row, require=PERF_ROW_KEYS)
        assert row["tokens_per_s"] > 0
        assert row["mfu"] >= 0
        assert row["loss_scale"] == 1.0


def test_telemetry_mirrors_structured_events(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tel = Telemetry(path)
    structured_warning("overflow_storm", consecutive_overflows=8)
    with tel.span("save"):
        pass
    tel.close()
    # events published after close must NOT land in the file
    structured_warning("after_close")
    _, events = read_jsonl(path)
    names = [e["event"] for e in events]
    assert "overflow_storm" in names
    assert "span" in names
    assert "after_close" not in names
    span = next(e for e in events if e["event"] == "span")
    assert span["name"] == "save" and span["ms"] >= 0


def test_telemetry_no_sync_until_flush(tmp_path, monkeypatch):
    """log_step buffers device arrays; flush() does ONE batched
    device_get for the whole buffer (the MetricLogger satellite)."""
    calls = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: calls.append(1) or real(x))
    logger = MetricLogger(str(tmp_path / "m.jsonl"))
    for i in range(5):
        logger.log(i, loss=jnp.float32(i), norm=jnp.float32(2 * i))
    assert calls == []  # nothing fetched while buffering
    logger.flush()
    assert len(calls) == 1  # one host sync for 10 buffered device scalars


def test_goodput_ledger_arithmetic():
    led = GoodputLedger()
    led.record_step(1.0)
    led.record_step(1.0)
    led.record_step(0.5, productive=False)
    led.record_stall("checkpoint_save", 0.5)
    s = led.summary()
    assert s["steps"] == 3 and s["skipped_steps"] == 1
    assert s["productive_s"] == pytest.approx(2.0)
    assert s["lost_s"] == pytest.approx(1.0)
    assert s["goodput_frac"] == pytest.approx(2.0 / 3.0)
    assert s["lost_by_cause"] == {"checkpoint_save": pytest.approx(0.5),
                                  "overflow_skip": pytest.approx(0.5)}


def test_goodput_ledger_subscribes_to_stall_events():
    with GoodputLedger() as led:
        publish_event("checkpoint_save_stall", step=3, seconds=1.25)
        publish_event("checkpoint_restore_stall", step=3, seconds=0.25)
    # detached: later events must not be counted
    publish_event("checkpoint_save_stall", step=4, seconds=99.0)
    s = led.summary()
    assert s["lost_by_cause"]["checkpoint_save"] == pytest.approx(1.25)
    assert s["lost_by_cause"]["checkpoint_restore"] == pytest.approx(0.25)
    assert s["events"]["checkpoint_save_stall"] == 1


def test_distributed_resilience_events_registered():
    """The distributed-resilience events are part of the telemetry schema:
    collective_stall (+cleared) is a timed goodput cause, quarantine and
    watchdog aborts are counted degradation signals."""
    from apex_tpu.monitor.goodput import COUNTED_EVENTS, STALL_EVENTS

    assert STALL_EVENTS["collective_stall"] == "collective_stall"
    assert STALL_EVENTS["collective_stall_cleared"] == "collective_stall"
    assert "checkpoint_quarantined" in COUNTED_EVENTS
    assert "collective_stall_abort" in COUNTED_EVENTS

    with GoodputLedger() as led:
        publish_event("collective_stall", name="allreduce", seconds=0.5)
        publish_event("collective_stall_cleared", name="allreduce",
                      seconds=0.25)
        publish_event("checkpoint_quarantined", step=3, reason="crc")
    s = led.summary()
    assert s["lost_by_cause"]["collective_stall"] == pytest.approx(0.75)
    assert s["events"]["checkpoint_quarantined"] == 1
    assert s["events"]["collective_stall"] == 1


def test_serve_events_registered():
    """Every serving event the serve package publishes must be part of
    the goodput event schema: queue wait is a timed cause, the request
    lifecycle and per-step latency are counted signals. The source grep
    makes an UNREGISTERED serve_* event a tier-1 failure, the same
    contract PR-4 established for the distributed-resilience events."""
    import os
    import re

    import apex_tpu.serve as serve_pkg
    from apex_tpu.monitor.goodput import COUNTED_EVENTS, STALL_EVENTS

    assert STALL_EVENTS["serve_queue_wait"] == "serve_queue_wait"
    for name in ("serve_request_admitted", "serve_request_completed",
                 "serve_request_evicted", "serve_decode_step"):
        assert name in COUNTED_EVENTS, name

    published = set()
    pkg_dir = os.path.dirname(serve_pkg.__file__)
    for fname in os.listdir(pkg_dir):
        if fname.endswith(".py"):
            with open(os.path.join(pkg_dir, fname)) as f:
                published |= set(re.findall(
                    r'publish_event\(\s*"(serve_[a-z_]+)"', f.read()))
    assert published, "serve package publishes no events?"
    unregistered = published - set(COUNTED_EVENTS) - set(STALL_EVENTS)
    assert not unregistered, \
        f"serve events missing from the goodput schema: {unregistered}"

    with GoodputLedger() as led:
        publish_event("serve_queue_wait", seconds=0.5, request_id="r0")
        publish_event("serve_request_admitted", request_id="r0", slot=1)
        publish_event("serve_decode_step", seconds=0.001, active=2)
        publish_event("serve_request_completed", request_id="r0", slot=1)
    s = led.summary()
    assert s["lost_by_cause"]["serve_queue_wait"] == pytest.approx(0.5)
    assert s["events"]["serve_request_admitted"] == 1
    assert s["events"]["serve_decode_step"] == 1


def test_repo_wide_event_schema_audit():
    """EVERY literal ``publish_event``/``structured_warning`` call site in
    the package must use a name registered in the goodput/event schema
    (STALL | COUNTED | INFO) — so a new subsystem cannot ship an event no
    monitoring consumer knows about. The audit itself is apexlint rule
    APX003 (AST-based, one source of truth — this test delegates instead
    of keeping its own regex scan, and proves the rule still *fires*)."""
    sys.path.insert(0, ROOT)
    try:
        from tools.apexlint.core import LintContext
        from tools.apexlint.rules.event_schema import (EventSchemaRule,
                                                       load_event_schema)
    finally:
        sys.path.pop(0)
    from apex_tpu.monitor.goodput import EVENT_SCHEMA

    # the rule audits against the same schema the runtime exposes
    assert load_event_schema(ROOT) == EVENT_SCHEMA

    ctx = LintContext(ROOT, [os.path.join(ROOT, "apex_tpu")])
    violations = list(EventSchemaRule().check(ctx))
    assert not violations, \
        "events missing from the monitor.goodput schema:\n" + \
        "\n".join(v.format() for v in violations)

    # sanity: the rule still SEES the real call sites — a refactor that
    # blinds the audit (renamed publish funcs, moved schema) must fail
    # here, not silently pass (the seed had ≈31 sites across ≥10 files)
    from tools.apexlint.rules.event_schema import _event_name_arg
    import ast as _ast

    sites = []
    for sf in ctx.iter_files(under="apex_tpu"):
        for node in _ast.walk(sf.tree):
            if isinstance(node, _ast.Call):
                arg = _event_name_arg(node)
                if arg is not None:
                    sites.append((sf.path, arg.value))
    assert len(sites) >= 25, sites
    assert len({p for p, _ in sites}) >= 10


def test_raising_subscriber_isolated_once(capsys):
    """The subscribe_events docstring contract: a raising subscriber is
    reported exactly once (even raising DIFFERENT exceptions each time)
    and every event still reaches the remaining subscribers."""
    calls = []
    n = [0]

    def bad(rec):
        n[0] += 1
        raise ValueError(f"boom {n[0]}")   # distinct message per raise

    def good(rec):
        calls.append(rec["event"])

    unsub_bad = subscribe_events(bad)
    unsub_good = subscribe_events(good)
    try:
        for _ in range(3):
            publish_event("span", name="x")
    finally:
        unsub_bad()
        unsub_good()
    assert calls == ["span"] * 3           # delivery survived the raiser
    assert capsys.readouterr().err.count("raised ValueError") == 1


def test_unsubscribe_during_publish_is_safe():
    seen = []
    unsubs = {}

    def s1(rec):
        seen.append("s1")
        unsubs["s2"]()                     # removes s2 mid-delivery

    def s2(rec):
        seen.append("s2")

    unsubs["s1"] = subscribe_events(s1)
    unsubs["s2"] = subscribe_events(s2)
    try:
        # snapshot semantics: s2 still sees THIS publish...
        publish_event("span", name="a")
        # ...and is gone for the next one
        publish_event("span", name="b")
    finally:
        unsubs["s1"]()
        unsubs["s2"]()                     # idempotent second call
    assert seen == ["s1", "s2", "s1"]


def test_telemetry_trace_jsonl_exports_chrome_trace(tmp_path):
    """Telemetry(trace_jsonl=...) enables the process tracer for the run,
    streams completed spans as Perfetto-loadable Chrome-trace JSON, keeps
    the high-rate span_open/span_close records OUT of the metric JSONL
    mirror, and restores the previous tracer on close."""
    from apex_tpu.monitor import read_chrome_trace
    from apex_tpu.monitor.trace import get_tracer

    path = str(tmp_path / "run.jsonl")
    tpath = str(tmp_path / "trace.json")
    prev = get_tracer()
    tel = Telemetry(path, trace_jsonl=tpath)
    assert get_tracer() is tel.tracer and tel.tracer.enabled
    with tel.span("checkpoint"):
        pass
    tel.close()
    assert get_tracer() is prev
    xs = [e for e in read_chrome_trace(tpath) if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["checkpoint"]
    _, events = read_jsonl(path)
    names = [e["event"] for e in events]
    assert "span" in names                        # the legacy aggregate
    assert "span_open" not in names and "span_close" not in names


def test_checkpoint_save_publishes_stall_event(tmp_path):
    # call-time imports for BOTH sides: a sys.modules purge can
    # leave collection-time and re-imported apex_tpu identities coexisting,
    # and publisher + subscriber must share one event-bus module
    from apex_tpu.monitor.goodput import GoodputLedger as Ledger
    from apex_tpu.resilience import CheckpointManager

    with Ledger() as led:
        CheckpointManager(str(tmp_path)).save(1, _params())
    assert led.events.get("checkpoint_save_stall") == 1
    assert led.lost_by_cause["checkpoint_save"] > 0


# ---------------------------------------------- overflow-storm goodput

@pytest.mark.fault
def test_goodput_under_injected_overflow_storm(tmp_path):
    """FaultInjector NaN burst through resilient_step with telemetry:
    every poisoned step is skipped, charged as lost time, and the emitted
    rows carry the overflow flag and the backed-off scale."""
    inj = FaultInjector(seed=3).nan_burst(start=2, length=3)
    scaler = DynamicGradScaler(init_scale=2.0 ** 8, growth_interval=1000)
    path = str(tmp_path / "storm.jsonl")
    tel = Telemetry(path, tokens_per_step=1.0).start()

    params = {"w": jnp.ones((4,))}

    def train_step(params, sstate, grads):
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                     grads)
        from apex_tpu.multi_tensor.functional import tree_check_finite
        return new, tree_check_finite(grads), jnp.float32(1.0)

    step = resilient_step(train_step, scaler, telemetry=tel)
    sstate = scaler.init()
    grads = {"w": jnp.full((4,), 0.5)}
    total = 8
    for i in range(total):
        g = inj.poison_grads(grads, i)
        params, sstate, found_inf, _loss = step(params, sstate, g)
    tel.close()

    assert step.skipped_steps == 3
    g = tel.ledger.summary()
    assert g["steps"] == total
    assert g["skipped_steps"] == 3
    assert g["events"]["overflow_step_skipped"] == 3
    assert g["lost_by_cause"]["overflow_skip"] > 0
    assert 0.0 < g["goodput_frac"] < 1.0
    assert g["productive_s"] + g["lost_s"] == pytest.approx(
        sum(v for v in g["lost_by_cause"].values()) + g["productive_s"])

    rows, _events = read_jsonl(path)
    assert len(rows) == total
    skipped_rows = [r for r in rows if r["found_inf"]]
    assert len(skipped_rows) == 3
    # params kept + scale backed off on the skipped steps; update_norm and
    # param_norm were collected in-graph by the resilient post-step
    for r in rows:
        assert "param_norm" in r and "update_norm" in r
        assert "loss_scale" in r and r["loss"] == 1.0


# ------------------------------------------------------------ satellites

def test_steptimer_stop_before_start_raises():
    t = StepTimer()
    with pytest.raises(RuntimeError, match="before start"):
        t.stop()
    t.start()
    assert t.stop() >= 0.0
    t.reset()
    with pytest.raises(RuntimeError):
        t.stop()


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


@pytest.mark.parametrize("kind,expected", [
    ("TPU v5e", "v5e"), ("TPU v5 lite", "v5e"), ("TPU v6e", "v6e"),
    ("TPU v6 lite", "v6e"), ("TPU v5p", "v5p"), ("TPU v5", "v5p"),
])
def test_detect_chip_known_kinds(kind, expected):
    assert detect_chip([_FakeDev("tpu", kind)]) == expected


def test_detect_chip_cpu_and_unknown():
    assert detect_chip([_FakeDev("cpu", "cpu")]) is None
    # unknown TPU generation: an error, never another chip's peaks
    with pytest.raises(ValueError, match="unknown TPU device_kind"):
        detect_chip([_FakeDev("tpu", "TPU v9 hyper")])


def test_roofline_uses_detected_chip(monkeypatch):
    # patch + call through the SAME module object (see identity note above)
    import apex_tpu.utils.prof as prof

    monkeypatch.setattr(prof, "detect_chip", lambda devices=None: "v6e")
    out = prof.roofline(lambda x: x @ x, jnp.ones((64, 64)))
    assert out["chip"] == "v6e"
    assert out["flops"] >= 0


def test_roofline_matmul_cost_model():
    r = roofline(lambda a, b: a @ b, jnp.ones((256, 256)),
                 jnp.ones((256, 256)), chip="v5e", measured_ms=1.0)
    assert r["flops"] >= 2 * 256 ** 3 * 0.9
    assert r["bound"] in ("mxu", "hbm")
    assert 0 < r["achieved_frac"] < 1
    with pytest.raises(ValueError, match="unknown chip"):
        roofline(lambda a: a + 1, jnp.ones((8,)), chip="v99x")


def test_compile_cache_is_one_fixed_place(monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and the
    helper touches nothing; unset, every entry point gets
    <checkout>/.jax_cache — no temp name, pid or time in the path."""
    from apex_tpu.utils import env

    updates = []
    monkeypatch.setattr(env.jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert env.enable_compile_cache() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(ROOT, ".jax_cache")
    assert env.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    # and nothing else in the program sets a cache directory
    import pathlib

    offenders = {
        str(p.relative_to(ROOT)) for p in pathlib.Path(ROOT).rglob("*.py")
        if not any(part.startswith(".") for part in p.relative_to(ROOT).parts)
        and "jax_compilation_cache_dir" in p.read_text()}
    assert offenders == {"apex_tpu/utils/env.py",
                         "tests/test_monitor.py"}, offenders


def test_programs_out_of_the_compile_cache_serve_as_compiled_ones_do(
        tmp_path):
    """What undid the first attempt at this (PERF.md, PR 31): an
    executable that is LOADED from the persistent compile cache hands
    back arrays that report the runtime's default layout, so a pool kept
    in any other layout was refused by the call after. The pool's layout
    is its shape now: a second engine, whose programs all come out of
    the cache the first one filled, serves the same streams over a
    donated pool, call after call."""
    from jax._src import compilation_cache

    from test_serve_resident_pool import _serve, _tiny_engine

    knobs = {"jax_compilation_cache_dir": str(tmp_path),
             "jax_enable_compilation_cache": True,
             "jax_persistent_cache_min_compile_time_secs": 0.0,
             "jax_persistent_cache_min_entry_size_bytes": -1}
    before = {name: getattr(jax.config, name) for name in knobs}
    try:
        for name, value in knobs.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        want = _serve(_tiny_engine("paged"))
        filled = len(os.listdir(tmp_path))
        assert filled > 0
        eng = _tiny_engine("paged")
        first = eng.cache
        assert _serve(eng) == want
        assert first.lengths.is_deleted()
        assert len(os.listdir(tmp_path)) == filled     # nothing compiled
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_bench_atomic_write_leaves_no_partial_file(tmp_path):
    sys.path.insert(0, ROOT)
    try:
        import bench
    finally:
        sys.path.remove(ROOT)
    path = tmp_path / "x.json"
    bench.atomic_write_json(str(path), {"a": 1})
    assert json.load(open(path)) == {"a": 1}
    assert not os.path.exists(str(path) + ".tmp")


# ------------------------------------------------------- regression gate

def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def _gate(current, baseline, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "check_regression.py"),
         current, baseline, *extra],
        capture_output=True, text=True, timeout=120)


def test_check_regression_pass_and_fail(tmp_path):
    rows = [{"step": i, "loss": 4.0, "grad_norm": 1.0, "loss_scale": 1.0,
             "step_ms": 10.0, "tokens_per_s": 1000.0, "mfu": 0.02}
            for i in range(5)]
    base = str(tmp_path / "base.jsonl")
    _write_jsonl(base, rows)

    same = str(tmp_path / "same.jsonl")
    _write_jsonl(same, rows)
    r = _gate(same, base)
    assert r.returncode == 0, r.stdout + r.stderr

    slow = str(tmp_path / "slow.jsonl")
    _write_jsonl(slow, [{**row, "step_ms": row["step_ms"] * 1.2}
                        for row in rows])
    r = _gate(slow, base)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "REGRESSION" in r.stdout and "step_ms" in r.stdout

    # within tolerance at 25%: the same 20% slowdown passes
    r = _gate(slow, base, "--tolerance", "0.25")
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_regression_throughput_direction(tmp_path):
    base = str(tmp_path / "b.jsonl")
    cur = str(tmp_path / "c.jsonl")
    _write_jsonl(base, [{"step": 0, "tokens_per_s": 1000.0},
                        {"step": 1, "tokens_per_s": 1000.0}])
    _write_jsonl(cur, [{"step": 0, "tokens_per_s": 700.0},
                       {"step": 1, "tokens_per_s": 700.0}])
    r = _gate(cur, base, "--warmup", "0")
    assert r.returncode == 1  # throughput DROP is a regression
    r = _gate(base, cur, "--warmup", "0")
    assert r.returncode == 0  # throughput gain is not


def test_check_regression_single_row_jsonl(tmp_path):
    """A one-row capture is a single JSON dict too — it must be read as a
    telemetry row, not misclassified as an (empty) suite."""
    base = str(tmp_path / "b.jsonl")
    cur = str(tmp_path / "c.jsonl")
    _write_jsonl(base, [{"step": 0, "step_ms": 10.0}])
    _write_jsonl(cur, [{"step": 0, "step_ms": 13.0}])
    assert _gate(base, base).returncode == 0
    assert _gate(cur, base).returncode == 1


def test_telemetry_flush_every_bounds_buffer(tmp_path):
    path = str(tmp_path / "m.jsonl")
    tel = Telemetry(path, flush_every=2).start()
    for i in range(5):
        tel.log_step(i, loss=jnp.float32(i))
    # 4 rows flushed by the every-2 policy; row 5 still buffered
    rows, _ = read_jsonl(path)
    assert len(rows) == 4
    tel.close()
    rows, _ = read_jsonl(path)
    assert len(rows) == 5


def test_check_regression_device_kind_mismatch(tmp_path):
    """Capture provenance satellite: a CPU-smoke capture gating a TPU
    baseline warns LOUDLY, and --fail-device-mismatch makes it exit 1
    even when every metric is within tolerance."""
    entry = {"metric": "a_ms", "value": 10.0, "unit": "ms"}
    base = {"device_kind": "TPU v5e", "interpret_mode": False,
            "bench_a": entry}
    cur = {"device_kind": "TPU v3 (cpu-smoke)", "interpret_mode": True,
           "bench_a": entry}
    basep, curp = str(tmp_path / "b.json"), str(tmp_path / "c.json")
    with open(basep, "w") as f:
        json.dump(base, f)
    with open(curp, "w") as f:
        json.dump(cur, f)
    r = _gate(curp, basep)
    assert r.returncode == 0               # warn-only by default
    assert "device-kind mismatch" in r.stderr
    r = _gate(curp, basep, "--fail-device-mismatch")
    assert r.returncode == 1
    # same kinds: silent, flag or not
    r = _gate(basep, basep, "--fail-device-mismatch")
    assert r.returncode == 0 and "mismatch" not in r.stderr
    # legacy captures without the stamps keep gating without noise
    legacy = {"bench_a": entry}
    with open(curp, "w") as f:
        json.dump(legacy, f)
    r = _gate(curp, basep, "--fail-device-mismatch")
    assert r.returncode == 0 and "mismatch" not in r.stderr
    # vocabularies never mix: a new capture (device_kind "cpu" + chip
    # "cpu-smoke") against the committed legacy baseline (chip only)
    # compares chip-vs-chip — identical hardware must NOT flag...
    with open(basep, "w") as f:
        json.dump({"chip": "cpu-smoke", "bench_a": entry}, f)
    with open(curp, "w") as f:
        json.dump({"device_kind": "cpu", "chip": "cpu-smoke",
                   "bench_a": entry}, f)
    r = _gate(curp, basep, "--fail-device-mismatch")
    assert r.returncode == 0 and "mismatch" not in r.stderr
    # ...while a REAL chip difference still does
    with open(basep, "w") as f:
        json.dump({"chip": "v5e", "bench_a": entry}, f)
    r = _gate(curp, basep, "--fail-device-mismatch")
    assert r.returncode == 1 and "device-kind mismatch" in r.stderr
    # same chip but interpret-mode capture vs compiled baseline: still
    # not comparable (interpret Pallas on a TPU host != the real chip)
    with open(basep, "w") as f:
        json.dump({"device_kind": "TPU v5e", "interpret_mode": False,
                   "bench_a": entry}, f)
    with open(curp, "w") as f:
        json.dump({"device_kind": "TPU v5e", "interpret_mode": True,
                   "bench_a": entry}, f)
    r = _gate(curp, basep, "--fail-device-mismatch")
    assert r.returncode == 1 and "interpret_mode" in r.stderr


def test_check_regression_suite_baseline(tmp_path):
    suite = {"backend": "cpu", "complete": True,
             "bench_a": {"metric": "a_ms", "value": 10.0, "unit": "ms",
                         "step_ms": 10.0}}
    basep = str(tmp_path / "BENCH_BASE.json")
    with open(basep, "w") as f:
        json.dump(suite, f)
    worse = {"backend": "cpu", "complete": True,
             "bench_a": {"metric": "a_ms", "value": 13.0, "unit": "ms",
                         "step_ms": 13.0}}
    curp = str(tmp_path / "cur.json")
    with open(curp, "w") as f:
        json.dump(worse, f)
    assert _gate(basep, basep).returncode == 0
    assert _gate(curp, basep).returncode == 1
    assert _gate(str(tmp_path / "nope.json"), basep).returncode == 2


# ----------------------------------------------------------- bench smoke

def _run_cli(args, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "-m", "apex_tpu.bench_cli"]
                          + args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=timeout)


def test_bench_cli_telemetry_smoke(tmp_path):
    """Tier-1 gate: ``apex-tpu-bench --telemetry-jsonl`` runs a few steps
    on CPU and every emitted row validates against the schema with the
    acceptance keys present. ``--trace-jsonl`` on the same run exports a
    Perfetto-loadable Chrome trace with one train_step trace per step
    and captures the calibrated step's static memory reservation."""
    path = str(tmp_path / "bench.jsonl")
    tpath = str(tmp_path / "bench_trace.json")
    # pre-seed the file with a stale row: a per-run sink must truncate, or
    # mixed-run medians would skew the regression gate; the '=' flag form
    # must be recognized too
    with open(path, "w") as f:
        f.write(json.dumps({"step": 99, "stale": True}) + "\n")
    r = _run_cli([f"--telemetry-jsonl={path}", f"--trace-jsonl={tpath}",
                  "--steps", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    headline = json.loads(r.stdout.strip().splitlines()[-1])
    assert headline["metric"] == "telemetry_train_step_ms_lm_tiny"
    assert headline["value"] > 0
    assert headline["goodput"] == pytest.approx(1.0)

    rows, events = read_jsonl(path)
    assert len(rows) == 4  # the stale pre-run row was truncated away
    for row in rows:
        validate_row(row, require=PERF_ROW_KEYS)
        assert row["step_ms"] > 0
        assert row["tokens_per_s"] > 0
        assert row["loss_scale"] == 2.0 ** 12
    # calibrate's AOT point published its static memory reservation
    assert any(e["event"] == "hbm_snapshot" and e.get("kind") == "static"
               for e in events)

    from apex_tpu.monitor.trace import read_chrome_trace

    xs = [e for e in read_chrome_trace(tpath) if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["train_step"] * 4
    # per-step spans line up with the logged rows (same wall clock)
    durs_ms = sorted(e["dur"] / 1e3 for e in xs)
    assert durs_ms[0] > 0


def test_bench_fatal_step_leaves_flight_dump(tmp_path, monkeypatch):
    """A fatal exception inside the telemetry bench's step loop has no
    bus record — the armed flight recorder's guard must still dump, and
    teardown must restore the process tracer and terminate the Chrome
    trace (in-process; a subprocess would only burn budget)."""
    import apex_tpu.bench_cli as bc
    from apex_tpu.monitor.trace import get_tracer, read_chrome_trace

    real = bc._make_telemetry_step

    def exploding():
        step, state, tokens, tps = real()
        calls = [0]

        def bad_step(i, st, tk):
            calls[0] += 1
            if calls[0] >= 3:       # past calibrate + warmup: mid-loop
                raise RuntimeError("xla died")
            return step(i, st, tk)

        bad_step.lower = step.lower     # calibrate path stays intact
        return bad_step, state, tokens, tps

    monkeypatch.setattr(bc, "_make_telemetry_step", exploding)
    fpath = str(tmp_path / "f.json")
    tpath = str(tmp_path / "t.json")
    with pytest.raises(RuntimeError, match="xla died"):
        bc._telemetry_bench(None, steps=10, trace_jsonl=tpath,
                            flight_path=fpath)
    d = json.loads(open(fpath).read())
    assert d["reason"] == "exception:RuntimeError:telemetry_bench"
    assert get_tracer() is not None and not get_tracer().enabled
    read_chrome_trace(tpath)            # terminated, parseable
    # the recorder unsubscribed: later events don't touch the dump
    mtime = os.path.getmtime(fpath)
    from apex_tpu.utils.logging import publish_event
    publish_event("preemption_requested", level="warning")
    assert os.path.getmtime(fpath) == mtime


def test_bench_cli_step_is_single_jitted_call():
    """The telemetry bench's step function is ONE jitted callable whose
    single invocation yields the new state AND the metrics — and its
    trace contains no host callbacks."""
    from apex_tpu.bench_cli import _make_telemetry_step
    # resolved at call time alongside bench_cli so both share one module
    # identity even after a sys.modules purge (see note above)
    from apex_tpu.monitor.metrics import TrainMetrics as TM

    step, state, tokens, tokens_per_step = _make_telemetry_step()
    assert hasattr(step, "lower")  # a jit-wrapped callable, not a python loop
    jaxpr = str(jax.make_jaxpr(step)(0, state, tokens))
    assert "callback" not in jaxpr
    (params, m, v, sstate), tm = step(0, state, tokens)
    assert isinstance(tm, TM)
    assert tm.grad_norm is not None and tm.loss_scale is not None
    assert tokens_per_step == tokens.shape[0] * (tokens.shape[1] - 1)
