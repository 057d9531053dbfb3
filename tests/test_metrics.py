"""Live serving metrics tier-1: mergeable registry, per-tenant
accounting, SLO burn rates, export surfaces.

The acceptance claims under test:

- **one percentile rule** — the scheduler's exact end-of-run summary and
  the histogram quantile estimator share :func:`percentile`'s
  nearest-rank rule (the seed's ``summary()`` used ``len//2`` indexing
  for TTFT but round-half-even linear indexing for step fields);
- **exact merge** — folding N per-rank snapshots is bit-identical to
  recording the union stream into one registry (counts/buckets exact,
  quantiles identical), and ``tools/metrics_merge.py`` is that fold as a
  no-jax CLI;
- **bounded error** — a histogram quantile estimate ``e`` for exact
  value ``q`` satisfies ``q <= e <= q * HIST_GROWTH`` inside the
  bucketed range (the scheduler's exact sorted-list percentiles are the
  oracle);
- **live scrape during decode** — an in-process serve loop scraped over
  HTTP mid-run returns Prometheus text + JSON whose per-tenant counters
  sum to the exact end-of-run summary, with ``decode_traces == 1``;
- **exactly-one breach/recovery** — an induced deadline storm raises ONE
  ``serve_slo_breach`` and its drain ONE ``serve_slo_recovered``, never
  a flap per tick;
- ``check_regression`` gates a metrics snapshot directly with the same
  direction hints the serve bench uses.

Engine-driven tests share one compiled engine via ``Engine.reset()``
(the test_serve idiom); everything else is host-only and fast.
"""

import json
import math
import subprocess
import sys
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.monitor.export import (HIST_GROWTH, HIST_LO, HIST_MAX_INDEX,
                                     MetricsExporter, MetricsRegistry,
                                     bucket_index, bucket_upper,
                                     histogram_quantile, merge_snapshots,
                                     percentile, snapshot_to_prometheus,
                                     write_snapshot)
from apex_tpu.monitor.slo import SLObjective, SLOTracker, parse_slo_specs
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.metrics import ServeMetrics
from apex_tpu.serve.scheduler import Request, ServeScheduler, ServeStats
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session, and a function-local re-import after that
# would subscribe to a FRESH bus the (old) modules never publish to
from apex_tpu.utils.logging import subscribe_events

import os

pytestmark = pytest.mark.monitor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def engine3():
    """Shared greedy 3-slot engine; tests reset() it — compiled once."""
    return Engine(CFG, init_gpt2_params(CFG, seed=0),
                  EngineConfig(num_slots=3, max_len=32, temperature=0.0),
                  seed=0)


def _tokens(n, seed=7):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, CFG.vocab_size, n)]


# ------------------------------------------------- the one percentile rule

def test_percentile_nearest_rank():
    vals = [30.0, 10.0, 20.0, 40.0]
    assert percentile(vals, 0.0) == 10.0     # rank clamps to 1: the min
    assert percentile(vals, 0.25) == 10.0    # ceil(.25*4) = 1
    assert percentile(vals, 0.50) == 20.0    # ceil(.50*4) = 2
    assert percentile(vals, 0.51) == 30.0    # ceil(.51*4) = 3
    assert percentile(vals, 0.99) == 40.0
    assert percentile(vals, 1.0) == 40.0
    assert percentile([], 0.5) == 0.0
    assert percentile([7.0], 0.99) == 7.0


def test_scheduler_summary_uses_the_shared_percentile_rule():
    """The satellite fix: ttft_p50 no longer uses len//2 indexing and the
    step fields no longer use a different rounding — every percentile
    field is the same nearest-rank helper, and ttft_p99_ms (the live SLO
    oracle) is now a summary field too."""
    steps = [0.004, 0.001, 0.003, 0.002, 0.010]
    reqs = [{"state": "completed", "ttft_s": t}
            for t in (0.5, 0.1, 0.3, 0.2)]
    stats = ServeStats(requests=reqs, decode_steps=5, decode_step_s=steps,
                       decode_tokens=15, total_new_tokens=19, wall_s=1.0)
    s = stats.summary()
    assert s["p50_step_ms"] == round(percentile(steps, 0.50) * 1e3, 3)
    assert s["p99_step_ms"] == round(percentile(steps, 0.99) * 1e3, 3)
    assert s["ttft_p50_ms"] == round(percentile([0.1, 0.2, 0.3, 0.5],
                                                0.50) * 1e3, 3) == 200.0
    assert s["ttft_p99_ms"] == 500.0
    # the old len//2 indexing would have answered 300.0 for the median
    assert s["ttft_p50_ms"] != 300.0


# ------------------------------------------------------- bucket geometry

def test_bucket_index_fixed_boundaries():
    assert bucket_index(0.0) == 0
    assert bucket_index(HIST_LO) == 0        # at the lower edge
    assert bucket_index(-5.0) == 0           # negatives land low, no crash
    assert bucket_index(float("nan")) == 0   # poisoned sample, no crash
    assert bucket_index(float("inf")) == HIST_MAX_INDEX
    assert bucket_index(1e12) == HIST_MAX_INDEX
    # monotonic, and the value sits inside its bucket's (lower, upper]
    prev = -1
    for v in (2e-6, 1e-4, 0.01, 0.5, 1.0, 7.3, 500.0):
        idx = bucket_index(v)
        assert idx >= prev or v < 1e-5
        assert v <= bucket_upper(idx) < v * HIST_GROWTH + 1e-18
        prev = idx


def test_histogram_quantile_error_bound():
    """The documented contract: for an exact nearest-rank percentile q in
    the bucketed range, the streaming estimate e satisfies
    q <= e <= q * HIST_GROWTH."""
    rng = np.random.RandomState(3)
    vals = list(np.exp(rng.uniform(np.log(1e-4), np.log(30.0), 500)))
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "t")
    for v in vals:
        h.record(v)
    series = h.labels()
    for p in (0.01, 0.25, 0.50, 0.90, 0.99, 1.0):
        exact = percentile(vals, p)
        est = series.quantile(p)
        assert exact <= est <= exact * HIST_GROWTH, (p, exact, est)


# ---------------------------------------------------------- registry core

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.labels().value == 3.5
    with pytest.raises(ValueError):
        c.labels().inc(-1.0)
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.inc(3)
    assert g.labels().value == 10.0
    h = reg.histogram("lat_seconds", "t")
    for v in (0.5, 1.5, 2.5):
        h.record(v)
    s = h.labels()
    assert s.count == 3 and s.sum == pytest.approx(4.5)
    state = s.state()
    assert state["min"] == 0.5 and state["max"] == 2.5


def test_family_getters_idempotent_and_kind_mismatch_loud():
    reg = MetricsRegistry()
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x_total")
    with pytest.raises(ValueError, match="agg"):
        reg.gauge("g", agg="median")


def test_label_cardinality_bounded_overflow_folds_to_other():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "t", labels=("tenant",), max_series=2)
    c.inc(tenant="a")
    c.inc(tenant="b")
    c.inc(tenant="c")          # past max_series: folds
    c.inc(tenant="d")          # same fold series
    c.inc(tenant="a")          # existing series still addressable
    series = {tuple(s.labels.items()): s.value for s in c.series()}
    assert series[(("tenant", "a"),)] == 2.0
    assert series[(("tenant", "b"),)] == 1.0
    assert series[(("tenant", "__other__"),)] == 2.0
    assert len(series) == 3    # a tenant explosion cannot grow the scrape
    with pytest.raises(ValueError, match="labels"):
        c.inc(user="a")        # undeclared label name


# ------------------------------------------------------------ exact merge

def _record_stream(reg, stream):
    c = reg.counter("done_total", "d", labels=("tenant",))
    h = reg.histogram("lat_seconds", "t")
    for tenant, v in stream:
        c.inc(tenant=tenant)
        h.record(v)


def test_merging_rank_snapshots_equals_recording_the_union_stream():
    """THE mergeable-histogram property (the aggregation seam multi-chip
    serving reuses): counters/bucket counts exact, quantiles identical."""
    rng = np.random.RandomState(11)
    streams = []
    for r in range(3):
        n = 40 + 30 * r
        streams.append([
            (f"t{int(rng.randint(0, 3))}",
             float(np.exp(rng.uniform(np.log(1e-4), np.log(5.0)))))
            for _ in range(n)])
    ranks = []
    for stream in streams:
        reg = MetricsRegistry()
        _record_stream(reg, stream)
        ranks.append(reg.snapshot(meta={"rank": len(ranks)}))
    union = MetricsRegistry()
    _record_stream(union, [s for stream in streams for s in stream])

    merged = merge_snapshots(ranks)
    want = union.snapshot()
    assert merged["meta"] == {"merged_from": 3}
    # counters: per-tenant values identical
    got_c = {tuple(sorted(s["labels"].items())): s["value"]
             for s in merged["metrics"]["done_total"]["series"]}
    want_c = {tuple(sorted(s["labels"].items())): s["value"]
              for s in want["metrics"]["done_total"]["series"]}
    assert got_c == want_c
    # histogram: count and EVERY bucket exact, sum to fp tolerance
    got_h = merged["metrics"]["lat_seconds"]["series"][0]
    want_h = want["metrics"]["lat_seconds"]["series"][0]
    assert got_h["count"] == want_h["count"] == sum(map(len, streams))
    assert got_h["buckets"] == want_h["buckets"]
    assert got_h["sum"] == pytest.approx(want_h["sum"])
    assert got_h["min"] == want_h["min"]
    assert got_h["max"] == want_h["max"]
    # quantiles computed over the merged buckets == the union registry's
    for p in (0.5, 0.9, 0.99):
        assert histogram_quantile(got_h["buckets"], got_h["count"], p) \
            == histogram_quantile(want_h["buckets"], want_h["count"], p)
    # and within the documented bound of the exact union percentile
    exact = percentile([v for s in streams for _, v in s], 0.99)
    est = histogram_quantile(got_h["buckets"], got_h["count"], 0.99)
    assert exact <= est <= exact * HIST_GROWTH


def test_merge_gauge_aggregations():
    snaps = []
    for v in (3.0, 9.0, 5.0):
        reg = MetricsRegistry()
        reg.gauge("res", agg="sum").set(v)
        reg.gauge("peak", agg="max").set(v)
        reg.gauge("free", agg="min").set(v)
        reg.gauge("last", agg="last").set(v)
        snaps.append(reg.snapshot())
    m = merge_snapshots(snaps)["metrics"]
    assert m["res"]["series"][0]["value"] == 17.0
    assert m["peak"]["series"][0]["value"] == 9.0
    assert m["free"]["series"][0]["value"] == 3.0
    assert m["last"]["series"][0]["value"] == 5.0


def test_merge_propagates_provenance_meta():
    """A fleet merge must not drop provenance: check_regression's
    device-mismatch guard reads snapshot meta, so agreeing keys pass
    through RAW (a bool stays a bool — ``bool("False")`` is truthy) and
    a mixed fleet joins with "|" so it matches NEITHER side's baseline."""
    def snap(device_kind, interpret_mode):
        reg = MetricsRegistry()
        reg.counter("x_total", "x").inc()
        return reg.snapshot(meta={"device_kind": device_kind,
                                  "interpret_mode": interpret_mode,
                                  "git": "abc123"})

    same = merge_snapshots([snap("cpu", False), snap("cpu", False)])
    assert same["meta"]["device_kind"] == "cpu"
    assert same["meta"]["interpret_mode"] is False   # raw, not "False"
    assert same["meta"]["git"] == "abc123"
    assert same["meta"]["merged_from"] == 2
    mixed = merge_snapshots([snap("cpu", True), snap("TPU v5e", False)])
    assert mixed["meta"]["device_kind"] == "TPU v5e|cpu"
    assert mixed["meta"]["interpret_mode"] == "False|True"


def test_histogram_poisoned_samples_do_not_break_the_snapshot():
    """NaN/inf samples are COUNTED (bucket 0 / overflow) but must not
    contaminate sum/min/max: one NaN would make the sum NaN forever and
    NaN/Infinity are not valid JSON — a single bad sample would break
    every later /metrics.json scrape."""
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "t")
    h.record(float("nan"))          # first sample: must not pin min/max
    h.record(float("inf"))
    h.record(0.5)
    state = reg.snapshot()["metrics"]["lat_seconds"]["series"][0]
    assert state["count"] == 3
    assert state["sum"] == 0.5 and state["min"] == 0.5 \
        and state["max"] == 0.5
    assert state["buckets"][str(bucket_index(0.5))] == 1
    assert state["buckets"][str(HIST_MAX_INDEX)] == 1   # inf: overflow
    assert state["buckets"]["0"] == 1                   # nan: bucket 0
    # strict-JSON serializable (RFC 8259: no NaN/Infinity literals)
    json.dumps(reg.snapshot(), allow_nan=False)


def test_merge_refuses_incompatible_snapshots():
    reg = MetricsRegistry()
    reg.counter("x", "x").inc()
    good = reg.snapshot()
    with pytest.raises(ValueError, match="schema"):
        merge_snapshots([good, {"schema": "other/v9"}])
    with pytest.raises(ValueError, match="at least one"):
        merge_snapshots([])
    other = MetricsRegistry()
    other.gauge("x", "x").set(1.0)
    with pytest.raises(ValueError, match="type mismatch"):
        merge_snapshots([good, other.snapshot()])
    hreg = MetricsRegistry()
    hreg.histogram("h", "h").record(1.0)
    a, b = hreg.snapshot(), json.loads(json.dumps(hreg.snapshot()))
    b["metrics"]["h"]["growth"] = 2.0   # somebody else's bucket scheme
    with pytest.raises(ValueError, match="geometry"):
        merge_snapshots([a, b])
    # gauge agg is the one field where merge SEMANTICS differ per
    # declaration — a cross-build mismatch must refuse like type/geometry,
    # never fold first-doc-wins under the wrong aggregation
    g1, g2 = MetricsRegistry(), MetricsRegistry()
    g1.gauge("free", "f", agg="min").set(0.5)
    g2.gauge("free", "f", agg="sum").set(0.5)
    with pytest.raises(ValueError, match="agg"):
        merge_snapshots([g1.snapshot(), g2.snapshot()])


# -------------------------------------------------------- export surfaces

def test_prometheus_text_exposition_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "all requests",
                labels=("tenant",)).inc(3, tenant='evil"\nco')
    h = reg.histogram("lat_seconds", "latency")
    for v in (0.5, 0.5, 2.0):
        h.record(v)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE reqs_total counter" in lines
    # label values escaped per the exposition format
    assert r'reqs_total{tenant="evil\"\nco"} 3' in lines
    assert "# TYPE lat_seconds histogram" in lines
    # cumulative buckets, then +Inf == count, then sum/count
    bucket_lines = [l for l in lines if l.startswith("lat_seconds_bucket")]
    counts = [int(l.rsplit(" ", 1)[1]) for l in bucket_lines]
    assert counts == sorted(counts) and counts[-1] == 3
    assert bucket_lines[-1].startswith('lat_seconds_bucket{le="+Inf"}')
    assert "lat_seconds_count 3" in lines
    assert any(l.startswith("lat_seconds_sum 3") for l in lines)
    # a merged snapshot renders through the same path
    assert snapshot_to_prometheus(merge_snapshots([reg.snapshot()])) \
        .splitlines()[0].startswith("# HELP")
    # le labels come from the SNAPSHOT'S serialized geometry, never this
    # build's constants — a capture under different lo/growth must
    # render its own bucket edges
    foreign = json.loads(json.dumps(reg.snapshot()))
    fam = foreign["metrics"]["lat_seconds"]
    fam["lo"], fam["growth"] = 1.0, 2.0
    first_idx = min(int(k) for k in fam["series"][0]["buckets"])
    text2 = snapshot_to_prometheus(foreign)
    assert f'le="{1.0 * 2.0 ** first_idx:.10g}"' in text2


def test_write_snapshot_atomic_and_bus_event(tmp_path):
    reg = MetricsRegistry()
    reg.counter("x_total", "x").inc(2)
    events = []
    # function-local import, DELIBERATELY inverted from the module-level
    # idiom above: export.py publishes through a deferred call-time
    # import (it must stay stdlib-only at import time), so after
    # a mid-session sys.modules purge it publishes to
    # the FRESH bus — the subscription must resolve at call time too
    from apex_tpu.utils.logging import subscribe_events as _sub
    unsub = _sub(events.append)
    try:
        path = str(tmp_path / "snap.json")
        write_snapshot(reg, path, meta={"rank": 0})
        doc = json.loads(open(path).read())
        assert doc["schema"] == "apex_tpu.metrics/v1"
        assert doc["meta"] == {"rank": 0}
        assert not os.path.exists(path + ".tmp")   # committed, not torn
        assert [e["event"] for e in events] == ["metrics_snapshot"]
    finally:
        unsub()


def test_exporter_scrapes_text_and_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("scraped_total", "x").inc(5)
    events = []
    # call-time import: matches the exporter's deferred publish_event
    # import (see test_write_snapshot_atomic_and_bus_event)
    from apex_tpu.utils.logging import subscribe_events as _sub
    unsub = _sub(events.append)
    snap_path = str(tmp_path / "final.json")
    try:
        with MetricsExporter(reg, port=0, snapshot_path=snap_path,
                             meta={"rank": 1}) as exp:
            base = f"http://127.0.0.1:{exp.port}"
            text = urllib.request.urlopen(base + "/metrics",
                                          timeout=5).read().decode()
            assert "scraped_total 5" in text
            doc = json.loads(urllib.request.urlopen(
                base + "/metrics.json", timeout=5).read())
            assert doc["schema"] == "apex_tpu.metrics/v1"
            assert doc["meta"] == {"rank": 1}
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(base + "/nope", timeout=5)
        scrapes = [e for e in events if e["event"] == "metrics_scrape"]
        assert {e["path"] for e in scrapes} == {"/metrics",
                                               "/metrics.json"}
        # stop() committed the per-rank snapshot artifact
        final = json.loads(open(snap_path).read())
        assert final["metrics"]["scraped_total"]["series"][0]["value"] == 5
    finally:
        unsub()


# ------------------------------------------------------------ SLO tracker

def _clock():
    """Deterministic injectable clock."""
    state = {"t": 1000.0}

    def now():
        return state["t"]

    now.advance = lambda dt: state.__setitem__("t", state["t"] + dt)
    return now


def test_slo_breach_and_recovery_fire_exactly_once():
    clock = _clock()
    obj = SLObjective.shed_frac(0.1, min_events=4, short_window_s=10.0,
                                long_window_s=50.0)
    tr = SLOTracker([obj], clock=clock)
    events = []
    unsub = subscribe_events(events.append)
    try:
        for _ in range(4):
            tr.observe("shed", bad=True)
        # a sustained storm evaluated every tick raises ONE breach
        for _ in range(5):
            tr.evaluate()
            clock.advance(0.5)
        breaches = [e for e in events if e["event"] == "serve_slo_breach"]
        assert len(breaches) == 1
        assert breaches[0]["objective"] == "shed_frac"
        assert breaches[0]["burn_short"] == pytest.approx(10.0)
        # good traffic dilutes the short-window burn under the factor
        for _ in range(60):
            tr.observe("shed", bad=False)
        for _ in range(5):
            tr.evaluate()
            clock.advance(0.5)
        recs = [e for e in events if e["event"] == "serve_slo_recovered"]
        assert len(recs) == 1
        assert tr.summary()["shed_frac"]["breached"] is False
        assert tr.summary()["shed_frac"]["breaches"] == 1
    finally:
        unsub()


def test_slo_min_events_and_window_pruning():
    clock = _clock()
    obj = SLObjective.deadline_miss_frac(0.5, min_events=8,
                                         short_window_s=10.0,
                                         long_window_s=50.0)
    tr = SLOTracker([obj], clock=clock)
    for _ in range(7):
        tr.observe("deadline", bad=True)
    # burning hot, but below min_events: one bad tick must not page
    assert tr.evaluate() == []
    assert tr.summary()["deadline_miss_frac"]["breached"] is False
    # events age out of the short window (totals prune with them)
    clock.advance(11.0)
    tr.evaluate()
    s = tr.summary()["deadline_miss_frac"]
    assert s["short_events"] == 0 and s["long_events"] == 7


def test_slo_latency_objective_classifies_against_threshold():
    clock = _clock()
    tr = SLOTracker([SLObjective.ttft_p99_ms(50.0, min_events=2,
                                             short_window_s=10.0,
                                             long_window_s=50.0)],
                    clock=clock)
    tr.observe("ttft", value=0.010)    # under 50ms: good
    tr.observe("ttft", value=0.500)    # over: bad
    tr.observe("ttft", bad=True)       # verdict-only: no latency, skipped
    s = tr.summary()["ttft_p99_ms"]
    assert s["short_events"] == 2
    assert s["burn_short"] == pytest.approx(0.5 / 0.01)


def test_slo_validation_and_spec_parsing():
    with pytest.raises(ValueError, match="source"):
        SLObjective(name="x", source="nope", bad_frac_budget=0.1)
    with pytest.raises(ValueError, match="bad_frac_budget"):
        SLObjective(name="x", source="shed", bad_frac_budget=0.0)
    with pytest.raises(ValueError, match="window"):
        SLObjective(name="x", source="shed", bad_frac_budget=0.1,
                    short_window_s=60.0, long_window_s=60.0)
    # a zero/negative span would prune every event per evaluate() —
    # armed but structurally inert (breach can never fire): refuse loudly
    with pytest.raises(ValueError, match="positive"):
        SLObjective(name="x", source="shed", bad_frac_budget=0.1,
                    short_window_s=0.0, long_window_s=300.0)
    with pytest.raises(ValueError, match="positive"):
        parse_slo_specs(["shed_frac=0.1"], short_window_s=-5.0,
                        long_window_s=300.0)
    with pytest.raises(ValueError, match="duplicate"):
        SLOTracker([SLObjective.shed_frac(0.1),
                    SLObjective.shed_frac(0.2)])
    objs = parse_slo_specs(["ttft_p99_ms=50", "shed_frac=0.1"],
                           short_window_s=5.0, long_window_s=25.0)
    assert [o.name for o in objs] == ["ttft_p99_ms", "shed_frac"]
    assert objs[0].threshold_s == pytest.approx(0.050)
    assert objs[0].short_window_s == 5.0
    for bad in ("nope=1", "ttft_p99_ms", "shed_frac=zero",
                "shed_frac=-1"):
        with pytest.raises(ValueError):
            parse_slo_specs([bad])


# ------------------------------------------------- training-side registry

def test_telemetry_records_into_registry():
    reg = MetricsRegistry()
    from apex_tpu.monitor import Telemetry

    tel = Telemetry(None, goodput=False, mirror_events=False,
                    registry=reg)
    try:
        tel.log_step(0, step_ms=10.0)
        tel.log_step(1, step_ms=20.0, skipped=True)
    finally:
        tel.close()
    assert reg.counter("train_steps_total").labels().value == 2
    assert reg.counter("train_skipped_steps_total").labels().value == 1
    h = reg.histogram("train_step_seconds").labels()
    assert h.count == 2 and h.sum == pytest.approx(0.030)


# --------------------------------------------------------- tools: the CLI

def test_metrics_merge_cli_equals_union(tmp_path):
    rng = np.random.RandomState(5)
    paths, all_vals = [], []
    for r in range(2):
        reg = MetricsRegistry()
        vals = [float(v) for v in np.exp(
            rng.uniform(np.log(1e-3), np.log(2.0), 25))]
        all_vals.extend(vals)
        h = reg.histogram("lat_seconds", "t")
        for v in vals:
            h.record(v)
        reg.counter("done_total", "d").inc(len(vals))
        p = str(tmp_path / f"rank{r}.json")
        write_snapshot(reg, p, meta={"rank": r})
        paths.append(p)
    out = str(tmp_path / "fleet.json")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "metrics_merge.py"),
         *paths, "-o", out], capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    union = MetricsRegistry()
    uh = union.histogram("lat_seconds", "t")
    for v in all_vals:
        uh.record(v)
    union.counter("done_total", "d").inc(len(all_vals))
    merged = json.loads(open(out).read())
    want = union.snapshot()
    assert merged["metrics"]["done_total"]["series"][0]["value"] == 50
    assert merged["metrics"]["lat_seconds"]["series"][0]["buckets"] \
        == want["metrics"]["lat_seconds"]["series"][0]["buckets"]
    # --prometheus renders the merged view through the shared formatter
    r2 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "metrics_merge.py"),
         *paths, "--prometheus"], capture_output=True, text=True)
    assert r2.returncode == 0 and "done_total 50" in r2.stdout
    # a non-snapshot input is a usage error, never a fabricated fleet view
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write('{"schema": "other"}')
    r3 = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "metrics_merge.py"),
         paths[0], bad], capture_output=True, text=True)
    assert r3.returncode == 2 and "schema" in r3.stderr


def test_check_regression_gates_snapshots_directly(tmp_path):
    from tools.check_regression import main as gate

    def snap(path, ttft_scale, rejected):
        reg = MetricsRegistry()
        sm = ServeMetrics(reg)
        for i in range(20):
            sm.submitted.inc(tenant=f"t{i % 2}")
            sm.ttft.record(0.010 * ttft_scale, tenant=f"t{i % 2}")
        for _ in range(rejected):
            sm.submitted.inc(tenant="t0")
            sm.rejected.inc(tenant="t0")
        write_snapshot(reg, path)

    base = str(tmp_path / "base.json")
    same = str(tmp_path / "same.json")
    worse = str(tmp_path / "worse.json")
    snap(base, 1.0, 0)
    snap(same, 1.0, 0)
    snap(worse, 4.0, 5)      # 4x TTFT and a 5/25 shed_frac
    assert gate([same, base]) == 0
    assert gate([worse, base]) == 1
    # direction hints: ttft_p99_ms regresses as lower-is-better, and
    # shed_frac's 0 -> N move gates even from the zero baseline (the
    # _frac higher-is-better family must NOT claim it)
    from tools.check_regression import (load_metrics, lower_is_better)
    cur = load_metrics(worse, warmup=0)
    assert "ttft_p99_ms" in cur and "shed_frac" in cur
    assert cur["shed_frac"][0] == pytest.approx(5 / 25)
    assert lower_is_better("shed_frac")
    assert lower_is_better("deadline_miss_frac")
    assert not lower_is_better("prefix_hit_frac")
    # more mid-stream evictions is strictly worse — without the hint a
    # 0 -> N eviction storm would gate as an improvement
    assert lower_is_better("serve_requests_evicted_total")
    # the snapshot quantile rule is LOADED from monitor.export, never a
    # second spelling that could silently diverge from the exporter's
    from tools.check_regression import _export_module
    assert _export_module().histogram_quantile is not None
    # only *_seconds histograms become _p50_ms/_p99_ms: a token-count
    # distribution scaled by 1e3 and forced lower-is-better via the ms
    # unit would gate silently wrong in value AND direction
    from tools.check_regression import metrics_from_snapshot
    nreg = MetricsRegistry()
    nreg.histogram("prompt_tokens", "not a latency").record(128.0)
    nreg.histogram("wait_seconds", "a latency").record(0.5)
    derived = metrics_from_snapshot(nreg.snapshot())
    assert "wait_p99_ms" in derived
    assert not any(k.startswith("prompt_tokens") for k in derived)


def test_serve_cli_inapplicable_metric_flags_are_usage_errors(capsys):
    """Silently ignoring a metrics/SLO spec would leave the user
    believing it is configured: --slo-window with no --slo objective,
    and --tenants with --stdin (stdin lines carry no tenant identity),
    both exit 2 with the fix spelled out."""
    from apex_tpu.serve.cli import main
    assert main(["--slo-window", "30:150", "--requests", "1"]) == 2
    assert "--slo-window needs" in capsys.readouterr().err
    assert main(["--stdin", "--tenants", "4"]) == 2
    assert "--tenants" in capsys.readouterr().err
    # an unbindable port fails in milliseconds with exit 2 — BEFORE the
    # engine pays for params + compiles, never a raw OSError traceback
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        taken = s.getsockname()[1]
        assert main(["--requests", "1",
                     "--metrics-port", str(taken)]) == 2
    assert "cannot bind" in capsys.readouterr().err
    # bench: --tenants without a metrics surface is armed-but-inert —
    # the labels reach no observable output; refuse loudly (and cheaply:
    # before the engine builds)
    from apex_tpu.bench_cli import _serve_bench
    with pytest.raises(SystemExit, match="tenants"):
        _serve_bench(steps=1, tenants=2)


# ---------------------------------------------- live serving e2e (serve)

@pytest.mark.serve
def test_live_scrape_during_decode_reconciles_with_exact_summary(engine3):
    """THE acceptance e2e: scrape a RUNNING serve loop over HTTP; the
    per-tenant counters sum to the scheduler's exact end-of-run summary,
    histogram p50/p99 match the exact sorted-list percentiles within the
    documented bucket error — and decode still compiled exactly once."""
    eng = engine3.reset()
    t0 = eng.decode_traces
    metrics = ServeMetrics()
    sched = ServeScheduler(eng, metrics=metrics)
    tenants = [None, "acme", "acme", "globex", None, "acme"]
    for i, tenant in enumerate(tenants):
        sched.submit(Request(request_id=f"r{i}", tokens=_tokens(6, i),
                             max_new_tokens=4, tenant=tenant))
    with MetricsExporter(metrics.registry, port=0) as exp:
        # a few ticks in, requests still in flight: scrape LIVE
        for _ in range(3):
            sched.step()
        base = f"http://127.0.0.1:{exp.port}"
        live_text = urllib.request.urlopen(base + "/metrics",
                                           timeout=5).read().decode()
        live = json.loads(urllib.request.urlopen(base + "/metrics.json",
                                                 timeout=5).read())
        while sched.step():
            pass
    assert 'serve_requests_admitted_total{tenant="acme"}' in live_text
    live_admitted = sum(s["value"] for s in
                        live["metrics"]["serve_requests_admitted_total"]
                        ["series"])
    assert 0 < live_admitted <= 6          # mid-run view, monotonic
    assert eng.decode_traces == 1          # scrapes never touched the jit

    stats = sched.stats()
    s = stats.summary()
    snap = metrics.registry.snapshot()

    def total(name):
        return sum(x["value"]
                   for x in snap["metrics"][name].get("series", []))

    assert total("serve_requests_submitted_total") == s["requests"] == 6
    assert total("serve_requests_completed_total") == s["completed"] == 6
    assert total("serve_requests_rejected_total") == s["rejected"] == 0
    assert total("serve_deadline_exceeded_total") \
        == s["deadline_exceeded"] == 0
    assert total("serve_generated_tokens_total") == s["new_tokens"]
    # per-tenant split is what was submitted per tenant
    by_tenant = {x["labels"]["tenant"]: x["value"] for x in
                 snap["metrics"]["serve_requests_completed_total"]
                 ["series"]}
    assert by_tenant == {"default": 2.0, "acme": 3.0, "globex": 1.0}
    # streaming TTFT quantiles vs the exact oracle, within the bound
    hist = snap["metrics"]["serve_ttft_seconds"]["series"]
    buckets, count = {}, 0
    for x in hist:
        count += x["count"]
        for k, n in x["buckets"].items():
            buckets[int(k)] = buckets.get(int(k), 0) + n
    exact_ttfts = [r["ttft_s"] for r in stats.requests if "ttft_s" in r]
    assert count == len(exact_ttfts) == 6
    for p, field in ((0.50, "ttft_p50_ms"), (0.99, "ttft_p99_ms")):
        exact = s[field] / 1e3
        est = histogram_quantile(buckets, count, p)
        assert exact <= est * 1.001 and est <= exact * HIST_GROWTH * 1.001
    # the compact live summary agrees too
    assert metrics.summary()["totals"][
        "serve_requests_completed_total"] == 6


def test_terminal_requests_with_first_token_are_ttft_witnesses():
    """A request that reached its first token and THEN expired (or was
    evicted) witnessed a TTFT the exact summary counts — the histogram
    and the ttft SLO stream must count it too, or under deadline
    pressure the live p99 reads systematically better than the oracle
    (the worst TTFTs are exactly the requests that die by deadline)."""
    import types

    slo = SLOTracker([SLObjective.ttft_p99_ms(
        1e-6, min_events=1, burn_factor=1.0)])
    sm = ServeMetrics(slo=slo)
    dead = types.SimpleNamespace(tenant="t0", generated=[1, 2],
                                 ttft_s=0.5, latency_s=0.9)
    sm.on_deadline(dead)
    sm.on_evict(dead, "aborted")
    fam = sm.registry.snapshot()["metrics"]["serve_ttft_seconds"]
    assert fam["series"][0]["count"] == 2       # both witnessed
    slo.evaluate()
    state = slo.summary()["ttft_p99_ms"]
    assert state["short_events"] == 2 and state["breached"]


def test_every_terminal_status_feeds_every_fraction_window_once():
    """The live fraction denominators must match the documented
    objectives (deadline_miss_frac over TERMINAL requests, shed_frac
    over everything that asked): one completion, one rejection, one
    deadline miss, one eviction → each window holds 4 events with
    exactly one bad. Before this, rejected/evicted requests fed no
    deadline event, so 60 rejections + 10 misses read as 10/40 = the
    budget and paged the operator while the true miss frac held."""
    import types

    slo = SLOTracker([
        SLObjective.deadline_miss_frac(0.5, min_events=100),
        SLObjective.shed_frac(0.5, min_events=100)])
    sm = ServeMetrics(slo=slo)
    req = types.SimpleNamespace(tenant=None, generated=[1],
                                ttft_s=0.01, latency_s=0.02)
    sm.on_complete(req)
    sm.on_reject(req, "queue_full")
    sm.on_deadline(req)
    sm.on_evict(req, "aborted")
    slo.evaluate()
    state = slo.summary()
    for name, bad_frac in (("deadline_miss_frac", 0.25),
                           ("shed_frac", 0.25)):
        assert state[name]["short_events"] == 4, (name, state[name])
        assert state[name]["burn_short"] == pytest.approx(
            bad_frac / 0.5), (name, state[name])


@pytest.mark.serve
def test_final_tick_completions_reach_the_exit_slo_state(engine3):
    """Completions landing on the LAST decode tick must feed that tick's
    evaluate(): with a one-request run whose only completion is the
    final tick's, the breach must publish before run() exits and the
    exit snapshot's breached gauge must reflect it (the tick used to
    evaluate BEFORE the accept loop, leaving the exit state one tick
    stale and the breach unpublished)."""
    eng = engine3.reset()
    slo = SLOTracker([SLObjective.ttft_p99_ms(
        1e-6, min_events=1, burn_factor=1.0)])   # any real TTFT is bad
    metrics = ServeMetrics(slo=slo)
    sched = ServeScheduler(eng, metrics=metrics)
    events = []
    unsub = subscribe_events(events.append)
    try:
        sched.submit(Request(request_id="only", tokens=_tokens(4),
                             max_new_tokens=2))
        sched.run()
    finally:
        unsub()
    assert [e["event"] for e in events
            if e["event"].startswith("serve_slo")] == ["serve_slo_breach"]
    g = metrics.registry.gauge("serve_slo_breached").labels(
        objective="ttft_p99_ms")
    assert g.value == 1.0


@pytest.mark.serve
def test_deadline_storm_raises_exactly_one_breach_recovery_pair(engine3):
    """An induced deadline storm (queued requests expiring with ZERO
    decode steps run — the idle-tick path) breaches once; draining it
    with good traffic recovers once. Never a flap per tick."""
    eng = engine3.reset()
    t0 = eng.decode_traces
    slo = SLOTracker([SLObjective.deadline_miss_frac(
        0.5, min_events=8, burn_factor=1.0)])
    metrics = ServeMetrics(slo=slo)
    sched = ServeScheduler(eng, metrics=metrics)
    events = []
    unsub = subscribe_events(events.append)
    try:
        # the storm: already-expired deadlines, swept before admission
        for i in range(8):
            sched.submit(Request(request_id=f"dead{i}",
                                 tokens=_tokens(4, i),
                                 max_new_tokens=4, deadline_ms=1e-3))
        for _ in range(4):          # several evaluations of one storm
            sched.step()
        assert eng.decode_traces == t0  # breached with zero decode steps
        # the drain: good traffic dilutes the short-window burn
        for i in range(10):
            sched.submit(Request(request_id=f"ok{i}",
                                 tokens=_tokens(4, 100 + i),
                                 max_new_tokens=2))
        while sched.step():
            pass
    finally:
        unsub()
    names = [e["event"] for e in events
             if e["event"].startswith("serve_slo")]
    assert names == ["serve_slo_breach", "serve_slo_recovered"]
    breach = next(e for e in events if e["event"] == "serve_slo_breach")
    assert breach["objective"] == "deadline_miss_frac"
    assert breach["burn_short"] >= 1.0
    s = sched.stats().summary()
    assert s["deadline_exceeded"] == 8 and s["completed"] == 10
    assert eng.decode_traces == 1        # metrics+SLO stayed off the jit
    # the burn gauges mirrored the live state per tick
    g = metrics.registry.gauge("serve_slo_breached").labels(
        objective="deadline_miss_frac")
    assert g.value == 0.0                # recovered by the end
