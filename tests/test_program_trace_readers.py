"""The benchmark's two readers of the program's own tracing
(``benchmark/readers/program_spans.py``, ``device_scopes.py``) over a
hand-made ``.xplane.pb``: three whole decode steps, one prefill call and a
fourth decode step that the trace cuts, with known starts, ends,
attributes and scoped operations, so that each of the 17 per-layer metrics
has a value worked out by hand. Built as a text proto and serialised by
``jax.profiler.ProfileData``; CPU only, no chip, no time is measured.
"""

import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from readers import device_scopes, device_trace, program_spans  # noqa: E402

MS = 10 ** 9                    # picoseconds in a millisecond

# (name, start ms, end ms, attributes) on the host's "python" line
TICKS = [
    ("apex.sched.step", 0.0, 24.0, {"queued": 4}),
    ("apex.decode_step", 0.1, 23.8, {"active": 4, "slots": 4,
                                     "resident": 400, "pages_in_use": 12,
                                     "pages": 16, "attended_chunks": 2,
                                     "key_chunks": 16}),
    ("apex.decode_step.launch", 0.2, 1.2, {}),
    ("apex.decode_step.fetch", 1.25, 22.0, {}),
    ("apex.sched.accept", 23.85, 23.95, {}),

    ("apex.sched.step", 24.5, 49.0, {"queued": 4}),
    ("apex.decode_step", 24.6, 48.7, {"active": 3, "slots": 4,
                                      "resident": 404, "pages_in_use": 13,
                                      "pages": 16, "attended_chunks": 2,
                                      "key_chunks": 16}),
    ("apex.decode_step.launch", 24.7, 25.5, {}),
    ("apex.decode_step.fetch", 25.6, 45.7, {}),
    ("apex.sched.accept", 48.75, 48.9, {}),

    ("apex.sched.step", 50.0, 130.0, {"queued": 5}),
    ("apex.sched.admit", 50.1, 105.0, {}),
    ("apex.prefill", 50.2, 104.9, {"admitted": 1, "slots": 4}),
    ("apex.prefill.plan", 50.3, 51.0, {}),
    ("apex.prefill.launch", 51.1, 52.0, {"bucket": 64, "slots": 4,
                                         "real_positions": 48,
                                         "hit_tokens": 0, "new_pages": 3}),
    ("apex.prefill.fetch", 52.1, 103.9, {}),
    ("apex.prefill.index", 104.0, 104.1, {}),
    ("apex.decode_step", 105.5, 129.5, {"active": 4, "slots": 4,
                                        "resident": 450, "pages_in_use": 16,
                                        "pages": 16, "attended_chunks": 3,
                                        "key_chunks": 16}),
    ("apex.decode_step.launch", 105.6, 106.6, {}),
    ("apex.decode_step.fetch", 106.7, 127.9, {}),
    ("apex.sched.accept", 129.6, 129.9, {}),
    # what the benchmark's own wrappers open: not these readers' to read
    ("bench.step", 49.95, 130.05, {}),
]
# the tick whose device run the trace cuts: its spans are whole, its run
# is not, so nothing of it counts
CUT_TICK = [
    ("apex.sched.step", 130.6, 160.0, {"queued": 4}),
    ("apex.decode_step", 130.7, 159.5, {"active": 1, "slots": 4,
                                        "resident": 454, "pages_in_use": 4,
                                        "pages": 16, "attended_chunks": 16,
                                        "key_chunks": 16}),
    ("apex.decode_step.launch", 130.8, 131.5, {}),
    ("apex.decode_step.fetch", 131.6, 159.0, {}),
]

DECODE, PREFILL = "jit__decode_fn(111)", "jit_prefill_fn(222)"
# (instruction, scope path, offset ms into the run, ms) of a decode run
DECODE_OPS = [
    ("%copy.1 = bf16[2,9,16,4,8] copy(%cache_v.1)", "cache.v", 0.0, 1.0),
    # 1.2 ms in which no operation runs
    ("%fusion.1 = f32[4] fusion()", "jit(_decode_fn)/ln_qkv/dot_general",
     2.2, 3.0),
    ("%fusion.2 = bf16[4,4,8] fusion()",
     "jit(_decode_fn)/attention/kv_write/scatter", 5.2, 0.3),
    ("%fusion.3 = f32[4,4,8] fusion()",
     "jit(_decode_fn)/attention/bhk,bkhd->bhd/dot_general", 5.5, 8.0),
    ("%fusion.4 = f32[4,32] fusion()",
     "jit(_decode_fn)/attention/attn_proj/dot_general", 13.5, 1.0),
    ("%fusion.5 = f32[4,32] fusion()", "jit(_decode_fn)/mlp/dot_general",
     14.5, 3.0),
    ("%fusion.6 = f32[4,128] fusion()",
     "jit(_decode_fn)/sampling/dot_general", 17.5, 1.5),
    ("%fusion.7 = s32[4] fusion()", "jit(_decode_fn)/sampling/argmax",
     19.0, 1.0),
]
DECODE_RUNS = [1.5, 25.4, 107.0]           # each 20 ms, on the host's clock
PREFILL_RUN = (52.5, 102.5)
# the file has every device event this much early, as the profiler's map of
# the chip's clock does; the runtime enqueues each run a little before it
# starts, the second decode run at the very moment
SKEW = 0.4
ENQUEUED = {1.5: 1.45, 25.4: 25.4, 107.0: 106.9, 52.5: 52.42, 132.0: 131.9}
PREFILL_OPS = [                 # the scan's body nests in its `while`
    ("%while.1 = (s32[]) while()", None, 0.5, 49.0),
    ("%fusion.11 = f32[4] fusion()",
     "jit(prefill_fn)/while/body/closed_call/ln_qkv/dot_general", 0.5, 10.0),
    ("%fusion.12 = bf16[4,4,8] fusion()",
     "jit(prefill_fn)/while/body/closed_call/attention/kv_write/scatter",
     10.5, 2.0),
    ("%fusion.13 = f32[4,4,8] fusion()",
     "jit(prefill_fn)/while/body/closed_call/attention/gather", 12.5, 20.0),
    ("%fusion.14 = f32[4,32] fusion()",
     "jit(prefill_fn)/while/body/closed_call/attention/attn_proj/"
     "dot_general", 32.5, 3.0),
    ("%fusion.15 = f32[4,32] fusion()",
     "jit(prefill_fn)/while/body/closed_call/mlp/dot_general", 35.5, 10.0),
    ("%fusion.16 = f32[4,128] fusion()",
     "jit(prefill_fn)/sampling/dot_general", 49.7, 0.3),
]
CUT_RUN = 132.0           # a decode run whose operations stop after 5.5 ms

# each metric's value by hand, from the numbers above
EXPECTED = {
    # ticks 1-3: 24.0 - 23.7, 24.5 - 24.1, 80.0 - (54.7 + 24.0)
    "sched_self_ms_per_tick": (0.3 + 0.4 + 1.3) / 3,
    # span start to run start: 1.5 - 0.1, 25.4 - 24.6, 107.0 - 105.5
    "decode_launch_lag_ms_per_step": (1.4 + 0.8 + 1.5) / 3,
    # launch end to run start, not under 0: 0.3, (25.4 - 25.5 ->) 0, 0.4
    "decode_device_lag_ms_per_step": (0.3 + 0.0 + 0.4) / 3,
    # run end to fetch end: 22.0 - 21.5, 45.7 - 45.4, 127.9 - 127.0
    "decode_fetch_lag_ms_per_step": (0.5 + 0.3 + 0.9) / 3,
    # 23.8 -> 24.6 only: a prefill lies between the second and the third
    "decode_host_between_ms_per_step": 0.8,
    "prefill_host_ms_per_call": 54.7 - 50.0,
    "decode_slot_occupancy": 100 * (4 + 3 + 4) / 12,
    "prefill_useful_position_share": 100 * 48 / (64 * 4),
    "pool_pages_in_use_share": 100 * (12 + 13 + 16) / 48,
    "decode_dense_ms_per_step": 3.0 + 1.0 + 3.0 + 1.5,
    "decode_attention_ms_per_step": 8.0,
    "decode_kv_write_ms_per_step": 0.3,
    "decode_other_ms_per_step": 1.0 + 1.0 + 1.2,
    "prefill_dense_ms_per_call": 10.0 + 3.0 + 10.0 + 0.3,
    "prefill_attention_ms_per_call": 20.0,
    "prefill_kv_write_ms_per_call": 2.0,
    # the while's own 4.0, and 0.7 in which no operation runs
    "prefill_other_ms_per_call": 4.0 + 0.7,
}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


class _Plane:
    """One XPlane as a text proto: events by name, stats by name."""

    def __init__(self, number, name):
        self.head = f'id: {number} name: "{name}"'
        self.lines, self.events, self.stats = [], {}, {}
        self.meta_stats = {}

    def _id(self, table, name):
        return table.setdefault(name, len(table) + 1)

    def _stat(self, key, value):
        kind = "str_value" if isinstance(value, str) else "int64_value"
        text = json.dumps(value)
        return (f"stats {{ metadata_id: {self._id(self.stats, key)} "
                f"{kind}: {text} }}")

    def line(self, name, events):
        """``events``: ``(name, start ms, end ms, {stat: value})``."""
        body = "".join(
            f"events {{ metadata_id: {self._id(self.events, n)} "
            f"offset_ps: {round(t0 * MS)} "
            f"duration_ps: {round((t1 - t0) * MS)} "
            + " ".join(self._stat(k, v) for k, v in stats.items()) + " }\n"
            for n, t0, t1, stats in events)
        self.lines.append(f'lines {{ id: {len(self.lines) + 1} '
                          f'name: "{name}" timestamp_ns: 0\n{body}}}\n')

    def scoped(self, name, path, program):
        """Give the instruction ``name`` its ``tf_op`` and ``program_id``
        on its metadata, where the chip's profiler puts them."""
        self.meta_stats[name] = (
            self._stat(program_spans.SCOPE_STAT, path) + " "
            + self._stat(program_spans.PROGRAM_STAT, program))

    def text(self):
        meta = "".join(
            f"event_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(n)} {self.meta_stats.get(n, '')} }} }}\n"
            for n, i in self.events.items())
        stats = "".join(
            f"stat_metadata {{ key: {i} value {{ id: {i} "
            f"name: {json.dumps(n)} }} }}\n" for n, i in self.stats.items())
        return (f"planes {{ {self.head}\n" + "".join(self.lines) + meta
                + stats + "}\n")


def _write(tmp_path, host, cut=False, device=True, scopes=True,
           skewed=True):
    """The trace as a directory the readers take, and the ``obs`` that
    points at it."""
    from jax.profiler import ProfileData

    planes, enqueued = [], []
    if device:
        chip = _Plane(1, "/device:TPU:0")
        runs = [(DECODE, t, t + 20.0) for t in DECODE_RUNS]
        runs.append((PREFILL,) + PREFILL_RUN)
        ops = [(n, t + at, t + at + ms, {})
               for t in DECODE_RUNS for n, _, at, ms in DECODE_OPS]
        ops += [(n, PREFILL_RUN[0] + at, PREFILL_RUN[0] + at + ms, {})
                for n, _, at, ms in PREFILL_OPS]
        if cut:
            runs.append((DECODE, CUT_RUN, CUT_RUN + 20.0))
            ops += [(n, CUT_RUN + at, CUT_RUN + at + ms, {})
                    for n, _, at, ms in DECODE_OPS[:3]]
        runs.sort(key=lambda r: r[1])
        chip.line("XLA Modules", [(n, a - SKEW, b - SKEW, {"run_id": 900 + i})
                                  for i, (n, a, b) in enumerate(runs)])
        chip.line("XLA Ops", sorted(((n, a - SKEW, b - SKEW, st)
                                     for n, a, b, st in ops),
                                    key=lambda e: e[1]))
        if scopes:
            for name, program in ((DECODE, DECODE_OPS),
                                  (PREFILL, PREFILL_OPS)):
                for n, path, _, _ in program:
                    if path is not None:
                        chip.scoped(n, path + ":",
                                    int(program_spans.program_id(name)))
        planes.append(chip)
        if skewed:
            enqueued = [(program_spans.ENQUEUE, ENQUEUED[a],
                         ENQUEUED[a] + 0.05, {"run_id": 900 + i})
                        for i, (_, a, _) in enumerate(runs)]
    cpu = _Plane(2, "/host:CPU")
    cpu.line("python", sorted(host + (CUT_TICK if cut else []),
                              key=lambda e: (e[1], -e[2])))
    cpu.line("tfrt-non-blocking-queue/650", enqueued)
    planes.append(cpu)
    raw = ProfileData.text_proto_to_serialized_xspace(
        "".join(p.text() for p in planes))
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(raw)
    return {"trace_dir": str(tmp_path), "slice": (0.0, 1.0), "spans": []}


def _read(name, obs):
    reader = {"program_spans": program_spans,
              "device_scopes": device_scopes}[_spec(name)["reader"]]
    return reader.read(_spec(name), obs)


@pytest.fixture(scope="module")
def whole(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("whole"), TICKS)


@pytest.fixture(scope="module")
def cut(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("cut"), TICKS, cut=True)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_metric_reads_its_hand_computed_value(name, whole):
    assert _read(name, whole) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_run_the_trace_cuts_and_its_spans_are_left_out(name, cut):
    """The cut tick would move every one of them: its step feeds one slot
    of four and holds four pages, waits 1.2 ms after the one before, and
    its run holds 4.3 ms of operations."""
    assert _read(name, cut) == pytest.approx(EXPECTED[name], rel=1e-9)


def test_attended_chunk_share_reads_the_steps_trip_counts(whole, cut,
                                                          tmp_path):
    """PR 33's one metric, on the ``share`` reader PR 28 brought: the
    trips the three whole steps ran (2, 2, 3) over the 16 chunks a slot
    has; the cut step's 16 of 16 is left out with its run; and spans that
    carry no such attribute (a program from before the loop) give nothing
    to read, never 0."""
    name = "decode_attended_chunk_share"
    assert _read(name, whole) == pytest.approx(100 * (2 + 2 + 3) / 48)
    assert _read(name, cut) == pytest.approx(100 * (2 + 2 + 3) / 48)
    before = [(n, a, b, {k: v for k, v in st.items()
                         if k not in ("attended_chunks", "key_chunks")})
              for n, a, b, st in TICKS]
    assert _read(name, _write(tmp_path, before)) is None
    assert _read("pool_pages_in_use_share", _write(tmp_path / "again",
                                                   before)) \
        == pytest.approx(EXPECTED["pool_pages_in_use_share"])
    entry = {m["name"]: m for m in _bench()["per_layer"]}[name]
    spec = _spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"], entry["better"],
            entry["source"], entry["workloads"]) == (
        entry["layer"], entry["unit"], entry["moves"], "lower",
        "program_counter", ["gpt2-xl.chat-short", "ouro-2.6b.chat-turns",
                            "ling-3.0-flash-vl-ep4.passage-chat"])


def test_the_parts_sum_to_the_run_and_the_trace_is_read_once(whole,
                                                             monkeypatch):
    for program, per, run_ms in (("decode", "step", 20.0),
                                 ("prefill", "call", 50.0)):
        parts = [_read(f"{program}_{part}_ms_per_{per}", whole)
                 for part in ("dense", "attention", "kv_write", "other")]
        assert sum(parts) == pytest.approx(run_ms, rel=1e-9)
    # both readers took what they needed from the one parse kept on obs
    monkeypatch.setattr(program_spans, "_read", None)
    monkeypatch.setattr(program_spans, "op_scopes", None)
    assert _read("decode_launch_lag_ms_per_step", whole) > 0
    assert _read("prefill_kv_write_ms_per_call", whole) > 0


def test_matching_is_by_order_and_the_old_readers_see_no_apex_span(whole):
    tr = program_spans.trace(whole)
    got = program_spans.pairs(tr, "apex.decode_step", "decode_fn")
    assert [(round(s[1] * 1e3, 3), round(r[0] * 1e3, 3)) for s, r in got] \
        == [(0.1, 1.5), (24.6, 25.4), (105.5, 107.0)]
    assert [s[3]["active"] for s, _ in got] == [4, 3, 4]
    assert all(s[0].startswith("apex.") for s in tr["spans"])
    # device_trace keeps bench.* only: the eight metrics the benchmark had
    # read what they read before
    assert [h[0] for h in device_trace._trace(whole)["host"]] \
        == ["bench.step"]


def test_the_chips_clock_is_moved_to_where_no_run_precedes_its_enqueue(
        whole, tmp_path):
    """The file has the device 0.4 ms early. The second decode run was
    enqueued the moment it started, so the shift comes out whole; with no
    enqueue events in the file there is nothing to find it from, and the
    lags read what the file's clock says: the run's start 0.4 ms sooner
    (never before the launch returned: not under 0) and its end too."""
    assert program_spans.trace(whole)["shift"] == pytest.approx(SKEW * 1e-3)
    raw = _write(tmp_path, TICKS, skewed=False)
    assert program_spans.trace(raw)["shift"] == 0.0
    assert _read("decode_launch_lag_ms_per_step", raw) == pytest.approx(
        EXPECTED["decode_launch_lag_ms_per_step"] - SKEW)
    assert _read("decode_fetch_lag_ms_per_step", raw) == pytest.approx(
        EXPECTED["decode_fetch_lag_ms_per_step"] + SKEW)
    assert _read("decode_device_lag_ms_per_step", raw) == pytest.approx(0.0)
    # what is read on one clock alone does not move
    for name in ("prefill_host_ms_per_call", "sched_self_ms_per_tick",
                 "decode_host_between_ms_per_step", "decode_slot_occupancy",
                 "decode_attention_ms_per_step"):
        assert _read(name, raw) == pytest.approx(EXPECTED[name])
    # a chip whose clock is the later one is left where it is
    assert program_spans.clock_shift(
        [("jit_f(1)", 2.0, 3.0, 7)], {7: 1.5}) == 0.0
    assert program_spans.clock_shift(
        [("jit_f(1)", 2.0, 3.0, 7), ("jit_f(1)", 4.0, 5.0, 8)],
        {7: 2.25, 8: 4.5, 9: 99.0}) == 0.5


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_no_device_plane_is_nothing_to_read(name, tmp_path):
    """A run with no accelerator (the rehearsal), and a parent whose
    program opens no ``apex.*`` range: left out, never 0."""
    assert _read(name, _write(tmp_path / "a", TICKS, device=False)) is None
    bare = [t for t in TICKS if t[0].startswith("bench.")]
    got = _read(name, _write(tmp_path / "b", bare))
    if _spec(name)["reader"] == "program_spans":
        assert got is None
    else:                       # the device's side needs no host span
        assert got == pytest.approx(EXPECTED[name], rel=1e-9)
    assert _read(name, {"trace_dir": None, "slice": None, "spans": []}) \
        is None


def test_the_programs_own_spans_reach_the_reader_and_no_chip_reads_nothing(
        tmp_path):
    """What a traced rehearsal has: the real scheduler and engine under a
    profiler session on the CPU. The reader finds every ``apex.*`` span
    the program opened, with its attributes; with no accelerator plane
    there is no slice to count them in, so each of the 17 is left out
    (``tests/benchmark`` holds the whole rehearsal's line to that)."""
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt2 import GPT2Config
    from apex_tpu.serve.engine import (Engine, EngineConfig,
                                       init_gpt2_params)
    from apex_tpu.serve.scheduler import Request, ServeScheduler

    cfg = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                     n_head=2, compute_dtype=jnp.float32)
    engine = Engine(cfg, init_gpt2_params(cfg, seed=0), EngineConfig(
        num_slots=2, max_len=32, temperature=0.0, page_size=8,
        prefix_cache=True))
    sched = ServeScheduler(engine)
    for i in range(3):
        sched.submit(Request(request_id=i, tokens=list(range(1, 6 + i)),
                             max_new_tokens=3))
    sched.step()                           # compiled before the session
    before = engine.decode_calls, engine.prefill_calls
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        sched.run()
    finally:
        jax.profiler.stop_trace()
    obs = {"trace_dir": str(tmp_path), "slice": (0.0, 1.0), "spans": []}
    tr = program_spans.trace(obs)
    named = collections.Counter(s[0] for s in tr["spans"])
    steps = engine.decode_calls - before[0]
    calls = engine.prefill_calls - before[1]
    assert steps > 2 and calls == 1
    assert named["apex.decode_step"] == named["apex.decode_step.launch"] \
        == named["apex.decode_step.fetch"] == named["apex.sched.accept"] \
        == named["apex.sched.step"] == steps
    assert named["apex.prefill"] == named["apex.prefill.plan"] \
        == named["apex.prefill.launch"] == named["apex.prefill.fetch"] \
        == named["apex.prefill.index"] == named["apex.sched.admit"] == calls
    for s in tr["spans"]:
        if s[0] == "apex.decode_step":
            assert set(s[3]) == {"active", "slots", "resident",
                                 "pages_in_use", "pages",
                                 "attended_chunks", "key_chunks"}
            assert 0 < s[3]["active"] <= s[3]["slots"] == 2
            assert 0 < s[3]["attended_chunks"] <= s[3]["key_chunks"]
        elif s[0] == "apex.prefill.launch":
            assert s[3] == {"bucket": 8, "slots": 2, "real_positions": 7,
                            "hit_tokens": 0, "new_pages": 2}
    assert tr["ops"] == [] and tr["modules"] == [] and tr["shift"] == 0.0
    assert {name: _read(name, obs) for name in EXPECTED} \
        == dict.fromkeys(EXPECTED)


def test_a_program_without_the_new_scopes_leaves_their_metrics_out(
        tmp_path, monkeypatch):
    """The parent's programs (and ones a compile cache serves from before
    the scopes existed) carry ``attention`` and no ``kv_write``: the cache
    write and the projection read as attention, their own metric is
    absent, the parts still sum to the run."""
    for ops in (DECODE_OPS, PREFILL_OPS):
        monkeypatch.setattr(sys.modules[__name__], "DECODE_OPS"
                            if ops is DECODE_OPS else "PREFILL_OPS", [
            (n, p and p.replace("/kv_write", "").replace("/attn_proj", ""),
             at, ms) for n, p, at, ms in ops])
    obs = _write(tmp_path, TICKS)
    assert _read("decode_kv_write_ms_per_step", obs) is None
    assert _read("prefill_kv_write_ms_per_call", obs) is None
    assert _read("decode_attention_ms_per_step", obs) \
        == pytest.approx(8.0 + 0.3 + 1.0)
    assert _read("decode_dense_ms_per_step", obs) == pytest.approx(7.5)
    assert _read("decode_other_ms_per_step", obs) == pytest.approx(3.2)
    # no scope on any operation at all: nothing to split by
    bare = _write(tmp_path / "bare", TICKS, scopes=False)
    assert _read("decode_dense_ms_per_step", bare) is None
    assert _read("decode_other_ms_per_step", bare) is None


@pytest.mark.parametrize("path, part", [
    ("jit(_decode_fn)/attention/kv_write/scatter", "kv_write"),
    ("jit(_decode_fn)/attention/attn_proj/dot_general", "dense"),
    ("jit(_decode_fn)/attention/bhd,bkhd->bhk/dot_general", "attention"),
    ("jit(prefill_fn)/while/body/closed_call/ln_qkv/reduce_sum", "dense"),
    ("jit(prefill_fn)/while/body/closed_call/mlp/erf", "dense"),
    ("jit(_decode_fn)/sampling/dot_general", "dense"),
    ("jit(verify_fn)/while/body/closed_call/verify/dot_general", "dense"),
    ("jit(_decode_fn)/sampling/argmax", "other"),
    ("jit(_decode_fn)/mlp", "other"),       # the last segment is the op
    ("cache.v", "other"), (None, "other"), ("", "other"),
])
def test_an_operation_goes_to_the_innermost_scope_on_its_path(path, part):
    assert device_scopes.part_of(path) == part


def test_every_new_metric_has_its_file_its_entry_and_its_reader():
    bench = _bench()
    entries = {m["name"]: m for m in bench["per_layer"]}
    files = sorted(f[:-5] for f in os.listdir(
        os.path.join(BENCH, "layer_metrics")) if f.endswith(".json"))
    # PR 24's 8, PR 28's 17, PR 30's 9 for the deepseek_v3 cell, PR 33's 1,
    # PR 35's 5 for the ouro cell, PR 37's 8 for the ling_hybrid cell
    assert files == sorted(entries) and len(files) == 48
    assert list(entries)[8:25] == [
        "sched_self_ms_per_tick", "decode_launch_lag_ms_per_step",
        "decode_device_lag_ms_per_step", "decode_fetch_lag_ms_per_step",
        "decode_host_between_ms_per_step", "prefill_host_ms_per_call",
        "decode_slot_occupancy", "prefill_useful_position_share",
        "pool_pages_in_use_share", "decode_dense_ms_per_step",
        "decode_attention_ms_per_step", "decode_kv_write_ms_per_step",
        "decode_other_ms_per_step", "prefill_dense_ms_per_call",
        "prefill_attention_ms_per_call", "prefill_kv_write_ms_per_call",
        "prefill_other_ms_per_call"]
    assert set(list(entries)[8:25]) == set(EXPECTED)
    sources = collections.Counter()
    for name in EXPECTED:
        spec, entry = _spec(name), entries[name]
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) \
            == (name, entry["layer"], entry["unit"], entry["moves"])
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        # a later cell is appended where a reader needs no GPT-2 count
        assert entry["workloads"] == ["gpt2-xl.chat-short",
                                      "gigachat3.1-702b-ep16.think-long",
                                      "ouro-2.6b.chat-turns",
                                      "ling-3.0-flash-vl-ep4.passage-chat"]
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        sources[entry["source"], spec["reader"]] += 1
    assert sources == {("program_span", "program_spans"): 6,
                       ("program_counter", "program_spans"): 3,
                       ("device_trace", "device_scopes"): 8}
