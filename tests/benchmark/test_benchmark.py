"""The benchmark's own tests: CPU only, `tiny` only, no chip.

What a test run can hold of the yardstick: the plain reference against the
program's training forward, the traffic generator, the window and
percentile arithmetic, the reduction of a device trace, the work counted
from shapes, the peaks table, the refusals, the names in BENCHMARK.json, and
a rehearsal of one whole run through ``run.py`` from a temporary root (which
also shows that a cell is added with files and entries alone), once honest,
once with a token altered where it is produced, and once with the control,
the reference in int8, in the program's place. The first cell's schedule is
replayed with no engine (``_replay``: counts and arithmetic, no time is
measured), to hold ``ttft_mean_ms`` to what it was chosen for: it reads a
uniform slowdown as it is, a slower ramp not at all and one stalled call by
a fraction of its bound, where the nearest-rank 90th percentile it replaced
moved by nothing or by more than its bound.
"""

import collections
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import generator  # noqa: E402
import limits_tool  # noqa: E402
import run  # noqa: E402
from readers import device_trace, spans, stamps, work  # noqa: E402

TINY = dict(activation_function="gelu_new", vocab_size=4096, n_positions=256, n_embd=128, n_layer=2,
            n_head=4, compute_dtype="bfloat16", reference="gpt2",
            serve={"num_slots": 4, "max_len": 256, "page_size": 16,
                   "num_pages": 33, "prefix_cache": True})
MIX = dict(driver="serve", callers=8, ramp_requests=4, ramp_limit_s=120,
           prompt_tokens=[9, 16], answer_tokens=[24, 48], check_requests=4,
           trace_seconds=0.5)
CELL = "tiny.dummy-mix"


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A root with one cell that the repo does not have: new files and new
    entries, and not one edit to a file of the benchmark."""
    root = tmp_path_factory.mktemp("root")
    data = root / "benchmark"
    for sub in ("configs", "traffic", "limits"):
        (data / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    data / "layer_metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), data / "peaks.json")
    (data / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (data / "traffic" / "dummy-mix.json").write_text(json.dumps(MIX))
    (data / "limits" / f"{CELL}.json").write_text(
        json.dumps({"logit_noise_share": {"limit": TINY_LIMIT}}))
    bench = _bench()
    bench["configs"] = [{"name": "tiny", "source": "test", "reduced": [],
                         "file": "benchmark/configs/tiny.json", "why": "t"}]
    bench["workloads"] = [{"name": CELL, "config": "tiny", "chips": 1,
                           "traffic": "dummy-mix", "why": "t"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


# the tiny cell's limit: two layers, 128 wide (CPU, seeds 7, 2**31 + 3 and
# 1..4): the program reads a `logit_noise_share` of 3.3e-5 to 3.7e-5, the
# control, the reference in int8 in its place, 3.2e-4 to 3.3e-4
TINY_LIMIT = 1e-4


def _rehearse(root, capsys, seed=7, trace=0, seconds=2.5, extra=()):
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", str(trace), "--rehearse",
                     *extra], root=root) == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), out, err


def test_reference_forward_matches_the_programs_training_forward():
    import jax.numpy as jnp
    from apex_tpu.models.gpt2 import GPT2, GPT2Config
    from reference import gpt2 as reference

    cfg = dict(TINY, vocab_size=512)
    params = reference.make_params(cfg, 2**31 + 11)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 24))
    model = GPT2(GPT2Config(vocab_size=512, n_positions=256, n_embd=128,
                            n_layer=2, n_head=4, compute_dtype=jnp.float32))
    theirs = np.asarray(model.apply(params, jnp.asarray(tokens)))
    rows = [(i, j) for i in range(2) for j in range(24)]
    ours = np.asarray(reference.forward_logits(cfg, params, tokens, rows))
    np.testing.assert_allclose(ours, theirs.reshape(48, 512), atol=2e-4)
    # causal: what follows a position does not reach it
    tokens[:, 12:] = 0
    cut = np.asarray(reference.forward_logits(cfg, params, tokens, rows))
    np.testing.assert_allclose(cut.reshape(2, 24, 512)[:, :12],
                               ours.reshape(2, 24, 512)[:, :12], atol=1e-5)


def test_generator_repeats_for_a_seed_and_keeps_to_its_bucket():
    mix = dict(MIX, prompt_tokens=[33, 64], answer_tokens=[64, 128])
    seed = 2**31 + 5

    def take(s, n=40):
        gen = generator.requests(mix, s, 50257)
        return [next(gen) for _ in range(n)]

    a, b, other = take(seed), take(seed), take(seed + 1)
    assert a == b and a != other
    for prompt, answer in a:
        assert 33 <= len(prompt) <= 64 and 64 <= answer <= 128
        assert all(0 <= t < 50257 for t in prompt)
    # every seed is dealt the same sizes; the prompts' order is the seed's,
    # the answers' order is the same for all
    assert [r[1] for r in a] == [r[1] for r in other]
    assert [len(r[0]) for r in a] != [len(r[0]) for r in other]
    for column in (lambda r: len(r[0]), lambda r: r[1]):
        assert sorted(map(column, a[:16])) == sorted(map(column, other[:16]))
        assert sorted(map(column, a[:16])) == sorted(map(column, a[16:32]))
    assert a[0][0][:8] != a[1][0][:8]            # nothing shared


def _request(tokens, submit=0.0, admit=None, failed=False, done=None):
    return {"submit_t": submit, "admit_t": admit, "token_t": tokens,
            "first_token_t": tokens[0] if tokens else None, "failed": failed,
            "done_t": done}


def test_window_and_percentile_arithmetic_on_hand_made_stamps():
    assert stamps.percentile([5, 1, 3, 2, 4], 0.5) == 3
    assert stamps.percentile(range(1, 101), 0.99) == 99
    assert stamps.percentile([7], 0.9) == 7
    window = (10.0, 20.0)
    steady = [_request([9.5 + 0.1 * i for i in range(200)], submit=9.0)]
    assert stamps.tokens_per_s(steady, window) == pytest.approx(10.0)
    gaps = stamps.gaps_ms(steady, window)
    assert len(gaps) == 100 and max(gaps) == pytest.approx(100.0)
    # a 2 s stall in the window: fewer tokens, and the tail sees it
    stalled = [_request([t if t < 15 else t + 2.0
                         for t in steady[0]["token_t"]], submit=9.0)]
    assert stamps.tokens_per_s(stalled, window) == pytest.approx(8.0)
    assert stamps.percentile(stamps.gaps_ms(stalled, window), 0.99) \
        == pytest.approx(2100.0)
    # first tokens outside the window do not count; a failure is the worst;
    # a wait that began before the window opened (in the ramp) does not
    # count either, be it served or failed inside the window
    reqs = [_request([11.0], submit=10.5), _request([25.0], submit=12.0),
            _request([], submit=12.0, failed=True, done=13.0),
            _request([10.5], submit=4.0, admit=9.9),
            _request([], submit=9.0, failed=True, done=11.0)]
    assert sorted(stamps.ttft_ms(reqs, window)) == pytest.approx(
        [500.0, 10000.0])
    assert sorted(stamps.ttft_ms(reqs, window, ramp_too=True)) \
        == pytest.approx([500.0, 6500.0, 10000.0, 10000.0])
    shape = stamps.ttft_shape(reqs, window)
    assert shape == pytest.approx({
        "count": 2, "mean_ms": 5250.0, "median_ms": 500.0,
        "p90_ms": 10000.0, "longest_ms": 10000.0, "former_count": 4,
        "former_p90_ms": 10000.0})
    assert stamps.end_to_end(steady + reqs, window)["ttft_mean_ms"] \
        == pytest.approx(5250.0)
    obs = {"window": window, "requests": [
        _request([12.0], submit=10.0, admit=11.0),
        _request([13.0], submit=10.0, admit=12.5),
        _request([12.0], submit=4.0, admit=11.0),
        _request([12.0], submit=9.9, admit=11.0),
        _request([30.0], submit=10.0, admit=29.0)]}
    spec = {"args": {"quantity": "queue_wait_p50"}}
    assert stamps.read(spec, obs) == pytest.approx(1000.0)
    assert stamps.read(spec, dict(obs, requests=[])) is None
    assert stamps.read(spec, dict(obs, requests=obs["requests"][2:])) is None


def test_with_no_request_of_the_windows_own_the_ttft_reader_raises():
    window = (10.0, 20.0)
    reqs = [_request([10.5], submit=4.0), _request([25.0], submit=12.0),
            _request([12.5], submit=9.0)]
    with pytest.raises(ValueError, match="of 3 requests, 1 were submitted "
                                         "inside it and 2 got their first"):
        stamps.ttft_shape(reqs, window)
    with pytest.raises(ValueError, match="no request was submitted and"):
        stamps.end_to_end(reqs, window)


# the first cell's schedule, as PERF.md, section 6 (PR 27), replays it: the
# chip's median prefill call, decode step and scheduler tick (ledger, PR 26),
# and a moment for the driver's own loop between a tick and the next look at
# the clock: a reply's follow-up is submitted before that look, so the one
# that follows the ramp's last reply is submitted before the window opens
CALL_S, STEP_S, TICK_S, LOOP_S = 0.7526, 0.0230, 0.00011, 0.00002
XL_MIX = dict(callers=8, ramp_requests=4, prompt_tokens=[33, 64],
              answer_tokens=[64, 128])


def _replay(slow=0.0, ramp_extra_s=0.0, stall_at_s=None, stall_s=0.080,
            seconds=40.0, mix=XL_MIX, slots=4):
    """``drivers/serve.py:drive`` over ``ServeScheduler.step()`` with a
    clock that is counted, not read: the callers submit at once, a tick
    admits into every free slot with one prefill call (``CALL_S``, first
    tokens at its end), then one decode step gives every running request a
    token (``STEP_S``); a reply is followed by its caller's next request;
    the window opens at the next look at the clock once ``ramp_requests``
    are done. The answers' order is
    the generator's own, so this is every seed's schedule. ``slow``: every
    time longer by that share. ``ramp_extra_s``: the first call, which on
    the chip is the programs' first execution, takes that much longer.
    ``stall_at_s``: the first engine call to begin that many seconds into
    the window stands still for ``stall_s``. Returns the requests as
    ``readers/stamps.py`` takes them, and the window."""
    call_s, step_s, tick_s = (x * (1 + slow) for x in (CALL_S, STEP_S, TICK_S))
    stream = generator.requests(mix, 1, 50257)
    sent, queue, running = [], collections.deque(), [None] * slots
    t, opened, completed, stall_due = 0.0, None, 0, stall_at_s is not None
    extra = ramp_extra_s

    def submit():
        sent.append(dict(_request([], submit=t), answer=next(stream)[1]))
        queue.append(sent[-1])

    def stalled():
        nonlocal stall_due
        if stall_due and opened is not None and t >= opened + stall_at_s:
            stall_due = False
            return stall_s
        return 0.0

    for _ in range(mix["callers"]):
        submit()
    while True:
        t += LOOP_S * (1 + slow)
        if opened is None and completed >= mix["ramp_requests"]:
            opened = t
        if opened is not None and t >= opened + seconds:
            return sent, (opened, opened + seconds)
        batch = []
        for slot, held in enumerate(running):
            if held is None and queue:
                running[slot] = queue.popleft()
                running[slot]["admit_t"] = t
                batch.append(running[slot])
        if batch:
            t += call_s + extra + stalled()
            extra = 0.0
            for r in batch:
                r["first_token_t"] = t
                r["token_t"].append(t)
        t += step_s + stalled()
        done = 0
        for slot, r in enumerate(running):
            r["token_t"].append(t)
            if len(r["token_t"]) >= r["answer"]:
                r["done_t"], running[slot] = t, None
                done += 1
        t += tick_s
        for _ in range(done):
            completed += 1
            submit()


def _moved(shape, base, key):
    return shape[key] / base[key] - 1.0


@pytest.fixture(scope="module")
def undisturbed():
    return stamps.ttft_shape(*_replay())


def test_the_replayed_schedule_is_the_one_the_chip_ran(undisturbed):
    """What the ledger read (PR 24, PR 26): the window opens 5.56 s after
    the first submit, 2 950 tokens and 30 first tokens fall in 40 s, the
    former ``ttft_p90_ms`` 6 512-6 520 ms, ``itl_p99_ms`` 777.2 ms."""
    requests, window = _replay()
    assert window[0] == pytest.approx(5.5547, abs=1e-4)
    assert stamps.tokens_per_s(requests, window) == pytest.approx(73.75)
    assert undisturbed["former_count"] == 30 and undisturbed["count"] == 25
    assert 6510 < undisturbed["former_p90_ms"] < 6522
    # my chip run, PR 27, the runs with no call over 760 ms: 6 001.6-6 025.4
    assert undisturbed["mean_ms"] == pytest.approx(6009.41, abs=0.01)
    assert 775 < stamps.end_to_end(requests, window)["itl_p99_ms"] < 778
    # the comb: the waits differ by whole decode steps, and the former
    # reading's neighbours lie 23 ms below it and 70 and 93 ms above
    top = sorted(stamps.ttft_ms(requests, window, ramp_too=True))[-5:]
    assert [round(b - a) for a, b in zip(top, top[1:])] == [23, 69, 23, 0]
    # five of the 30 waits began before the window opened: the eighth
    # caller's is the whole ramp, and the last was submitted on the ramp's
    # last reply, a moment before the look at the clock that opened it
    ramp = [r for r in requests if r["submit_t"] < window[0]
            and r["first_token_t"] >= window[0]]
    assert len(ramp) == 5 and ramp[0]["submit_t"] == 0.0
    assert ramp[0]["admit_t"] == pytest.approx(window[0])
    assert ramp[-1]["submit_t"] == pytest.approx(window[0] - LOOP_S)


def test_the_windows_recorded_on_the_chip_read_as_perf_md_says(capsys):
    """``benchmark/recorded/pr27``: twelve windows of the first cell on the
    chip (PERF.md, section 6, PR 27), each within 0.7 % of the replay."""
    over = limits_tool.replay(os.path.join(BENCH, "recorded", "pr27"))
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert over["windows"] == 12 and len(lines) == 13
    assert all(x["count"] == 25 and x["former_count"] == 30
               for x in lines[:12])
    assert 5996 < over["mean_ms"]["least"] and over["mean_ms"]["most"] < 6051
    assert over["mean_ms"]["spread"] == pytest.approx(0.00249, abs=1e-5)
    assert over["former_p90_ms"]["spread"] == pytest.approx(0.00298, abs=1e-5)


@pytest.mark.parametrize("slow", [0.005, 0.01, 0.02])
def test_ttft_mean_reads_a_uniform_slowdown_as_it_is(undisturbed, slow):
    shape = stamps.ttft_shape(*_replay(slow=slow))
    assert shape["count"] == undisturbed["count"]
    assert _moved(shape, undisturbed, "mean_ms") == pytest.approx(
        slow, abs=0.001)


@pytest.mark.parametrize("ramp_extra_s", [0.1, 0.2, 0.4])
def test_ttft_mean_does_not_move_with_the_ramp(undisturbed, ramp_extra_s):
    requests, window = _replay(ramp_extra_s=ramp_extra_s)
    assert window[0] == pytest.approx(5.5547 + ramp_extra_s, abs=1e-4)
    shape = stamps.ttft_shape(requests, window)
    assert shape["count"] == undisturbed["count"]
    assert shape["mean_ms"] == pytest.approx(undisturbed["mean_ms"],
                                             rel=1e-9)
    # the former statistic held the eighth caller's wait, the whole ramp:
    # past 0.21 s it crossed the rank read and the reading stepped up
    assert _moved(shape, undisturbed, "former_p90_ms") == pytest.approx(
        0.0107 if ramp_extra_s > 0.21 else 0.0, abs=0.0002)


STALL_SECONDS = [2.5, 7.5, 12.5, 17.5, 22.5, 27.5, 32.5, 37.5]


@pytest.mark.parametrize("at_s", STALL_SECONDS)
def test_one_stalled_call_moves_ttft_mean_by_a_fraction_of_its_bound(
        undisturbed, at_s):
    shape = stamps.ttft_shape(*_replay(stall_at_s=at_s))
    assert shape["count"] == undisturbed["count"]
    assert 0.0 <= _moved(shape, undisturbed, "mean_ms") < 0.0035
    # the former statistic, one order statistic on a comb: not at all, or
    # by a step about as wide as its bound of 1 %
    former = _moved(shape, undisturbed, "former_p90_ms")
    assert former == pytest.approx(0.0, abs=1e-9) or former > 0.008


def test_one_stalled_call_moved_the_former_ttft_p90_by_more_than_its_bound(
        undisturbed):
    """Why ``ttft_p90_ms`` went: one 80 ms stall, 0.2 % of the window."""
    moves = [_moved(stamps.ttft_shape(*_replay(stall_at_s=at_s)),
                    undisturbed, "former_p90_ms") for at_s in STALL_SECONDS]
    assert max(moves) > 0.01 and min(moves) == pytest.approx(0.0, abs=1e-9)


def test_replaying_dumped_windows_prints_both_statistics(tmp_path, capsys):
    """``limits_tool.py --replay`` over dumps as ``--dump`` writes them:
    stamps counted from the window's opening."""
    for i, at_s in enumerate([None, 7.5, 27.5]):
        dump = limits_tool.dumped(*_replay(stall_at_s=at_s))
        assert dump["seconds"] == pytest.approx(40.0)
        (tmp_path / f"cell.{i}.json").write_text(json.dumps(dump))
    assert limits_tool.main(["--replay", str(tmp_path)]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["window"] for x in lines[:3]] == ["cell.0", "cell.1", "cell.2"]
    assert all(x["count"] == 25 and x["former_count"] == 30
               for x in lines[:3])
    assert lines[0]["mean_ms"] == pytest.approx(6009.41, abs=0.01)
    over = lines[3]
    assert over["windows"] == 3 and over["directory"] == str(tmp_path)
    assert over["mean_ms"]["spread"] == pytest.approx(
        (lines[1]["mean_ms"] - lines[0]["mean_ms"]) / lines[1]["mean_ms"])
    assert over["mean_ms"]["spread"] < 0.0025 \
        < 0.01 < over["former_p90_ms"]["spread"]
    (tmp_path / "one").mkdir()
    shutil.copy(tmp_path / "cell.0.json", tmp_path / "one")
    with pytest.raises(SystemExit, match="a spread needs two"):
        limits_tool.main(["--replay", str(tmp_path / "one")])


def test_span_self_time_and_median():
    obs = {"window": (0.0, 10.0), "spans": [
        ("step", 0.0, 1.0, {}), ("prefill", 0.1, 0.6, {}),
        ("decode_step", 0.6, 0.9, {}), ("step", 1.0, 1.5, {}),
        ("decode_step", 1.1, 1.4, {}), ("step", 11.0, 19.0, {})]}
    host = spans.read({"args": {"self_of": "step",
                                "minus": ["prefill", "decode_step"]}}, obs)
    assert host == pytest.approx((1.5 - 1.1) * 1e3 / 2)
    assert spans.read({"args": {"p50_of": "decode_step"}}, obs) \
        == pytest.approx(300.0)
    assert spans.read({"args": {"p50_of": "verify"}}, obs) is None


def test_trace_reduction_on_hand_made_events():
    assert device_trace.union_seconds([(0, 2), (1, 3), (5, 6)]) == 4
    assert device_trace.gaps([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(3, 5)]
    nested = [("while", 0.0, 10.0), ("fusion.1", 1.0, 4.0),
              ("fusion.1", 5.0, 7.0), ("copy", 12.0, 13.0)]
    assert device_trace.self_seconds(nested) == pytest.approx(
        {"while": 5.0, "fusion.1": 5.0, "copy": 1.0})
    cfg = dict(TINY)
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ops = [("fusion", 100.0, 100.4), ("fusion", 100.5, 100.9),
           ("copy", 101.0, 101.2)]
    obs = {"slice": (50.0, 52.0), "config": cfg, "peaks": peaks,
           "trace_dir": None, "spans": [
               ("decode_step", 50.1, 50.5, {"active": 4, "resident": 100}),
               ("decode_step", 50.6, 50.9, {"active": 4, "resident": 104}),
               ("prefill", 51.0, 51.3, {"prompts": [12], "hits": [0]}),
               ("decode_step", 49.0, 50.05, {"active": 1, "resident": 1})],
           "_trace": {"host": [("bench.step", 100.0, 100.95),
                               ("bench.decode_step", 100.35, 100.6)],
                      "chips": [{"ops": ops, "modules": [
                          ("jit__decode_fn(1)", 100.0, 100.4),
                          ("jit__decode_fn(1)", 100.5, 100.9),
                          ("jit_prefill_fn(2)", 101.0, 101.2)]}]}}
    assert device_trace.busy(obs) == pytest.approx((1.0, 1.2))
    read = device_trace.read
    assert read({"args": {"quantity": "idle_share"}}, obs) \
        == pytest.approx(100 * 0.2 / 1.2)
    least = sum(work.least_seconds(*work.decode_step(cfg, 4, r), peaks)
                for r in (100, 104))
    decode = {"span": "decode_step", "module": "decode_fn",
              "work": "decode_step"}
    prefill = {"span": "prefill", "module": "prefill_fn",
               "work": "prefill_call"}
    assert read({"args": {"quantity": "roofline", **decode}}, obs) \
        == pytest.approx(100 * least / 0.8)
    # a trace that filled before the slice ended: the calls it holds whole
    cut = dict(obs, _trace={"host": [], "chips": [{"ops": ops[:1], "modules":
               [("jit__decode_fn(1)", 100.0, 100.4)]}]})
    assert device_trace.busy(cut) == pytest.approx((0.4, 0.4))
    assert read({"args": {"quantity": "roofline", **decode}}, cut) \
        == pytest.approx(100 * work.least_seconds(
            *work.decode_step(cfg, 4, 100), peaks) / 0.4)
    assert read({"args": {"quantity": "roofline", **prefill}}, cut) is None
    flops = sum(work.decode_step(cfg, 4, r)[0] for r in (100, 104)) \
        + work.prefill_call(cfg, [12], [0])[0]
    assert read({"args": {"quantity": "mfu",
                          "programs": [decode, prefill]}}, obs) \
        == pytest.approx(100 * flops / (1.2 * 1e12))
    parts = device_trace.breakdown(obs)
    assert parts["device_ops"][0] == ["decode_fn: fusion",
                                      pytest.approx(0.8)]
    assert device_trace.kind(
        "%copy.117 = bf16[48,17,64]{2,1,0:T(8,128)(2,1)} copy(bf16[48,17,64]"
        "{1,2,0} %cache_k.1)") == "copy bf16[48,17,64]"
    assert device_trace.kind("%f.2 = (f32[4]{0}, bf16[4,8]{1,0}) fusion(%a)"
                             ) == "f f32[4]"
    assert device_trace.by_program([("x", 0.0, 1.0)], [])[0][0] == "x"
    assert dict(map(tuple, parts["idle_gaps"])) == pytest.approx(
        {"bench.decode_step": 0.1, "outside_spans": 0.1})
    # nothing traced: nothing read, and never a 0
    bare = dict(obs, _trace={"chips": [], "host": []})
    assert device_trace.busy(bare) is None
    assert read({"args": {"quantity": "idle_share"}}, bare) is None


def test_work_counts_for_gpt2_xl_against_numbers_worked_by_hand():
    with open(os.path.join(BENCH, "configs", "gpt2-xl.json")) as f:
        cfg = json.load(f)
    n = work.parameters(cfg)
    assert n["layer"] == 12 * 1600 * 1600 + 13 * 1600 == 30_740_800
    assert n["total"] == 1_557_611_200
    assert work.kv_bytes_per_token(cfg) == 2 * 48 * 1600 * 2 == 307_200
    with open(os.path.join(BENCH, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    flops, nbytes = work.decode_step(cfg, 4, 600)
    # every weight but the positions once in bf16, 600 resident tokens of
    # keys and values, four rows of float32 logits
    assert nbytes == (1_557_611_200 - 1024 * 1600 - 3200) * 2 \
        + 600 * 307_200 + 4 * 50257 * 4
    assert flops == 4 * 2 * (48 * 30_740_800 + 50257 * 1600) \
        + 4 * 48 * 1600 * 600
    assert work.least_seconds(flops, nbytes, v5e) \
        == pytest.approx(nbytes / 819e9)          # memory bound: 4.0 ms
    assert 3.9e-3 < nbytes / 819e9 < 4.1e-3
    # a padded bucket and a prefix hit are not work
    assert work.prefill_call(cfg, [40])[0] < work.prefill_call(cfg, [64])[0]
    assert work.prefill_call(cfg, [40], [24])[0] \
        > work.prefill_call(cfg, [40])[0]


def test_unknown_device_kind_is_an_error():
    data = os.path.join(ROOT, "benchmark")
    assert run.peaks_for(data, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v9"):
        with pytest.raises(SystemExit, match="no peaks"):
            run.peaks_for(data, kind)


def _command(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *_bench()["command"][1:], *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


CELL_ARGS = ["--workload", "gpt2-xl.chat-short", "--seed", "1", "--seconds",
             "1", "--trace", "0"]


def test_without_a_tpu_the_command_fails_and_prints_no_result():
    r = _command(CELL_ARGS, ROOT)
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert "accelerator" in r.stderr or "tpu" in r.stderr.lower()


def test_with_only_the_benchmarks_files_the_command_fails(tmp_path):
    bench = _bench()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path)
    r = _command(CELL_ARGS + ["--rehearse"], str(tmp_path))
    assert r.returncode != 0 and '"correct"' not in r.stdout
    assert "apex_tpu" in r.stderr


def test_benchmark_json_keeps_to_the_permitted_names_and_files():
    bench = _bench()
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    assert all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"])
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        cell = run.resolve(ROOT, w["name"])      # every file is found
        assert cell.traffic["driver"]
        assert all(spec["limit"] > 0 for spec in cell.limits.values())
    for c in bench["configs"]:
        assert name.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(bench["paths"][0] + "/")
    for m in metrics:
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["layer"], spec["unit"], spec["moves"]) \
            == (m["layer"], m["unit"], m["moves"])
        assert os.path.exists(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    assert len(json.dumps(bench)) < 64 * 1024


def test_rehearsal_of_a_whole_run_from_a_root_with_a_new_cell(tiny_root,
                                                              capsys):
    line, out, err = _rehearse(tiny_root, capsys)
    assert list(line)[-1] == "checks" and line["rehearsal"] is True
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 4
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_mean_ms",
                                    "itl_p99_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert "compilations inside the window 0" in out.splitlines()[-2]
    shape = re.search(r"time to first token over the (\d+) requests "
                      r"submitted and first served inside the window: mean "
                      r"([\d.]+), median ([\d.]+), 90th ([\d.]+), longest "
                      r"([\d.]+) ms", out.splitlines()[-2])
    assert int(shape.group(1)) >= 1
    mean, median, p90, longest = map(float, shape.groups()[1:])
    assert mean == pytest.approx(line["metrics"]["ttft_mean_ms"]["value"],
                                 abs=0.06)
    assert median <= p90 <= longest and mean <= longest
    assert err.strip().splitlines()[-1].startswith("compared ")


def test_traced_rehearsal_reports_spans_and_leaves_out_what_it_cannot_read(
        tiny_root, capsys):
    line, _, _ = _rehearse(tiny_root, capsys, seed=2**31 + 3, trace=1)
    assert line["correct"] is True
    # no chip, so no device plane: the roofline shares are absent, not 0
    assert set(line["metrics"]) == {
        "queue_wait_p50_ms", "sched_host_ms_per_step", "decode_step_p50_ms",
        "prefill_call_p50_ms"}
    assert "busy_s" not in line["device"]
    assert not os.path.exists(os.path.join(tiny_root, ".bench_trace", CELL))


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, capsys, monkeypatch):
    from apex_tpu.serve.engine import Engine

    honest = Engine.decode_step

    def altered(self, last_tokens, active):
        tokens, logits = honest(self, last_tokens, active)
        return (tokens + 1) % TINY["vocab_size"], logits

    monkeypatch.setattr(Engine, "decode_step", altered)
    line, _, err = _rehearse(tiny_root, capsys)
    assert line["correct"] is False
    # the served tokens are no longer the best of the logits they came
    # from, and the reference, which follows them, no longer meets the
    # program's logits
    assert line["checks"]["logit_noise_share"]["value"] > 100 * TINY_LIMIT
    assert line["checks"]["served_below_own_best"]["value"] > 100
    assert "compared served_below_own_best" in err


def test_the_control_in_the_programs_place_is_not_correct(tiny_root, capsys):
    """The control of `correct`, at a size a test can hold and through the
    run's own comparison: the reference in int8, one step below the stated
    bfloat16, at every position of the same prompts and served tokens."""
    for seed in (1, 2, 3):
        honest, _, _ = _rehearse(tiny_root, capsys, seed=seed)
        line, out, _ = _rehearse(tiny_root, capsys, seed=seed,
                                 extra=("--control", "int8"))
        assert honest["correct"] is True and line["correct"] is False
        assert line["control"] == "int8" and "CONTROL int8" in out
        noise = line["checks"]["logit_noise_share"]
        assert noise["value"] > 2 * TINY_LIMIT, (seed, noise)
        assert honest["checks"]["logit_noise_share"]["value"] \
            < TINY_LIMIT / 2, (seed, honest["checks"])
