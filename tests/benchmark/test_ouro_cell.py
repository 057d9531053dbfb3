"""The ``ouro`` cell's part of the benchmark, on the CPU at a tiny size: the
new cell resolves from ``BENCHMARK.json``; a whole run through ``run.py``
from a temporary root whose one cell is a tiny looped model under the new
driver (the ring of kept logits, the reference, the control); the work
counted from shapes against the numbers of ISSUE 35; the new reader on
hand-made spans and operations, and its silence on a configuration or a
trace that has nothing for it.
"""

import contextlib
import io
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from readers import work, work_ouro as wo  # noqa: E402

CELL = "ouro-2.6b.chat-turns"
TINY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=160,
    num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None,
    max_position_embeddings=512, total_ut_steps=3, early_exit_threshold=1,
    use_sliding_window=False, model_type="ouro", compute_dtype="bfloat16",
    reference="ouro",
    serve={"num_slots": 8, "max_len": 64, "page_size": 16, "num_pages": 33,
           "prefix_cache": True})
MIX = dict(driver="serve_ouro", callers=16, ramp_requests=4,
           ramp_limit_s=200, prompt_tokens=[9, 16], answer_tokens=[24, 48],
           check_requests=12, trace_seconds=0.5, kept_share=1, kept_rows=4096)
TINY_CELL = "tiny-ouro.dummy-turns"
# CPU, bfloat16 at 64 wide through 9 layer applications, 6 windows on 6 seeds
# with some 300 tokens scored in each: the program reads a
# `logit_noise_share` of 1.3e-4 to 1.7e-4, the control (the reference in
# int8) 1.7e-3 to 3.4e-3; the limit lies at their geometric middle
TINY_LIMIT = 5e-4


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "ouro-2.6b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("root")
    data = root / "benchmark"
    for sub in ("configs", "traffic", "limits"):
        (data / sub).mkdir(parents=True)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    data / "layer_metrics")
    shutil.copy(os.path.join(BENCH, "peaks.json"), data / "peaks.json")
    (data / "configs" / "tiny-ouro.json").write_text(json.dumps(TINY))
    (data / "traffic" / "dummy-turns.json").write_text(json.dumps(MIX))
    (data / "limits" / f"{TINY_CELL}.json").write_text(
        json.dumps({"logit_noise_share": {"limit": TINY_LIMIT}}))
    bench = _bench()
    bench["configs"] = [{"name": "tiny-ouro", "source": "test",
                         "reduced": [], "why": "t",
                         "file": "benchmark/configs/tiny-ouro.json"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny-ouro",
                           "chips": 1, "traffic": "dummy-turns", "why": "t"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if CELL in metric.get("workloads", [CELL]):
            metric["workloads"] = [TINY_CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _rehearse(root, seed, trace=0, extra=()):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", TINY_CELL, "--seed", str(seed),
                         "--seconds", "2.5", "--trace", str(trace),
                         "--rehearse", *extra], root=root) == 0
    out = out.getvalue()
    return json.loads(out.strip().splitlines()[-1]), out


@pytest.fixture(scope="module")
def honest(tiny_root):
    """One whole untraced run, on a seed that takes more than 31 bits."""
    return _rehearse(tiny_root, seed=2**31 + 3)


def test_the_new_cell_resolves_with_its_files_and_entries(cfg):
    cell = run.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_ouro"
    assert cell.traffic["callers"] == 32
    assert cell.traffic["ramp_requests"] == 16
    assert cell.traffic["prompt_tokens"] == [33, 64]
    assert cell.traffic["answer_tokens"] == [96, 192]
    assert cell.traffic["kept_rows"] == 4096
    assert cell.limits["logit_noise_share"]["limit"] > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tokens_per_s", "ttft_mean_ms", "itl_p99_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert not names & {"decode_roofline", "prefill_roofline", "step_mfu",
                        "moe_mla_step_mfu", "held_expert_hit_share",
                        "decode_latent_attention_roofline"}
    new = {"looped_step_mfu", "looped_decode_roofline",
           "looped_prefill_roofline", "decode_plane_attention_roofline",
           "loop_passes_per_token"}
    assert new | {"device_idle_share", "decode_attended_chunk_share",
                  "queue_wait_p50_ms", "decode_attention_ms_per_step",
                  "prefill_kv_write_ms_per_call",
                  "prefill_useful_position_share"} <= names
    assert len(names) == 23 + 5
    bench = _bench()
    for metric in bench["per_layer"]:
        if metric["name"] in new:
            assert metric["workloads"] == [CELL]
            assert metric["moves"] == "tokens_per_s"
            with open(os.path.join(BENCH, "layer_metrics",
                                   metric["name"] + ".json")) as f:
                assert json.load(f)["reader"] == "work_ouro"
    # the configuration: every key of the catalog's row at its value,
    # nothing reduced, and what config.json has no key for under `assumed`
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and entry["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, max_position_embeddings=65536,
        max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_hidden_layers=48, num_key_value_heads=16, rms_norm_eps=1e-6,
        rope_scaling=None, rope_theta=1000000, sliding_window=None,
        tie_word_embeddings=False, total_ut_steps=4, early_exit_threshold=1,
        use_sliding_window=False, vocab_size=49152)
    assert {k: cfg[k] for k in published} == published
    assert cfg["layer_types"] == ["full_attention"] * 48
    assert {"sandwich_norm", "no_bias", "pass_norm", "exit_gate",
            "exit_rule", "planes", "initialisation"} <= set(cfg["assumed"])
    geo = cfg["serve"]
    assert geo["num_pages"] == geo["num_slots"] * geo["max_len"] \
        // geo["page_size"] + 1 == 65


def test_a_parent_without_the_cell_or_the_model_exits_at_once(monkeypatch):
    with pytest.raises(SystemExit, match="no cell"):
        run.resolve(ROOT, "ouro-2.6b.chat-long")
    # a parent that has this benchmark laid over it has the cell and no
    # ``models/ouro.py``: the driver's first statement raises
    from drivers import serve_ouro

    monkeypatch.setitem(sys.modules, "apex_tpu.models.ouro", None)
    with pytest.raises(ImportError):
        serve_ouro.build(run.resolve(ROOT, CELL), 1)


def test_work_counts_against_the_issues_table(cfg):
    n = wo.parameters(cfg)
    assert round(n["layer"] / 1e6, 2) == 51.39
    assert round(48 * n["layer"] / 1e6, 1) == 2466.6
    assert round(2 * n["table"] / 1e6, 1) == 201.3
    assert round(n["total"] / 1e9, 3) == 2.668
    assert round(2 * n["total"] / 1e9, 2) == 5.34
    assert wo.planes(cfg) == 192
    assert wo.kv_bytes_per_token(cfg) == 1_572_864
    # the weights a call streams: the 48 layers once a PASS, the head once
    assert wo.weight_bytes(cfg) == 2 * (4 * 48 * n["layer"] + n["table"])
    assert round(wo.weight_bytes(cfg) / 1e9, 2) == 19.93
    # a decode step of 16 rows over 1 900 resident tokens
    flops, nbytes = wo.decode_step(cfg, 16, 1900)
    assert nbytes == wo.weight_bytes(cfg) + 16 * 2048 * 2 \
        + (1900 + 16) * 1_572_864 + 16 * 49152 * 4
    assert flops == 2 * 192 * n["matrices"] * 16 + 2 * n["table"] * 16 \
        + 4 * 192 * 2048 * (1900 + 16)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert work.least_seconds(flops, nbytes, v5e) \
        == pytest.approx(nbytes / 819e9)      # memory bound: 28.0 ms
    assert 27.5e-3 < nbytes / 819e9 < 28.5e-3
    assert wo.plane_attention(cfg, 1916, 1900)[1] == 1900 * 1_572_864
    # 19.73 GFLOP of products a real prefill position; the head once a slot
    one, two = (wo.prefill_call(cfg, 1, p, 0)[0] for p in (40, 41))
    assert round(2 * 192 * n["matrices"] / 1e9, 2) == 19.73
    assert two - one == 2 * 192 * n["matrices"] + 4 * 192 * 2048 * 41
    assert wo.prefill_call(cfg, 2, 80, 0)[0] - wo.prefill_call(
        cfg, 1, 80, 0)[0] == pytest.approx(
            2 * n["table"] - 4 * 192 * 2048 * 40 * 40, rel=1e-9)
    # a whole [16, 64] call is 20.2 TFLOP, compute-bound at 103 ms; what
    # counts is the real positions, and one admission is weight-bound
    whole = wo.prefill_call(cfg, 16, 1024, 0)
    assert 20.0e12 < whole[0] < 20.5e12
    assert work.least_seconds(*whole, v5e) == pytest.approx(whole[0] / 197e12)
    single = wo.prefill_call(cfg, 1, 48, 0)
    assert work.least_seconds(*single, v5e) == pytest.approx(
        single[1] / 819e9)
    assert wo.prefill_call(cfg, 1, 16, 32)[1] - wo.prefill_call(
        cfg, 1, 16, 0)[1] == 32 * 1_572_864


def test_readers_on_hand_made_spans_and_operations(cfg):
    """Two decode runs and one prefill run with their ``apex.*`` spans and
    operations under the forward's scopes; the reader's arithmetic by hand,
    then silence where something is missing."""
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    loop = {"planes": 192, "early_exits": 0}
    spans = [
        ("apex.decode_step", 10.0, 10.070, {"active": 16, "slots": 16,
                                            "resident": 1900}),
        ("apex.decode_step.loop", 10.069, 10.0695,
         dict(loop, passes=64, rows=16)),
        ("apex.decode_step", 10.08, 10.150, {"active": 15, "slots": 16,
                                             "resident": 1800}),
        ("apex.decode_step.loop", 10.149, 10.1495,
         dict(loop, passes=60, rows=15)),
        ("apex.prefill", 10.2, 10.45, {"admitted": 1, "slots": 16}),
        ("apex.prefill.launch", 10.2, 10.21, {
            "bucket": 64, "slots": 16, "real_positions": 48,
            "hit_tokens": 0, "new_pages": 4}),
        ("apex.prefill.loop", 10.44, 10.441,
         dict(loop, passes=192, rows=48))]
    modules = [("jit__decode_fn(1)", 10.001, 10.061, 1),
               ("jit__decode_fn(1)", 10.081, 10.141, 2),
               ("jit_prefill_fn(2)", 10.205, 10.405, 3)]
    ops = [("%a", 10.001, 10.041), ("%b", 10.041, 10.043),
           ("%c", 10.043, 10.053), ("%d", 10.053, 10.061),
           ("%a", 10.081, 10.121), ("%b", 10.121, 10.123),
           ("%c", 10.123, 10.133), ("%d", 10.133, 10.141),
           ("%a2", 10.205, 10.405)]
    body = "jit(_decode_fn)/while/body/closed_call/while/body/closed_call/"
    scopes = {("1", "%a"): body + "mlp/dot_general",
              ("1", "%b"): body + "attention/kv_write/scatter",
              ("1", "%c"): body + "attention/while/body/dot_general",
              ("1", "%d"): body + "attention/attn_proj/dot_general",
              ("2", "%a2"): "jit(prefill_fn)/while/body/mlp/dot_general"}
    obs = {"config": cfg, "peaks": peaks, "slice": (10.0, 10.5),
           "trace_dir": None,
           "_trace": {"host": [], "chips": [{"ops": ops, "modules": [
               m[:3] for m in modules]}]},
           "_program_trace": {"spans": spans, "modules": modules,
                              "enqueued": {}, "shift": 0.0, "ops": ops,
                              "scopes": scopes}}

    def read(**args):
        return wo.read({"args": args}, obs)

    assert read(quantity="passes_per_row", program="decode") == 4.0
    steps = [dict(active=16, resident=1900), dict(active=15, resident=1800)]
    least = sum(work.least_seconds(*wo.decode_step(cfg, **s), peaks)
                for s in steps)
    assert read(quantity="roofline", program="decode") \
        == pytest.approx(100 * least / 0.120)
    assert 45 < read(quantity="roofline", program="decode") < 48
    call = dict(admitted=1, real_positions=48, hit_tokens=0)
    assert read(quantity="roofline", program="prefill") == pytest.approx(
        100 * work.least_seconds(*wo.prefill_call(cfg, **call), peaks) / 0.2)
    flops = sum(wo.decode_step(cfg, **s)[0] for s in steps) \
        + wo.prefill_call(cfg, **call)[0]
    assert read(quantity="mfu") == pytest.approx(
        100 * flops / (0.404 * 197e12))
    # attention alone: the planes' resident bytes over the time under
    # `attention` less `kv_write` and `attn_proj` (10 ms a run)
    planes = sum(work.least_seconds(*wo.plane_attention(
        cfg, s["resident"] + s["active"], s["resident"]), peaks)
        for s in steps)
    assert read(quantity="attention_roofline", program="decode") \
        == pytest.approx(100 * planes / 0.020)
    with pytest.raises(ValueError, match="cannot read"):
        read(quantity="scope_ms", program="decode")
    # a program whose spans carry no loop (a parent commit, GPT-2): none
    bare = dict(obs, _program_trace=dict(
        obs["_program_trace"],
        spans=[s for s in spans if not s[0].endswith(".loop")]))
    bare.pop("_deepseek_scopes", None)
    for args in (dict(quantity="mfu"),
                 dict(quantity="roofline", program="decode"),
                 dict(quantity="passes_per_row", program="decode"),
                 dict(quantity="attention_roofline", program="decode")):
        assert wo.read({"args": args}, bare) is None
    # another model's configuration: none, whatever the trace holds
    with open(os.path.join(BENCH, "configs", "gpt2-xl.json")) as f:
        other = dict(obs, config=json.load(f))
    assert wo.read({"args": dict(quantity="mfu")}, other) is None


def test_rehearsal_of_a_whole_run_of_a_tiny_ouro_cell(honest):
    line, out = honest
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["attempted"] > 4
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_mean_ms",
                                    "itl_p99_ms", "setup_s"}
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert line["checks"]["served_below_own_best"]["value"] == 0
    assert line["checks"]["logit_noise_share"]["value"] < TINY_LIMIT
    assert "logits kept of" in out and "reference scored" in out


def test_traced_rehearsal_reads_spans_and_leaves_the_device_metrics_out(
        tiny_root):
    line, _ = _rehearse(tiny_root, seed=1, trace=1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "queue_wait_p50_ms", "sched_host_ms_per_step", "decode_step_p50_ms",
        "prefill_call_p50_ms"}


def test_the_control_in_the_programs_place_is_not_correct(tiny_root, honest):
    line, out = _rehearse(tiny_root, seed=2**31 + 3,
                          extra=("--control", "int8"))
    assert honest[0]["correct"] is True and line["correct"] is False
    assert "CONTROL int8" in out
    assert line["checks"]["logit_noise_share"]["value"] > TINY_LIMIT
    assert honest[0]["checks"]["logit_noise_share"]["value"] < TINY_LIMIT
