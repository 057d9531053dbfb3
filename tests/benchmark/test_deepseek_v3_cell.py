"""The ``deepseek_v3`` cell's part of the benchmark, on the CPU at a tiny
size: the new cell resolves from ``BENCHMARK.json``; a whole run through
``run.py`` from a temporary root whose one cell is a tiny ``deepseek_v3``
under the new driver (the ring of kept logits, the reference, the control);
the work counted from shapes against the numbers of ISSUE 30's table; the
new readers on hand-made spans and operations, and their silence on a
configuration or a trace that has nothing for them.
"""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import run  # noqa: E402
from drivers import serve_deepseek_v3 as driver  # noqa: E402
from readers import program_spans, work, work_deepseek_v3 as wd  # noqa: E402

CELL = "gigachat3.1-702b-ep16.think-long"
TINY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    n_shared_experts=1, n_routed_experts=2, routed_scaling_factor=2.5,
    kv_lora_rank=16, q_lora_rank=32, qk_rope_head_dim=8, v_head_dim=12,
    qk_nope_head_dim=8, n_group=8, topk_group=4, num_experts_per_tok=8,
    first_k_dense_replace=1, norm_topk_prob=True, rms_norm_eps=1e-6,
    rope_theta=100000, max_position_embeddings=512,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=64,
                      rope_type="yarn"),
    published=dict(n_routed_experts=32), deployment=dict(expert_offset=4),
    compute_dtype="bfloat16", reference="deepseek_v3",
    serve={"num_slots": 8, "max_len": 64, "page_size": 16, "num_pages": 33,
           "prefix_cache": True})
MIX = dict(driver="serve_deepseek_v3", callers=16, ramp_requests=4,
           ramp_limit_s=200, prompt_tokens=[9, 16], answer_tokens=[24, 48],
           check_requests=12, trace_seconds=0.5, kept_share=1, kept_rows=4096)
TINY_CELL = "tiny-deepseek.dummy-mix"
# CPU, bfloat16 at 64 wide, 11 windows on 9 seeds with some 300 tokens scored
# in each: the program reads a `logit_noise_share` of 0.8e-4 to 3.8e-4 (a
# router near-tie that the rounding flips moves one token's logits by a whole
# expert's output: the spread is that), the control (the reference in int8)
# 1.06e-3 to 1.50e-3
TINY_LIMIT = 6.5e-4


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "gigachat3.1-702b-ep16.json")) as f:
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
    (data / "configs" / "tiny-deepseek.json").write_text(json.dumps(TINY))
    (data / "traffic" / "dummy-mix.json").write_text(json.dumps(MIX))
    (data / "limits" / f"{TINY_CELL}.json").write_text(
        json.dumps({"logit_noise_share": {"limit": TINY_LIMIT}}))
    bench = _bench()
    bench["configs"] = [{"name": "tiny-deepseek", "source": "test",
                         "reduced": [], "why": "t",
                         "file": "benchmark/configs/tiny-deepseek.json"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny-deepseek",
                           "chips": 1, "traffic": "dummy-mix", "why": "t"}]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [TINY_CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root)


def _rehearse(root, capsys, seed, trace=0, extra=()):
    assert run.main(["--workload", TINY_CELL, "--seed", str(seed),
                     "--seconds", "2.5", "--trace", str(trace), "--rehearse",
                     *extra], root=root) == 0
    out, _ = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), out


def test_the_new_cell_resolves_with_its_files_and_entries(cfg):
    cell = run.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_deepseek_v3"
    assert cell.traffic["callers"] == 128 and cell.config is not None
    assert cell.traffic["prompt_tokens"] == [33, 64]
    assert cell.traffic["answer_tokens"] == [512, 1024]
    assert cell.limits["logit_noise_share"]["limit"] > 0
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tokens_per_s", "ttft_mean_ms", "itl_p99_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert not names & {"decode_roofline", "prefill_roofline", "step_mfu"}
    assert {"moe_mla_step_mfu", "moe_mla_decode_roofline",
            "moe_mla_prefill_roofline", "decode_experts_roofline",
            "decode_latent_attention_roofline", "decode_experts_ms_per_step",
            "decode_router_ms_per_step", "prefill_experts_ms_per_call",
            "held_expert_hit_share", "device_idle_share",
            "decode_attention_ms_per_step"} <= names
    # the configuration: every number of the catalog's row under its key,
    # but the keys listed as reduced; no width among those
    entry = next(c for c in _bench()["configs"]
                 if c["name"] == "gigachat3.1-702b-ep16")
    assert entry["file"] == "benchmark/configs/gigachat3.1-702b-ep16.json"
    assert sorted(entry["reduced"]) == sorted(cfg["published"])
    assert (cfg["hidden_size"], cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["v_head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"]) == (7168, 1536, 512, 192, 2048, 8)
    assert cfg["published"]["n_routed_experts"] == 256
    assert cfg["deployment"]["chips_per_layer"] * cfg["n_routed_experts"] \
        == 256
    geo = cfg["serve"]
    assert geo["num_pages"] == geo["num_slots"] * geo["max_len"] \
        // geo["page_size"] + 1


def test_a_parent_without_the_cell_exits_at_once():
    with pytest.raises(SystemExit, match="no cell"):
        run.resolve(ROOT, "gigachat3.1-702b-ep16.think-short")


def test_work_counts_against_the_issues_table(cfg):
    n = wd.parameters(cfg)
    assert round(n["mla"] / 1e6, 1) == 132.6
    assert n["dense"] == 3 * 7168 * 18432 and n["expert"] == 3 * 7168 * 2048
    assert round(n["expert"] / 1e6, 2) == 44.04
    assert n["router"] == 7168 * 256 + 256
    assert round(n["layer_expert"] / 1e6, 1) == 883.1
    assert round(n["layer_dense"] / 1e6, 1) == 529.0
    assert round(2 * n["table"] / 1e6, 1) == 229.8
    assert round(n["total"] / 1e9, 2) == 5.17
    assert round(2 * n["total"] / 1e9, 2) == 10.35
    assert wd.latent_bytes_per_token(cfg) == 6 * 1152
    # a decode step of 64 rows that hit 70 of the 80 held experts with 160
    # picks, 30 000 tokens resident: every weight outside the experts but
    # the embedding once, the hit experts, the resident rows
    flops, nbytes = wd.decode_step(cfg, 64, 30_000, 70, 160)
    outside = n["total"] - 80 * n["expert"] - n["table"]
    assert nbytes == 2 * (outside + 70 * n["expert"]) + 64 * 7168 * 2 \
        + (30_000 + 64) * 6912 + 64 * 16032 * 4
    assert 9.0e9 < nbytes < 9.6e9            # the issue's 9.2 GB a step
    with open(os.path.join(BENCH, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert work.least_seconds(flops, nbytes, v5e) \
        == pytest.approx(nbytes / 819e9)      # memory bound: 11.3 ms
    assert wd.experts(cfg, 70, 160) == (2 * n["expert"] * 160,
                                        70 * n["expert"] * 2)
    # an expert that no row was routed to is not read; a pick is a row
    assert wd.decode_step(cfg, 64, 30_000, 69, 160)[1] \
        == nbytes - 2 * n["expert"]
    lat = wd.latent_attention(cfg, 64, 30_000)
    assert lat[1] == 30_000 * 6912
    assert lat[0] == 2 * 6 * (64 * 64 * 512 * (128 + 192)
                              + 30_000 * 64 * (512 + 576))
    # a whole [64, 64] prefill call would be 13.5 TFLOP (the issue); what
    # counts is the real positions, and a padded prompt is no work
    whole = wd.prefill_call(cfg, 64, 4096, 0, 80, 2048)[0]
    assert 11e12 < whole < 14e12
    assert wd.prefill_call(cfg, 2, 80, 0, 40, 40)[0] < whole / 40


def test_readers_on_hand_made_spans_and_operations(cfg):
    """Two decode runs and one prefill run with their ``apex.*`` spans and
    operations under the forward's scopes; every reader's arithmetic by
    hand, then silence where something is missing."""
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    routing = [{"experts_hit": 70, "picks_here": 160, "experts_held": 80,
                "picks": 2560},
               {"experts_hit": 60, "picks_here": 150, "experts_held": 80,
                "picks": 2560}]
    spans = [
        ("apex.decode_step", 10.0, 10.040, {"active": 64, "slots": 64,
                                            "resident": 30000}),
        ("apex.decode_step.routing", 10.039, 10.0395, routing[0]),
        ("apex.decode_step", 10.05, 10.090, {"active": 64, "slots": 64,
                                             "resident": 30064}),
        ("apex.decode_step.routing", 10.089, 10.0895, routing[1]),
        ("apex.prefill", 10.1, 10.3, {"admitted": 2, "slots": 64}),
        ("apex.prefill.launch", 10.1, 10.11, {
            "bucket": 64, "slots": 64, "real_positions": 100,
            "hit_tokens": 0, "new_pages": 34}),
        ("apex.prefill.routing", 10.29, 10.291, {
            "experts_hit": 40, "picks_here": 50, "experts_held": 80,
            "picks": 4000})]
    modules = [("jit__decode_fn(1)", 10.001, 10.031, 1),
               ("jit__decode_fn(1)", 10.051, 10.081, 2),
               ("jit_prefill_fn(2)", 10.105, 10.255, 3)]
    ops = [("%a", 10.001, 10.009),
           ("%ragged-dot-none.2 = f32[512,2048]{1,0} custom-call(%x)", 10.009,
            10.011), ("%b", 10.011, 10.014),
           ("%c", 10.014, 10.031),
           ("%a", 10.051, 10.060),
           ("%ragged-dot-none.2 = f32[512,2048]{1,0} custom-call(%x)", 10.060,
            10.063), ("%b", 10.063, 10.066),
           ("%c", 10.066, 10.081), ("%a2", 10.105, 10.125),
           ("%d2", 10.125, 10.255)]
    scopes = {("1", "%a"): "jit(_decode_fn)/mlp/experts/ragged_dot",
              ("1", "%b"): "jit(_decode_fn)/mlp/router/dot_general",
              ("1", "%c"): "jit(_decode_fn)/attention/dot_general",
              ("2", "%a2"): "jit(prefill_fn)/mlp/experts/ragged_dot",
              ("2", "%d2"): "jit(prefill_fn)/ln_qkv/dot_general"}
    obs = {"config": cfg, "peaks": peaks, "slice": (10.0, 10.4),
           "trace_dir": None,
           "_trace": {"host": [], "chips": [{"ops": ops, "modules": [
               m[:3] for m in modules]}]},
           "_program_trace": {"spans": spans, "modules": modules,
                              "enqueued": {}, "shift": 0.0, "ops": ops,
                              "scopes": scopes}}

    def read(**args):
        return wd.read({"args": args}, obs)

    assert read(quantity="scope_ms", program="decode", scope="experts") \
        == pytest.approx(11.0)
    assert read(quantity="scope_ms", program="decode", scope="router") \
        == pytest.approx(3.0)
    assert read(quantity="scope_ms", program="prefill", scope="experts") \
        == pytest.approx(20.0)
    assert read(quantity="scope_ms", program="prefill",
                scope="shared_expert") is None
    steps = [dict(active=64, resident=r, experts_hit=h, picks_here=p)
             for r, h, p in ((30000, 70, 160), (30064, 60, 150))]
    least = sum(work.least_seconds(*wd.decode_step(cfg, **s), peaks)
                for s in steps)
    assert read(quantity="roofline", program="decode") \
        == pytest.approx(100 * least / 0.060)
    assert 30 < read(quantity="roofline", program="decode") < 40
    only = sum(work.least_seconds(*wd.experts(cfg, s["experts_hit"],
                                              s["picks_here"]), peaks)
               for s in steps)
    assert read(quantity="scope_roofline", program="decode",
                scopes=["experts"], work="experts") \
        == pytest.approx(100 * only / 0.022)
    lat = sum(work.least_seconds(*wd.latent_attention(
        cfg, 64, s["resident"]), peaks) for s in steps)
    assert read(quantity="scope_roofline", program="decode",
                scopes=["attention"], work="latent_attention") \
        == pytest.approx(100 * lat / 0.032)
    call = dict(admitted=2, real_positions=100, hit_tokens=0, experts_hit=40,
                picks_here=50)
    assert read(quantity="roofline", program="prefill") == pytest.approx(
        100 * work.least_seconds(*wd.prefill_call(cfg, **call), peaks) / 0.15)
    flops = sum(wd.decode_step(cfg, **s)[0] for s in steps) \
        + wd.prefill_call(cfg, **call)[0]
    assert read(quantity="mfu") == pytest.approx(
        100 * flops / (0.254 * 197e12))
    # the counter's share, through the accepted reader
    with open(os.path.join(BENCH, "layer_metrics",
                           "held_expert_hit_share.json")) as f:
        assert program_spans.read(json.load(f), obs) \
            == pytest.approx(100 * 130 / 160)
    # a program whose spans carry no routing (a parent commit, GPT-2): none
    bare = dict(obs, _program_trace=dict(
        obs["_program_trace"],
        spans=[s for s in spans if not s[0].endswith(".routing")]))
    bare.pop("_deepseek_scopes", None)
    for args in (dict(quantity="mfu"),
                 dict(quantity="roofline", program="decode"),
                 dict(quantity="scope_roofline", program="decode",
                      scopes=["experts"], work="experts")):
        assert wd.read({"args": args}, bare) is None
    # another model's configuration: none, whatever the trace holds
    with open(os.path.join(BENCH, "configs", "gpt2-xl.json")) as f:
        other = dict(obs, config=json.load(f))
    assert wd.read({"args": dict(quantity="mfu")}, other) is None
    assert wd.read({"args": dict(quantity="scope_ms", program="decode",
                                 scope="experts")}, other) is None


def test_the_ring_keeps_the_rows_it_is_given_and_knows_what_it_lost():
    import jax.numpy as jnp

    ring = driver.Ring(80, 5)
    logits = jnp.arange(40, dtype=jnp.float32).reshape(8, 5)
    first = ring.keep(logits, [1, 6])
    assert first == {1: 0, 6: 1} and ring.head == 2
    np.testing.assert_array_equal(ring.fetch([0, 1]),
                                  np.asarray(logits)[[1, 6]])
    kept = [ring.keep(logits * (i + 2), [0, 3]) for i in range(40)]
    # a block is GATHER rows and never runs over the ring's end: the
    # positions 50-79 were skipped the first time round
    assert kept[23] == {0: 48, 3: 49} and kept[24] == {0: 80, 3: 81}
    assert ring.head == 80 + 2 * 16
    # its padding has run over the oldest rows; the newest are their own
    assert not ring.holds(0) and not ring.holds(kept[15][0])
    for i in (30, 39):
        assert ring.holds(kept[i][0])
        np.testing.assert_array_equal(
            ring.fetch([kept[i][0], kept[i][3]]),
            (i + 2) * np.asarray(logits)[[0, 3]])
    # 36 slots take two blocks
    wide = driver.Ring(160, 5)
    many = wide.keep(jnp.tile(logits, (5, 1)), list(range(36)))
    assert sorted(many.values()) == list(range(36))
    np.testing.assert_array_equal(
        wide.fetch([many[35]])[0], np.asarray(logits)[35 % 8])
    assert driver.Kept({2: 7}).at[2] == 7


def test_rehearsal_of_a_whole_run_of_a_tiny_deepseek_cell(tiny_root, capsys):
    line, out = _rehearse(tiny_root, capsys, seed=2**31 + 3)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and line["attempted"] > 4
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_mean_ms",
                                    "itl_p99_ms", "setup_s"}
    assert line["checks"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert line["checks"]["served_below_own_best"]["value"] == 0
    assert line["checks"]["logit_noise_share"]["value"] < TINY_LIMIT
    assert "logits kept of" in out and "reference scored" in out


def test_traced_rehearsal_reads_spans_and_leaves_the_device_metrics_out(
        tiny_root, capsys):
    line, _ = _rehearse(tiny_root, capsys, seed=1, trace=1)
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "queue_wait_p50_ms", "sched_host_ms_per_step", "decode_step_p50_ms",
        "prefill_call_p50_ms"}


def test_the_control_in_the_programs_place_is_not_correct(tiny_root, capsys):
    for seed in (2, 3):
        honest, _ = _rehearse(tiny_root, capsys, seed=seed)
        line, out = _rehearse(tiny_root, capsys, seed=seed,
                              extra=("--control", "int8"))
        assert honest["correct"] is True and line["correct"] is False
        assert "CONTROL int8" in out
        assert line["checks"]["logit_noise_share"]["value"] > TINY_LIMIT
        assert honest["checks"]["logit_noise_share"]["value"] < TINY_LIMIT
