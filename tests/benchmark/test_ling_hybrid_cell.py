"""The ``ling_hybrid`` cell's part of the benchmark, on the CPU at a tiny
size: the new cell resolves from ``BENCHMARK.json``; a whole run through
``run.py`` from a temporary root whose one cell is a tiny hybrid model under
the new driver (the ring of kept logits, the reference, the control); the
work counted from shapes against the numbers of ISSUE 37; the new reader on
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
from readers import work, work_ling_hybrid as wl  # noqa: E402

CELL = "ling-3.0-flash-vl-ep4.passage-chat"
TINY = dict(
    vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    num_hidden_layers=7, first_k_dense_replace=1, layer_group_size=6,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    num_kv_heads_for_linear_attn=0, short_conv_kernel_size=4,
    kda_lower_bound=-5, q_lora_rank=None, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=6000000, rms_norm_eps=1e-6, max_position_embeddings=512,
    num_experts=1, num_experts_per_tok=8, n_group=8, topk_group=4,
    routed_scaling_factor=2.5, norm_topk_prob=True,
    expert_swiglu_limit_list=[0] * 7, share_expert_swiglu_limit_list=[0] * 7,
    published=dict(num_experts=32), deployment=dict(expert_offset=4),
    model_type="bailing_hybrid", compute_dtype="bfloat16",
    reference="ling_hybrid",
    serve={"num_slots": 8, "max_len": 64, "page_size": 16, "num_pages": 33,
           "prefix_cache": False})
MIX = dict(driver="serve_ling_hybrid", callers=16, ramp_requests=4,
           ramp_limit_s=200, prompt_tokens=[9, 16], answer_tokens=[24, 48],
           check_requests=12, trace_seconds=0.5, kept_share=1, kept_rows=4096)
TINY_CELL = "tiny-ling.dummy-passages"
# CPU, bfloat16 at 64 wide, rank 1 of 32 (one expert held: what dominates at
# this size is a router near-tie flipped by the rounding, tests/
# test_ling_hybrid.py), windows on 5 seeds with some 100 requests in each:
# the program reads a `logit_noise_share` of 6.4e-4 to 1.22e-3, the control
# (the reference in int8) 3.8e-3 to 7.5e-3; the limit lies at the geometric
# middle of the two ends
TINY_LIMIT = 2.1e-3


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "ling-3.0-flash-vl-ep4.json")) as f:
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
    (data / "configs" / "tiny-ling.json").write_text(json.dumps(TINY))
    (data / "traffic" / "dummy-passages.json").write_text(json.dumps(MIX))
    (data / "limits" / f"{TINY_CELL}.json").write_text(
        json.dumps({"logit_noise_share": {"limit": TINY_LIMIT}}))
    bench = _bench()
    bench["configs"] = [{"name": "tiny-ling", "source": "test",
                         "reduced": [], "why": "t",
                         "file": "benchmark/configs/tiny-ling.json"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny-ling",
                           "chips": 1, "traffic": "dummy-passages",
                           "why": "t"}]
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


NEW = {"hybrid_step_mfu", "hybrid_decode_roofline", "hybrid_prefill_roofline",
       "decode_kda_state_ms_per_step", "prefill_kda_scan_ms_per_call",
       "decode_kda_state_roofline", "prefill_kda_scan_roofline",
       "decode_small_experts_roofline"}


def test_the_new_cell_resolves_with_its_files_and_entries(cfg):
    cell = run.resolve(ROOT, CELL)
    assert cell.chips == 1 and cell.traffic["driver"] == "serve_ling_hybrid"
    assert cell.traffic["callers"] == 128
    assert cell.traffic["ramp_requests"] == 16
    assert cell.traffic["check_requests"] == 12
    assert cell.traffic["trace_seconds"] == 2.5
    assert cell.traffic["prompt_tokens"] == [257, 512]
    assert cell.traffic["answer_tokens"] == [256, 512]
    # the ring of kept logits stays under 1.3 GB
    assert cell.traffic["kept_rows"] * 39296 * 4 < 1.3e9
    assert cell.limits["logit_noise_share"]["limit"] > 0
    assert "set_from" in cell.limits["logit_noise_share"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e == {"tokens_per_s", "ttft_mean_ms", "itl_p99_ms", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    # none whose work count is another model's
    assert not names & {"decode_roofline", "prefill_roofline", "step_mfu",
                        "moe_mla_step_mfu", "moe_mla_decode_roofline",
                        "decode_experts_roofline", "looped_step_mfu",
                        "decode_latent_attention_roofline",
                        "decode_experts_ms_per_step",
                        "loop_passes_per_token"}
    assert NEW | {"device_idle_share", "held_expert_hit_share",
                  "decode_attended_chunk_share", "queue_wait_p50_ms",
                  "decode_attention_ms_per_step",
                  "prefill_kv_write_ms_per_call",
                  "prefill_useful_position_share"} <= names
    assert len(names) == 23 + 1 + len(NEW)
    bench = _bench()
    assert len(bench["workloads"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == [CELL]
            assert metric["layer"] == "model step"
            with open(os.path.join(BENCH, "layer_metrics",
                                   metric["name"] + ".json")) as f:
                assert json.load(f)["reader"] == "work_ling_hybrid"
    # the configuration: every key of the catalog's row at its value but
    # the four reduced, the deployment, and what the row has no key for
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ling-3.0-flash-vl-ep4")
    assert entry["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                "num_experts", "vocab_size"]
    assert entry["source"] == ("https://huggingface.co/inclusionAI/"
                               "Ling-3.0-flash-VL/blob/main/config.json")
    published = dict(
        hidden_size=2560, intermediate_size=6144, moe_intermediate_size=768,
        moe_shared_expert_intermediate_size=768, num_attention_heads=32,
        num_key_value_heads=32, head_dim=128, q_lora_rank=None,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, rope_theta=6000000, rms_norm_eps=1e-6,
        max_position_embeddings=131072, num_experts_per_tok=8, n_group=8,
        topk_group=4, routed_scaling_factor=2.5, norm_topk_prob=True,
        layer_group_size=6, short_conv_kernel_size=4, kda_lower_bound=-5,
        kda_safe_gate=True, no_kda_lora=True, use_kda_lora=False,
        linear_silu=True, use_qk_norm=True, group_norm_size=1,
        num_kv_heads_for_linear_attn=0, score_function="sigmoid",
        gated_attention_proj_granularity_type="head_wise",
        partial_rotary_factor=0.5, rotary_dim=64, image_patch_token=157157)
    assert {k: cfg[k] for k in published} == published
    assert cfg["published"] == dict(num_hidden_layers=42,
                                    first_k_dense_replace=2, num_experts=512,
                                    vocab_size=157184)
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["num_experts"], cfg["vocab_size"]) == (7, 1, 128, 39296)
    assert len(cfg["expert_swiglu_limit_list"]) == 42
    assert not any(cfg["expert_swiglu_limit_list"][:7]
                   + cfg["share_expert_swiglu_limit_list"][:7])
    assert cfg["deployment"]["chips_per_layer"] == 4
    assert cfg["deployment"]["expert_offset"] == 0
    assert {"layer_pattern", "kda_short_conv", "kda_qk_norm", "kda_decay",
            "kda_beta", "kda_state", "kda_output", "mla", "rotary",
            "experts", "swiglu_limit", "language_model_only",
            "initialisation"} <= set(cfg["assumed"])
    assert cfg["serve"] == dict(num_slots=64, max_len=1024, page_size=64,
                                num_pages=1025, prefix_cache=False)


def test_a_parent_without_the_cell_or_the_model_exits_at_once(monkeypatch):
    with pytest.raises(SystemExit, match="no cell"):
        run.resolve(ROOT, "ling-3.0-flash-vl-ep4.passage-long")
    # a parent that has this benchmark laid over it has the cell and no
    # ``models/ling_hybrid.py``: the driver's first statement raises
    from drivers import serve_ling_hybrid

    monkeypatch.setitem(sys.modules, "apex_tpu.models.ling_hybrid", None)
    with pytest.raises(ImportError):
        serve_ling_hybrid.build(run.resolve(ROOT, CELL), 1)


def test_work_counts_against_the_issues_numbers(cfg):
    n = wl.parameters(cfg)
    assert wl.layers(cfg) == dict(mla=1, kda=6, dense=1, expert=6)
    assert round(n["kda"] / 1e6, 1) == 52.6
    assert round(n["mla"] / 1e6, 1) == 32.0
    assert n["expert"] == 3 * 2560 * 768 == 5_898_240
    assert n["expert"] * 2 == 11_796_480            # 11.8 MB an expert
    assert round(128 * n["expert"] / 1e6, 1) == 755.0
    assert round((n["kda"] + n["dense"]) / 1e6, 1) == 99.8
    assert round((n["kda"] + n["shared"] + n["router"]
                  + 128 * n["expert"]) / 1e6, 1) == 814.8
    # 794.15 M: the issue's 794.2 is the sum of its rounded parts
    assert round((n["mla"] + n["shared"] + n["router"]
                  + 128 * n["expert"]) / 1e6, 2) == 794.15
    assert round(2 * n["table"] / 1e6, 1) == 201.2
    assert round(n["total"] / 1e9, 2) == 5.17
    assert round(2 * n["total"] / 1e9, 2) == 10.34
    assert wl.state_bytes_per_slot(cfg) == 2_097_152 + 73_728   # 2.10 + 0.07
    assert round(6 * 64 * wl.state_bytes_per_slot(cfg) / 1e9, 2) == 0.83
    assert wl.latent_bytes_per_token(cfg) == 1152
    # a decode step of 64 rows over 30 000 resident tokens that landed 128
    # picks a layer on 81 of a layer's 128 experts (63 %)
    hit, landed = 6 * 81, 6 * 128
    flops, nbytes = wl.decode_step(cfg, 64, 30_000, hit, landed, 64)
    state = wl.kda_state_step(cfg, 64)
    assert state[1] == 2 * 6 * 64 * 2_170_880
    assert round(state[1] / 1e9, 2) == 1.67
    assert wl.experts(cfg, hit, landed) == (2 * 5_898_240 * landed,
                                            11_796_480 * hit)
    assert round(11_796_480 * hit / 1e9, 1) == 5.7
    outside = wl._outside_experts(cfg, 64)[1]
    assert nbytes == outside + state[1] + 11_796_480 * hit \
        + 30_000 * 1152 + wl._head_and_rows(cfg, 64, 64)[1]
    assert 8.2e9 < nbytes < 8.8e9                   # about 8.5 GB
    with open(os.path.join(BENCH, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert work.least_seconds(flops, nbytes, v5e) \
        == pytest.approx(nbytes / 819e9)            # memory bound: 10.4 ms
    assert 10.0e-3 < nbytes / 819e9 < 10.8e-3
    # a prefill call of 8 x 512 real positions: compute bound beside a read
    # of every weight (all 128 experts of every layer are hit)
    call = wl.prefill_call(cfg, 8, 4096, 0, 6 * 128, 6 * 4096 * 2, 8)
    assert 3.0e12 < call[0] < 4.5e12
    assert 10.0e9 < call[1] < 11.5e9
    assert work.least_seconds(*call, v5e) == pytest.approx(call[0] / 197e12)
    # one more real position: its products outside the experts, one more
    # row of pairs in the MLA layer, one position of the recurrence
    one, two = (wl.prefill_call(cfg, 1, p, 0, 10, 20, 1)[0]
                for p in (300, 301))
    per_position = 6 * 32 * (4 * 64 * 128 + 6 * 128 * 128 + 3 * 64 * 128)
    assert wl.kda_scan_call(cfg, 1, 301)[0] \
        - wl.kda_scan_call(cfg, 1, 300)[0] == per_position
    assert two - one == pytest.approx(
        wl._outside_experts(cfg, 1)[0] + per_position
        + 2 * (32 * 320 * 301 + n["kv_b"]), rel=1e-9)


def _hand_made(cfg):
    """Two decode runs and one prefill run with their ``apex.*`` spans and
    operations under the forward's scopes."""
    per = 6 * (2_097_152 + 73_728)
    routing = {"experts_held": 768, "picks": 0}
    spans = [
        ("apex.decode_step", 10.0, 10.030, {"active": 64, "slots": 64,
                                            "resident": 30000}),
        ("apex.decode_step.routing", 10.029, 10.0295,
         dict(routing, picks_here=768, experts_hit=486, state_slots=64,
              state_bytes=2 * 64 * per)),
        ("apex.decode_step", 10.04, 10.070, {"active": 60, "slots": 64,
                                             "resident": 28000}),
        ("apex.decode_step.routing", 10.069, 10.0695,
         dict(routing, picks_here=700, experts_hit=470, state_slots=60,
              state_bytes=2 * 60 * per)),
        ("apex.prefill", 10.1, 10.2, {"admitted": 2, "slots": 64}),
        ("apex.prefill.launch", 10.1, 10.11, {
            "bucket": 512, "slots": 8, "real_positions": 800,
            "hit_tokens": 0, "new_pages": 32}),
        ("apex.prefill.routing", 10.19, 10.191,
         dict(routing, picks_here=9600, experts_hit=768, state_slots=2,
              state_bytes=2 * per))]
    modules = [("jit__decode_fn(1)", 10.001, 10.026, 1),
               ("jit__decode_fn(1)", 10.041, 10.066, 2),
               ("jit_prefill_fn(2)", 10.105, 10.185, 3)]
    ops = [("%a", 10.001, 10.006), ("%s", 10.006, 10.010),
           ("%ragged-dot-none.3", 10.010, 10.022), ("%d", 10.022, 10.026),
           ("%a", 10.041, 10.046), ("%s", 10.046, 10.050),
           ("%ragged-dot-none.3", 10.050, 10.062), ("%d", 10.062, 10.066),
           ("%w", 10.105, 10.145), ("%i", 10.110, 10.130),
           ("%a2", 10.145, 10.185)]
    scopes = {("1", "%a"): "jit(_decode_fn)/ln_qkv/dot_general",
              ("1", "%s"): "jit(_decode_fn)/attention/kda_state/reduce_sum",
              ("1", "%d"): "jit(_decode_fn)/attention/attn_proj/dot_general",
              ("2", "%w"): "jit(prefill_fn)/attention/kda_state/while",
              ("2", "%i"): "jit(prefill_fn)/attention/kda_state/while/"
                           "body/closed_call/dot_general",
              ("2", "%a2"): "jit(prefill_fn)/mlp/dot_general"}
    return {"config": cfg, "slice": (10.0, 10.5), "trace_dir": None,
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "_trace": {"host": [], "chips": [{"ops": ops, "modules": [
                m[:3] for m in modules]}]},
            "_program_trace": {"spans": spans, "modules": modules,
                               "enqueued": {}, "shift": 0.0, "ops": ops,
                               "scopes": scopes}}


def test_readers_on_hand_made_spans_and_operations(cfg):
    """The reader's arithmetic by hand, then silence where something is
    missing."""
    obs = _hand_made(cfg)
    peaks = obs["peaks"]

    def read(**args):
        return wl.read({"args": args}, obs)

    steps = [dict(active=64, resident=30000, experts_hit=486,
                  picks_here=768, state_slots=64),
             dict(active=60, resident=28000, experts_hit=470,
                  picks_here=700, state_slots=60)]
    call = dict(admitted=2, real_positions=800, hit_tokens=0,
                experts_hit=768, picks_here=9600, state_slots=2)
    least = sum(work.least_seconds(*wl.decode_step(cfg, **s), peaks)
                for s in steps)
    assert read(quantity="roofline", program="decode") \
        == pytest.approx(100 * least / 0.050)
    assert 35 < read(quantity="roofline", program="decode") < 45
    assert read(quantity="roofline", program="prefill") == pytest.approx(
        100 * work.least_seconds(*wl.prefill_call(cfg, **call), peaks) / 0.08)
    flops = sum(wl.decode_step(cfg, **s)[0] for s in steps) \
        + wl.prefill_call(cfg, **call)[0]
    assert read(quantity="mfu") == pytest.approx(
        100 * flops / (0.184 * 197e12))
    # own time under `kda_state`: 4 ms a step; in the call the `while`
    # (40 ms) holds its body's operation, and both are the scope's
    assert read(quantity="scope_ms", program="decode",
                scope="kda_state") == pytest.approx(4.0)
    assert read(quantity="scope_ms", program="prefill",
                scope="kda_state") == pytest.approx(40.0)
    state = sum(wl.kda_state_step(cfg, s["state_slots"])[1] for s in steps)
    assert read(quantity="scope_roofline", program="decode",
                scope="kda_state", work="kda_state_step") \
        == pytest.approx(100 * state / 819e9 / 0.008)
    scan = wl.kda_scan_call(cfg, 2, 800)
    assert read(quantity="scope_roofline", program="prefill",
                scope="kda_state", work="kda_scan_call") \
        == pytest.approx(100 * work.least_seconds(*scan, peaks) / 0.040)
    # the grouped products, whose scope the compiler drops: by their name
    hit = sum(wl.experts(cfg, s["experts_hit"], s["picks_here"])[1]
              for s in steps)
    assert read(quantity="scope_roofline", program="decode",
                scope="experts", work="experts") \
        == pytest.approx(100 * hit / 819e9 / 0.024)
    with pytest.raises(ValueError, match="cannot read"):
        read(quantity="passes_per_row", program="decode")
    every = (dict(quantity="mfu"),
             dict(quantity="roofline", program="decode"),
             dict(quantity="roofline", program="prefill"),
             dict(quantity="scope_roofline", program="decode",
                  scope="kda_state", work="kda_state_step"),
             dict(quantity="scope_roofline", program="decode",
                  scope="experts", work="experts"))
    # a program whose routing spans carry no state (deepseek_v3's; a
    # parent commit has none at all): nothing is read
    for drop in (("state_slots", "state_bytes"), None):
        bare = _hand_made(cfg)
        tr = bare["_program_trace"]
        tr["spans"] = [
            (s[0], s[1], s[2], {k: v for k, v in s[3].items()
                                if k not in drop})
            for s in tr["spans"]] if drop else [
            s for s in tr["spans"] if not s[0].endswith(".routing")]
        for args in every:
            assert wl.read({"args": args}, bare) is None
    # bytes on the span that are not the bytes counted here: nothing
    wrong = _hand_made(cfg)
    span = wrong["_program_trace"]["spans"][1]
    span[3]["state_bytes"] += 4
    assert wl.read({"args": every[1]}, wrong) is None
    # a trace with no operation under the scope: the time is left out
    unscoped = _hand_made(cfg)
    unscoped["_program_trace"]["scopes"] = {
        k: v.replace("kda_state/", "")
        for k, v in unscoped["_program_trace"]["scopes"].items()}
    assert wl.read({"args": dict(quantity="scope_ms", program="decode",
                                 scope="kda_state")}, unscoped) is None
    assert wl.read({"args": every[3]}, unscoped) is None
    # another model's configuration: none, whatever the trace holds
    for other in ("gpt2-xl", "gigachat3.1-702b-ep16", "ouro-2.6b"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert wl.read({"args": dict(quantity="mfu")},
                           dict(_hand_made(cfg), config=json.load(f))) is None


def test_rehearsal_of_a_whole_run_of_a_tiny_hybrid_cell(honest):
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
