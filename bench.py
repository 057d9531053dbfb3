"""Benchmark driver — prints ONE JSON line (the headline metric) to stdout
and writes the full suite to BENCH_SUITE.json.

Headline (BASELINE.json row 1): fused Adam step latency at 1B params on one
TPU chip, via the flat-buffer Pallas kernel
(apex_tpu/ops/pallas/fused_adam_kernel.py) — the TPU equivalent of the
reference's ``multi_tensor_adam`` launch path (csrc/multi_tensor_adam.cu:24
via csrc/multi_tensor_apply.cuh:32-103). Dtype mix matches the reference's
mixed-precision setup: bf16 params + bf16 grads + fp32 exp_avg/exp_avg_sq
(fused_adam.py:212-232 groups). The op is HBM-bound: 22 bytes/element.

Timing methodology: K chained steps inside ONE jitted ``lax.fori_loop`` with
donated state, completion forced by a host fetch of one output element
(apex_tpu/utils/benchtime.py) — per-dispatch overhead is amortized over K,
the honest analog of the reference's CUDA-graph "capturable" mode (one
launch, K steps).

Suite (BASELINE.md configs 2-5 coverage):
- ``fused_adam_1b``: the headline.
- ``layer_norm``: Pallas LN fwd/bwd (csrc/layer_norm_cuda_kernel.cu path).
- ``flash_attention``: causal flash fwd/bwd (megatron softmax + MHA path).
- ``resnet50_train``: one jitted ResNet-50 train step (fwd+bwd+FusedAdam),
  imgs/sec/chip — the north-star recipe of tests/L1 (main_amp.py).

``vs_baseline``: measured-time ratio vs an A100-class estimate for the same
op (HBM-bandwidth model at 1555 GB/s · 85% achievable for memory-bound ops;
published MLPerf A100 throughput for ResNet-50). >1 ⇒ faster than the A100
reference path. NOTE: a v5e has 819 GB/s HBM vs an A100's 1555 — for
HBM-bound ops the chip-fair comparison is ``hbm_frac`` (fraction of this
chip's peak achieved) vs the reference kernels' ~85%-of-A100-peak; and
``efficiency_vs_ref`` = hbm_frac / 0.85 reports exactly that ratio.

``python bench.py`` runs the suite in this process and needs a TPU: it exits
non-zero when JAX finds none or when any sub-bench errors, and writes the
suite to ``chiprun_out/BENCH_SUITE.json`` (the committed ``BENCH_SUITE.json``
is the dated 2026-07-29 capture and is not overwritten). Off-TPU only
``run_suite`` is reachable, through ``apex-tpu-bench --kernels`` at tiny
shapes in interpret mode: a smoke of the code path, never a measurement.
"""

from __future__ import annotations

import json
import os
import sys
import time

_A100_GBPS = 1555e9 * 0.85  # apex multi_tensor kernels reach ~85% of peak


def bench_fused_adam(jax, jnp, on_tpu, chip, floor_s):
    from apex_tpu.ops.pallas.fused_adam_kernel import LANE, fused_adam_flat
    from apex_tpu.utils.benchtime import timed_steps

    n = (999_999_488 if on_tpu else 1_048_576)
    rows = n // LANE
    # state lives as (rows, 128) — the kernel's native tiling — so no
    # relayout copy sits between steps (a 1-D->2-D copy of fp32 state is
    # 7.4 GB and OOMs the 1B case)
    p = jax.random.normal(jax.random.PRNGKey(0), (rows, LANE),
                          jnp.bfloat16) * 0.02
    g = jax.random.normal(jax.random.PRNGKey(1), (rows, LANE), jnp.bfloat16)
    m = jnp.zeros((rows, LANE), jnp.float32)
    v = jnp.zeros((rows, LANE), jnp.float32)

    def step(i, st, g):
        p, m, v = st
        p, m, v = fused_adam_flat(p, g, m, v, lr=1e-3, weight_decay=0.01,
                                  step=i + 1, inv_scale=1.0)
        return (p, m, v)

    ms = timed_steps(step, (p, m, v), iters=30 if on_tpu else 2,
                     consts=(g,), floor_s=floor_s)
    bytes_moved = n * 22  # r: p2+g2+m4+v4, w: p2+m4+v4
    ref_ms = bytes_moved / _A100_GBPS * 1e3
    hbm_frac = bytes_moved / (ms / 1e3) / 1e9 / chip["hbm_gbps"]
    return {
        "metric": f"fused_adam_step_ms_at_{n // 1_000_000}M_params_"
                  f"bf16p_f32state",
        "value": round(ms, 3),
        "unit": "ms",
        "vs_baseline": round(ref_ms / ms, 3),
        "hbm_frac": round(hbm_frac, 3),
        "efficiency_vs_ref": round(hbm_frac / 0.85, 3),
    }


def bench_layer_norm(jax, jnp, on_tpu, chip, floor_s):
    rows, cols = (8192, 4096) if on_tpu else (256, 512)
    from apex_tpu.normalization.fused_layer_norm import \
        fused_layer_norm_affine
    from apex_tpu.utils.benchtime import timed_steps

    x = jax.random.normal(jax.random.PRNGKey(0), (rows, cols), jnp.bfloat16)
    w = jnp.ones((cols,), jnp.float32)
    b = jnp.zeros((cols,), jnp.float32)
    iters = 50 if on_tpu else 2

    def fwd_step(i, x, w, b):
        # LN output is normalized, so chaining is numerically stable
        return fused_layer_norm_affine(x, w, b, cols).astype(x.dtype)

    ms_fwd = timed_steps(fwd_step, x, iters=iters, consts=(w, b),
                         floor_s=floor_s, donate=False)

    gradfn = jax.grad(
        lambda x, w, b: jnp.sum(fused_layer_norm_affine(x, w, b, cols)
                                .astype(jnp.float32) ** 2))

    def bwd_step(i, x, w, b):
        return (x + 1e-6 * gradfn(x, w, b).astype(x.dtype)).astype(x.dtype)

    ms_fb = timed_steps(bwd_step, x, iters=iters, consts=(w, b),
                        floor_s=floor_s, donate=False)

    n = rows * cols
    ref_fwd = (n * 4) / _A100_GBPS * 1e3  # r2 + w2 bytes
    hbm_frac = (n * 4) / (ms_fwd / 1e3) / 1e9 / chip["hbm_gbps"]
    return {
        "metric": f"layer_norm_fwd_ms_{rows}x{cols}_bf16",
        "value": round(ms_fwd, 3), "unit": "ms",
        "fwd_bwd_ms": round(ms_fb, 3),
        "vs_baseline": round(ref_fwd / ms_fwd, 3),
        "hbm_frac": round(hbm_frac, 3),
        "efficiency_vs_ref": round(hbm_frac / 0.85, 3),
    }


def bench_flash_attention(jax, jnp, on_tpu, chip, floor_s):
    b, h, s, d = (4, 16, 2048, 64) if on_tpu else (1, 2, 256, 64)
    from apex_tpu.ops.pallas.flash_attention import flash_attention
    from apex_tpu.utils.benchtime import timed_steps

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(k_, (b, h, s, d), jnp.bfloat16) * 0.2
               for k_ in ks)
    iters = 20 if on_tpu else 2

    def fwd_step(i, q, k, v):
        return flash_attention(q, k, v, True).astype(q.dtype)

    ms_fwd = timed_steps(fwd_step, q, iters=iters, consts=(k, v),
                         floor_s=floor_s, donate=False)

    gradfn = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True).astype(jnp.float32) ** 2))

    def bwd_step(i, q, k, v):
        return (q + 1e-3 * gradfn(q, k, v).astype(q.dtype)).astype(q.dtype)

    ms_fb = timed_steps(bwd_step, q, iters=iters, consts=(k, v),
                        floor_s=floor_s, donate=False)

    # causal: 2 matmuls over s²/2 valid positions
    flops = 2 * 2 * b * h * s * s * d / 2
    tflops = flops / (ms_fwd / 1e3) / 1e12
    # A100 bf16 peak 312 TFLOPs; flash-attn fwd typically ~60% of peak
    ref_ms = flops / (312e12 * 0.6) * 1e3
    return {
        "metric": f"flash_attention_causal_fwd_ms_b{b}h{h}s{s}d{d}",
        "value": round(ms_fwd, 3), "unit": "ms",
        "fwd_bwd_ms": round(ms_fb, 3),
        "vs_baseline": round(ref_ms / ms_fwd, 3),
        "tflops": round(tflops, 1),
        "mxu_frac": round(tflops / chip["tflops"], 3),
    }


def bench_softmax_rope(jax, jnp, on_tpu, chip, floor_s):
    """Microbench for the megatron-kernel equivalents (VERDICT weak 7):
    scaled_upper_triang_masked_softmax and fused RoPE (sbhd). These are
    jnp+custom-VJP designs whose claim is that XLA fusion matches the
    reference's warp kernels — this measures that claim."""
    from apex_tpu.transformer.rope import fused_rope
    from apex_tpu.transformer.softmax import \
        scaled_upper_triang_masked_softmax
    from apex_tpu.utils.benchtime import timed_steps

    b, h, s, d = (8, 16, 1024, 64) if on_tpu else (1, 2, 128, 32)
    iters = 50 if on_tpu else 2
    x = jax.random.normal(jax.random.PRNGKey(0), (b, h, s, s),
                          jnp.bfloat16) * 0.1

    def sm_step(i, x):
        # softmax output is a stable input distribution (rows sum to 1,
        # entries ~1/sk), so the carry chains straight through with NO
        # extra elementwise pass — the old `(y*s)*0.1` renorm was its own
        # read+write over the matrix and halved the apparent hbm_frac
        return scaled_upper_triang_masked_softmax(x, 0.5).astype(x.dtype)

    ms_sm = timed_steps(sm_step, x, iters=iters, floor_s=floor_s)
    sm_bytes = x.size * 2 * 2  # read + write bf16

    t = jax.random.normal(jax.random.PRNGKey(1), (s, b, h, d), jnp.bfloat16)

    freqs = (jnp.arange(s, dtype=jnp.float32)[:, None]
             * jnp.exp(-jnp.arange(d // 2, dtype=jnp.float32) / d))
    freqs = jnp.concatenate([freqs, freqs], axis=-1)  # (s, d)

    def rope_step(i, t, freqs):
        return fused_rope(t, freqs).astype(t.dtype)

    ms_rope = timed_steps(rope_step, t, iters=iters, consts=(freqs,),
                          floor_s=floor_s)
    rope_bytes = t.size * 2 * 2
    return {
        "metric": f"softmax_causal_fwd_ms_b{b}h{h}s{s}",
        "value": round(ms_sm, 3), "unit": "ms",
        "hbm_frac": round(sm_bytes / (ms_sm / 1e3) / 1e9
                          / chip["hbm_gbps"], 3),
        "rope_sbhd_ms": round(ms_rope, 3),
        "rope_hbm_frac": round(rope_bytes / (ms_rope / 1e3) / 1e9
                               / chip["hbm_gbps"], 3),
        "vs_baseline": round(((sm_bytes / _A100_GBPS * 1e3) / ms_sm), 3),
    }


def bench_resnet50(jax, jnp, on_tpu, chip, floor_s):
    from apex_tpu.models.resnet import ResNet18ish, ResNet50
    from apex_tpu.optimizers.functional import adam_update
    from apex_tpu.utils.benchtime import timed_steps

    if on_tpu:
        model, batch, hw = ResNet50(), 128, 224
    else:
        model, batch, hw = ResNet18ish(), 8, 32
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, hw, hw, 3),
                          jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0,
                           1000 if on_tpu else 10, jnp.int32)
    variables = model.init(jax.random.PRNGKey(2), x)
    params, bstats = variables["params"], variables["batch_stats"]
    m0 = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                                params)
    v0 = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                                params)

    def train_step(i, state, x, y):
        params, m, v, bstats = state

        def loss_fn(p):
            logits, updated = model.apply(
                {"params": p, "batch_stats": bstats}, x,
                mutable=["batch_stats"])
            onehot = jax.nn.one_hot(y, logits.shape[-1])
            loss = -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))
            return loss, updated["batch_stats"]

        (loss, bs2), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params)
        params, m, v = adam_update(params, grads, m, v, step=i + 1,
                                   lr=1e-3, weight_decay=1e-4)
        return (params, m, v, bs2)

    iters = 10 if on_tpu else 2
    ms = timed_steps(train_step, (params, m0, v0, bstats), iters=iters,
                     consts=(x, y), floor_s=floor_s)
    imgs_sec = batch / (ms / 1e3)
    # MLPerf-class A100 ResNet-50 ≈ 2900 imgs/sec/GPU (amp, DALI input)
    ref = 2900.0 if on_tpu else float("nan")
    entry = {
        "metric": f"resnet50_train_imgs_per_sec_b{batch}_{hw}px"
                  if on_tpu else
                  f"resnet18ish_train_imgs_per_sec_b{batch}_{hw}px",
        "value": round(imgs_sec, 1), "unit": "imgs/sec",
        "step_ms": round(ms, 2),
    }
    if on_tpu:
        entry["vs_baseline"] = round(imgs_sec / ref, 3)
    else:
        entry["vs_baseline"] = 0.0
    return entry


def bench_bert_lamb(jax, jnp, on_tpu, chip, floor_s):
    """BASELINE config 4 (single-chip slice): BERT-large MLM-style train step
    with fused LAMB — exercises FusedRMSNorm-class fused LN, xentropy-style
    loss, and the two-phase LAMB trust-ratio update
    (csrc/multi_tensor_lamb.cu via optimizers/functional.lamb_update)."""
    from apex_tpu.contrib.xentropy import softmax_cross_entropy_loss
    from apex_tpu.models.bert import Bert, BertConfig
    from apex_tpu.optimizers.functional import lamb_update
    from apex_tpu.utils.benchtime import timed_steps

    if on_tpu:
        # b32 keeps every matmul MXU-shaped (b8 left the 1024-wide GEMMs
        # M-starved at s128); metric name records the config
        cfg, batch, seq = BertConfig.large(), 32, 128
    else:
        cfg, batch, seq = BertConfig.tiny(), 2, 32
    model = Bert(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                                cfg.vocab_size, jnp.int32)
    labels = jnp.roll(tokens, 1, axis=1)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    m0 = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                                params)
    v0 = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32),
                                params)
    nparams = sum(p.size for p in jax.tree_util.tree_leaves(params))

    def train_step(i, state, tokens, labels):
        params, m, v = state

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens)
            # the BASELINE config-4 loss: contrib.xentropy (gather-based
            # fused CE, one lse residual) — not an O(N·V) onehot matmul
            return jnp.mean(softmax_cross_entropy_loss(
                logits.astype(jnp.float32), labels))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, m, v, _gnorm = lamb_update(params, grads, m, v, step=i + 1,
                                           lr=1e-3, weight_decay=0.01)
        return (params, m, v)

    iters = 10 if on_tpu else 2
    ms = timed_steps(train_step, (params, m0, v0), iters=iters,
                     consts=(tokens, labels), floor_s=floor_s)
    seqs_sec = batch / (ms / 1e3)
    # model-FLOPs baseline: train ≈ 6·params·tokens per seq; apex+LAMB BERT
    # on A100 sustains ~45% MFU of 312 bf16 TFLOPs (MLPerf-class recipe) —
    # vs_baseline is our throughput over that A100 estimate, mfu is the
    # chip-fair absolute
    step_flops = 6.0 * nparams * batch * seq
    mfu = step_flops / (ms / 1e3) / 1e12 / chip["tflops"]
    a100_seqs = (312e12 * 0.45) / (6.0 * nparams * seq)
    return {
        "metric": f"bert_{'large' if on_tpu else 'tiny'}_lamb_train_"
                  f"seqs_per_sec_b{batch}_s{seq}",
        "value": round(seqs_sec, 2), "unit": "seqs/sec",
        "step_ms": round(ms, 2), "params_m": round(nparams / 1e6, 1),
        "mfu": round(mfu, 3),
        "vs_baseline": round(seqs_sec / a100_seqs, 3),
    }


def bench_gpt2_fwd(jax, jnp, on_tpu, chip, floor_s):
    """BASELINE config 5 (single-chip slice): GPT-2 1.5B (xl) bf16 forward —
    the megatron softmax + RoPE + flash MHA stack at full model scale (the
    1.5B TRAIN step is a multi-chip job; fwd at 3 GB of bf16 params is the
    single-chip capability claim)."""
    from apex_tpu.models.gpt2 import GPT2, GPT2Config
    from apex_tpu.utils.benchtime import timed_steps

    if on_tpu:
        cfg, batch = GPT2Config.xl(), 4
    else:
        cfg, batch = GPT2Config.tiny(), 1
    cfg = type(cfg)(**{**cfg.__dict__, "n_positions": 512}) if on_tpu else cfg
    seq = min(cfg.n_positions, 512)
    model = GPT2(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (batch, seq), 0,
                                cfg.vocab_size, jnp.int32)
    params = model.init(jax.random.PRNGKey(1), tokens)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16)
        if p.dtype == jnp.float32 else p, params)
    nparams = sum(p.size for p in jax.tree_util.tree_leaves(params))

    def fwd_step(i, carry, params, tokens):
        # derive the inputs from the carry and fold the FULL logits back in:
        # an invariant body gets hoisted out of the while loop, and summing a
        # logits slice lets XLA narrow the lm-head matmul to that slice —
        # either way the "measurement" would stop measuring the forward.
        # (1e-30 scale, not *0: a zero multiply is itself simplifiable)
        toks = (tokens + carry.astype(jnp.int32) % cfg.vocab_size) \
            % cfg.vocab_size
        logits = model.apply(params, toks)
        return carry * 0.5 + jnp.sum(logits.astype(jnp.float32)) * 1e-30

    iters = 10 if on_tpu else 2
    ms = timed_steps(fwd_step, jnp.float32(0.0), iters=iters,
                     consts=(params, tokens), floor_s=floor_s,
                     donate=False)
    toks_sec = batch * seq / (ms / 1e3)
    # model-FLOPs baseline: fwd ≈ 2·params per token; a well-tuned A100
    # inference fwd sustains ~55% MFU of 312 bf16 TFLOPs
    mfu = 2.0 * nparams * toks_sec / 1e12 / chip["tflops"]
    a100_toks = (312e12 * 0.55) / (2.0 * nparams)
    return {
        "metric": f"gpt2_{'xl_1p5b' if on_tpu else 'tiny'}_fwd_"
                  f"tokens_per_sec_b{batch}_s{seq}",
        "value": round(toks_sec, 1), "unit": "tokens/sec",
        "step_ms": round(ms, 2), "params_m": round(nparams / 1e6, 1),
        "mfu": round(mfu, 3),
        "vs_baseline": round(toks_sec / a100_toks, 3),
    }


BENCHES = [("fused_adam_1b", bench_fused_adam),
           ("layer_norm", bench_layer_norm),
           ("flash_attention", bench_flash_attention),
           ("softmax_rope", bench_softmax_rope),
           ("resnet50_train", bench_resnet50),
           ("bert_lamb", bench_bert_lamb),
           ("gpt2_fwd", bench_gpt2_fwd)]

_HERE = os.path.dirname(os.path.abspath(__file__))


def atomic_write_json(path: str, obj) -> None:
    """Write-tmp-then-rename so a reader (or a crash mid-write) never
    observes a truncated file. Shared by bench and the AOT tools."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f, indent=1)
    os.replace(tmp, path)


def run_suite(jax, jnp, backend: str, out_path: str | None = None,
              only=None) -> dict:
    """Run every bench against an ALREADY-initialized backend. The suite
    dict is rewritten to ``out_path`` after each bench so a mid-run crash
    still leaves a partial artifact on disk. A failing sub-bench is
    recorded as ``{"error": ...}`` and the rest still run; ``main`` turns
    any such entry into a non-zero exit.

    ``only``: optional collection of bench names (``apex-tpu-bench
    --kernels``) restricting the run to that subset; unknown names raise
    so a typo cannot silently produce an empty baseline."""
    if only is not None:
        known = {name for name, _ in BENCHES}
        unknown = sorted(set(only) - known)
        if unknown:
            raise ValueError(f"unknown bench name(s) {unknown}; "
                             f"known: {sorted(known)}")
    from apex_tpu.utils.benchtime import measure_fetch_floor
    from apex_tpu.utils.prof import chip_peaks

    on_tpu = backend == "tpu"
    # the attached chip's published peaks; a TPU not in the table raises
    chip = chip_peaks()
    floor_s = measure_fetch_floor()

    # capture provenance — device_kind/interpret_mode/git/captured from
    # THE shared builder (apex-tpu-bench --serve stamps identically), so
    # check_regression compares consistently stamped captures; its
    # interpret_mode honors APEX_TPU_FORCE_COMPILED, which `not on_tpu`
    # would misreport
    from apex_tpu.utils.env import capture_provenance

    suite = {"backend": backend,
             "chip": chip["chip"] if on_tpu else "cpu-smoke",
             **capture_provenance(),
             "fetch_floor_ms": round(floor_s * 1e3, 1),
             "complete": False}

    def flush():
        if out_path is not None:
            atomic_write_json(out_path, suite)

    flush()
    for name, fn in BENCHES:
        if only is not None and name not in only:
            continue
        try:
            t0 = time.perf_counter()
            entry = fn(jax, jnp, on_tpu, chip, floor_s)
            entry["bench_wall_s"] = round(time.perf_counter() - t0, 1)
            suite[name] = entry
            print(f"[bench] {name}: {entry}", file=sys.stderr, flush=True)
        except Exception as e:  # a failing sub-bench must not kill the suite
            suite[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"[bench] {name} FAILED: {e}", file=sys.stderr, flush=True)
        flush()
    # a subset capture must never read as a full suite (the regression
    # gate keys off "complete")
    suite["complete"] = only is None
    if only is not None:
        suite["subset"] = sorted(only)
    flush()
    return suite


def main() -> int:
    """Run the suite on the TPU in this process; non-zero without a TPU
    or when any sub-bench errored. Prints the headline as one JSON line."""
    from apex_tpu.utils.env import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"[bench] needs a TPU; JAX found {backend!r}. "
              f"Nothing was measured.", file=sys.stderr)
        return 3
    out_dir = os.path.join(_HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    suite = run_suite(jax, jnp, backend,
                      out_path=os.path.join(out_dir, "BENCH_SUITE.json"))
    failed = [name for name, _ in BENCHES if "error" in suite[name]]
    headline = suite["fused_adam_1b"]
    print(json.dumps({
        **{k: headline[k] for k in ("metric", "value", "unit",
                                    "vs_baseline") if k in headline},
        "backend": backend, "device_kind": suite["device_kind"],
        "failed": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
