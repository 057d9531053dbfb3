"""Block-scale low-precision subsystem (PR 20) — ``apex_tpu.quant``.

Layers under test:

1. **Codec core** — the jax int8/mxfp8 block-scale codecs are
   BIT-EXACT against their pure-numpy fp32 references (codes AND
   scales), and the documented round-trip error bounds hold as tested
   properties across adversarial inputs (zeros, denormal-scale blocks,
   sign mixes, large magnitudes).
2. **Quantized matmul + MXNorm** — per-block weight scales with the
   tune-registry block key; both are TOLERANCE oracles against the
   fp32 computation on the dequantized operand (float association is
   the only difference — the bound is derived, not hand-waved).
3. **The quantized engine** — ``EngineConfig(kv_quant=...)`` holds the
   serving invariants: one decode trace under admit/evict/abort/
   prefix-hit churn, slot-vs-paged bit-exactness at equal block_k
   (quantization is deterministic, so the layouts still agree
   bit-for-bit), the >= 2x KV capacity win in ``kv_cache_bytes``, the
   perplexity delta vs the fp32 engine within ``QUANT_PPL_TOL``, and
   the loud build-time refusal matrix.
4. **Certified migration** — exported quantized pages carry scale
   planes under the SAME payload digest: a flipped scale byte in a
   streamed page is refused (reason "digest") with bit-exact local
   re-prefill, and a codec mismatch between replicas refuses with
   reason "quant_codec" + a counted ``serve_quant_fallback`` event.
5. **The gate + CLIs** — ``resident_tokens_per_hbm_byte`` (higher) and
   ``quant_ppl_delta`` (lower) gate direction-aware on a REAL bench
   capture, quantized captures refuse to gate against fp32 baselines
   (``kv_quant``/``quant_block`` incomparable axes), and both CLIs
   refuse the incompatible flag combinations with clean usage errors.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.quant import (decode_int8, decode_int8_ref, decode_kv,
                            decode_mxfp8, decode_mxfp8_ref, encode_int8,
                            encode_int8_ref, encode_kv, encode_mxfp8,
                            encode_mxfp8_ref, has_float8, check_kv_codec,
                            int8_error_bound, kv_storage_dtype,
                            mx_layer_norm, mxfp8_error_bound,
                            quant_matmul, quantize_weight,
                            resolve_quant_block)
from apex_tpu.resilience.fault_injection import FaultInjector
from apex_tpu.serve.disagg import DisaggController
from apex_tpu.serve.engine import Engine, EngineConfig, init_gpt2_params
from apex_tpu.serve.fleet import EngineReplica
from apex_tpu.serve.kv_cache import init_paged_cache, paged_write_token
from apex_tpu.serve.scheduler import Request, ServeScheduler
# bound at collection time: a test that purges apex_tpu.* from
# sys.modules mid-session (see test_serve for the history)
from apex_tpu.utils.logging import subscribe_events

pytestmark = pytest.mark.serve

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The documented quality tolerance (docs/quantization.md): mean-NLL
# delta of a quantized engine vs its fp32 reference on a forced
# continuation. Measured headroom on this geometry is ~75x (int8
# ~2e-4, mxfp8 ~7e-4 nats).
QUANT_PPL_TOL = 0.05

CFG = GPT2Config(vocab_size=97, n_positions=64, n_embd=32, n_layer=2,
                 n_head=2, compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_gpt2_params(CFG, seed=0)


def _engine(params, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("max_len", 32)
    kw.setdefault("temperature", 0.0)
    return Engine(CFG, params, EngineConfig(**kw), seed=0)


def _tokens(n, seed=7, vocab=97):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, vocab, n)]


def _cases(seed=0):
    """Adversarial codec inputs: zero blocks, mixed signs, tiny and
    huge magnitudes, non-trivial leading shapes."""
    rng = np.random.RandomState(seed)
    return [
        (np.zeros((3, 16), np.float32), 4),
        (rng.randn(5, 8).astype(np.float32), 8),
        (rng.randn(2, 3, 32).astype(np.float32) * 1e4, 16),
        (rng.randn(4, 16).astype(np.float32) * 1e-6, 4),
        (np.where(rng.rand(6, 24) > 0.5, 0.0,
                  rng.randn(6, 24)).astype(np.float32), 8),
    ]


# ------------------------------------------------------- 1. codec core

def test_int8_codec_bit_exact_vs_numpy_reference():
    for x, block in _cases():
        codes, scales = encode_int8(jnp.asarray(x), block)
        rcodes, rscales = encode_int8_ref(x, block)
        np.testing.assert_array_equal(np.asarray(codes), rcodes)
        np.testing.assert_array_equal(np.asarray(scales), rscales)
        got = np.asarray(decode_int8(codes, scales, block))
        np.testing.assert_array_equal(got,
                                      decode_int8_ref(rcodes, rscales,
                                                      block))


def test_int8_round_trip_error_bound_property():
    for x, block in _cases(seed=3):
        codes, scales = encode_int8(jnp.asarray(x), block)
        rt = np.asarray(decode_int8(codes, scales, block))
        bound = int8_error_bound(np.asarray(scales), block, x.shape)
        err = np.abs(rt - x)
        assert (err <= bound).all(), \
            f"int8 bound violated: max err {err.max()} vs {bound.max()}"
    # zero blocks decode exactly (scale 1.0, codes 0)
    z, s = encode_int8(jnp.zeros((2, 8)), 4)
    assert np.asarray(s).min() == 1.0
    np.testing.assert_array_equal(
        np.asarray(decode_int8(z, s, 4)), np.zeros((2, 8), np.float32))


@pytest.mark.skipif(not has_float8(), reason="no float8_e4m3fn")
def test_mxfp8_codec_vs_numpy_reference():
    """Scales BIT-EXACT vs the numpy reference; payloads within ONE
    e4m3 grid step (XLA's compiled f32->f8 convert double-rounds
    through an intermediate precision on near-tie values — see the
    blockscale docstring; the round-trip bound below holds either
    way, and that bound is what the quality gate rides on)."""
    for x, block in _cases(seed=5):
        codes, scales = encode_mxfp8(jnp.asarray(x), block)
        rcodes, rscales = encode_mxfp8_ref(x, block)
        np.testing.assert_array_equal(np.asarray(scales), rscales)
        a = np.asarray(codes).astype(np.float32)
        b = rcodes.astype(np.float32)
        mag = np.maximum(np.abs(b), np.float32(2.0 ** -6))
        ulp = np.maximum(np.exp2(np.floor(np.log2(mag)) - 3),
                         np.float32(2.0 ** -9))
        assert (np.abs(a - b) <= ulp).all(), \
            f"mxfp8 payload drifted past one grid step: " \
            f"{np.abs(a - b).max()}"
        got = np.asarray(decode_mxfp8(codes, scales, block))
        ref = decode_mxfp8_ref(rcodes, rscales, block)
        sb = np.repeat(rscales, block, axis=-1).reshape(x.shape)
        assert (np.abs(got - ref) <= ulp * sb).all()


@pytest.mark.skipif(not has_float8(), reason="no float8_e4m3fn")
def test_mxfp8_error_bound_and_power_of_two_scales():
    for x, block in _cases(seed=9):
        codes, scales = encode_mxfp8(jnp.asarray(x), block)
        s = np.asarray(scales)
        # shared-exponent contract: every scale is an EXACT power of
        # two — frexp mantissa 0.5, not a log2-looks-integral check
        # (which f32 precision passes even for the ulp-off exp2 values
        # the ldexp fix removed)
        assert (np.frexp(s)[0] == 0.5).all()
        # no-inf contract: e4m3fn overflow would be NaN — never emitted
        payload = np.asarray(codes).astype(np.float32)
        assert np.isfinite(payload).all()
        rt = np.asarray(decode_mxfp8(codes, scales, block))
        bound = mxfp8_error_bound(x, s, block)
        err = np.abs(rt - x)
        assert (err <= bound).all(), \
            f"mxfp8 bound violated: max err {err.max()}"


def test_codec_block_validation():
    x = jnp.ones((2, 12))
    for bad in (0, -4, 5, 24):
        with pytest.raises(ValueError, match="quant block"):
            encode_int8(x, bad)


def test_kv_codec_glue_and_refusals():
    assert check_kv_codec(None) is None
    assert kv_storage_dtype(None) is None
    assert kv_storage_dtype("int8") == jnp.int8
    with pytest.raises(ValueError, match="unknown kv_quant codec"):
        check_kv_codec("int4")
    x = jnp.asarray(np.random.RandomState(0).randn(5, 2, 16),
                    jnp.float32)
    codes, scales = encode_kv("int8", x)
    # one scale per (token, head): payload shape minus head_dim
    assert codes.shape == x.shape and scales.shape == x.shape[:-1]
    rt = np.asarray(decode_kv(codes, scales))
    bound = int8_error_bound(np.asarray(scales)[..., None], 16, x.shape)
    assert (np.abs(rt - np.asarray(x)) <= bound).all()


# ------------------------------------------- 2. quant matmul + MXNorm

def test_quant_matmul_within_derived_bound():
    """Tolerance oracle: the quantization error of w is bounded
    elementwise by the codec bound, so |x @ w - quant_matmul| <=
    |x| @ bound — a derived bound, not an eyeballed rtol."""
    rng = np.random.RandomState(11)
    x = rng.randn(5, 32).astype(np.float32)
    w = rng.randn(32, 24).astype(np.float32)
    block = resolve_quant_block(32, 24)
    assert block == 32                   # largest pow2 divisor <= 128
    codes, scales = quantize_weight(jnp.asarray(w), block)
    assert codes.shape == (32, 24) and scales.shape == (1, 24)
    y = np.asarray(quant_matmul(jnp.asarray(x), codes, scales, block))
    ref = x @ w
    wb = int8_error_bound(np.asarray(scales).T, block,
                          (24, 32)).T      # elementwise |w - dq(w)| bound
    slack = np.abs(x) @ wb + 1e-4
    assert (np.abs(y - ref) <= slack).all(), \
        f"quant_matmul drifted past the derived bound: " \
        f"{np.abs(y - ref).max()} vs {slack.min()}"


def test_resolve_quant_block_matrix():
    assert resolve_quant_block(256, 64) == 128   # capped at 128
    assert resolve_quant_block(96, 7) == 32      # pow2 divisor of 96
    assert resolve_quant_block(64, 64, block=16) == 16
    with pytest.raises(ValueError, match="does not divide"):
        resolve_quant_block(64, 64, block=24)


def test_mx_layer_norm_matches_dequant_reference():
    """MXNorm's scale-reusing moments vs manual_layer_norm on the
    dequantized vector: float association is the only difference."""
    from apex_tpu.normalization.fused_layer_norm import manual_layer_norm

    rng = np.random.RandomState(13)
    x = (rng.randn(4, 64) * 3).astype(np.float32)
    block = 16
    codes, scales = encode_int8(jnp.asarray(x), block)
    w = jnp.asarray(rng.randn(64).astype(np.float32))
    b = jnp.asarray(rng.randn(64).astype(np.float32))
    got = np.asarray(mx_layer_norm(codes, scales, w, b, block))
    dq = decode_int8(codes, scales, block)
    ref = np.asarray(manual_layer_norm(dq, w, b, (64,), 1e-5))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # weight/bias-free form too
    got0 = np.asarray(mx_layer_norm(codes, scales, None, None, block))
    ref0 = np.asarray(manual_layer_norm(dq, None, None, (64,), 1e-5))
    np.testing.assert_allclose(got0, ref0, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="does not divide"):
        mx_layer_norm(codes, scales, None, None, 24)


# -------------------------------------------- 3. the quantized engine

def test_quant_cache_write_is_masked_and_bounded():
    """kv_cache surgery unit: a quantized write stores codec bytes +
    scales under the SAME mask discipline — masked-off slots' payload
    AND scale bytes stay bit-untouched. One page a slot: slot ``b``
    owns page ``b + 1`` under the null page."""
    cache = init_paged_cache(n_layer=1, num_slots=4, max_len=8,
                             page_size=8, num_pages=5, heads=2,
                             head_dim=16, kv_quant="int8")
    cache = cache.replace(
        page_table=jnp.arange(1, 5, dtype=jnp.int32)[:, None],
        # a masked-off slot's page holds codes and scales of its own
        k=cache.k.at[0, 2].set(3), k_scale=cache.k_scale.at[0, 2].set(0.5))
    assert cache.k.dtype == jnp.int8
    # 2 heads in a head axis allocated as a whole group of 8
    assert cache.k_scale.shape == (1, 5, 8, 8)
    x = np.random.RandomState(0).randn(4, 2, 16).astype(np.float32)
    pos = jnp.zeros((4,), jnp.int32)
    mask = jnp.array([True, False, True, False])
    out = jax.jit(paged_write_token,
                  static_argnums=(1, 6))(cache, 0, jnp.asarray(x),
                                         jnp.asarray(x), pos, mask,
                                         "int8")
    got = np.asarray(out.k[0, 1, 0, :2]).astype(np.float32) \
        * np.asarray(out.k_scale[0, 1, 0, :2])[..., None]
    bound = int8_error_bound(np.asarray(out.k_scale[0, 1, 0, :2])[..., None],
                             16, x[0].shape)
    assert not np.asarray(out.v[..., 2:, :]).any()
    assert not np.asarray(out.v_scale[..., 2:]).any()
    assert (np.abs(got - x[0]) <= bound).all()
    for page in (0, 2, 4):        # the null page and the masked-off slots'
        np.testing.assert_array_equal(np.asarray(out.k[0, page]),
                                      np.asarray(cache.k[0, page]))
        np.testing.assert_array_equal(np.asarray(out.k_scale[0, page]),
                                      np.asarray(cache.k_scale[0, page]))


def _mixed_requests(n=5, seed0=0, max_new=5):
    return [Request(request_id=f"r{i}",
                    tokens=_tokens(4 + 3 * (i % 4), seed=seed0 + i),
                    max_new_tokens=max_new) for i in range(n)]


def _trace_outputs(eng, reqs, injector=None):
    sched = ServeScheduler(eng, fault_injector=injector)
    for r in reqs:
        sched.submit(r)
    return {r["request_id"]: r for r in sched.run().requests}


@pytest.mark.parametrize("codec", ["int8", "mxfp8"])
def test_quant_decode_compiles_once_across_churn(params, codec):
    """THE one-compile acceptance with kv_quant armed: scales are DATA
    in the cache pytree, so admissions, completions, a scripted abort,
    backfill, and prefix-hit page churn trace decode_step exactly once
    — for BOTH codecs on the paged layout."""
    if codec == "mxfp8" and not has_float8():
        pytest.skip("no float8_e4m3fn")
    eng = _engine(params, num_slots=2, page_size=8, prefix_cache=True,
                  kv_quant=codec)
    inj = FaultInjector(seed=0).abort_request("r2", at_step=4)
    sched = ServeScheduler(eng, fault_injector=inj)
    for i, plen in enumerate((4, 6, 5, 3, 7)):
        sched.submit(Request(request_id=f"r{i}",
                             tokens=_tokens(plen, seed=i),
                             max_new_tokens=4 + i % 3))
    stats = sched.run()
    assert len(stats.requests) == 5
    assert {r["state"] for r in stats.requests} == {"completed",
                                                    "evicted"}
    assert eng.decode_traces == 1, \
        "quantized page/scale churn must not retrace decode_step"
    assert eng.prefill_traces <= 2          # pow2 buckets {4, 8}


def test_quant_paged_bit_exact_vs_quant_slot(params):
    """Encode is deterministic and per-(token, head), so the slot and
    paged layouts still agree BIT-FOR-BIT at equal block_k with
    kv_quant armed — the fp32 layout-parity guarantee survives
    quantization unchanged."""
    slot = _engine(params, block_k=8, kv_quant="int8")
    paged = _engine(params, page_size=8, kv_quant="int8")
    assert slot.block_k == paged.block_k == 8
    base = _trace_outputs(slot, _mixed_requests())
    got = _trace_outputs(paged, _mixed_requests())
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}
    assert slot.decode_traces == 1 and paged.decode_traces == 1


def test_quant_ppl_delta_within_documented_tolerance(params):
    """Quality gate: mean NLL of a forced continuation under the
    quantized engine stays within QUANT_PPL_TOL nats of the fp32
    engine (the exact reference by construction)."""
    seq = _tokens(24, seed=7)

    def mean_nll(kv_quant):
        eng = _engine(params, keep_prefill_logits=True,
                      kv_quant=kv_quant)
        _, _, logits = eng.prefill({1: seq})
        lg = np.asarray(logits)[:, 1, :].astype(np.float64)
        m = lg.max(-1, keepdims=True)
        lp = lg - m - np.log(np.exp(lg - m).sum(-1, keepdims=True))
        tgt = np.array(seq[1:])
        return float(-lp[np.arange(len(tgt)), tgt].mean())

    ref = mean_nll(None)
    codecs = ["int8"] + (["mxfp8"] if has_float8() else [])
    for codec in codecs:
        delta = abs(mean_nll(codec) - ref)
        assert delta <= QUANT_PPL_TOL, \
            f"{codec} ppl delta {delta} exceeds {QUANT_PPL_TOL}"


def test_quant_kv_capacity_at_least_2x(params):
    """THE capacity acceptance: same geometry, >= 2x fewer KV-cache
    HBM bytes (int8 payload + one fp32 scale per (token, head) vs fp32
    payload). At head_dim=16 the exact ratio is 64/(16+4) = 3.2."""
    fp32 = _engine(params, page_size=8)
    for codec in ("int8",) + (("mxfp8",) if has_float8() else ()):
        q = _engine(params, page_size=8, kv_quant=codec)
        ratio = fp32.kv_cache_bytes / q.kv_cache_bytes
        assert ratio >= 2.0, \
            f"{codec} capacity win {ratio:.2f}x below the 2x floor"
        assert ratio == pytest.approx(3.2)
        assert q.quant_block == 16          # = head_dim, by construction
    assert fp32.quant_block == 0


def test_quant_engine_refusal_matrix(params):
    with pytest.raises(ValueError, match="unknown kv_quant codec"):
        _engine(params, kv_quant="int4")
    with pytest.raises(ValueError, match="requires compute_dtype"):
        bf = GPT2Config(vocab_size=97, n_positions=64, n_embd=32,
                        n_layer=2, n_head=2,
                        compute_dtype=jnp.bfloat16)
        Engine(bf, init_gpt2_params(bf, seed=0),
               EngineConfig(num_slots=2, max_len=32, temperature=0.0,
                            kv_quant="int8"), seed=0)
    with pytest.raises(ValueError, match="incompatible with"):
        _engine(params, kv_quant="int8", spec_draft_len=2)


@pytest.mark.slow
def test_quant_tp2_bit_exact_vs_single_chip(params, tp_devices):
    """Sharding acceptance: per-(token, head) encode is rank-local (no
    cross-head reduction), so a tp=2 quantized engine's greedy stream
    is bit-identical to the single-chip quantized engine at equal
    block_k — scales shard with their pages on the head axis by
    construction."""
    base = _trace_outputs(_engine(params, num_slots=2, kv_quant="int8"),
                          _mixed_requests(n=3))
    got = _trace_outputs(
        _engine(params, num_slots=2, tp=2, kv_quant="int8"),
        _mixed_requests(n=3))
    assert {k: v["generated"] for k, v in got.items()} == \
           {k: v["generated"] for k, v in base.items()}


# ------------------------------------------- 4. certified migration

DCFG = GPT2Config(vocab_size=61, n_positions=32, n_embd=16, n_layer=1,
                  n_head=2, compute_dtype=jnp.float32)
DPAGE = 4


@pytest.fixture(scope="module")
def dparams():
    return init_gpt2_params(DCFG, seed=0)


def _dengine(dparams, **kw):
    kw.setdefault("kv_quant", "int8")
    return Engine(DCFG, dparams,
                  EngineConfig(num_slots=2, max_len=32, temperature=0.0,
                               page_size=DPAGE, num_pages=24,
                               prefix_cache=True, **kw),
                  seed=0).aot_compile([4, 8])


@pytest.fixture(scope="module")
def qengines(dparams):
    """Three int8-quantized paged engines sharing one param pytree:
    prefill + decode + oracle; tests reset()."""
    return [_dengine(dparams) for _ in range(3)]


@pytest.fixture(scope="module")
def fengines(dparams):
    """Two fp32 engines on the same params: the codec-mismatch target
    and its oracle."""
    return [_dengine(dparams, kv_quant=None) for _ in range(2)]


def _dtokens(n, seed=7):
    return _tokens(n, seed=seed, vocab=61)


def _oracle(engine, req):
    sched = ServeScheduler(engine.reset())
    sched.submit(Request(request_id=req.request_id,
                         tokens=list(req.tokens),
                         max_new_tokens=req.max_new_tokens))
    sched.run(max_steps=2_000)
    done, _ = sched.done_since(0)
    rec, = [q.record() for q in done]
    return rec["generated"]


def test_quant_export_import_round_trip(qengines):
    """Quantized pages stream with their scale planes and install into
    a same-codec pool: prefix hits on the receiver, no retrace,
    bit-exact output; a codec-mismatched import is a loud refusal at
    the structural door (the certifying caller refuses earlier)."""
    prompt = _dtokens(8, seed=3)
    a, b = qengines[0].reset(), qengines[1].reset()
    sa = ServeScheduler(a)
    sa.submit(Request(request_id="seed", tokens=list(prompt),
                      max_new_tokens=1))
    sa.run(max_steps=50)
    payloads = sa.export_prefix_pages(list(prompt))
    assert len(payloads) == 2
    for p in payloads:
        assert p["codec"] == "int8"
        assert p["k"].dtype == np.int8
        assert p["k_scale"].dtype == np.float32
        assert set(p) >= {"chain_hash", "k", "v", "k_scale", "v_scale",
                          "digest"}

    sb = ServeScheduler(b)
    first = sb.import_prefix_pages(payloads)
    assert first["installed"] == 2
    traces = b.decode_traces
    sb.submit(Request(request_id="real", tokens=list(prompt),
                      max_new_tokens=4))
    sb.run(max_steps=50)
    done, _ = sb.done_since(0)
    rec, = [q.record() for q in done]
    assert sb.prefix_hits >= 1 and b.decode_traces == traces
    assert rec["generated"] == _oracle(
        qengines[2], Request(request_id="real", tokens=list(prompt),
                             max_new_tokens=4))
    # structural door: a fp32 payload must never install into an int8
    # pool (the bytes would be misread)
    bad = [dict(p, codec=None) for p in payloads]
    with pytest.raises(ValueError, match="codec"):
        sb.import_prefix_pages(bad)


def test_quant_flipped_scale_byte_refused_bit_exact_fallback(qengines):
    """ISSUE 20 acceptance: the payload digest certifies codes ‖ scales
    TOGETHER — one flipped byte in an in-flight k_scale plane (payload
    bytes pristine) is refused exactly like a payload flip (reason
    "digest", nothing installs) and the request completes bit-exactly
    via local re-prefill on the quantized decode replica."""
    req = Request(request_id="c0", tokens=_dtokens(8, seed=11),
                  max_new_tokens=4)
    oracle = _oracle(qengines[2], req)

    handles = [
        EngineReplica("p0", qengines[0].reset(), role="prefill"),
        EngineReplica("d0", qengines[1].reset(), role="decode"),
    ]
    src = handles[0].scheduler
    orig_export = src.export_prefix_pages

    def corrupt_scale_export(tokens):
        payloads = orig_export(tokens)
        if payloads:                   # flip AFTER the digest is stamped
            ks = np.array(payloads[0]["k_scale"], copy=True)
            raw = bytearray(ks.tobytes())
            raw[0] ^= 0x01
            payloads[0]["k_scale"] = np.frombuffer(
                bytes(raw), dtype=ks.dtype).reshape(ks.shape)
        return payloads

    src.export_prefix_pages = corrupt_scale_export
    fleet = DisaggController(handles, heartbeat_ms=25,
                             suspect_misses=5_000, dead_misses=10_000)
    refusals = []
    unsub = subscribe_events(
        lambda r: refusals.append(r)
        if r.get("event") == "serve_handoff_refused" else None)
    try:
        fleet.submit(Request(request_id="c0", tokens=list(req.tokens),
                             max_new_tokens=4))
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()
        del src.export_prefix_pages

    rec, = stats.requests
    assert rec["state"] == "completed"
    assert rec["generated"] == oracle, \
        "scale-flip fallback drifted from the quantized oracle"
    assert stats.handoffs_refused == 1 and stats.pages_migrated == 0
    assert len(refusals) == 1
    assert refusals[0]["reason"] == "digest"
    assert refusals[0]["page_index"] == 0


def test_quant_codec_mismatch_refused_with_fallback_event(qengines,
                                                          fengines):
    """A quantized prefill replica handing off to an fp32 decode
    replica: bytes are pristine but the pools are incomparable — the
    chain refuses with reason "quant_codec", the counted
    ``serve_quant_fallback`` event fires once, and the request
    completes bit-exactly under the TARGET's own codec."""
    req = Request(request_id="m0", tokens=_dtokens(8, seed=17),
                  max_new_tokens=4)
    oracle = _oracle(fengines[1], req)     # fp32: the target's codec

    fleet = DisaggController(
        [EngineReplica("p0", qengines[0].reset(), role="prefill"),
         EngineReplica("d0", fengines[0].reset(), role="decode")],
        heartbeat_ms=25, suspect_misses=5_000, dead_misses=10_000)
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("event") in ("serve_handoff_refused",
                              "serve_quant_fallback") else None)
    try:
        fleet.submit(Request(request_id="m0", tokens=list(req.tokens),
                             max_new_tokens=4))
        stats = fleet.run(max_wall_s=30)
    finally:
        unsub()

    rec, = stats.requests
    assert rec["state"] == "completed"
    assert rec["generated"] == oracle
    assert stats.handoffs_refused == 1 and stats.pages_migrated == 0
    by_event = {r["event"]: r for r in seen}
    assert by_event["serve_handoff_refused"]["reason"] == "quant_codec"
    fb = by_event["serve_quant_fallback"]
    assert fb["source_codec"] == "int8" and fb["target_codec"] is None


def test_quant_pages_event_counted(qengines):
    """Satellite: ``serve_kv_quantized_pages`` is published (and
    COUNTED) when a quantized prefill allocates pages."""
    from apex_tpu.monitor.goodput import COUNTED_EVENTS
    assert "serve_kv_quantized_pages" in COUNTED_EVENTS
    assert "serve_quant_fallback" in COUNTED_EVENTS
    eng = qengines[0].reset()
    seen = []
    unsub = subscribe_events(
        lambda r: seen.append(r)
        if r.get("event") == "serve_kv_quantized_pages" else None)
    try:
        _trace_outputs(eng, [Request(request_id="q0",
                                     tokens=_dtokens(8, seed=1),
                                     max_new_tokens=2)])
    finally:
        unsub()
    assert seen and seen[0]["codec"] == "int8"
    assert seen[0]["pages"] >= 2


# ------------------------------------------------ 5. the gate + CLIs

def _check_regression():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import check_regression
    finally:
        sys.path.pop(0)
    return check_regression


def test_gate_directions_for_quant_metrics():
    cr = _check_regression()
    assert not cr.lower_is_better("resident_tokens_per_hbm_byte")
    assert cr.lower_is_better("quant_ppl_delta")
    assert cr.lower_is_better("serve_quant_fallback_total")
    for k in ("kv_quant", "quant_block"):
        assert k in cr.INCOMPARABLE_WORKLOAD_KEYS


def test_quant_bench_capture_and_real_gate_run(tmp_path, capsys):
    """Satellite acceptance, on a REAL quantized bench capture: the
    workload stamps ``kv_quant``/``quant_block`` provenance, the
    capacity metric gates higher-is-better, an injected
    ``quant_ppl_delta`` gates lower-is-better, and a baseline whose
    workload says fp32 is REFUSED (exit 2), never silently compared."""
    from apex_tpu.bench_cli import _serve_bench

    _serve_bench(steps=6, num_slots=2, kv_quant="int8")
    suite = json.loads(capsys.readouterr().out)
    entry = suite["serve_decode"]
    assert entry["workload"]["kv_quant"] == "int8"
    assert entry["workload"]["quant_block"] > 0
    assert entry["resident_tokens_per_hbm_byte"] > 0
    # stamp the quality metric the offline eval writes into captures
    entry["quant_ppl_delta"] = 0.001

    cr = _check_regression()
    cur, base = tmp_path / "cur.json", tmp_path / "base.json"
    cur.write_text(json.dumps(suite))
    base.write_text(json.dumps(suite))
    args = ["--suite", str(base), "--kernels", "serve_decode"]
    assert cr.main([str(cur)] + args) == 0
    # capacity drop regresses (higher-is-better)...
    worse = json.loads(json.dumps(suite))
    worse["serve_decode"]["resident_tokens_per_hbm_byte"] = \
        entry["resident_tokens_per_hbm_byte"] * 0.4
    cur.write_text(json.dumps(worse))
    assert cr.main([str(cur)] + args) == 1
    # ...quality erosion regresses (lower-is-better)...
    worse = json.loads(json.dumps(suite))
    worse["serve_decode"]["quant_ppl_delta"] = 0.02
    cur.write_text(json.dumps(worse))
    assert cr.main([str(cur)] + args) == 1
    # ...and an fp32 baseline is incomparable, not compared
    cur.write_text(json.dumps(suite))
    fp32 = json.loads(json.dumps(suite))
    fp32["serve_decode"]["workload"]["kv_quant"] = None
    fp32["serve_decode"]["workload"]["quant_block"] = 0
    base.write_text(json.dumps(fp32))
    assert cr.main([str(cur)] + args) == 2


@pytest.mark.slow
def test_quant_bench_capacity_vs_fp32_capture(capsys):
    """The headline capacity claim on real captures: same workload,
    quantized pool holds >= 2x the resident tokens per KV HBM byte."""
    from apex_tpu.bench_cli import _serve_bench

    kw = dict(steps=8, num_slots=2, max_len=64, prompt_len="8:16",
              page_size=8, num_pages=17, prefix_cache=True)
    _serve_bench(**kw)
    fp32 = json.loads(capsys.readouterr().out)["serve_decode"]
    _serve_bench(**kw, kv_quant="int8")
    quant = json.loads(capsys.readouterr().out)["serve_decode"]
    assert quant["resident_tokens_per_hbm_byte"] >= \
        2.0 * fp32["resident_tokens_per_hbm_byte"], \
        "quantized KV must multiply resident-token capacity per byte"
    assert quant["workload"]["kv_quant"] == "int8"
    assert fp32["workload"]["kv_quant"] is None


def test_serve_cli_kv_quant_matrix(capsys):
    from apex_tpu.serve.cli import main

    for argv, msg in [
            (["--kv-quant", "int8", "--dtype", "bf16"],
             "needs --dtype fp32"),
            (["--kv-quant", "mxfp8", "--spec-draft-len", "2"],
             "incompatible with --spec-draft-len"),
    ]:
        assert main(argv) == 2, argv
        assert msg in capsys.readouterr().err, argv


def test_bench_cli_kv_quant_matrix(monkeypatch):
    from apex_tpu.bench_cli import _serve_bench
    from apex_tpu.bench_cli import main as bench_main

    with pytest.raises(SystemExit, match="unknown kv_quant codec"):
        _serve_bench(steps=1, kv_quant="int4")
    with pytest.raises(SystemExit, match="incompatible"):
        _serve_bench(steps=1, kv_quant="int8", spec_draft_len=2)
    # --kv-quant without --serve: the serve-only matrix exits 2
    monkeypatch.setattr(sys, "argv",
                        ["apex-tpu-bench", "--kv-quant", "int8"])
    with pytest.raises(SystemExit) as ei:
        bench_main()
    assert ei.value.code == 2
