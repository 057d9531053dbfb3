"""DeepSeek-V3 on the serving path, at a small size on the CPU.

The program (``models/deepseek_v3.py`` through ``serve.Engine`` and
``ServeScheduler``, the latent paged cache, ``serve/moe.py``) against the
plain reference (``benchmark/reference/deepseek_v3.py``, which imports
nothing of ``apex_tpu``) on seeded weights: logits, never tokens.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from reference import deepseek_v3 as reference  # noqa: E402

from apex_tpu.models.deepseek_v3 import (DeepseekV3Config,  # noqa: E402
                                         deepseek_v3_token_forward,
                                         expert_layer)
from apex_tpu.models.gpt2 import GPT2Config, gpt2_token_forward  # noqa: E402
from apex_tpu.serve import kv_cache, moe  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   init_gpt2_params)
from apex_tpu.serve.scheduler import Request, ServeScheduler  # noqa: E402
from apex_tpu.transformer.rope import (rope_interleaved,  # noqa: E402
                                       yarn_inv_freq)

ROUTED, RANKS = 32, 16
SCALING = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
               mscale_all_dim=1, original_max_position_embeddings=64,
               rope_type="yarn")


def tiny(dtype="float32", held=2, offset=4, **kw):
    """A configuration file's dict at a size for the CPU: the reference
    reads it as it is, the program through ``DeepseekV3Config.from_dict``."""
    return dict(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, n_shared_experts=1, n_routed_experts=held,
        routed_scaling_factor=2.5, kv_lora_rank=16, q_lora_rank=32,
        qk_rope_head_dim=8, v_head_dim=12, qk_nope_head_dim=8, n_group=8,
        topk_group=4, num_experts_per_tok=8, first_k_dense_replace=1,
        norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=100000,
        max_position_embeddings=512, rope_scaling=SCALING,
        published=dict(n_routed_experts=ROUTED),
        deployment=dict(expert_offset=offset), compute_dtype=dtype), **kw)


def model_of(cfg):
    return DeepseekV3Config.from_dict(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=cfg["deployment"]["expert_offset"],
        vocab_held=cfg["vocab_size"])


def engine_of(cfg, params, **kw):
    geometry = dict(num_slots=4, max_len=128, temperature=0.0, page_size=16,
                    num_pages=33, prefix_cache=True)
    return Engine(model_of(cfg), params, EngineConfig(**{**geometry, **kw}))


def noise_share(got, ref):
    """The benchmark's ``logit_noise_share``: squared distance over
    squared size, each row about its own mean."""
    def centred(x):
        return x - x.mean(-1, keepdims=True)

    return float(np.square(centred(got) - centred(ref)).sum()
                 / np.square(centred(ref)).sum())


# float32: the two are the same mathematics in another order of float32
# sums (absorbed against plain attention, a grouped against a dense expert
# product, 64-wide reductions): 1e-4 absolute on logits of unit size is a
# hundred times the rounding and a hundredth of any mistake. bfloat16:
# weights are the same bfloat16 values on both sides, the program rounds
# every product's output to bfloat16 (2**-9 relative) where the reference
# keeps float32, and a router near-tie that this flips moves a token's
# logits by a whole expert: at 64 wide over 164 tokens the share reads
# 0.8e-4 to 6.2e-4 (three seeds, two ranks; 2.8e-4 on this one), where the
# reference in int8 (the precision below) reads 1.3e-3 to 1.8e-3.
@pytest.mark.parametrize("dtype, limit", [("float32", 1e-9),
                                          ("bfloat16", 6e-4)])
def test_prefill_then_decode_through_the_latent_cache_matches_the_reference(
        dtype, limit):
    cfg = tiny(dtype)
    params = reference.make_params(cfg, 2**31 + 5)
    assert params["params"]["l_1"]["w_gate"].dtype == jnp.dtype(dtype)
    engine = engine_of(cfg, params)
    rng = np.random.default_rng(0)
    prompts = {s: rng.integers(0, 512, n).tolist()
               for s, n in enumerate((11, 16, 9, 14))}
    first, last_logits, _ = engine.prefill(prompts)
    seqs = {s: list(p) + [int(first[s])] for s, p in prompts.items()}
    got = {s: [np.asarray(last_logits[s])] for s in prompts}
    active = np.ones((4,), bool)
    for _ in range(40):
        nxt, logits = engine.decode_step(engine.last_tokens, active)
        for s in prompts:
            got[s].append(np.asarray(logits[s]))
            seqs[s].append(int(nxt[s]))
    assert engine.decode_traces == 1 and engine.prefill_traces == 1
    tokens = np.zeros((4, max(map(len, seqs.values()))), np.int64)
    rows = []
    for i, s in enumerate(prompts):
        tokens[i, :len(seqs[s])] = seqs[s]
        rows += [(i, len(prompts[s]) - 1 + j) for j in range(41)]
    want = np.asarray(reference.forward_logits(cfg, params, tokens, rows))
    have = np.concatenate([np.stack(got[s]) for s in prompts])
    assert 0.8 < want.std() < 1.2                 # logits are O(1)
    assert noise_share(have, want) < limit
    if dtype == "float32":
        np.testing.assert_allclose(have, want, atol=1e-4)
    else:                                         # and the control is apart
        control = np.asarray(reference.forward_logits(cfg, params, tokens,
                                                      rows, "int8"))
        assert noise_share(control, want) > 1.5 * limit


def test_absorbed_decode_agrees_with_plain_chunk_attention():
    """The two forms of MLA over one cache: a prompt through the chunk
    form (plain attention over expanded keys and values) gives, at every
    position, the logits that feeding it a token at a time through the
    decode form (absorbed, off the latent pages) gives."""
    cfg = tiny()
    model = model_of(cfg)
    params = reference.make_params(cfg, 11)
    tokens = np.random.default_rng(1).integers(0, 512, (2, 24))

    def fresh():
        cache = kv_cache.init_paged_latent_cache(3, 2, 32, 8, 9,
                                                 model.latent_width)
        return cache.replace(page_table=jnp.asarray(
            np.arange(1, 9, dtype=np.int32).reshape(2, 4)))

    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    chunk, cache_a, _ = deepseek_v3_token_forward(
        model, params, fresh(), jnp.asarray(tokens), pos,
        jnp.ones((2, 24), bool))
    cache_b, steps = fresh(), []
    step = jax.jit(lambda c, t, p: deepseek_v3_token_forward(
        model, params, c, t, p, jnp.ones((2,), bool))[:2])
    for t in range(24):
        logits, cache_b = step(cache_b, jnp.asarray(tokens[:, t]),
                               jnp.full((2,), t, jnp.int32))
        steps.append(np.asarray(logits))
    np.testing.assert_allclose(np.stack(steps, 1), np.asarray(chunk),
                               atol=1e-4)
    # and both wrote the same rows: one cache, two paths
    np.testing.assert_allclose(np.asarray(cache_b.rows),
                               np.asarray(cache_a.rows), atol=1e-5)
    # a row of 16 + 8 lies whole lanes wide, the padding zero
    assert cache_a.rows.shape == (3, 9, 8, 128)
    assert not np.asarray(cache_a.rows)[..., 16 + 8:].any()


def test_a_prefix_hit_reads_latent_pages():
    cfg = tiny()
    params = reference.make_params(cfg, 5)
    rng = np.random.default_rng(2)
    shared = rng.integers(0, 512, 36).tolist()
    first = shared + rng.integers(0, 512, 4).tolist()
    second = shared + rng.integers(0, 512, 9).tolist()
    engine = engine_of(cfg, params)
    engine.prefill({0: first})
    _, hit_logits, _ = engine.prefill({1: second})
    stats = engine.last_prefill_stats[1]
    # the two whole pages of 16 are shared read-only: the call runs the tail
    assert stats["hit_tokens"] == 32 and stats["scanned"] == 13
    cold = engine_of(cfg, params, prefix_cache=False)
    _, cold_logits, _ = cold.prefill({1: second})
    assert cold.last_prefill_stats[1]["hit_tokens"] == 0
    np.testing.assert_allclose(np.asarray(hit_logits[1]),
                               np.asarray(cold_logits[1]), atol=1e-4)
    # a prompt that is cached whole runs its last token again, into its
    # own copy of the page that token lies on (copy-on-write of latent rows)
    _, cow_logits, _ = engine.prefill({2: shared[:32]})
    assert engine.last_prefill_stats[2] == {"hit_tokens": 31, "hit_pages": 1,
                                            "scanned": 1}
    _, cold_cow, _ = cold.prefill({2: shared[:32]})
    np.testing.assert_allclose(np.asarray(cow_logits[2]),
                               np.asarray(cold_cow[2]), atol=1e-4)
    # decode goes on over shared and own pages alike
    active = np.array([False, True, True, False])
    a, la = engine.decode_step(engine.last_tokens, active)
    b, lb = cold.decode_step(cold.last_tokens, active)
    assert (a[1:3] == b[1:3]).all()
    np.testing.assert_allclose(np.asarray(la[1:3]), np.asarray(lb[1:3]),
                               atol=1e-4)


def test_the_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """Every rank's routed part, with the shared expert counted once,
    equals the uncut reference's expert layer."""
    whole = tiny(held=ROUTED, offset=0)
    params = reference.make_params(whole, 3)["params"]["l_1"]
    u = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    want = np.asarray(reference._experts(u, params, whole,
                                         reference.shape_of(whole), "fp32"))
    mask = jnp.ones((40,), bool)
    per = ROUTED // RANKS
    total, picks, shared = 0.0, 0, None
    for rank in range(RANKS):
        model = model_of(tiny(held=per, offset=rank * per))
        blk = dict(params, **{k: params[k][rank * per:(rank + 1) * per]
                              for k in ("w_gate", "w_up", "w_down")})
        out, counts = expert_layer(model, blk, u, mask)
        if shared is None:
            shared = moe.swiglu(u, blk["shared_gate"], blk["shared_up"],
                                blk["shared_down"])
        total = total + (out - shared)
        picks += int(counts[0])
        assert 0 <= int(counts[1]) <= per
    assert picks == 40 * 8                   # every pick lives somewhere
    np.testing.assert_allclose(np.asarray(total + shared), want, atol=2e-5)


def test_router_on_hand_made_scores():
    """Group limit, the bias used for the choice and not for the weight,
    normalisation, the scaling factor: 16 experts in 4 groups of 4, two
    groups a token, three experts a token. The hidden state IS the
    router's logits (an identity for ``W_g``)."""
    logits = np.full((2, 16), -4.0, np.float32)
    logits[0, [0, 1]] = [3.0, 2.0]           # group 0: best pair
    logits[0, [4, 5]] = [2.5, -1.0]          # group 1
    logits[0, [8, 9]] = [2.8, 2.6]           # group 2: second best pair
    logits[0, 12] = 2.9                      # group 3: one good expert
    logits[1, [2, 6, 10, 14]] = [1.0, 0.9, 0.8, 0.7]
    bias = np.zeros((16,), np.float32)
    kw = dict(n_group=4, topk_group=2, top_k=3, norm_topk_prob=True,
              routed_scaling_factor=2.5)
    experts, weights = moe.route_noaux_tc(
        jnp.asarray(logits), jnp.eye(16), jnp.asarray(bias), **kw)

    def sig(x):
        return 1 / (1 + np.exp(-np.asarray(x, np.float64)))

    # row 0: groups 0 and 2 stay (sums of their two best), so expert 12
    # (2.9) and expert 4 (2.5) are out though they beat expert 1 (2.0)
    assert sorted(np.asarray(experts[0])) == [0, 8, 9]
    s = sig([3.0, 2.8, 2.6])
    got = dict(zip(np.asarray(experts[0]).tolist(),
                   np.asarray(weights[0]).tolist()))
    for e, want in zip((0, 8, 9), 2.5 * s / s.sum()):
        assert got[e] == pytest.approx(want, rel=1e-5)
    assert float(weights[0].sum()) == pytest.approx(2.5, rel=1e-5)
    # a bias moves the choice and leaves the weight alone: +1 on expert 12
    # lifts group 3 (0.948 + 1 + 0.018) over group 2 (0.943 + 0.931) and
    # group 0 (0.953 + 0.881), which falls out with its experts 0 and 1
    bias[12] = 1.0
    experts, weights = moe.route_noaux_tc(
        jnp.asarray(logits), jnp.eye(16), jnp.asarray(bias), **kw)
    assert sorted(np.asarray(experts[0])) == [8, 9, 12]
    s = sig([2.8, 2.6, 2.9])
    got = dict(zip(np.asarray(experts[0]).tolist(),
                   np.asarray(weights[0]).tolist()))
    assert got[12] == pytest.approx(2.5 * s[2] / s.sum(), rel=1e-5)
    # without normalisation the weights are the scores times the factor
    experts, weights = moe.route_noaux_tc(
        jnp.asarray(logits), jnp.eye(16), jnp.zeros(16),
        **dict(kw, norm_topk_prob=False))
    assert float(weights[1].max()) == pytest.approx(2.5 * sig(1.0), rel=1e-5)
    # and the reference's router, written apart, chooses and weighs alike
    cfg = dict(n_group=4, topk_group=2, num_experts_per_tok=3,
               norm_topk_prob=True, routed_scaling_factor=2.5)
    theirs = reference.route(jnp.asarray(logits), jnp.eye(16),
                             jnp.asarray(bias), cfg)
    ours = moe.route_noaux_tc(jnp.asarray(logits), jnp.eye(16),
                              jnp.asarray(bias), **kw)
    np.testing.assert_array_equal(np.asarray(theirs[0]), np.asarray(ours[0]))
    np.testing.assert_allclose(np.asarray(theirs[1]), np.asarray(ours[1]),
                               rtol=1e-6)


@pytest.mark.parametrize("chunk_rows", [512, 8])
def test_routed_experts_sum_the_held_picks_whatever_the_chunk(monkeypatch,
                                                              chunk_rows):
    """The grouped product against a plain loop over the held experts; a
    chunk of 8 picks makes it take several trips, one of 512 one; a
    masked-off row is routed nowhere."""
    monkeypatch.setattr(moe, "_CHUNK_ROWS", chunk_rows)
    monkeypatch.setattr(moe, "_CHUNK_SHARE", 64)
    key = jax.random.split(jax.random.PRNGKey(4), 6)
    rows, hidden, width, held, offset = 24, 16, 8, 3, 5
    u = jax.random.normal(key[0], (rows, hidden))
    experts = jax.random.randint(key[1], (rows, 4), 0, 12)
    weights = jax.random.uniform(key[2], (rows, 4))
    w_gate = jax.random.normal(key[3], (held, hidden, width)) * 0.3
    w_up = jax.random.normal(key[4], (held, hidden, width)) * 0.3
    w_down = jax.random.normal(key[5], (held, width, hidden)) * 0.3
    mask = jnp.arange(rows) % 5 != 0
    out, counts = moe.routed_experts(u, experts, weights, mask, w_gate, w_up,
                                     w_down, expert_offset=offset)
    want = np.zeros((rows, hidden), np.float32)
    landed, hit = 0, set()
    for r in range(rows):
        for e, w in zip(np.asarray(experts[r]), np.asarray(weights[r])):
            if mask[r] and offset <= e < offset + held:
                i = int(e) - offset
                want[r] += w * np.asarray(moe.swiglu(
                    u[r:r + 1], w_gate[i], w_up[i], w_down[i]))[0]
                landed, hit = landed + 1, hit | {i}
    assert landed > 8 and np.asarray(counts).tolist() == [landed, len(hit)]
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    assert not np.asarray(out)[::5].any()


def test_yarn_frequencies_against_the_closed_form():
    """GigaChat3.1's own numbers: 64 rope dims, theta 1e5, factor 64 over
    4096 positions, beta 32 and 1. The correction dims are 64 ln(4096 / (2
    pi beta)) / (2 ln 1e5) = 8.38 and 18.01, floored and ceiled to 8 and
    19: pairs up to 8 keep their frequency, from 19 on it is a 64th,
    between them the blend is linear in the pair's number."""
    inv = yarn_inv_freq(64, 1e5, 64.0, 4096, 32.0, 1.0)
    base = 1e5 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:9], base[:9], rtol=1e-12)
    np.testing.assert_allclose(inv[19:], base[19:] / 64, rtol=1e-12)
    for i in range(9, 19):
        ramp = (i - 8) / 11
        assert inv[i] == pytest.approx(base[i] * (1 - ramp + ramp / 64),
                                       rel=1e-12)
    full = dict(qk_rope_head_dim=64, rope_theta=100000,
                rope_scaling=dict(SCALING,
                                  original_max_position_embeddings=4096))
    np.testing.assert_allclose(reference.yarn_inv_freq(full), inv,
                               rtol=1e-12)
    # factor 1 is plain RoPE; the softmax scale is 192^-0.5 * 1.4159^2
    np.testing.assert_allclose(yarn_inv_freq(64, 1e5, 1.0, 4096), base)
    model = DeepseekV3Config(
        qk_rope_head_dim=64, qk_nope_head_dim=128, rope_factor=64.0,
        rope_mscale_all_dim=1.0, rope_theta=1e5)
    assert model.softmax_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(64) + 1) ** 2)
    assert reference.softmax_scale(dict(
        full, qk_nope_head_dim=128)) == pytest.approx(model.softmax_scale)
    # rotating the pairs in place (the program) or de-interleaved (the
    # published code, the reference): every query-key product is the same
    key = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(key[0], (5, 3, 64))
    k = jax.random.normal(key[1], (5, 64))
    pos = jnp.asarray([0, 1, 7, 300, 4000])
    ours = jnp.einsum("thd,td->th", rope_interleaved(q, pos[:, None], inv),
                      rope_interleaved(k, pos, inv))
    theirs = jnp.einsum("thd,td->th", reference._rope(q, pos, inv),
                        reference._rope(k, pos, inv))
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("knobs, names", [
    (dict(tp=2), "tp=2: there is no per-rank forward"),
    (dict(spec_draft_len=2), "spec_draft_len=2: the verify scan"),
    (dict(kv_quant="int8"), "kv_quant='int8': the block-scale codec"),
    (dict(block_k=8), "block_k=8"),
])
def test_engine_modes_this_model_lacks_are_refused_at_build(knobs, names):
    cfg = tiny()
    params = reference.make_params(cfg, 1)
    with pytest.raises(ValueError, match=names):
        engine_of(cfg, params, **knobs)


def test_the_latent_cache_serves_with_one_page_a_slot():
    """No ``page_size``: a page is a slot's whole ``max_len`` (5 pages
    for 4 slots). The decode step gathers a slot's whole key axis
    whatever the page, and a prefill with no hit never reads the cache,
    so the logits are those of the 16-token pages, to the bit."""
    cfg = tiny()
    params = reference.make_params(cfg, 7)
    rng = np.random.default_rng(1)
    prompts = {s: rng.integers(0, 512, n).tolist()
               for s, n in enumerate((11, 16, 9))}
    active = np.array([True, True, True, False])
    got = {}
    for name, knobs in (("pages", {}), ("one", dict(
            page_size=None, num_pages=None, prefix_cache=False))):
        engine = engine_of(cfg, params, **knobs)
        first, last_logits, _ = engine.prefill(prompts)
        rows = [np.asarray(last_logits)]
        for _ in range(20):
            _, logits = engine.decode_step(engine.last_tokens, active)
            rows.append(np.asarray(logits))
        got[name] = (first.tolist(), np.stack(rows)[:, :3])
        assert engine.decode_traces == 1
    assert engine.page_size == 128 and engine.cache.rows.shape[1:3] == (5, 128)
    assert engine.paging_state()["free_pages"] == 1
    assert got["one"][0] == got["pages"][0]
    np.testing.assert_array_equal(got["one"][1], got["pages"][1])


def test_latent_pages_do_not_migrate_and_the_ledger_names_the_model():
    cfg = tiny()
    engine = engine_of(cfg, reference.make_params(cfg, 1))
    prompt = list(range(40))
    engine.prefill({0: prompt})
    with pytest.raises(ValueError, match="deepseek_v3 pages do not migrate"):
        engine.export_prefix_pages(prompt)
    with pytest.raises(ValueError, match="deepseek_v3 pages do not migrate"):
        engine.import_prefix_pages([])
    assert engine.kv_cache_bytes == 3 * 33 * 16 * 128 * 4
    workload = engine.cost_ledger(chip="cpu")["workload"]
    assert workload["model"] == "deepseek_v3"
    assert workload["experts_held"] == 2
    assert workload["n_routed_experts"] == ROUTED
    with pytest.raises(TypeError, match="no servable model config"):
        Engine(object(), {})


def test_routing_counters_ride_the_calls_own_spans(monkeypatch):
    """The two programs return the picks that landed here and the held
    experts hit; the engine leaves them, with what they are shares of, on
    ``apex.<call>.routing`` inside the call's span, after the fetch."""
    from apex_tpu.serve import engine as engine_module

    seen = []
    real = engine_module.annotate

    def recording(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(engine_module, "annotate", recording)
    cfg = tiny(held=4, offset=8)
    engine = engine_of(cfg, reference.make_params(cfg, 9))
    engine.prefill({0: list(range(1, 12)), 3: list(range(20, 36))})
    engine.decode_step(engine.last_tokens,
                       np.array([True, False, False, True]))
    names = [n for n, _ in seen]
    for call in ("apex.prefill", "apex.decode_step"):
        assert names.index(call + ".fetch") < names.index(call + ".routing")
    spans = dict(seen)
    layers, held = 2, 4
    pre, dec = spans["apex.prefill.routing"], spans["apex.decode_step.routing"]
    assert pre["picks"] == (11 + 16) * 8 * layers
    assert dec["picks"] == 2 * 8 * layers
    for got in (pre, dec):
        assert got["experts_held"] == held * layers
        assert 0 < got["picks_here"] <= got["picks"]
        assert 0 < got["experts_hit"] <= min(got["experts_held"],
                                             got["picks_here"])
    # a GPT-2 engine's calls carry no such span
    seen.clear()
    small = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=1,
                       n_head=2, compute_dtype=jnp.float32)
    gpt2 = Engine(small, init_gpt2_params(small), EngineConfig(num_slots=2))
    gpt2.prefill({0: [1, 2, 3]})
    gpt2.decode_step(gpt2.last_tokens, np.array([True, False]))
    assert not [n for n, _ in seen if n.endswith(".routing")]


def test_the_scheduler_serves_the_model_through_the_normal_path():
    cfg = tiny()
    params = reference.make_params(cfg, 21)
    engine = engine_of(cfg, params)
    sched = ServeScheduler(engine)
    rng = np.random.default_rng(3)
    requests = [Request(request_id=i, max_new_tokens=6 + i,
                        tokens=rng.integers(0, 512, 5 + 3 * i).tolist())
                for i in range(7)]                # 7 requests, 4 slots
    for r in requests:
        sched.submit(r)
    for _ in range(200):
        if all(r.state == "completed" for r in requests):
            break
        sched.step()
    assert all(r.state == "completed" and r.finish_reason == "length"
               and len(r.generated) == r.max_new_tokens for r in requests)
    assert engine.decode_traces == 1 and engine.prefill_traces <= 3
    # greedy through the scheduler is greedy through the reference: the
    # longest request's tokens are the argmax of the reference's logits
    r = requests[-1]
    seq = list(r.tokens) + list(r.generated)
    rows = [(0, len(r.tokens) - 1 + j) for j in range(len(r.generated))]
    want = np.asarray(reference.forward_logits(
        cfg, params, np.asarray([seq]), rows))
    gap = want.max(-1) - want[np.arange(len(rows)), r.generated]
    assert gap.max() < 1e-3


def test_the_seam_leaves_gpt2s_decode_program_as_it_was():
    """``decode_fn`` of a tiny GPT-2 engine, lowered, against the body
    written out here with no seam (and the cache resident, as every
    engine program has it): the same text, so the same program."""
    cfg = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                     n_head=2, compute_dtype=jnp.float32)
    params = init_gpt2_params(cfg)
    engine = Engine(cfg, params, EngineConfig(
        num_slots=2, max_len=64, temperature=0.0, page_size=16, num_pages=9,
        prefix_cache=True))
    engine.aot_compile([16])

    def _decode_fn(weights, cache, last_tokens, active, rng):
        logits, cache = gpt2_token_forward(
            cfg, weights, cache, last_tokens, cache.lengths, active,
            block_k=engine.block_k, kv_quant=None, final_scope="sampling")
        with jax.named_scope("sampling"):
            rng, _ = jax.random.split(rng)
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tokens, logits, kv_cache.advance(cache, active), rng

    before = jax.jit(_decode_fn, donate_argnums=1).lower(
        *engine._decode_args()).as_text()
    assert engine._decode_lowered.as_text() == before
    assert "decode_fn" in before.splitlines()[0]

    def prefill_fn(weights, cache, tokens, admit, start, tail_lens, rng):
        t = jnp.arange(16, dtype=jnp.int32)[None, :]
        write = admit[:, None] & (t < tail_lens[:, None])
        last = jnp.maximum(tail_lens - 1, 0)
        logits, cache = gpt2_token_forward(
            cfg, weights, cache, tokens, start[:, None] + t, write, last,
            block_k=engine.block_k, kv_quant=None, final_scope="sampling")
        cache = kv_cache.set_lengths(cache, admit, start + tail_lens)
        with jax.named_scope("sampling"):
            rng, _ = jax.random.split(rng)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cache, first, logits, None, rng

    assert engine._prefill_lowered[16].as_text() == jax.jit(
        prefill_fn, donate_argnums=1).lower(
            *engine._prefill_args(16)).as_text()
