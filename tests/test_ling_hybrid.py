"""Ling-3.0-flash's language model (``bailing_hybrid``) on the serving path,
at a small size on the CPU.

The program (``models/ling_hybrid.py`` through ``serve.Engine`` and
``ServeScheduler``, ``kv_cache.HybridCache``: latent pages for the one MLA
layer in six, a recurrent state a slot for the KDA layers) against the plain
reference (``benchmark/reference/ling_hybrid.py``, which imports nothing of
``apex_tpu``) on seeded weights: logits, never tokens.
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

from reference import ling_hybrid as reference  # noqa: E402

from apex_tpu.models import ling_hybrid  # noqa: E402
from apex_tpu.models.deepseek_v3 import expert_layer  # noqa: E402
from apex_tpu.models.ling_hybrid import (LingHybridConfig,  # noqa: E402
                                         kda_chunk_scan, kda_step)
from apex_tpu.serve import kv_cache, moe  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   prefill_rows)
from apex_tpu.serve.scheduler import Request, ServeScheduler  # noqa: E402

ROUTED, RANKS = 32, 4


def tiny(dtype="float32", held=8, offset=0, **kw):
    """A configuration file's dict at a size for the CPU, cut as the
    benchmark's is (7 layers: 0-4 KDA, 5 MLA, 6 KDA; layer 0 dense): the
    reference reads it as it is, the program through
    ``LingHybridConfig.from_dict``."""
    return dict(dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
        num_hidden_layers=7, first_k_dense_replace=1, layer_group_size=6,
        num_attention_heads=4, num_key_value_heads=4, head_dim=16,
        num_kv_heads_for_linear_attn=0, short_conv_kernel_size=4,
        kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
        linear_silu=True, use_qk_norm=True, group_norm_size=1,
        gated_attention_proj_granularity_type="head_wise",
        q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=6000000,
        rms_norm_eps=1e-6, max_position_embeddings=512, num_experts=held,
        num_experts_per_tok=8, n_group=8, topk_group=4,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        score_function="sigmoid", moe_router_enable_expert_bias=True,
        # the layers past the cut keep their published clamps: not read
        expert_swiglu_limit_list=[0] * 7 + [4],
        share_expert_swiglu_limit_list=[0] * 7 + [5],
        published=dict(num_experts=ROUTED),
        deployment=dict(expert_offset=offset), compute_dtype=dtype), **kw)


def model_of(cfg):
    return LingHybridConfig.from_dict(
        cfg, num_experts=cfg["published"]["num_experts"],
        experts_held=cfg["num_experts"],
        expert_offset=cfg["deployment"]["expert_offset"],
        vocab_held=cfg["vocab_size"])


def engine_of(cfg, params, **kw):
    geometry = dict(num_slots=4, max_len=256, temperature=0.0, page_size=16,
                    num_pages=65)
    return Engine(model_of(cfg), params, EngineConfig(**{**geometry, **kw}))


def noise_share(got, ref):
    """The benchmark's ``logit_noise_share``."""
    def centred(x):
        return x - x.mean(-1, keepdims=True)

    return float(np.square(centred(got) - centred(ref)).sum()
                 / np.square(centred(ref)).sum())


def serve(engine, prompts, steps, got=None, seqs=None):
    """Prefill ``{slot: prompt}`` in one call, then ``steps`` decode steps
    over those slots; ``({slot: logits rows}, {slot: prompt + tokens})``."""
    first, last_logits, _ = engine.prefill(prompts)
    got = {} if got is None else got
    seqs = {} if seqs is None else seqs
    for s, p in prompts.items():
        got[s] = [np.asarray(last_logits[s])]
        seqs[s] = list(p) + [int(first[s])]
    active = np.zeros((engine.config.num_slots,), bool)
    active[list(got)] = True
    for _ in range(steps):
        nxt, logits = engine.decode_step(engine.last_tokens, active)
        for s in got:
            got[s].append(np.asarray(logits[s]))
            seqs[s].append(int(nxt[s]))
    return got, seqs


def against_reference(cfg, params, got, seqs, prompt_lens, mode=None):
    """``(the program's rows, the reference's at the same positions)``."""
    slots = sorted(got)
    tokens = np.zeros((len(slots), max(len(seqs[s]) for s in slots)),
                      np.int64)
    rows = []
    for i, s in enumerate(slots):
        tokens[i, :len(seqs[s])] = seqs[s]
        rows += [(i, prompt_lens[s] - 1 + j) for j in range(len(got[s]))]
    want = np.asarray(reference.forward_logits(cfg, params, tokens, rows,
                                               mode))
    return np.concatenate([np.stack(got[s]) for s in slots]), want


# float32: the two are the same mathematics in another order of float32
# sums (the chunkwise recurrence against a token at a time, absorbed against
# plain attention, a grouped against a dense expert product): 1e-4 absolute
# on logits of unit size is ten times the rounding read (7e-6 to 1.1e-5) and
# a hundredth of any mistake. bfloat16: the weights are the same bfloat16
# values on both sides; the program rounds every product's output to
# bfloat16 (2**-9 relative) but the decay's and the gates', whose
# accumulators stay float32, and keeps the convolution's tail in bfloat16.
# What dominates at this size is the router: a near-tie that the rounding
# flips moves a token by a whole expert, so the share follows how many of the
# 32 experts are held (CPU, four seeds each): with 1 held the program reads
# 2.9e-4 to 7.3e-4 and the reference in int8 3.6e-3 to 5.1e-3; with 8 held
# 5.9e-3 to 1.05e-2 against 1.8e-2 to 2.4e-2 (with the routed part scaled to
# 0: 3.2e-4 against 4e-3). The comparison is made with 1 held, where the two
# ends are furthest apart, at their geometric middle. With the decay's input
# rounded to bfloat16 as well the share was 2.5 times larger: a decay's
# rounding is multiplied up by every later position.
@pytest.mark.parametrize("dtype, share, limit", [
    ("float32", dict(), 1e-9),
    ("bfloat16", dict(held=1, offset=4), 1.6e-3)])
def test_prefill_then_decode_through_pages_and_state_matches_the_reference(
        dtype, share, limit):
    """Mixed prompt lengths in ONE padded call (bucket 128: two chunks of
    the recurrence, rows that end in the first chunk and in the second),
    then 30 decode steps."""
    cfg = tiny(dtype, **share)
    params = reference.make_params(cfg, 2**31 + 5)
    assert params["params"]["l_1"]["w_gate"].dtype == jnp.dtype(dtype)
    assert params["params"]["l_1"]["a_log"].dtype == jnp.float32
    engine = engine_of(cfg, params)
    assert isinstance(engine.cache, kv_cache.HybridCache)
    rng = np.random.default_rng(0)
    lens = {0: 100, 1: 128, 2: 9, 3: 65}
    prompts = {s: rng.integers(0, 512, n).tolist() for s, n in lens.items()}
    got, seqs = serve(engine, prompts, 30)
    assert engine.decode_traces == 1 and engine.prefill_traces == 1
    have, want = against_reference(cfg, params, got, seqs, lens)
    assert 0.8 < want.std() < 1.2                 # logits are O(1)
    assert noise_share(have, want) < limit
    if dtype == "float32":
        np.testing.assert_allclose(have, want, atol=1e-4)
    else:                                         # and the control is apart
        _, control = against_reference(cfg, params, got, seqs, lens, "int8")
        assert noise_share(control, want) > 2 * limit


def test_the_row_view_program_and_a_re_admitted_slot_match_the_reference():
    """16 slots at bucket 128 have a ``[2, 128]`` program beside ``[16,
    128]``. Ten slots are admitted through the full program and decode;
    two are evicted and two NEW requests admitted into them through the
    small program while the others hold their state; every stream, the
    re-admitted slots' included, is the reference's: no state leaks from
    the evicted requests, and the view writes no other slot."""
    cfg = tiny()
    params = reference.make_params(cfg, 77)
    engine = engine_of(cfg, params, num_slots=16, num_pages=16 * 16 + 1)
    assert prefill_rows(16, 128) == 2
    rng = np.random.default_rng(1)
    lens = {s: int(n) for s, n in zip(range(10), rng.integers(65, 129, 10))}
    prompts = {s: rng.integers(0, 512, n).tolist() for s, n in lens.items()}
    got, seqs = serve(engine, prompts, 5)
    before = jax.tree_util.tree_map(np.asarray, engine.cache)
    engine.evict([3, 7])
    done = {s: (got.pop(s), seqs.pop(s)) for s in (3, 7)}
    again = {3: rng.integers(0, 512, 70).tolist(),
             7: rng.integers(0, 512, 97).tolist()}
    first, last_logits, _ = engine.prefill(again)
    assert engine.prefill_traces == 2             # the small program ran
    after = engine.cache
    others = [s for s in range(16) if s not in (3, 7)]
    np.testing.assert_array_equal(np.asarray(after.state)[:, others],
                                  before.state[:, others])
    np.testing.assert_array_equal(np.asarray(after.conv)[:, others],
                                  before.conv[:, others])
    assert not np.array_equal(np.asarray(after.state)[:, 3],
                              before.state[:, 3])
    new_got = {s: [np.asarray(last_logits[s])] for s in again}
    new_seqs = {s: list(p) + [int(first[s])] for s, p in again.items()}
    active = np.zeros((16,), bool)
    active[list(got) + [3, 7]] = True
    for _ in range(6):
        nxt, logits = engine.decode_step(engine.last_tokens, active)
        for part, where in ((got, seqs), (new_got, new_seqs)):
            for s in part:
                part[s].append(np.asarray(logits[s]))
                where[s].append(int(nxt[s]))
    assert engine.decode_traces == 1
    have, want = against_reference(cfg, params, got, seqs, lens)
    np.testing.assert_allclose(have, want, atol=1e-4)
    have, want = against_reference(cfg, params, new_got, new_seqs,
                                   {3: 70, 7: 97})
    np.testing.assert_allclose(have, want, atol=1e-4)
    # and the requests that were evicted had been served right until then
    have, want = against_reference(
        cfg, params, {s: g for s, (g, _) in done.items()},
        {s: q for s, (_, q) in done.items()}, lens)
    np.testing.assert_allclose(have, want, atol=1e-4)


def test_a_chunk_call_in_row_blocks_is_the_call_in_one(monkeypatch):
    """A chunk call over more than ``BLOCK_POSITIONS`` positions runs a
    block of rows at a time over row views of the cache (here: 6 rows of
    bucket 32 in blocks of 4, the last block padded with rows that name
    no slot); logits, pages and states are those of the call in one."""
    cfg = tiny()
    params = reference.make_params(cfg, 5)
    rng = np.random.default_rng(2)
    prompts = {s: rng.integers(0, 512, n).tolist()
               for s, n in zip((0, 1, 2, 4, 5), (20, 32, 17, 25, 30))}
    out = {}
    for positions in (4096, 128):
        monkeypatch.setattr(ling_hybrid, "BLOCK_POSITIONS", positions)
        engine = engine_of(cfg, params, num_slots=6, max_len=64,
                           num_pages=25)
        first, last_logits, _ = engine.prefill(prompts)
        out[positions] = (np.asarray(last_logits)[list(prompts)],
                          jax.tree_util.tree_map(np.asarray, engine.cache))
    (one, whole), (blocks, blocked) = out[4096], out[128]
    np.testing.assert_allclose(blocks, one, atol=2e-5)
    np.testing.assert_allclose(blocked.state, whole.state, atol=2e-5)
    np.testing.assert_array_equal(blocked.lengths, whole.lengths)
    assert blocked.slots is None
    assert not blocked.state[:, 3].any()          # the slot not admitted
    np.testing.assert_allclose(blocked.rows, whole.rows, atol=2e-5)
    np.testing.assert_array_equal(blocked.conv, whole.conv)


def _recurrence_inputs(key, rows, t, heads=2, d=8, g_scale=1.0, g_shift=0.0):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (rows, t, heads, d))
    k = jax.random.normal(ks[1], (rows, t, heads, d))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (rows, t, heads, d))
    g = -5.0 * jax.nn.sigmoid(
        g_scale * jax.random.normal(ks[3], (rows, t, heads, d)) + g_shift)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (rows, t, heads)))
    return q, k, v, g, beta


def _token_at_a_time(q, k, v, g, beta):
    outs, states = zip(*(reference.kda_recurrence(*(x[i] for x in
                                                   (q, k, v, g, beta)))
                         for i in range(q.shape[0])))
    return np.stack(outs), np.stack(states)


# decays spread over (-5, 0); decays AT the lower bound, -5 a token over
# whole chunks (a cumulated -320 a chunk and -960 in all: exp(320) is not
# a float32, so a form that splits the pairwise decay reads inf or NaN
# here); and a mix of channels that forget at once beside ones that never do
@pytest.mark.parametrize("g_scale, g_shift", [(1.0, 0.0), (0.0, 30.0),
                                              (8.0, 0.0)])
def test_the_chunkwise_recurrence_is_the_token_at_a_time_one(g_scale,
                                                             g_shift):
    q, k, v, g, beta = _recurrence_inputs(jax.random.PRNGKey(3), 3, 192,
                                          g_scale=g_scale, g_shift=g_shift)
    if g_shift:
        assert float(g.max()) < -4.99
    o, state = kda_chunk_scan(q, k, v, g, beta)
    assert np.isfinite(np.asarray(o)).all()
    assert np.isfinite(np.asarray(state)).all()
    want_o, want_state = _token_at_a_time(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-5)
    # and the decode form, a token at a time from the same zero state
    s = jnp.zeros_like(state)
    for t in range(8):
        o_t, s = kda_step(s, *(x[:, t] for x in (q, k, v, g, beta)))
        np.testing.assert_allclose(np.asarray(o_t), want_o[:, t], atol=2e-5)


def test_a_padded_tail_leaves_the_state_after_the_last_real_position():
    """Rows of 70, 64 and 1 real positions in a call of 128: with ``b = 0``
    and ``a = 1`` past the row's length the state that comes out is the
    one the row's real positions alone give; a length that is not a power
    of two is padded the same way."""
    lens = (70, 64, 1)
    q, k, v, g, beta = _recurrence_inputs(jax.random.PRNGKey(4), 3, 128)
    real = jnp.arange(128)[None, :] < jnp.asarray(lens)[:, None]
    o, state = kda_chunk_scan(q, k, v,
                              jnp.where(real[..., None, None], g, 0.0),
                              jnp.where(real[..., None], beta, 0.0))
    for row, n in enumerate(lens):
        want_o, want_state = _token_at_a_time(
            *(x[row:row + 1, :n] for x in (q, k, v, g, beta)))
        np.testing.assert_allclose(np.asarray(state[row]), want_state[0],
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(o[row, :n]), want_o[0],
                                   atol=2e-5)
    o, state = kda_chunk_scan(*(x[:, :70] for x in (q, k, v, g, beta)))
    want_o, want_state = _token_at_a_time(
        *(x[:, :70] for x in (q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(state), want_state, atol=2e-5)
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """Every rank's routed part, with the shared expert counted once,
    equals the uncut reference's expert layer (the guide's section 4)."""
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


@pytest.mark.parametrize("knobs, names", [
    (dict(prefix_cache=True), "prefix_cache=True: a shared page is of no "
                              "use without the recurrent state"),
    (dict(tp=2), "tp=2: there is no per-rank forward"),
    (dict(spec_draft_len=2), "spec_draft_len=2: a rejected draft is rolled "
                             "back by set_lengths"),
    (dict(kv_quant="int8"), "kv_quant='int8': the block-scale codec"),
    (dict(block_k=8), "block_k=8"),
])
def test_engine_modes_this_model_lacks_are_refused_at_build(knobs, names):
    with pytest.raises(ValueError, match=names):
        engine_of(tiny(), {}, **knobs)


@pytest.mark.parametrize("change, names", [
    (dict(expert_swiglu_limit_list=[0, 0, 4, 0, 0, 0, 0]),
     r"expert_swiglu_limit_list\[:7\].*clamps that layer's SwiGLU"),
    (dict(share_expert_swiglu_limit_list=[0] * 6 + [7]),
     r"share_expert_swiglu_limit_list\[:7\].*clamp"),
    (dict(q_lora_rank=32), "q_lora_rank"),
    (dict(rope_scaling={"factor": 2}), "rope_scaling"),
    (dict(use_kda_lora=True), "use_kda_lora"),
    (dict(gated_attention_proj_granularity_type="element_wise"),
     "gated_attention_proj_granularity_type"),
    (dict(num_key_value_heads=2), "grouped key-value heads"),
    (dict(v_head_dim=8), "head_dim x head_dim"),
])
def test_a_config_this_forward_cannot_compute_is_refused(change, names):
    with pytest.raises(ValueError, match=names):
        model_of(tiny(**change))
    if "swiglu" in names:                    # and the reference refuses it
        cfg = tiny(**change)
        with pytest.raises(ValueError, match="clamp"):
            reference.forward_logits(cfg, {}, np.zeros((1, 4)), [(0, 0)])


def test_page_and_state_paths_the_model_has_no_mechanism_for_are_named():
    cfg = tiny()
    engine = engine_of(cfg, reference.make_params(cfg, 1))
    with pytest.raises(ValueError, match="ling_hybrid pages do not migrate: "
                                         "a page is of no use without"):
        engine.export_prefix_pages([1, 2, 3])
    with pytest.raises(ValueError, match="ling_hybrid pages do not migrate"):
        engine.import_prefix_pages([])
    with pytest.raises(ValueError, match="copy_page: a page of this cache "
                                         "is shared with nothing"):
        kv_cache.copy_page(engine.cache, 1, 2)


def test_the_seam_says_fewer_planes_than_layers_and_counts_the_state():
    cfg = tiny()
    model = model_of(cfg)
    assert [model.is_mla(i) for i in range(7)] == [False] * 5 + [True, False]
    assert (model.kda_layers, model.mla_layers) == (6, 1)
    engine = engine_of(cfg, reference.make_params(cfg, 1))
    seam = engine.model
    assert (seam.name, seam.n_layer, seam.cache_planes) == ("ling_hybrid",
                                                            7, 1)
    assert (seam.heads, seam.head_dim, seam.counters_span) == (4, 24,
                                                               "routing")
    cache = engine.cache
    assert cache.rows.shape == (1, 65, 16, 128)     # 24 wide in whole lanes
    assert cache.state.shape == (6, 4, 4, 16, 16)
    assert cache.state.dtype == jnp.float32
    assert cache.conv.shape == (6, 4, 3 * 3 * 64) and cache.slots is None
    per_slot = 4 * 4 * 16 * 16 + 3 * 192 * 4       # float32 at this size
    assert model.state_bytes_per_slot == per_slot
    assert engine.kv_cache_bytes == 65 * 16 * 128 * 4 + 6 * 4 * per_slot
    workload = engine.cost_ledger(chip="cpu")["workload"]
    assert workload["model"] == "ling_hybrid" and workload["n_layer"] == 7
    assert workload["kda_layers"] == 6 and workload["mla_layers"] == 1
    # at the published widths: 2.10 MB of state a slot a layer, 2.17 with
    # the convolution's tail in bfloat16
    full = LingHybridConfig()
    assert 4 * 32 * 128 * 128 == 2_097_152
    assert full.state_bytes_per_slot == 2_097_152 + 3 * 12_288 * 2
    assert (full.kda_layers, full.mla_layers) == (35, 7)


def test_counters_ride_the_calls_own_spans_with_the_state_they_moved(
        monkeypatch):
    from apex_tpu.serve import engine as engine_module

    seen = []
    real = engine_module.annotate

    def recording(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(engine_module, "annotate", recording)
    cfg = tiny(held=8, offset=8)
    engine = engine_of(cfg, reference.make_params(cfg, 9))
    engine.prefill({0: list(range(1, 12)), 3: list(range(20, 36))})
    engine.decode_step(engine.last_tokens,
                       np.array([True, False, False, True]))
    names = [n for n, _ in seen]
    for call in ("apex.prefill", "apex.decode_step"):
        assert names.index(call + ".fetch") < names.index(call + ".routing")
    spans = dict(seen)
    layers, held = 6, 8
    pre, dec = spans["apex.prefill.routing"], spans["apex.decode_step.routing"]
    assert pre["picks"] == (11 + 16) * 8 * layers
    assert dec["picks"] == 2 * 8 * layers
    for got in (pre, dec):
        assert got["experts_held"] == held * layers
        assert 0 < got["picks_here"] <= got["picks"]
        assert 0 < got["experts_hit"] <= min(got["experts_held"],
                                             got["picks_here"])
        assert got["state_slots"] == 2
    per_slot = model_of(cfg).state_bytes_per_slot
    assert pre["state_bytes"] == 2 * 6 * per_slot        # written
    assert dec["state_bytes"] == 2 * 2 * 6 * per_slot    # read and written


def test_the_scheduler_serves_the_model_through_the_normal_path():
    """Nine requests on four slots with an abort mid-stream and one in the
    queue: slots are evicted and re-admitted under the scheduler's own
    churn, the decode program compiles once, and a stream served last, in
    a slot that others used before it, is greedy by the reference."""
    cfg = tiny()
    params = reference.make_params(cfg, 21)
    engine = engine_of(cfg, params)
    sched = ServeScheduler(engine)
    rng = np.random.default_rng(3)
    requests = [Request(request_id=i, max_new_tokens=6 + i,
                        tokens=rng.integers(0, 512, 5 + 7 * i).tolist())
                for i in range(9)]
    for r in requests:
        sched.submit(r)
    for tick in range(300):
        if tick == 3:
            assert sched.abort(1) is True         # running, mid-stream
            assert sched.abort(8) is True         # still queued
        if all(r.state not in ("queued", "running") for r in requests):
            break
        sched.step()
    served = [r for r in requests if r.request_id not in (1, 8)]
    assert all(r.state == "completed" and r.finish_reason == "length"
               and len(r.generated) == r.max_new_tokens for r in served)
    assert engine.decode_traces == 1
    for r in served[-2:]:
        seq = list(r.tokens) + list(r.generated)
        rows = [(0, len(r.tokens) - 1 + j) for j in range(len(r.generated))]
        want = np.asarray(reference.forward_logits(
            cfg, params, np.asarray([seq]), rows))
        gap = want.max(-1) - want[np.arange(len(rows)), r.generated]
        assert gap.max() < 1e-3


def test_importing_apex_tpu_loads_nothing_of_the_model():
    import subprocess

    child = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import apex_tpu; "
         "import apex_tpu.serve.engine, apex_tpu.serve.model; "
         "assert 'apex_tpu.models.ling_hybrid' not in sys.modules; "
         "assert 'apex_tpu.models.ouro' not in sys.modules" % ROOT],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert child.returncode == 0, child.stderr[-2000:]
