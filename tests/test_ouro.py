"""Ouro (a looped decoder) on the serving path, at a small size on the CPU.

The program (``models/ouro.py`` through ``serve.Engine`` and
``ServeScheduler``: 3 layers of weights run 3 times a token over a paged
pool of 9 planes) against the plain reference (``benchmark/reference/
ouro.py``, which imports nothing of ``apex_tpu``) on seeded weights:
logits, never tokens.
"""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from reference import ouro as reference  # noqa: E402

from apex_tpu.models import ouro  # noqa: E402
from apex_tpu.models.gpt2 import GPT2Config  # noqa: E402
from apex_tpu.models.ouro import OuroConfig, exit_step  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   init_gpt2_params)
from apex_tpu.serve.scheduler import Request, ServeScheduler  # noqa: E402
from apex_tpu.transformer.rope import rope_rotate_half  # noqa: E402

LAYERS, PASSES = 3, 3


def tiny(dtype="float32", **kw):
    """A configuration file's dict at a size for the CPU: the reference
    reads it as it is, the program through ``OuroConfig.from_dict``."""
    return dict(dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        num_hidden_layers=LAYERS, num_attention_heads=4,
        num_key_value_heads=4, head_dim=16, rms_norm_eps=1e-6,
        rope_theta=1000000, rope_scaling=None, max_position_embeddings=512,
        total_ut_steps=PASSES, early_exit_threshold=1,
        use_sliding_window=False, model_type="ouro", compute_dtype=dtype),
        **kw)


def engine_of(cfg, params, **kw):
    geometry = dict(num_slots=4, max_len=128, temperature=0.0, page_size=16,
                    num_pages=33, prefix_cache=True)
    return Engine(OuroConfig.from_dict(cfg), params,
                  EngineConfig(**{**geometry, **kw}))


def noise_share(got, ref):
    """The benchmark's ``logit_noise_share``: squared distance over
    squared size, each row about its own mean."""
    def centred(x):
        return x - x.mean(-1, keepdims=True)

    return float(np.square(centred(got) - centred(ref)).sum()
                 / np.square(centred(ref)).sum())


def served_against_reference(cfg, seed=2**31 + 5, steps=24, control=False):
    """Four prompts prefilled together, then ``steps`` decode steps through
    the cache: ``(the logits every token was drawn from, the reference's
    full forward at the same positions, the int8 control's where asked
    for, the engine)``."""
    params = reference.make_params(cfg, seed)
    engine = engine_of(cfg, params)
    rng = np.random.default_rng(0)
    prompts = {s: rng.integers(0, 512, n).tolist()
               for s, n in enumerate((11, 16, 9, 14))}
    first, last_logits, _ = engine.prefill(prompts)
    seqs = {s: list(p) + [int(first[s])] for s, p in prompts.items()}
    got = {s: [np.asarray(last_logits[s])] for s in prompts}
    active = np.ones((4,), bool)
    for _ in range(steps):
        nxt, logits = engine.decode_step(engine.last_tokens, active)
        for s in prompts:
            got[s].append(np.asarray(logits[s]))
            seqs[s].append(int(nxt[s]))
    tokens = np.zeros((4, max(map(len, seqs.values()))), np.int64)
    rows = []
    for i, s in enumerate(prompts):
        tokens[i, :len(seqs[s])] = seqs[s]
        rows += [(i, len(prompts[s]) - 1 + j) for j in range(steps + 1)]
    want = np.asarray(reference.forward_logits(cfg, params, tokens, rows))
    control = control and np.asarray(reference.forward_logits(
        cfg, params, tokens, rows, "int8"))
    have = np.concatenate([np.stack(got[s]) for s in prompts])
    return have, want, control, engine


# float32: both sides compute the same sums in another order (a batched
# product against a one-row product, the softmax over a prompt against 16-key
# chunks of the cache): 1e-12 on this seed, the limit a thousand times the
# rounding and a hundred-millionth of a mistake (one plane for all passes
# reads 0.63). bfloat16: the weights are the same bfloat16 values on both
# sides, the program rounds every product's output to bfloat16 (2**-9
# relative) where the reference keeps float32, through 9 layer applications:
# 2.5e-4 on this seed (1.4e-4 to 2.5e-4 over five), where the reference in
# int8 (the precision below) reads 1.8e-3 to 4.3e-3.
@pytest.mark.parametrize("dtype, limit", [("float32", 1e-9),
                                          ("bfloat16", 6e-4)])
def test_prefill_then_decode_through_the_planes_matches_the_reference(
        dtype, limit):
    cfg = tiny(dtype)
    have, want, control, engine = served_against_reference(cfg, control=True)
    assert engine.cache.k.shape[0] == LAYERS * PASSES == 9
    assert engine.cache.k.dtype == jnp.dtype(dtype)
    assert engine.decode_traces == 1 and engine.prefill_traces == 1
    assert 0.8 < want.std() < 1.2                 # logits are O(1)
    assert noise_share(have, want) < limit
    assert noise_share(control, want) > 2 * limit   # the control is apart
    if dtype == "float32":
        np.testing.assert_allclose(have, want, atol=1e-4)


def test_one_plane_for_all_passes_fails_the_comparison(monkeypatch):
    """The negative: a forward whose pass ``t`` of layer ``i`` wrote and
    read plane ``i`` (one plane a weight layer, as every other model has
    it) passes prefill, whose chunk attends over its own keys, and fails
    every decode step, which reads the LAST pass's keys in every pass:
    a noise share of 0.63 where the honest program reads 1e-12."""
    real = ouro._append_and_attend

    def one_plane_a_layer(cache, plane, *args):
        return real(cache, plane % LAYERS, *args)

    monkeypatch.setattr(ouro, "_append_and_attend", one_plane_a_layer)
    have, want, _, _ = served_against_reference(tiny(), steps=4)
    assert noise_share(have[:1], want[:1]) < 1e-9   # a prefill's own row
    assert noise_share(have, want) > 0.1


@pytest.mark.parametrize("threshold", [0.5, 1])
def test_the_exit_rule_is_the_references(threshold, monkeypatch):
    """At 0.5 rows leave after the first or second pass and the head reads
    THAT pass's state; at 1 (the published value) every row takes the
    last. The program agrees with the reference to float32 rounding at
    both, and its counter says how many left early."""
    from apex_tpu.serve import engine as engine_module

    seen = []
    real = engine_module.annotate
    monkeypatch.setattr(
        engine_module, "annotate",
        lambda name, **attrs: (seen.append((name, attrs)),
                               real(name, **attrs))[1])
    cfg = tiny(early_exit_threshold=threshold)
    have, want, _, _ = served_against_reference(cfg, steps=6)
    assert noise_share(have, want) < 1e-9
    np.testing.assert_allclose(have, want, atol=1e-4)
    early = sum(a["early_exits"] for n, a in seen if n.endswith(".loop"))
    assert (early > 0) == (threshold < 1)
    # the rule itself, gate by gate, against the reference's
    rng = np.random.default_rng(3)
    lams = jnp.asarray(rng.uniform(0.02, 0.98, (PASSES + 1, 500)),
                       jnp.float32)
    state = (jnp.ones((500,)), jnp.zeros((500,)),
             jnp.full((500,), -1, jnp.int32))
    for t in range(PASSES + 1):
        state, _ = exit_step(lams[t], t, PASSES, threshold, state)
    np.testing.assert_array_equal(
        state[2], reference.exit_pass(lams, threshold))
    assert (np.asarray(state[2]) < PASSES).any() == (threshold < 1)


def test_rotate_half_over_the_whole_head_against_the_reference():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(7, 4, 16)), jnp.float32)
    inv = 1e6 ** (-np.arange(0, 16, 2) / 16)
    ours = rope_rotate_half(x, jnp.arange(7)[:, None], inv)
    theirs = reference._rope(x, {"rope_theta": 1e6})
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               rtol=1e-5, atol=1e-5)
    # position 0 is left alone; a rotation keeps every pair's length
    np.testing.assert_array_equal(np.asarray(ours[0]), np.asarray(x[0]))
    np.testing.assert_allclose(np.linalg.norm(ours, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_a_prefix_hit_reads_pages_of_every_plane_and_equals_a_re_prefill():
    cfg = tiny()
    params = reference.make_params(cfg, 11)
    rng = np.random.default_rng(5)
    head = rng.integers(0, 512, 32).tolist()          # two whole pages
    prompt = head + rng.integers(0, 512, 9).tolist()
    cold = engine_of(cfg, params)
    _, want, _ = cold.prefill({1: prompt})
    assert cold.prefix_hit_tokens == 0
    warm = engine_of(cfg, params)
    warm.prefill({0: head + [7, 8, 9]})
    _, got, _ = warm.prefill({1: prompt})
    assert warm.last_prefill_stats[1]["hit_tokens"] == 32
    assert warm.last_prefill_stats[1]["scanned"] == 9
    # the cached head goes through 16-key chunks, the re-prefill through one
    # block: float32 rounding
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               atol=1e-4)
    assert noise_share(np.asarray(got[1:2]), np.asarray(want[1:2])) < 1e-9
    # and the hit's pages are read in every pass's planes: with the first
    # shared page of one plane of pass t zeroed, the tail's logits move
    page = int(warm._page_table[1, 0])
    for t in range(PASSES):
        warm.evict([1])
        kept = warm.cache
        warm.cache = kept.replace(
            k=kept.k.at[t * LAYERS + 1, page].set(0))
        _, moved, _ = warm.prefill({1: prompt})
        assert warm.last_prefill_stats[1]["hit_tokens"] == 32
        assert noise_share(np.asarray(moved[1:2]),
                           np.asarray(want[1:2])) > 1e-6, t
        warm.cache = warm.cache.replace(
            k=warm.cache.k.at[t * LAYERS + 1, page].set(
                cold.cache.k[t * LAYERS + 1,
                             int(cold._page_table[1, 0])]))


def test_pages_migrate_with_all_their_planes():
    """Export from one engine, import into another: the payload is a page
    of every plane, and the importer's prefix hit gives the donor's
    logits."""
    cfg = tiny()
    params = reference.make_params(cfg, 13)
    prompt = np.random.default_rng(2).integers(0, 512, 40).tolist()
    donor, taker = engine_of(cfg, params), engine_of(cfg, params)
    _, want, _ = donor.prefill({0: prompt})
    payloads = donor.export_prefix_pages(prompt)
    assert len(payloads) == 2
    assert payloads[0]["k"].shape == (LAYERS * PASSES, 16, 8, 16)
    assert taker.import_prefix_pages(payloads) == {
        "installed": 2, "duplicate": 0, "no_capacity": 0}
    _, got, _ = taker.prefill({2: prompt})
    assert taker.last_prefill_stats[2]["hit_tokens"] == 32
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[0]),
                               atol=1e-4)
    with pytest.raises(ValueError, match="payload shape"):
        taker.import_prefix_pages([dict(payloads[0], chain_hash="x",
                                        k=payloads[0]["k"][:LAYERS])])


def test_one_decode_trace_under_admit_evict_and_abort_churn():
    cfg = tiny()
    params = reference.make_params(cfg, 21)
    engine = engine_of(cfg, params)
    sched = ServeScheduler(engine)
    rng = np.random.default_rng(3)
    requests = [Request(request_id=i, max_new_tokens=6 + i,
                        tokens=rng.integers(0, 512, 5 + 3 * i).tolist())
                for i in range(9)]                # 9 requests, 4 slots
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
    assert engine.decode_traces == 1 and engine.prefill_traces <= 3
    # greedy through the scheduler is greedy through the reference
    r = served[-1]
    seq = list(r.tokens) + list(r.generated)
    rows = [(0, len(r.tokens) - 1 + j) for j in range(len(r.generated))]
    want = np.asarray(reference.forward_logits(
        cfg, params, np.asarray([seq]), rows))
    gap = want.max(-1) - want[np.arange(len(rows)), r.generated]
    assert gap.max() < 1e-3


@pytest.mark.parametrize("knobs, names", [
    (dict(tp=2), "tp=2: there is no per-rank forward"),
    (dict(spec_draft_len=2), "spec_draft_len=2: the verify scan"),
    (dict(kv_quant="int8"), "kv_quant='int8': the codec's tolerance"),
    (dict(block_k=5), "block_k=5"),
])
def test_engine_modes_this_model_lacks_are_refused_at_build(knobs, names):
    cfg = tiny()
    with pytest.raises(ValueError, match=names):
        engine_of(cfg, {}, **knobs)


def test_a_config_this_forward_cannot_compute_is_refused():
    with pytest.raises(ValueError, match="grouped key-value heads"):
        OuroConfig.from_dict(tiny(num_key_value_heads=2))
    with pytest.raises(ValueError, match="plain rotary"):
        OuroConfig.from_dict(tiny(use_sliding_window=True))
    cfg = OuroConfig.from_dict(tiny("bfloat16"))
    assert cfg.cache_planes == 9 and cfg.compute_dtype == jnp.bfloat16
    model = cfg.serving_model()
    assert (model.n_layer, model.cache_planes, model.heads,
            model.head_dim) == (3, 9, 4, 16)


def test_the_seam_says_planes_and_layers_for_every_model():
    cfg = tiny()
    engine = engine_of(cfg, reference.make_params(cfg, 1))
    assert (engine.model.n_layer, engine.model.cache_planes) == (3, 9)
    assert engine.kv_cache_bytes == 2 * 9 * 33 * 16 * 8 * 16 * 4
    workload = engine.cost_ledger(chip="cpu")["workload"]
    assert workload["model"] == "ouro" and workload["n_layer"] == 3
    assert workload["cache_planes"] == 9 and workload["total_ut_steps"] == 3
    small = GPT2Config(vocab_size=64, n_positions=32, n_embd=32, n_layer=2,
                       n_head=2, compute_dtype=jnp.float32)
    gpt2 = Engine(small, init_gpt2_params(small), EngineConfig(num_slots=2))
    assert gpt2.model.cache_planes == gpt2.model.n_layer == 2
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_deepseek_v3 import model_of, tiny as tiny_deepseek

    latent = model_of(tiny_deepseek()).serving_model()
    assert latent.cache_planes == latent.n_layer == 3


def test_loop_counters_ride_the_calls_own_spans(monkeypatch):
    """The two programs return the passes run and the rows that left
    early; the engine leaves them, with the rows and the planes, on
    ``apex.<call>.loop`` inside the call's span, after the fetch."""
    from apex_tpu.serve import engine as engine_module

    seen = []
    real = engine_module.annotate

    def recording(name, **attrs):
        seen.append((name, attrs))
        return real(name, **attrs)

    monkeypatch.setattr(engine_module, "annotate", recording)
    cfg = tiny()
    engine = engine_of(cfg, reference.make_params(cfg, 9))
    engine.prefill({0: list(range(1, 12)), 3: list(range(20, 36))})
    engine.decode_step(engine.last_tokens,
                       np.array([True, False, False, True]))
    names = [n for n, _ in seen]
    for call in ("apex.prefill", "apex.decode_step"):
        assert names.index(call + ".fetch") < names.index(call + ".loop")
    assert not [n for n in names if n.endswith(".routing")]
    spans = dict(seen)
    assert spans["apex.prefill.loop"] == {
        "passes": (11 + 16) * PASSES, "rows": 11 + 16,
        "planes": LAYERS * PASSES, "early_exits": 0}
    assert spans["apex.decode_step.loop"] == {
        "passes": 2 * PASSES, "rows": 2, "planes": LAYERS * PASSES,
        "early_exits": 0}
    assert spans["apex.decode_step"]["key_chunks"] == 128 // 16
    assert spans["apex.decode_step"]["attended_chunks"] == 2  # 17 tokens


# the accepted models' four programs, lowered at a tiny size: one source,
# run here (this process has imported ``models/ouro.py``) and in a child
# that has not
_PROGRAMS = '''
import hashlib, os, sys
ROOT = {root!r}
for p in (ROOT, os.path.join(ROOT, "benchmark"), os.path.join(ROOT, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)
import jax
import jax.numpy as jnp
from apex_tpu.models.gpt2 import GPT2, GPT2Config
from apex_tpu.serve.engine import Engine, EngineConfig
from reference import deepseek_v3
from test_deepseek_v3 import model_of, tiny


def texts():
    geometry = dict(num_slots=2, max_len=64, temperature=0.0, page_size=16,
                    num_pages=9, prefix_cache=True)
    gpt2 = GPT2Config(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
                      n_head=2, compute_dtype=jnp.float32)
    cfg = tiny()
    out = {{}}
    # the weights are an argument of every program: their shapes do
    for name, model, weights in (
            ("gpt2", gpt2, jax.eval_shape(
                GPT2(gpt2).init, jax.random.PRNGKey(0),
                jnp.zeros((1, 8), jnp.int32))),
            ("deepseek_v3", model_of(cfg), jax.eval_shape(
                lambda: deepseek_v3.make_params(cfg, 1)))):
        engine = Engine(model, {{}}, EngineConfig(**geometry))
        out[name + ".decode"] = engine._decode.lower(
            weights, *engine._decode_args()[1:]).as_text()
        out[name + ".prefill_16"] = engine._make_prefill(16).lower(
            weights, *engine._prefill_args(16)[1:]).as_text()
    return {{k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in out.items()}}
'''


def test_the_accepted_models_programs_lower_the_same_with_ouro_imported():
    """``test_the_seam_leaves_gpt2s_decode_program_as_it_was``'s twin for
    both accepted models' decode and prefill programs: the lowered text
    in a process that has imported ``models/ouro.py`` (this one) equals
    that of one that has not (a child, which says so)."""
    source = _PROGRAMS.format(root=ROOT)
    child = subprocess.run(
        [sys.executable, "-c", source + (
            "\nimport json\nhashes = texts()\n"
            "assert 'apex_tpu.models.ouro' not in sys.modules\n"
            "print(json.dumps(hashes))\n")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert child.returncode == 0, child.stderr[-2000:]
    import json

    without = json.loads(child.stdout.strip().splitlines()[-1])
    assert "apex_tpu.models.ouro" in sys.modules
    scope: dict = {}
    exec(compile(source, "<programs>", "exec"), scope)
    assert scope["texts"]() == without
    assert sorted(without) == ["deepseek_v3.decode", "deepseek_v3.prefill_16",
                               "gpt2.decode", "gpt2.prefill_16"]
    assert len(set(without.values())) == 4
