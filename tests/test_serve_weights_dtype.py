"""GPT-2 serving holds its matrices in the compute dtype, at a small size on
the CPU.

The seam's ``serving_weights`` casts every matrix of the float32 tree to
``compute_dtype`` once, at engine build; the forward reads a matrix only as
``.astype(compute_dtype)``, so the products take the same bits they took
when each call cast the float32 tree itself (that path is kept here as the
oracle: an engine whose ``serving_weights`` returns the tree unchanged).
Biases and layer-norm leaves keep their dtype; a float32 engine, and the
three models whose weights are made in ``compute_dtype``, get their tree
back as it is; the spans of the engine calls carry the bytes the calls
read.
"""

import dataclasses
import functools
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

from apex_tpu.models.gpt2 import GPT2Config  # noqa: E402
from apex_tpu.serve import engine as engine_mod  # noqa: E402
from apex_tpu.serve.engine import (Engine, EngineConfig,  # noqa: E402
                                   init_gpt2_params)
from apex_tpu.serve.model import GPT2Serving, serving_model  # noqa: E402

CFG = GPT2Config(vocab_size=256, n_positions=64, n_embd=32, n_layer=2,
                 n_head=4, compute_dtype=jnp.bfloat16)
MATRICES = ("wte", "wpe", "attn_qkv']['kernel", "attn_out']['kernel",
            "mlp_fc_w", "mlp_proj_w")
PROMPTS = {0: [5, 17, 3, 99, 42], 1: [7, 7, 200, 1, 64, 31, 9, 12, 250],
           2: [11]}


@functools.lru_cache(maxsize=None)
def _params():
    """GPT-2's float32 tree, as a checkpoint or the benchmark hands it."""
    return init_gpt2_params(CFG, seed=3)


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(path): x for path, x
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_matrix(path: str) -> bool:
    return any(path.endswith(f"['{name}']") for name in MATRICES)


def _engine(params, uncast: bool = False, **kw) -> Engine:
    """An engine over ``params``; ``uncast`` builds it as it was before the
    cast at build: holding the tree as given, every call casting it."""
    geometry = dict(num_slots=3, max_len=32, page_size=8, temperature=0.0)
    geometry.update(kw)
    with pytest.MonkeyPatch.context() as mp:
        if uncast:
            mp.setattr(GPT2Serving, "serving_weights",
                       lambda self, params: params)
        return Engine(CFG, params, EngineConfig(**geometry), seed=11)


def _calls(eng: Engine, steps: int = 4):
    """One prefill call over three slots, then ``steps`` decode steps:
    every call's logits and tokens, off the device."""
    first, last_logits, _ = eng.prefill(PROMPTS)
    out = [(np.asarray(first), np.asarray(last_logits))]
    active = np.ones((3,), bool)
    for _ in range(steps):
        nxt, logits = eng.decode_step(eng.last_tokens, active)
        out.append((nxt, np.asarray(logits)))
    return out


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_serving_weights_casts_the_matrices_alone(dtype):
    """(a) Under bf16 compute every matrix turns bf16 and every bias and
    layer-norm leaf is the same object as before; under float32 compute
    the tree itself comes back."""
    cfg = dataclasses.replace(CFG, compute_dtype=getattr(jnp, dtype))
    params = _params()
    held = serving_model(cfg).serving_weights(params)
    if dtype == "float32":
        assert held is params
        return
    before, after = _leaves(params), _leaves(held)
    assert before.keys() == after.keys()
    assert sum(map(_is_matrix, before)) == 2 + 4 * CFG.n_layer
    for path, x in after.items():
        if _is_matrix(path):
            assert x.dtype == jnp.bfloat16, path
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(before[path].astype(jnp.bfloat16)))
        else:
            assert x is before[path], path
    assert serving_model(cfg).serving_weights(held) is held


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_engine_logits_equal_the_per_call_cast(temperature):
    """(b) An engine built from the float32 tree returns, in its prefill
    call and every decode step, the logits and tokens of the engine that
    holds the float32 tree and casts it in every call, bit for bit,
    greedy and sampled."""
    kw = dict(temperature=temperature, top_k=0 if temperature == 0 else 50)
    eng = _engine(_params(), **kw)
    assert _leaves(eng.params)["['params']['wte']"].dtype == jnp.bfloat16
    old = _engine(_params(), uncast=True, **kw)
    assert _leaves(old.params)["['params']['wte']"].dtype == jnp.float32
    for (tok, logits), (want_tok, want) in zip(_calls(eng), _calls(old)):
        np.testing.assert_array_equal(logits, want)
        np.testing.assert_array_equal(tok, want_tok)


@pytest.mark.parametrize("sync", ["exact"])
def test_tp_engine_logits_equal_the_per_call_cast(sync, tp_devices):
    """(c) A ``tp=2`` engine under bf16 compute on the CPU mesh shards the
    cast tree and returns the logits of ``gpt2_token_forward_tp`` over the
    float32 tree cast in every call, bit for bit."""
    eng = _engine(_params(), tp=2, tp_sync=sync)
    assert eng.params is None
    dtypes = {p: x.dtype for p, x in _leaves(eng._weights).items()}
    assert {dtypes[p] for p in dtypes if _is_matrix(p)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert {dtypes[p] for p in dtypes if not _is_matrix(p)} \
        == {jnp.dtype(jnp.float32)}
    old = _engine(_params(), uncast=True, tp=2, tp_sync=sync)
    for (tok, logits), (want_tok, want) in zip(_calls(eng), _calls(old)):
        np.testing.assert_array_equal(logits, want)
        np.testing.assert_array_equal(tok, want_tok)


def _other(name: str):
    """``(model config, params)`` of another served model, bf16, tiny."""
    if name == "ouro":
        from apex_tpu.models.ouro import OuroConfig
        from reference import ouro as reference

        from test_ouro import tiny
        cfg = tiny("bfloat16")
        return OuroConfig.from_dict(cfg), reference.make_params(cfg, 5)
    if name == "ling_hybrid":
        from reference import ling_hybrid as reference

        from test_ling_hybrid import model_of, tiny
    else:
        from reference import deepseek_v3 as reference

        from test_deepseek_v3 import model_of, tiny
    cfg = tiny("bfloat16")
    return model_of(cfg), reference.make_params(cfg, 5)


@pytest.mark.parametrize("name", ["deepseek_v3", "ouro", "ling_hybrid"])
def test_other_models_keep_their_tree(name):
    """(d) The three models whose weights are made in ``compute_dtype``
    get their tree back by identity, and their engine holds its leaves."""
    model_cfg, params = _other(name)
    model = serving_model(model_cfg)
    assert model.name == name
    assert model.serving_weights(params) is params
    eng = Engine(model_cfg, params, EngineConfig(
        num_slots=2, max_len=64, page_size=16, temperature=0.0))
    held, given = _leaves(eng.params), _leaves(params)
    assert held.keys() == given.keys()
    assert all(held[p] is given[p] for p in given)
    assert eng.weight_bytes == sum(x.nbytes for x in given.values())


@pytest.mark.parametrize("replicas", [2])
def test_engines_given_one_cast_tree_share_it(replicas):
    """(e) Engines given one tree cast up front, as a fleet's replicas are,
    hold the same arrays, not a copy each."""
    shared = serving_model(CFG).serving_weights(_params())
    engines = [_engine(shared) for _ in range(replicas)]
    for eng in engines:
        held = jax.tree_util.tree_leaves(eng.params)
        assert all(a is b for a, b in
                   zip(held, jax.tree_util.tree_leaves(shared)))


@pytest.mark.parametrize("dtype,share", [("bfloat16", 0.5),
                                         ("float32", 1.0)])
def test_weight_bytes_ride_the_call_spans(dtype, share, monkeypatch):
    """(f) ``weight_bytes`` on ``apex.decode_step`` and ``apex.prefill``
    is the bytes of the tree the engine holds: under bf16 compute the
    matrices' half of the float32 tree's, and all of it under float32."""
    seen = []
    real = engine_mod.annotate

    def recording(span, **attrs):
        seen.append((span, attrs))
        return real(span, **attrs)

    monkeypatch.setattr(engine_mod, "annotate", recording)
    cfg = dataclasses.replace(CFG, compute_dtype=getattr(jnp, dtype))
    eng = Engine(cfg, _params(), EngineConfig(num_slots=3, max_len=32,
                                              page_size=8, temperature=0.0))
    _calls(eng, steps=2)
    given = _leaves(_params())
    want = sum(x.nbytes * (share if _is_matrix(p) else 1)
               for p, x in given.items())
    assert eng.weight_bytes == want == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    spans = [(n, a["weight_bytes"]) for n, a in seen
             if n in ("apex.decode_step", "apex.prefill")]
    assert spans == [("apex.prefill", want)] \
        + [("apex.decode_step", want)] * 2
