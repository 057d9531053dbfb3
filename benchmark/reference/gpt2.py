"""GPT-2, plainly: seeded weights and a float32 forward, for the benchmark.

Imports nothing of ``apex_tpu``. It follows the published model (learned
positions, pre-LayerNorm blocks, fused qkv, tied output head), and computes
the GELU that the configuration's ``activation_function`` names: ``gelu_new``
(the tanh form, as published) or ``gelu`` (the exact erf form). The
parameter tree has the layout the program's ``Engine`` takes as input.

``mode`` says how the matrix products are computed: ``fp32`` (the
reference, at ``highest`` precision) or ``int8`` (weights per output column
and activations per row rounded to 127 levels): the control, one step below
the bfloat16 that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, mean, std)}`` for every leaf, in a fixed order."""
    e, v, p = cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    spec = {("wte",): ((v, e), 0.0, 0.02), ("wpe",): ((p, e), 0.0, 0.01)}

    def norm(*path):
        spec[path + ("weight",)] = ((e,), 1.0, 0.1)
        spec[path + ("bias",)] = ((e,), 0.0, 0.02)

    for i in range(cfg["n_layer"]):
        h = f"h_{i}"
        norm(h, "ln_1")
        spec[(h, "attn_qkv", "kernel")] = ((e, 3 * e), 0.0, 0.02)
        spec[(h, "attn_qkv", "bias")] = ((3 * e,), 0.0, 0.02)
        spec[(h, "attn_out", "kernel")] = ((e, e), 0.0, 0.02)
        spec[(h, "attn_out", "bias")] = ((e,), 0.0, 0.02)
        norm(h, "ln_2")
        spec[(h, "mlp_fc_w")] = ((4 * e, e), 0.0, 0.02)
        spec[(h, "mlp_fc_b")] = ((4 * e,), 0.0, 0.02)
        spec[(h, "mlp_proj_w")] = ((e, 4 * e), 0.0, 0.02)
        spec[(h, "mlp_proj_b")] = ((e,), 0.0, 0.02)
    norm("ln_f")
    return spec


def seed_key(seed: int):
    """A raw threefry key from any whole number up to 64 bits."""
    seed = int(seed)
    return jnp.asarray(np.array([(seed >> 32) & 0xFFFFFFFF,
                                 seed & 0xFFFFFFFF], np.uint32))


@functools.lru_cache(maxsize=4)
def _jitted_params(cfg_items: tuple):
    spec = param_spec(dict(cfg_items))

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree: dict = {}
        for i, (path, (shape, mean, std)) in enumerate(spec.items()):
            leaf = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return {"params": tree}

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    """Every weight from the seed, float32, in one jitted call."""
    keys = ("n_embd", "n_layer", "n_head", "vocab_size", "n_positions")
    return _jitted_params(tuple((k, int(cfg[k])) for k in keys))(
        seed_key(seed))


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, b, mode, w_in_axis=0):
    """``x [.., in] @ w`` with ``w`` as ``[in, out]`` (``w_in_axis`` 0) or
    ``[out, in]`` (1); ``b`` may be None."""
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, w_in_axis)
    elif mode != "fp32":
        raise ValueError(f"no mode {mode!r}")
    eq = "...i,io->...o" if w_in_axis == 0 else "...i,oi->...o"
    y = jnp.einsum(eq, x, w, precision=_HI)
    return y if b is None else y + b


def _layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["weight"] + p["bias"]


_GELU = {"gelu_new": functools.partial(jax.nn.gelu, approximate=True),
         "gelu": functools.partial(jax.nn.gelu, approximate=False)}


@functools.partial(jax.jit, static_argnames=("n_head", "act", "mode"))
def _block(x, blk, *, n_head, act, mode):
    n, t, e = x.shape
    d = e // n_head
    qkv = _linear(_layer_norm(x, blk["ln_1"]), blk["attn_qkv"]["kernel"],
                  blk["attn_qkv"]["bias"], mode)
    q, k, v = (a.reshape(n, t, n_head, d) for a in jnp.split(qkv, 3, -1))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=_HI) / (d ** 0.5)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v,
                   precision=_HI).reshape(n, t, e)
    x = x + _linear(o, blk["attn_out"]["kernel"], blk["attn_out"]["bias"],
                    mode)
    h = _linear(_layer_norm(x, blk["ln_2"]), blk["mlp_fc_w"],
                blk["mlp_fc_b"], mode, w_in_axis=1)
    h = _GELU[act](h)
    return x + _linear(h, blk["mlp_proj_w"], blk["mlp_proj_b"], mode,
                       w_in_axis=1)


@functools.partial(jax.jit, static_argnames=("mode",))
def _head(h, ln_f, wte, *, mode):
    return _linear(_layer_norm(h, ln_f), wte, None, mode, w_in_axis=1)


def forward_logits(cfg: dict, params, tokens, rows, mode: str = "fp32"):
    """Logits ``[len(rows), vocab]`` at the ``(sequence, position)`` pairs
    in ``rows``, for ``tokens [n, t]`` (causal, so padding at the end of a
    sequence changes nothing before it). One layer at a time, so that the
    activations of one layer are all that is live."""
    p = params["params"]
    tokens = jnp.asarray(tokens, jnp.int32)
    x = p["wte"][tokens] + p["wpe"][: tokens.shape[1]][None]
    for i in range(cfg["n_layer"]):
        x = _block(x, p[f"h_{i}"], n_head=cfg["n_head"],
                   act=cfg["activation_function"], mode=mode)
    seq, pos = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    return _head(x[seq, pos], p["ln_f"], p["wte"], mode=mode)
