"""Ouro (``model_type: ouro``, ByteDance's looped language models), plainly:
seeded weights and a float32 forward, for the benchmark. Imports nothing
of ``apex_tpu``. ``cfg`` is a configuration file's dict.

``T = total_ut_steps`` passes through the same ``L = num_hidden_layers``
layers. A token's row ``x`` at position ``p``:

- ``x = E[token]``;
- for ``t = 0 .. T-1``, for ``i = 0 .. L-1``, the same weights at every
  ``t``: ``a = rms(x, g1_i)``; ``q, k, v = a Wq_i, a Wk_i, a Wv_i`` as
  heads of ``head_dim``; ``q, k`` rotated by ``p * theta^(-2j / head_dim)``
  with the halves rotated (``[-b, a]``) over the whole head; causal
  softmax attention at ``1 / sqrt(head_dim)`` over the keys and values
  THIS pass made (no cache here: the full sequence is in hand); ``x = x +
  rms(o Wo_i, g2_i)``; ``m = rms(x, g3_i)``; ``x = x + rms((silu(m Wg_i) *
  (m Wu_i)) Wd_i, g4_i)``;
- after the last layer of a pass ``h_t = rms(x, g_f)``, the gate ``lam_t =
  sigmoid(h_t . w_e + b_e)``, and ``x = h_t`` enters pass ``t + 1``;
- exit: ``P_t = lam_t * prod_{j<t} (1 - lam_j)`` for ``t < T-1`` and the
  rest of the mass at ``T-1``; the token leaves at the first ``t`` whose
  running sum of ``P`` reaches ``early_exit_threshold``, and ``logits =
  h_t W_head`` of that ``t``. Every pass is computed whatever the gate
  says.

**What ``config.json`` has no key for**, taken from the published
description of the family (arXiv:2510.25741 and the repository's
``modeling_ouro.py``, as remembered: there is no network here) and listed
under ``assumed`` in the configuration's file: the norms after attention
and after the MLP (the "sandwich" norm), the absence of biases, the final
norm closing each pass and feeding the next, the gate's form, the exit
rule. No cache is shared or averaged between passes (those are the paper's
variants, not the published default).

Departure, marked ``DEPARTURE`` below: the mixed-precision recipe is one
statement: every weight holds a ``compute_dtype`` value (bfloat16 in the
configuration), every product and activation is float32 at ``highest``.

``mode``: ``fp32`` (the reference) or ``int8`` (weights per output column
and activations per row rounded to 127 levels): the control, one step
below the stated bfloat16.

**Initialisation**: a linear weight N(0, 1 / fan_in), the embedding N(0,
1), a norm's gain N(1, 0.1), the gate's weight N(0, 1 / hidden) with bias
0 (its logit is N(0, 1): ``lam`` spreads over 0.1-0.9), the head N(0, 1 /
hidden): activations and logits are O(1) at any width and depth, because
every sub-layer's output is normed before it is added.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "head_dim", "vocab_size", "rms_norm_eps",
         "rope_theta", "total_ut_steps", "early_exit_threshold",
         "compute_dtype")


def _slim(cfg: dict) -> str:
    return json.dumps({k: cfg[k] for k in _KEYS}, sort_keys=True)


def param_spec(cfg: dict) -> dict:
    """``{path: (shape, mean, std)}`` for every leaf, in a fixed order;
    a layer's leaves are stacked over the layers."""
    e, w, n = cfg["hidden_size"], cfg["intermediate_size"], \
        cfg["num_hidden_layers"]
    a = cfg["num_attention_heads"] * cfg["head_dim"]
    spec = {("embed",): ((cfg["vocab_size"], e), 0.0, 1.0),
            ("head",): ((cfg["vocab_size"], e), 0.0, e ** -0.5),
            ("norm",): ((e,), 1.0, 0.1),
            ("exit_w",): ((e,), 0.0, e ** -0.5),
            ("exit_b",): ((), 0.0, 0.0)}
    for name in ("g1", "g2", "g3", "g4"):
        spec[("layers", name)] = ((n, e), 1.0, 0.1)
    for name, fan_in, fan_out in (("wq", e, a), ("wk", e, a), ("wv", e, a),
                                  ("wo", a, e), ("w_gate", e, w),
                                  ("w_up", e, w), ("w_down", w, e)):
        spec[("layers", name)] = ((n, fan_in, fan_out), 0.0, fan_in ** -0.5)
    return spec


def seed_key(seed: int):
    """A raw threefry key from any whole number up to 64 bits."""
    seed = int(seed)
    return jnp.asarray(np.array([(seed >> 32) & 0xFFFFFFFF,
                                 seed & 0xFFFFFFFF], np.uint32))


@functools.lru_cache(maxsize=4)
def _jitted_params(cfg_json: str):
    cfg = json.loads(cfg_json)
    spec = param_spec(cfg)
    dtype = getattr(jnp, cfg["compute_dtype"])

    def make(key_data):
        key = jax.random.wrap_key_data(key_data)
        tree: dict = {}
        for i, (path, (shape, mean, std)) in enumerate(spec.items()):
            leaf = mean + std * jax.random.normal(
                jax.random.fold_in(key, i), shape, _F32)
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf.astype(dtype)   # drawn float32, held so
        return {"params": tree}

    return jax.jit(make)


def make_params(cfg: dict, seed: int):
    """Every weight from the seed, in one jitted call, each leaf drawn in
    float32 and rounded to ``compute_dtype`` inside it."""
    return _jitted_params(_slim(cfg))(seed_key(seed))


# ------------------------------------------------------------ the forward


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, mode):
    """``x [.., in] @ w [in, out]`` in float32 (DEPARTURE: whatever the
    weights are held in)."""
    x, w = x.astype(_F32), w.astype(_F32)
    if mode == "int8":
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode != "fp32":
        raise ValueError(f"no mode {mode!r}")
    return jnp.einsum("...i,io->...o", x, w, precision=_HI)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(_F32)


def _rope(x, cfg):
    """Rotate-half over the whole head: ``x [t, heads, d]``, position
    ``t`` along the first axis."""
    t, _, d = x.shape
    inv_freq = float(cfg["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=_F32) / d)
    angle = jnp.arange(t, dtype=_F32)[:, None] * inv_freq
    angle = jnp.concatenate([angle, angle], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(angle) + rotated * jnp.sin(angle)


def _layer(x, w, cfg, mode):
    """One layer over one sequence ``x [t, hidden]``."""
    t, eps = x.shape[0], cfg["rms_norm_eps"]
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    a = _rms(x, w["g1"], eps)
    q = _rope(_linear(a, w["wq"], mode).reshape(t, h, d), cfg)
    k = _rope(_linear(a, w["wk"], mode).reshape(t, h, d), cfg)
    v = _linear(a, w["wv"], mode).reshape(t, h, d)
    score = jnp.einsum("qhd,khd->hqk", q, k, precision=_HI) * d ** -0.5
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(score, -1), v,
                   precision=_HI).reshape(t, h * d)
    x = x + _rms(_linear(o, w["wo"], mode), w["g2"], eps)
    m = _rms(x, w["g3"], eps)
    y = _linear(jax.nn.silu(_linear(m, w["w_gate"], mode))
                * _linear(m, w["w_up"], mode), w["w_down"], mode)
    return x + _rms(y, w["g4"], eps)


@functools.partial(jax.jit, static_argnames=("cfg_json", "mode"))
def _block(x, w, *, cfg_json, mode):
    """One layer over ``x [n, t, hidden]``, a sequence at a time, so that
    one sequence's scores and one layer's float32 weights are all that is
    live."""
    cfg = json.loads(cfg_json)
    return jax.lax.map(lambda seq: _layer(seq, w, cfg, mode), x)


@functools.partial(jax.jit, static_argnames=("eps",))
def _close_pass(x, norm, exit_w, exit_b, *, eps):
    """``h_t`` and the gate ``lam_t`` (the gate is no linear layer of the
    model's width: float32 in every mode)."""
    h = _rms(x, norm, eps)
    return h, jax.nn.sigmoid(
        jnp.einsum("...i,i->...", h, exit_w.astype(_F32), precision=_HI)
        + exit_b.astype(_F32))


def exit_pass(lams, threshold: float):
    """The pass each row leaves at, from its gates ``lams [T, rows]``:
    the first whose running sum of ``P`` reaches ``threshold``, the last
    where none does."""
    last = len(lams) - 1
    left = jnp.ones_like(lams[0])
    total = jnp.zeros_like(lams[0])
    leaves_at = jnp.full(lams[0].shape, last, jnp.int32)
    for t in range(last):              # the last pass takes what is left
        total = total + lams[t] * left
        left = left * (1.0 - lams[t])
        leaves_at = jnp.minimum(leaves_at, jnp.where(
            total >= threshold, t, last)).astype(jnp.int32)
    return leaves_at


@functools.partial(jax.jit, static_argnames=("mode",))
def _head(h, head, *, mode):
    return _linear(h, head.T, mode)


def forward_logits(cfg: dict, params, tokens, rows, mode: str = None):
    """Logits ``[len(rows), vocab]`` at the ``(sequence, position)`` pairs
    in ``rows``, for ``tokens [n, t]`` (causal, so padding at the end of a
    sequence changes nothing before it): the full causal forward, no
    cache, pass by pass and layer by layer."""
    mode = mode or "fp32"
    p = params["params"] if "params" in params else params
    cfg_json = _slim(cfg)
    seq, pos = (jnp.asarray(a, jnp.int32) for a in zip(*rows))
    x = p["embed"][jnp.asarray(tokens, jnp.int32)].astype(_F32)
    states, lams = [], []
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            w = {k: v[i] for k, v in p["layers"].items()}
            x = _block(x, w, cfg_json=cfg_json, mode=mode)
        x, lam = _close_pass(x, p["norm"], p["exit_w"], p["exit_b"],
                             eps=cfg["rms_norm_eps"])
        states.append(x[seq, pos])
        lams.append(lam[seq, pos])
    at = exit_pass(jnp.stack(lams), float(cfg["early_exit_threshold"]))
    picked = jnp.take_along_axis(jnp.stack(states), at[None, :, None], 0)[0]
    return _head(picked, p["head"], mode=mode)
