"""Ouro (``model_type: ouro``: ByteDance's looped language models) on the
serving path: a dense decoder whose ``num_hidden_layers`` layers are run
``total_ut_steps`` times a token with the SAME weights, every pass with
key-value planes of its own.

A layer is RMSNorm before AND after each of attention and the MLP (four
norms), rotary embedding over the whole head (rotate-half, no scaling),
one key-value head a query head, SwiGLU, no bias anywhere. The final norm
closes every pass, and what it gives is what enters the next pass. A gate
on that normed state gives each pass an exit probability; the token leaves
at the first pass whose running sum reaches ``early_exit_threshold``, which
only picks WHICH pass's state goes to the untied head: every pass is
computed whatever the gate says, because later tokens attend over the keys
and values of every plane. At the published threshold, 1, every token
takes the last pass's state.

**Planes against layers.** Pass ``t`` of layer ``i`` appends to, and
attends over, cache plane ``t * num_hidden_layers + i``: the cache has
``total_ut_steps * num_hidden_layers`` planes for ``num_hidden_layers``
layers of weights. The passes are ONE loop in the traced program and the
layers inside a pass one scan over the stacked weights, so the plane is a
traced int32, the pool rides both loops' carries in place, and the module
is one layer long however deep the model is.

There is one forward, :func:`ouro_token_forward`, in the two shapes
``gpt2_token_forward`` has; ``serve.Engine`` reaches it through
:meth:`OuroConfig.serving_model`. The append and the attention are
GPT-2's own (``models/gpt2.py:_append_and_attend``: the paged pool, the
loop over the key chunks a slot can reach, the chunk's plain block).

The parameter tree (every leaf in ``compute_dtype``; ``L`` layers, ``A =
heads * head_dim``)::

    embed [vocab, hidden]   head [vocab, hidden]   norm [hidden]
    exit_w [hidden]         exit_b []
    layers: g1, g2, g3, g4 [L, hidden]
            wq, wk, wv [L, hidden, A]              wo [L, A, hidden]
            w_gate, w_up [L, hidden, width]        w_down [L, width, hidden]
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from apex_tpu.models.deepseek_v3 import _dot, rms_norm
from apex_tpu.models.gpt2 import _append_and_attend, _chunk_geometry
from apex_tpu.serve.moe import swiglu
from apex_tpu.transformer.rope import rope_rotate_half

_f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    """The published keys of an ``ouro`` ``config.json`` that shape the
    forward."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Any = None
    max_position_embeddings: int = 65536
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    use_sliding_window: bool = False
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "grouped key-value heads are not served: the paged pool "
                "holds one key-value head a query head")
        if self.rope_scaling is not None or self.use_sliding_window:
            raise ValueError("this forward has plain rotary frequencies "
                             "and full attention in every layer")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps must be at least 1")

    @classmethod
    def from_dict(cls, cfg: dict, **kw):
        """From a ``config.json``'s dict; keys this forward does not read
        are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        got = {k: v for k, v in cfg.items() if k in names}
        if isinstance(got.get("compute_dtype"), str):
            got["compute_dtype"] = getattr(jnp, got["compute_dtype"])
        return cls(**{**got, **kw})

    @property
    def cache_planes(self) -> int:
        """One key-value plane a layer a pass."""
        return self.total_ut_steps * self.num_hidden_layers

    def serving_model(self):
        """What ``serve.Engine`` asks of a model (``serve/model.py``)."""
        from apex_tpu.serve.model import OuroServing

        return OuroServing(self)


def exit_step(lam, t, last, threshold, state):
    """One pass of the exit rule, for rows whose gate reads ``lam``
    (float32 ``[rows]``) after pass ``t`` of ``last + 1``: pass ``t``
    takes ``lam`` of the mass still left (all of it at the last), and a
    row leaves at the first pass whose running sum reaches ``threshold``.
    ``state`` is ``(left, total, exit_pass)``, ``exit_pass`` ``-1`` while
    the row has not left. Returns ``(state, leaves now [rows] bool)``."""
    left, total, exit_pass = state
    share = jnp.where(t == last, left, lam * left)
    total = total + share
    leaves = (exit_pass < 0) & ((total >= threshold) | (t == last))
    return ((left * (1.0 - lam), total,
             jnp.where(leaves, t, exit_pass).astype(jnp.int32)), leaves)


def ouro_token_forward(cfg: OuroConfig, params, cache, tokens, positions,
                       write_mask, logits_at=None, *, block_k=None,
                       kv_quant=None, final_scope: str = "sampling"):
    """One token a slot, or one chunk of a prompt a slot, through the
    looped model with the paged cache: the contract of
    :func:`~apex_tpu.models.gpt2.gpt2_token_forward` (shapes, masks,
    ``logits_at``), over a cache of ``cfg.cache_planes`` planes, and a
    third result. Returns ``(logits float32, cache, loop int32[2])``:
    over the rows under ``write_mask``, the passes run (rows times
    passes) and the rows that left before the last pass.

    The scopes are ``gpt2_token_forward``'s: ``ln_qkv`` (the norm, the
    three projections, the rotation), ``attention`` with ``kv_write`` and
    ``attn_proj`` inside (the output projection and the norm that closes
    the sub-layer), ``mlp`` (its two norms with it), and ``final_scope``
    (the norm and the gate that close a pass, and the head)."""
    c = cfg
    p = params["params"] if "params" in params else params
    dt = c.compute_dtype
    h, d, eps = c.num_attention_heads, c.head_dim, c.rms_norm_eps
    n_layer, last = c.num_hidden_layers, c.total_ut_steps - 1
    pos = positions.astype(jnp.int32)
    flat_pos = pos.reshape(-1)
    real = write_mask.reshape(-1)
    block_k, trips = _chunk_geometry(cache, h, d, dt, block_k, pos,
                                     write_mask)
    inv_freq = c.rope_theta ** (-jnp.arange(0, d, 2, dtype=_f32) / d)

    def rotate(y):
        return rope_rotate_half(y.reshape(-1, h, d), flat_pos[:, None],
                                inv_freq)

    def layer(carry, xs):
        x, cache = carry
        plane, w = xs
        with jax.named_scope("ln_qkv"):
            a = rms_norm(x, w["g1"], eps)
            q, k = rotate(_dot(a, w["wq"])), rotate(_dot(a, w["wk"]))
            v = _dot(a, w["wv"]).reshape(-1, h, d)
        with jax.named_scope("attention"):
            o, cache = _append_and_attend(cache, plane, q, k, v, pos,
                                          write_mask, block_k, trips,
                                          kv_quant)
            with jax.named_scope("attn_proj"):
                x = x + rms_norm(_dot(o.reshape(-1, h * d), w["wo"]),
                                 w["g2"], eps)
        with jax.named_scope("mlp"):
            y = swiglu(rms_norm(x, w["g3"], eps), w["w_gate"], w["w_up"],
                       w["w_down"])
            x = x + rms_norm(y.astype(x.dtype), w["g4"], eps)
        return (x, cache), None

    def one_pass(t, carry):
        x, cache, picked, state = carry
        # THE loop: the same weights every trip, plane t * L + i as data
        planes = t * n_layer + jnp.arange(n_layer, dtype=jnp.int32)
        (x, cache), _ = jax.lax.scan(layer, (x, cache),
                                     (planes, p["layers"]))
        with jax.named_scope(final_scope):
            x = rms_norm(x, p["norm"], eps)     # h_t: enters pass t + 1
            lam = jax.nn.sigmoid(
                jnp.dot(x.astype(_f32), p["exit_w"].astype(_f32),
                        precision=jax.lax.Precision.HIGHEST)
                + p["exit_b"].astype(_f32))
            state, leaves = exit_step(lam, t, last, c.early_exit_threshold,
                                      state)
            picked = jnp.where(leaves[:, None], x, picked)
        return x, cache, picked, state

    x = p["embed"][tokens].astype(dt).reshape(-1, c.hidden_size)
    rows = x.shape[0]
    state = (jnp.ones((rows,), _f32), jnp.zeros((rows,), _f32),
             jnp.full((rows,), -1, jnp.int32))
    _, cache, picked, state = jax.lax.fori_loop(
        0, c.total_ut_steps, one_pass, (x, cache, jnp.zeros_like(x), state))
    with jax.named_scope(final_scope):
        picked = picked.reshape(pos.shape + picked.shape[-1:])
        if logits_at is not None:
            picked = picked[jnp.arange(pos.shape[0]),
                            logits_at.astype(jnp.int32)]
        logits = jax.lax.dot_general(
            picked, p["head"], (((picked.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_f32)
        real_rows = jnp.sum(real.astype(jnp.int32))
        loop = jnp.stack([real_rows * c.total_ut_steps,
                          jnp.sum((real & (state[2] < last))
                                  .astype(jnp.int32))])
    return logits, cache, loop
