"""Ling-3.0-flash (``model_type: bailing_hybrid``: inclusionAI's hybrid
language models, the VL release's language model included) on the serving
path: layers of two kinds in one stack, a leading run of dense SwiGLU
layers and then routed experts behind the ``noaux_tc`` router with one
shared expert, RMSNorm, an untied head.

Layer ``i`` is a **latent-attention (MLA)** layer when ``(i + 1) %
layer_group_size == 0``: ``models/deepseek_v3.py:_mla`` over the latent
pages (absorbed in decode, plain in prefill) with the query projected
directly (``q_lora_rank`` null), plain rotary frequencies and a sigmoid gate
a head. Every other layer is a **KDA** layer (Kimi Delta Attention,
arXiv:2510.26692: a gated delta rule with a decay a channel) and keeps NO
rows a token: a float32 state ``[heads, head_dim, head_dim]`` a slot and the
last ``short_conv_kernel_size - 1`` inputs of its convolution, overwritten in
place a call (``serve/kv_cache.py:HybridCache``). For ``u = rms(x)``:

- ``q~, k~, v~ = u W_q, u W_k, u W_v``, each channel through a causal
  convolution over its last 4 steps, then SiLU; per head ``q = l2norm(q) /
  sqrt(D)``, ``k = l2norm(k)``; no rotary;
- ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) * (u W_f + dt_bias))`` a
  head and channel, ``a_t = exp(g_t)``; ``b_t = sigmoid(u W_b)`` a head;
- ``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``;
- ``rms(o_t)`` a head with a gain, times ``sigmoid(u W_g)_h``, then ``W_o``.

The recurrence has two forms here. **Decode** (one token a slot):
:func:`kda_step`, one update of the slot's state. **Prefill** (a chunk a
slot): :func:`kda_chunk_scan`, the same recurrence ``CHUNK`` positions at a
time from a ZERO state (a prefill call is a whole prompt: the model refuses
the prefix cache), exact in float32; a position past a row's real length has
``b = 0`` and ``a = 1``, so the state written is the one after the row's
last real token, and a row the call does not admit writes nothing.

**A rank's share**, as ``models/deepseek_v3.py`` has it: ``experts_held``
routed experts from ``expert_offset`` on (the router keeps all
``num_experts`` columns) and ``vocab_held`` rows of the embedding and head.

There is one forward, :func:`ling_hybrid_token_forward`, in the two shapes
``gpt2_token_forward`` has. A chunk call over more than
``BLOCK_POSITIONS`` positions runs its rows a block at a time, each over a
row view of the cache (``kv_cache.slot_view``), so the largest program's
temporaries are those of a block. ``serve.Engine`` reaches the forward
through :meth:`LingHybridConfig.serving_model`.

The parameter tree (``reference/ling_hybrid.py:param_spec`` makes it; every
leaf in ``compute_dtype`` but ``a_log``, ``dt_bias`` and ``router_bias``,
which are float32; ``A = heads * head_dim``)::

    embed [vocab_held, hidden]   head [vocab_held, hidden]   norm [hidden]
    l_<i>: attn_norm, ffn_norm [hidden]
       KDA layers: q, k, v, f [hidden, A]      conv_q, conv_k, conv_v [taps, A]
                   beta, head_gate [hidden, heads]   a_log [heads]   dt_bias [A]
                   o_norm [head_dim]           o [A, hidden]
       MLA layers: q [hidden, heads * (nope + rope)]    kv_a [hidden, kv_rank + rope]
                   kv_norm [kv_rank]           kv_b [kv_rank, heads * (nope + v)]
                   head_gate [hidden, heads]   o [heads * v, hidden]
       dense layers:  gate, up [hidden, width]          down [width, hidden]
       expert layers: as ``models/deepseek_v3.py``'s
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.deepseek_v3 import (_dot, _dot32, _mla, expert_layer,
                                         head_gate, rms_norm)
from apex_tpu.serve import kv_cache
from apex_tpu.serve.moe import swiglu

_f32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# positions a chunk call's forward takes at a time: eight rows of bucket
# 512, far into the compute-bound range (``engine.prefill_rows``), and
# small enough that a block's float32 scores and states are a few hundred MB
BLOCK_POSITIONS = 4096
# the chunkwise recurrence: CHUNK positions a step of its scan, the decays
# inside a chunk taken against a reference point every SUB positions. SUB
# is bounded by float32: half of it away from the reference point, at the
# lower bound of -5 a step, a factor is ``exp(+-40)``, which leaves the
# float32 range (``exp(+-87)``) as much room again: at ``exp(-80)`` the
# smaller channels of a key fell under the smallest normal float32, and
# their flush to zero, times a partner of ``exp(80)``, read 2e-4 (CPU)
CHUNK, SUB = 64, 16


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    """The published keys of a ``bailing_hybrid`` ``config.json`` that
    shape the forward, and the rank's share."""

    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    layer_group_size: int = 6
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    num_kv_heads_for_linear_attn: int = 0
    head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kda_safe_gate: bool = True
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    linear_silu: bool = True
    use_qk_norm: bool = True
    group_norm_size: int = 1
    gated_attention_proj_granularity_type: str = "head_wise"
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6000000.0
    rope_scaling: Any = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    use_mla_nope: bool = False
    use_nGPT: bool = False
    scale_router_input: bool = False
    value_norm: bool = False
    up_proj_norm: bool = False
    expert_swiglu_limit_list: Tuple[float, ...] = ()
    share_expert_swiglu_limit_list: Tuple[float, ...] = ()
    # the rank's share: None holds every expert, every row of the vocabulary
    experts_held: Optional[int] = None
    expert_offset: int = 0
    vocab_held: Optional[int] = None
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        # every key whose other value would be other mathematics
        wanted = dict(
            q_lora_rank=None, rope_scaling=None, kda_safe_gate=True,
            no_kda_lora=True, use_kda_lora=False, linear_silu=True,
            use_qk_norm=True, group_norm_size=1, score_function="sigmoid",
            gated_attention_proj_granularity_type="head_wise",
            moe_router_enable_expert_bias=True, use_mla_nope=False,
            use_nGPT=False, scale_router_input=False, value_norm=False,
            up_proj_norm=False)
        other = {k: getattr(self, k) for k, v in wanted.items()
                 if getattr(self, k) != v}
        if other:
            raise ValueError(f"this forward computes {wanted}; the config "
                             f"says {other}")
        if self.num_kv_heads_for_linear_attn not in (
                0, self.num_attention_heads) \
                or self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("grouped key-value heads are not served: a "
                             "KDA state and the latent rows are a head a "
                             "query head")
        if self.qk_nope_head_dim != self.head_dim \
                or self.v_head_dim != self.head_dim:
            raise ValueError("the KDA state is head_dim x head_dim and the "
                             "MLA heads are head_dim wide")
        if self.num_experts % self.n_group:
            raise ValueError("n_group must divide num_experts")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            kept = tuple(getattr(self, key))[:self.num_hidden_layers]
            if any(kept):
                raise ValueError(
                    f"{key}[:{self.num_hidden_layers}] = {list(kept)}: a "
                    f"non-zero entry clamps that layer's SwiGLU, and the "
                    f"form of the clamp is not in the config: refused "
                    f"rather than guessed")
        if not 0 <= self.expert_offset <= self.num_experts - self.held:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.held}"
                f" are not among the layer's {self.num_experts}")

    @classmethod
    def from_dict(cls, cfg: dict, **share):
        """From a ``config.json``'s dict; keys this forward does not read
        are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            kw[key] = tuple(kw.get(key) or ())
        if isinstance(kw.get("compute_dtype"), str):
            kw["compute_dtype"] = getattr(jnp, kw["compute_dtype"])
        return cls(**{**kw, **share})

    # ---- derived
    @property
    def held(self) -> int:
        return self.num_experts if self.experts_held is None \
            else self.experts_held

    @property
    def vocab(self) -> int:
        return self.vocab_size if self.vocab_held is None \
            else self.vocab_held

    def is_mla(self, layer: int) -> bool:
        return (layer + 1) % self.layer_group_size == 0

    @property
    def mla_layers(self) -> int:
        return self.num_hidden_layers // self.layer_group_size

    @property
    def kda_layers(self) -> int:
        return self.num_hidden_layers - self.mla_layers

    @property
    def latent_width(self) -> int:
        """A latent row: the key-value latent and the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def conv_channels(self) -> int:
        """The channels a KDA layer convolves: ``q~, k~, v~`` side by
        side."""
        return 3 * self.num_attention_heads * self.head_dim

    @property
    def state_bytes_per_slot(self) -> int:
        """One KDA layer's float32 state and convolution tail, a slot."""
        return (4 * self.num_attention_heads * self.head_dim ** 2
                + (self.short_conv_kernel_size - 1) * self.conv_channels
                * jnp.dtype(self.compute_dtype).itemsize)

    def inv_freq(self) -> np.ndarray:
        """Plain rotary frequencies over the rotated slice."""
        d = self.qk_rope_head_dim
        return float(self.rope_theta) ** (
            -np.arange(0, d, 2, dtype=np.float64) / d)

    def serving_model(self):
        """What ``serve.Engine`` asks of a model (``serve/model.py``)."""
        from apex_tpu.serve.model import LingHybridServing

        return LingHybridServing(self)


# ------------------------------------------------------ the KDA recurrence


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def kda_step(state, q, k, v, g, beta):
    """One token a row through the recurrence. ``state [rows, heads, D,
    Dv]`` float32; ``q, k, g [rows, heads, D]``, ``v [rows, heads, Dv]``,
    ``beta [rows, heads]``, float32. Returns ``(o [rows, heads, Dv], the
    new state)``. Elementwise products and sums over the key axis, never a
    one-row matrix product: the state is read for ``k^T S`` and ``q^T S``
    together and once more for the update, and ``o = S'^T q + (q . k)
    delta`` comes without reading the new state back."""
    s = state * jnp.exp(g)[..., None]
    seen = jnp.sum(k[..., None] * s, axis=-2)
    read = jnp.sum(q[..., None] * s, axis=-2)
    delta = beta[..., None] * (v - seen)
    o = read + jnp.sum(q * k, axis=-1, keepdims=True) * delta
    return o, s + k[..., None] * delta[..., None, :]


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low [..., C, C]`` strictly lower triangular,
    ``C`` a power of two: the diagonal blocks of ``SUB`` rows by forward
    substitution, all at once (row ``r`` of a block's inverse is ``e_r -
    low[r, :r] X[:r]``), then pairs of blocks merged, ``[[A, 0], [C, D]]^-1 =
    [[A^-1, 0], [-D^-1 C A^-1, D^-1]]``, until one is left: ``SUB - 1`` small
    steps and two products a doubling, in matrix products and none of the
    device's triangular solve (a custom call that took 29 of a ``[8, 512]``
    call's 152 ms: my chip run, PR 37)."""
    c = low.shape[-1]
    size = min(SUB, c)
    m = c // size

    def blocks(below):
        """Blocks ``(i + below, i)`` of ``low`` as ``m x m`` blocks."""
        grid = low.reshape(low.shape[:-2] + (m, size, m, size))
        return jnp.moveaxis(jnp.diagonal(
            grid[..., below:, :, :m - below, :], axis1=-4, axis2=-2), -1, -3)

    diag = blocks(0)
    x = jnp.broadcast_to(jnp.eye(size, dtype=low.dtype), diag.shape)
    for r in range(1, size):
        x = x.at[..., r, :].add(-jnp.einsum(
            "...j,...jk->...k", diag[..., r, :r], x[..., :r, :],
            precision=_HI))
    while m > 1:
        first, second = x[..., 0::2, :, :], x[..., 1::2, :, :]
        corner = -jnp.einsum("...ij,...jk,...kl->...il", second,
                             blocks(1)[..., 0::2, :, :], first, precision=_HI)
        x = jnp.concatenate(
            [jnp.concatenate([first, jnp.zeros_like(first)], -1),
             jnp.concatenate([corner, second], -1)], -2)
        m, size = m // 2, size * 2
    return x[..., 0, :, :]


def _kda_chunk(state, q, k, v, g, beta):
    """``CHUNK`` (or fewer) positions of every row and head at once:
    ``state [b, h, D, Dv]``, ``q, k, g [b, h, C, D]``, ``v [b, h, C, Dv]``,
    ``beta [b, h, C]``. With ``G_t = sum_{i <= t} g_i``, ``u_t = b_t (v_t -
    (k_t exp(G_t))^T S_0 - sum_{j < t} A_tj u_j)`` solves a unit lower
    triangular system, ``o_t = (q_t exp(G_t))^T S_0 + sum_{j <= t} B_tj
    u_j`` and ``S_C = Diag(exp(G_C)) S_0 + sum_j (k_j exp(G_C - G_j))
    u_j^T``, where ``A_tj = sum_c k_t[c] k_j[c] exp(G_t[c] - G_j[c])`` and
    ``B`` the same with ``q_t``.

    The pairwise ``exp(G_t - G_j)`` is never split into ``exp(G_t) exp(-G_j)``
    (``exp(320)`` at the lower bound over a chunk). Row ``t`` is taken
    against ``r``, the cumulated decay at the MIDDLE of its stretch of
    ``SUB`` positions: ``exp(G_t - r)`` on its side and ``exp(r - G_j)`` on
    the column's both lie within ``exp(+-5 SUB / 2)`` for a column of the
    same stretch; a column before the stretch has a smaller factor (one that
    underflows multiplies a pair whose true weight is under ``exp(-47)``), and
    one after it, which the causal mask drops anyway, is given 0."""
    c = q.shape[-2]
    sub = min(SUB, c)
    m = c // sub
    big = jnp.cumsum(g, axis=-2)                           # G [b, h, C, D]
    ref = big[..., sub // 2::sub, :]                       # r [b, h, m, D]
    row_decay = jnp.exp(big.reshape(big.shape[:2] + (m, sub, -1))
                        - ref[..., None, :])
    col = jnp.arange(c)
    ahead = col[None, :] >= (jnp.arange(m)[:, None] + 1) * sub   # [m, C]
    col_decay = jnp.exp(jnp.where(
        ahead[..., None], -jnp.inf, ref[..., None, :] - big[..., None, :, :]))
    k_col = k[..., None, :, :] * col_decay                 # [b, h, m, C, D]

    def pairs(x):
        rows = x.reshape(x.shape[:2] + (m, sub, -1)) * row_decay
        return jnp.einsum("bhmtd,bhmjd->bhmtj", rows, k_col,
                          precision=_HI).reshape(x.shape[:2] + (c, c))

    below = col[:, None] > col[None, :]                    # j < t
    a = jnp.where(below, pairs(k), 0.0)
    b = jnp.where(below | (col[:, None] == col[None, :]), pairs(q), 0.0)
    decay = jnp.exp(big)
    rhs = beta[..., None] * (v - jnp.einsum(
        "bhcd,bhdv->bhcv", k * decay, state, precision=_HI))
    u = jnp.einsum("bhtj,bhjv->bhtv",
                   _unit_lower_inverse(beta[..., None] * a), rhs,
                   precision=_HI)
    o = jnp.einsum("bhcd,bhdv->bhcv", q * decay, state, precision=_HI) \
        + jnp.einsum("bhtj,bhjv->bhtv", b, u, precision=_HI)
    last = big[..., -1:, :]
    state = jnp.exp(last)[..., 0, :, None] * state + jnp.einsum(
        "bhcd,bhcv->bhdv", k * jnp.exp(last - big), u, precision=_HI)
    return state, o


def kda_chunk_scan(q, k, v, g, beta, state=None):
    """The recurrence over ``T`` positions a row, ``CHUNK`` at a time:
    ``q, k, g [rows, T, heads, D]``, ``v [rows, T, heads, Dv]``, ``beta
    [rows, T, heads]``, float32; from ``state`` (zeros where None). Returns
    ``(o [rows, T, heads, Dv], the state after position T - 1)``. A
    position with ``g = 0`` and ``beta = 0`` leaves the state as it was."""
    rows, t, h, d = q.shape
    c = min(CHUNK, t)
    pad = -t % c

    def chunks(x):                       # [rows, T, h, ...] -> [n, rows, h, C, ...]
        x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((rows, -1, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    if state is None:
        state = jnp.zeros((rows, h, d, v.shape[-1]), _f32)
    state, o = jax.lax.scan(
        lambda s, x: _kda_chunk(s, *x), state,
        tuple(chunks(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)          # [rows, n, C, h, Dv]
    return o.reshape(rows, -1, h, v.shape[-1])[:, :t], state


def _kda(cfg: LingHybridConfig, blk, x, cache, layer, pos, write_mask):
    """One KDA layer's mixing for the rows ``x [rows, hidden]`` whose
    positions are shaped ``pos`` (``[slots]`` or ``[slots, T]``): the
    output before the residual, and the cache with recurrent layer
    ``layer``'s state and convolution tail after the call."""
    c = cfg
    h, d, taps = c.num_attention_heads, c.head_dim, c.short_conv_kernel_size
    shape = pos.shape + (h, d)
    with jax.named_scope("ln_qkv"):
        u = rms_norm(x, blk["attn_norm"], c.rms_norm_eps)
        raw = jnp.concatenate([_dot(u, blk[n]) for n in ("q", "k", "v")], -1)
        # the gates' inputs stay float32: a rounding of the decay's is
        # multiplied up by every later position that the channel remembers
        f = _dot32(u, blk["f"])
        beta = jax.nn.sigmoid(_dot32(u, blk["beta"]))
        gate = head_gate(u, blk["head_gate"])
    with jax.named_scope("attention"):
        with jax.named_scope("kda_state"):
            w = jnp.concatenate([blk["conv_" + n] for n in ("q", "k", "v")],
                                -1).astype(_f32)           # [taps, 3A]
            g = c.kda_lower_bound * jax.nn.sigmoid(
                jnp.exp(blk["a_log"].astype(_f32))[:, None]
                * (f + blk["dt_bias"].astype(_f32)).reshape(shape))
            beta = beta.reshape(pos.shape + (h,))
            raw = raw.reshape(pos.shape + (-1,))
            if pos.ndim == 1:
                state, tail = kv_cache.read_state(cache, layer)
                window = jnp.concatenate(
                    [tail.reshape(tail.shape[0], taps - 1, -1),
                     raw[:, None]], axis=1)                # [b, taps, 3A]
                mixed = jax.nn.silu(jnp.sum(window.astype(_f32) * w, axis=1))
                q, k, v = (mixed[:, i * h * d:(i + 1) * h * d].reshape(shape)
                           for i in range(3))
                o, state = kda_step(state, _l2norm(q) * d ** -0.5,
                                    _l2norm(k), v, g, beta)
                cache = kv_cache.write_state(
                    cache, layer, state,
                    window[:, 1:].reshape(tail.shape), write_mask)
            else:
                b, t = pos.shape
                padded = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0)))
                mixed = jax.nn.silu(sum(
                    padded[:, j:j + t].astype(_f32) * w[j]
                    for j in range(taps)))
                q, k, v = (mixed[..., i * h * d:(i + 1) * h * d
                                 ].reshape(shape) for i in range(3))
                # past a row's real length: b = 0, a = 1
                real = write_mask[..., None]
                o, state = kda_chunk_scan(
                    _l2norm(q) * d ** -0.5, _l2norm(k), v,
                    jnp.where(real[..., None], g, 0.0),
                    jnp.where(real, beta, 0.0))
                # the tail: the inputs of the last taps - 1 real positions
                at = write_mask.sum(-1)[:, None] + jnp.arange(taps - 1)
                tail = jnp.take_along_axis(padded, at[..., None], axis=1)
                cache = kv_cache.write_state(
                    cache, layer, state, tail.reshape(b, -1),
                    write_mask.any(-1))
            o = rms_norm(o, blk["o_norm"].astype(_f32), c.rms_norm_eps)
            o = (o.reshape(-1, h, d) * gate).astype(x.dtype)
        with jax.named_scope("attn_proj"):
            out = _dot(o.reshape(-1, h * d), blk["o"])
    return out, cache


# ---------------------------------------------------------------- forward


def _forward(cfg: LingHybridConfig, p, cache, tokens, pos, write_mask,
             logits_at, final_scope):
    c = cfg
    inv_freq = c.inv_freq()
    real = write_mask.reshape(-1)
    x = p["embed"][tokens].reshape(-1, c.hidden_size)
    routing = jnp.zeros((2,), jnp.int32)
    plane = recurrent = 0
    for i in range(c.num_hidden_layers):
        blk = p[f"l_{i}"]
        if c.is_mla(i):
            attn, cache = _mla(c, blk, x, cache, plane, pos, write_mask,
                               inv_freq)
            plane += 1
        else:
            attn, cache = _kda(c, blk, x, cache, recurrent, pos, write_mask)
            recurrent += 1
        x = x + attn
        with jax.named_scope("mlp"):
            u = rms_norm(x, blk["ffn_norm"], c.rms_norm_eps)
            if i < c.first_k_dense_replace:
                y = swiglu(u, blk["gate"], blk["up"], blk["down"])
            else:
                y, counts = expert_layer(c, blk, u, real)
                routing = routing + counts
            x = x + y.astype(x.dtype)
    with jax.named_scope(final_scope):
        x = x.reshape(pos.shape + x.shape[-1:])
        if logits_at is not None:
            x = x[jnp.arange(pos.shape[0]), logits_at.astype(jnp.int32)]
        x = rms_norm(x, p["norm"], c.rms_norm_eps)
        logits = jax.lax.dot_general(
            x, p["head"], (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_f32)
    return logits, cache, routing


def _by_row_blocks(cfg, p, cache, tokens, pos, write_mask, logits_at,
                   final_scope, block: int):
    """A chunk call ``block`` rows at a time: each block is the forward
    over a row view of the cache (its rows' ``lengths``, page-table rows
    and slot ids beside the same pool and state arrays), one after the
    other, so the temporaries are a block's. Rows added to fill the last
    block name no slot and are masked."""
    rows = pos.shape[0]
    blocks = -(-rows // block)

    def split(x, fill=0):
        x = jnp.pad(x, [(0, blocks * block - rows)] + [(0, 0)] * (x.ndim - 1),
                    constant_values=fill)
        return x.reshape((blocks, block) + x.shape[1:])

    ids = split(jnp.arange(rows, dtype=jnp.int32), fill=cache.num_slots)
    none = jnp.zeros((block,), bool)

    def body(carry, xs):
        cache, routing = carry
        at, *data = xs
        view = kv_cache.slot_view(cache, at)
        logits, view, counts = _forward(cfg, p, view, *data, final_scope)
        # the view's pool and state arrays, the whole cache's bookkeeping
        cache = kv_cache.close_view(cache, view, at, none, at)
        return (cache, routing + counts), logits

    (cache, routing), logits = jax.lax.scan(
        body, (cache, jnp.zeros((2,), jnp.int32)),
        (ids, split(tokens), split(pos), split(write_mask),
         None if logits_at is None else split(logits_at)))
    return logits.reshape((-1,) + logits.shape[2:])[:rows], cache, routing


def ling_hybrid_token_forward(cfg: LingHybridConfig, params, cache, tokens,
                              positions, write_mask, logits_at=None, *,
                              final_scope: str = "sampling"):
    """One token a slot, or one chunk of a prompt a slot, through the
    model with a :class:`~apex_tpu.serve.kv_cache.HybridCache`: the
    contract of :func:`~apex_tpu.models.deepseek_v3.deepseek_v3_token_forward`
    (shapes, masks, ``logits_at``, and ``routing int32[2]``: the picks that
    landed on experts held here and the held experts that were hit).

    A chunk call is a WHOLE prompt from position 0: a KDA layer starts an
    admitted row from a zero state whatever the slot held, leaves the
    state after the row's last real position, and touches no other slot's.

    The scopes are the accepted ones (``ln_qkv``; ``attention`` with
    ``kv_write`` and ``attn_proj``; ``mlp`` with ``router``, ``experts``,
    ``shared_expert``; ``final_scope``), and inside ``attention`` of a KDA
    layer ``kda_state``: the convolution, the gates, the state's update
    and its read-out."""
    p = params["params"] if "params" in params else params
    pos = positions.astype(jnp.int32)
    if pos.ndim == 2:
        block = max(1, BLOCK_POSITIONS // pos.shape[1])
        if pos.shape[0] > block:
            return _by_row_blocks(cfg, p, cache, tokens, pos, write_mask,
                                  logits_at, final_scope, block)
    return _forward(cfg, p, cache, tokens, pos, write_mask, logits_at,
                    final_scope)
