"""DeepSeek-V3 (``model_type: deepseek_v3``: DeepSeek-V3/R1, GigaChat3
Ultra, Kimi-K2 ...) on the serving path: latent attention (MLA) over the
paged latent cache, a leading run of dense SwiGLU layers, then layers of
routed experts behind the ``noaux_tc`` router with one shared expert,
RMSNorm, YaRN RoPE on a 64-wide slice of every head, an untied head.

There is one forward, :func:`deepseek_v3_token_forward`, in the two shapes
``gpt2_token_forward`` has: one token a slot (decode: MLA in its absorbed
form straight off the latent pages) and one chunk a slot (prefill: MLA in
its plain form over the chunk's own expanded keys and values, the cached
head after a prefix hit read as latent rows). ``serve.Engine`` reaches it
through :meth:`DeepseekV3Config.serving_model`.

**A rank's share.** The config says what of each layer THIS chip holds:
``experts_held`` routed experts from ``expert_offset`` on (the router
keeps all ``n_routed_experts`` columns), and ``vocab_held`` rows of the
embedding and of the head. A chosen expert that lives elsewhere adds
nothing (``serve/moe.py``); token ids, logits and sampling are over the
held rows. The multi-token-prediction layer is not served (the published
inference code does not load it either).

The parameter tree (every leaf in ``compute_dtype`` but the router's
bias, which is float32)::

    embed [vocab_held, hidden]   head [vocab_held, hidden]   norm [hidden]
    l_<i>: attn_norm [hidden]    q_a [hidden, q_rank]        q_norm [q_rank]
           q_b [q_rank, heads * (nope + rope)]
           kv_a [hidden, kv_rank + rope]                     kv_norm [kv_rank]
           kv_b [kv_rank, heads * (nope + v)]                o [heads * v, hidden]
           ffn_norm [hidden]
       dense layers:  gate, up [hidden, width]               down [width, hidden]
       expert layers: router [hidden, n_routed]              router_bias [n_routed]
                      shared_gate, shared_up, shared_down    (one shared expert)
                      w_gate, w_up [held, hidden, moe_width] w_down [held, moe_width, hidden]
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from apex_tpu.normalization.fused_layer_norm import manual_rms_norm
from apex_tpu.transformer.rope import rope_interleaved, yarn_inv_freq

_f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published keys of a ``deepseek_v3`` ``config.json`` that shape
    the forward, and the rank's share."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    n_shared_experts: int = 1
    n_routed_experts: int = 256
    routed_scaling_factor: float = 2.5
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    n_group: int = 8
    topk_group: int = 4
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 3
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    # rope_scaling (rope_type yarn); factor 1 leaves the frequencies alone
    rope_factor: float = 1.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # the rank's share: None holds every expert, every row of the vocabulary
    experts_held: Optional[int] = None
    expert_offset: int = 0
    vocab_held: Optional[int] = None
    compute_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if self.n_shared_experts != 1:
            raise ValueError("one shared expert is what this forward has")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_group must divide n_routed_experts")
        held = self.n_routed_experts if self.experts_held is None \
            else self.experts_held
        if not 0 <= self.expert_offset <= self.n_routed_experts - held:
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + held} "
                f"are not among the layer's {self.n_routed_experts}")

    @classmethod
    def from_dict(cls, cfg: dict, **share):
        """From a ``config.json``'s dict (nested ``rope_scaling`` and
        all); keys this forward does not read are passed over."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in cfg.items() if k in names}
        for k, v in (cfg.get("rope_scaling") or {}).items():
            if "rope_" + k in names:
                kw["rope_" + k] = v
        if isinstance(kw.get("compute_dtype"), str):
            kw["compute_dtype"] = getattr(jnp, kw["compute_dtype"])
        return cls(**{**kw, **share})

    # ---- derived
    @property
    def held(self) -> int:
        return self.n_routed_experts if self.experts_held is None \
            else self.experts_held

    @property
    def vocab(self) -> int:
        return self.vocab_size if self.vocab_held is None \
            else self.vocab_held

    @property
    def latent_width(self) -> int:
        """A cache row: the key-value latent and the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim ** -0.5``, times the square of YaRN's
        ``0.1 * mscale_all_dim * ln(factor) + 1`` where that is set."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
            scale *= m * m
        return scale

    def inv_freq(self):
        """The rotary frequencies (YaRN-blended where ``rope_factor > 1``).
        The factor on cos and sin is ``mscale(factor, mscale) /
        mscale(factor, mscale_all_dim)``, 1 where the two are equal, as
        in every published ``deepseek_v3`` config; anything else is
        refused rather than silently left out."""
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim \
                and self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError("mscale != mscale_all_dim: the cos/sin factor "
                             "is not 1, and this forward applies none")
        return yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max_position_embeddings,
            self.rope_beta_fast, self.rope_beta_slow)

    def serving_model(self):
        """What ``serve.Engine`` asks of a model (``serve/model.py``)."""
        from apex_tpu.serve.model import DeepseekV3Serving

        return DeepseekV3Serving(self)


def rms_norm(x, weight, eps):
    """RMSNorm over the last axis: float32 inside, ``x``'s dtype out."""
    return manual_rms_norm(x, weight, x.shape[-1], eps)


def _dot(x, w):
    """A product in the weights' dtype with float32 accumulation, rounded
    to that dtype as a linear layer's output is."""
    return jnp.dot(x.astype(w.dtype), w,
                   preferred_element_type=_f32).astype(w.dtype)


def _dot32(x, w):
    """A product in the weights' dtype, its float32 accumulator kept."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_f32)


def head_gate(u, w):
    """``sigmoid(u W)`` a row and head, float32 ``[rows, heads, 1]``."""
    return jax.nn.sigmoid(_dot32(u, w))[..., None]


def expert_layer(cfg: DeepseekV3Config, blk, u, row_mask):
    """``shared(u) + sum over the chosen experts held here`` for the
    normalised rows ``u``; float32 ``[rows, hidden]`` and the routing
    counters of :func:`~apex_tpu.serve.moe.routed_experts`."""
    from apex_tpu.serve import moe

    with jax.named_scope("router"):
        experts, weights = moe.route_noaux_tc(
            u, blk["router"], blk["router_bias"], n_group=cfg.n_group,
            topk_group=cfg.topk_group, top_k=cfg.num_experts_per_tok,
            norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor)
    with jax.named_scope("experts"):
        routed, counts = moe.routed_experts(
            u, experts, weights, row_mask, blk["w_gate"], blk["w_up"],
            blk["w_down"], expert_offset=cfg.expert_offset)
    with jax.named_scope("shared_expert"):
        shared = moe.swiglu(u, blk["shared_gate"], blk["shared_up"],
                            blk["shared_down"])
    return shared + routed, counts


def _mla(cfg: DeepseekV3Config, blk, x, cache, layer, pos, write_mask,
         inv_freq):
    """One layer's latent attention for the rows ``x [rows, hidden]``
    whose positions are ``pos`` (``[slots]`` or ``[slots, T]``): the
    attention output before the residual, and the cache with the rows'
    latents appended. ``layer`` is the cache's plane. What the block
    holds says which variant it is: with no ``q_a`` the query is ``u W_q``
    directly (``q_lora_rank`` null), and with a ``head_gate`` every
    head's output is scaled by ``sigmoid(u W_gate)`` before ``o``
    (``models/ling_hybrid.py``)."""
    from apex_tpu.serve.attention import (latent_chunk_attention,
                                          latent_decode_attention)
    from apex_tpu.serve.kv_cache import write_latent

    c = cfg
    h, nope, rope, vd = (c.num_attention_heads, c.qk_nope_head_dim,
                         c.qk_rope_head_dim, c.v_head_dim)
    eps, rank = c.rms_norm_eps, c.kv_lora_rank
    flat_pos = pos.reshape(-1)
    with jax.named_scope("ln_qkv"):
        u = rms_norm(x, blk["attn_norm"], eps)
        q = _dot(rms_norm(_dot(u, blk["q_a"]), blk["q_norm"], eps),
                 blk["q_b"]) if "q_a" in blk else _dot(u, blk["q"])
        q = q.reshape(-1, h, nope + rope)
        q_nope = q[..., :nope]
        q_rope = rope_interleaved(q[..., nope:], flat_pos[:, None], inv_freq)
        kv = _dot(u, blk["kv_a"])
        # THE cache row: the normalised latent and the one rotated key
        row = jnp.concatenate(
            [rms_norm(kv[:, :rank], blk["kv_norm"], eps),
             rope_interleaved(kv[:, rank:], flat_pos, inv_freq)], axis=-1)
        w_kvb = blk["kv_b"].reshape(rank, h, nope + vd)
        w_kb, w_vb = w_kvb[..., :nope], w_kvb[..., nope:]
    with jax.named_scope("attention"):
        cache = write_latent(cache, layer, row.reshape(pos.shape + (-1,)),
                             pos, write_mask)
        row = row.astype(cache.rows.dtype)      # as a read would return it
        if pos.ndim == 1:
            # decode, absorbed: the key up-projection goes into the query,
            # the value up-projection comes after the weighted sum
            q_lat = jnp.einsum("bhn,chn->bhc", q_nope, w_kb,
                               preferred_element_type=_f32)
            o_lat = latent_decode_attention(
                q_lat.astype(q.dtype), q_rope, cache, layer, pos,
                scale=c.softmax_scale)
            o = jnp.einsum("bhc,chv->bhv", o_lat.astype(q.dtype), w_vb,
                           preferred_element_type=_f32).astype(q.dtype)
        else:
            # prefill, plain: the chunk's own keys and values, expanded
            b, t = pos.shape
            kvx = jnp.einsum("rc,chd->rhd", row[:, :rank], w_kvb,
                             preferred_element_type=_f32).astype(q.dtype)
            o = latent_chunk_attention(
                q_nope.reshape(b, t, h, nope), q_rope.reshape(b, t, h, rope),
                kvx[..., :nope].reshape(b, t, h, nope),
                row[:, rank:].reshape(b, t, rope),
                kvx[..., nope:].reshape(b, t, h, vd), w_kb, w_vb, cache,
                layer, pos[:, 0], scale=c.softmax_scale)
        if "head_gate" in blk:
            o = (o.reshape(-1, h, vd) * head_gate(u, blk["head_gate"])
                 ).astype(q.dtype)
        with jax.named_scope("attn_proj"):
            out = _dot(o.reshape(-1, h * vd), blk["o"])
    return out, cache


def deepseek_v3_token_forward(cfg: DeepseekV3Config, params, cache, tokens,
                              positions, write_mask, logits_at=None, *,
                              final_scope: str = "sampling"):
    """One token a slot, or one chunk of a prompt a slot, through the
    model with the paged latent cache: the contract of
    :func:`~apex_tpu.models.gpt2.gpt2_token_forward` (shapes, masks,
    ``logits_at``), and a third result. Returns ``(logits float32, cache,
    routing int32[2])``: over the expert layers and the rows under
    ``write_mask``, the picks that landed on experts held here and the
    held experts that were hit.

    The scopes are ``gpt2_token_forward``'s, so a device trace's reader
    splits this model as it splits that one: ``ln_qkv`` (the norms and
    the four MLA projections), ``attention`` with ``kv_write`` and
    ``attn_proj`` inside, ``mlp`` with ``router``, ``experts`` and
    ``shared_expert`` inside on an expert layer, and ``final_scope``."""
    c = cfg
    p = params["params"] if "params" in params else params
    pos = positions.astype(jnp.int32)
    inv_freq = c.inv_freq()
    real = write_mask.reshape(-1)
    x = p["embed"][tokens].reshape(-1, c.hidden_size)
    routing = jnp.zeros((2,), jnp.int32)
    for i in range(c.num_hidden_layers):
        blk = p[f"l_{i}"]
        attn, cache = _mla(c, blk, x, cache, i, pos, write_mask, inv_freq)
        x = x + attn
        with jax.named_scope("mlp"):
            u = rms_norm(x, blk["ffn_norm"], c.rms_norm_eps)
            if i < c.first_k_dense_replace:
                from apex_tpu.serve.moe import swiglu

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
