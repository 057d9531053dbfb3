"""The serving expert layer: a rank's share of an expert-parallel layer.

Nothing here is ``apex_tpu.parallel.moe`` (training: top-1 softmax, a
capacity that drops tokens, an ``all_to_all`` under ``shard_map``). A
serving rank is TOLD which experts it holds (``expert_offset`` and the
leading axis of its expert weights), routes every row over ALL the
published experts, drops nothing, and computes the part of the layer's
result that its own experts give: a chosen expert that lives on another
rank adds nothing here, and that partial sum is what goes on. On one chip
the layer runs without its exchange; nothing stands in for the absent
ranks.

- :func:`route_noaux_tc`: the DeepSeek-V3 router (``topk_method:
  noaux_tc``): sigmoid scores, a bias used for the choice and not for the
  weight, a limit on the groups a token may draw from. Float32 throughout.
- :func:`routed_experts`: the picks that land on held experts, sorted by
  expert, through one grouped product a projection
  (``jax.lax.ragged_dot``, which the TPU compiler lowers to a grouped
  matmul whose tiles follow the group sizes) over the rows actually
  routed: static shapes, no ``rows x experts`` product.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_f32 = jnp.float32
# a grouped product takes an eighth of a call's picks at a time, and never
# fewer than this many rows (see routed_experts)
_CHUNK_SHARE, _CHUNK_ROWS = 8, 512


def route_noaux_tc(u: jax.Array, w_router: jax.Array, bias: jax.Array, *,
                   n_group: int, topk_group: int, top_k: int,
                   norm_topk_prob: bool, routed_scaling_factor: float):
    """``(experts [rows, top_k] int32, weights [rows, top_k] float32)``
    for the normalised hidden rows ``u [rows, hidden]``.

    ``s = sigmoid(u W_g)``; the choice is made on ``s + bias``: a group's
    score is the sum of its two largest, the ``topk_group`` best groups
    stay, and the ``top_k`` largest among their experts are chosen (the
    published code fills the other groups with 0.0, not with minus
    infinity: kept). The weights are the chosen ``s``, without the bias,
    over their sum where ``norm_topk_prob``, times
    ``routed_scaling_factor``."""
    rows, n_experts = u.shape[0], w_router.shape[-1]
    s = jax.nn.sigmoid(jnp.dot(u.astype(_f32), w_router.astype(_f32),
                               precision=jax.lax.Precision.HIGHEST))
    choice = s + bias.astype(_f32)
    grouped = choice.reshape(rows, n_group, n_experts // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]
    open_ = jnp.zeros((rows, n_group), bool).at[
        jnp.arange(rows)[:, None], kept].set(True)
    choice = jnp.where(jnp.repeat(open_, n_experts // n_group, axis=1),
                       choice, 0.0)
    experts = jax.lax.top_k(choice, top_k)[1].astype(jnp.int32)
    weights = jnp.take_along_axis(s, experts, axis=1)
    if norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return experts, weights * jnp.float32(routed_scaling_factor)


def swiglu(u: jax.Array, gate: jax.Array, up: jax.Array,
           down: jax.Array) -> jax.Array:
    """``down(silu(gate(u)) * up(u))`` as float32: products in the
    weights' dtype with float32 accumulation, the gating in float32."""
    dt = gate.dtype
    g = jnp.dot(u.astype(dt), gate, preferred_element_type=_f32)
    a = jnp.dot(u.astype(dt), up, preferred_element_type=_f32)
    return jnp.dot((jax.nn.silu(g) * a).astype(dt), down,
                   preferred_element_type=_f32)


def routed_experts(u: jax.Array, experts: jax.Array, weights: jax.Array,
                   row_mask: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                   w_down: jax.Array, *, expert_offset: int):
    """``sum over a row's chosen experts HELD HERE of weight * expert(u)``
    as ``[rows, hidden]`` float32, and ``int32[2]``: the picks that landed
    here and the held experts that were hit.

    ``u [rows, hidden]``; ``experts``/``weights`` ``[rows, top_k]`` from
    the router; ``row_mask [rows]``: a masked-off row (an idle slot, a
    prompt's padding) is routed nowhere; ``w_gate``/``w_up`` ``[held,
    hidden, width]`` and ``w_down [held, width, hidden]``: the experts
    ``expert_offset .. expert_offset + held`` of the layer.

    The picks are sorted by held expert, those that live elsewhere last,
    and go through three grouped products ``chunk`` sorted picks at a
    time, each with the group sizes of its stretch of the sorted order, so
    the work follows the rows routed here. ``chunk`` is static: an eighth
    of ``rows * top_k`` (every pick could land here, and that is 16 times
    what uniform routing sends a rank of 16), at least 512. The number of
    chunks is data: one where the landed picks fit it, as they do under
    any routing near uniform, more where they do not. No pick is ever
    dropped, and no buffer is sized for the worst case."""
    rows, top_k = experts.shape
    held, dt = w_gate.shape[0], w_gate.dtype
    local = experts - jnp.int32(expert_offset)
    here = (local >= 0) & (local < held) & row_mask[:, None]
    flat = jnp.where(here, local, held).reshape(-1)        # elsewhere: last
    sizes = jnp.zeros((held + 1,), jnp.int32).at[flat].add(1)[:held]
    ends = jnp.cumsum(sizes)
    landed = ends[-1]
    every = rows * top_k
    chunk = min(max(every // _CHUNK_SHARE, _CHUNK_ROWS), every)
    chunks = -(-every // chunk)
    order = jnp.pad(jnp.argsort(flat, stable=True).astype(jnp.int32),
                    (0, chunks * chunk - every))
    flat_w = weights.reshape(-1)

    def body(i, out):
        lo = i * chunk
        picks = jax.lax.dynamic_slice_in_dim(order, lo, chunk)
        # the picks of expert e in this stretch of the sorted order
        part = jnp.clip(jnp.minimum(ends, lo + chunk)
                        - jnp.maximum(ends - sizes, lo), 0, None)
        row = picks // top_k
        xs = u[row].astype(dt)
        g = jax.lax.ragged_dot(xs, w_gate, part, preferred_element_type=_f32)
        a = jax.lax.ragged_dot(xs, w_up, part, preferred_element_type=_f32)
        y = jax.lax.ragged_dot((jax.nn.silu(g) * a).astype(dt), w_down, part,
                               preferred_element_type=_f32)
        # rows past the last group belong to no expert: whatever the
        # grouped product left there is masked, never multiplied
        y = jnp.where((lo + jnp.arange(chunk) < landed)[:, None],
                      y * flat_w[picks][:, None], 0.0)
        return out.at[row].add(y)

    out = jax.lax.fori_loop(0, (landed + chunk - 1) // chunk, body,
                            jnp.zeros((rows, u.shape[-1]), _f32))
    return out, jnp.stack([landed, (sizes > 0).sum()]).astype(jnp.int32)
