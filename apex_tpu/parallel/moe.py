"""Expert parallelism (MoE) over a mesh axis.

The reference has no expert parallelism (SURVEY §2.5); provided here as the
``ep`` axis counterpart to dp/tp/sp/pp. GShard-style top-1 routing with fixed
expert capacity: dispatch/combine are einsums (MXU-friendly one-hots, no
dynamic shapes) and the cross-device token exchange is ONE ``all_to_all``
each way over ICI — the collective the reference's NCCL backend never had a
use for (SURVEY §5 comm backend mapping).

Capacity overflow drops tokens (standard GShard behavior); the combine path
returns zeros for dropped tokens so the residual connection carries them.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size

_f32 = jnp.float32


def top1_dispatch(gate_logits: jax.Array, num_experts: int,
                  capacity: int) -> Tuple[jax.Array, jax.Array]:
    """Build (dispatch, combine) tensors from router logits.

    gate_logits: (T, E). Returns dispatch (T, E, C) one-hot and combine
    (T, E, C) = dispatch · router_prob.
    """
    t = gate_logits.shape[0]
    probs = jax.nn.softmax(gate_logits.astype(_f32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                     # (T,)
    onehot = jax.nn.one_hot(expert, num_experts, dtype=_f32)  # (T, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0          # (T, E)
    keep = (pos >= 0) & (pos < capacity)
    pos_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1).astype(jnp.int32),
                            capacity, dtype=_f32)            # (T, E, C)
    dispatch = pos_oh * keep.astype(_f32)[..., None]
    gate = jnp.sum(probs * onehot, axis=-1, keepdims=True)   # (T, 1)
    combine = dispatch * gate[..., None]
    return dispatch, combine


def moe_ffn_ep(x: jax.Array, gate_w: jax.Array, w1: jax.Array,
               w2: jax.Array, axis_name: str = "ep",
               capacity_factor: float = 1.25) -> jax.Array:
    """Expert-parallel MoE FFN. Call inside shard_map.

    x: (T, D) local tokens; gate_w: (D, E) replicated router;
    w1: (E_local, D, H), w2: (E_local, H, D) — this device's expert shard
    (pass stacked experts with in_specs=P('ep', ...)).
    Returns (T, D): combined expert outputs (dropped tokens → zeros).
    """
    ep = axis_size(axis_name)
    t, d = x.shape
    e_local = w1.shape[0]
    e = e_local * ep
    cap = max(int(t / e * capacity_factor), 1)

    logits = jnp.dot(x.astype(_f32), gate_w.astype(_f32),
                     preferred_element_type=_f32)
    dispatch, combine = top1_dispatch(logits, e, cap)

    # gather expert inputs: (E, C, D)
    exp_in = jnp.einsum("tec,td->ecd", dispatch, x.astype(_f32))
    # all_to_all: split the expert dim across devices, concat the token side
    # → each device gets its experts' slices from every peer: (E_l, ep*C, D)
    exp_in = exp_in.reshape(ep, e_local, cap, d)
    exp_in = jax.lax.all_to_all(exp_in, axis_name, split_axis=0,
                                concat_axis=0, tiled=False)
    exp_in = exp_in.transpose(1, 0, 2, 3).reshape(e_local, ep * cap, d)

    # local expert FFN (vmapped over this device's experts)
    def ffn(wi, wo, h):
        z = jax.nn.gelu(jnp.dot(h, wi.astype(_f32),
                                preferred_element_type=_f32))
        return jnp.dot(z, wo.astype(_f32), preferred_element_type=_f32)

    exp_out = jax.vmap(ffn)(w1, w2, exp_in)                 # (E_l, ep*C, D)

    # reverse exchange: back to (E, C, D) on every source device
    exp_out = exp_out.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
    exp_out = jax.lax.all_to_all(exp_out, axis_name, split_axis=0,
                                 concat_axis=0, tiled=False)
    exp_out = exp_out.reshape(e, cap, d)

    y = jnp.einsum("tec,ecd->td", combine, exp_out)
    return y.astype(x.dtype)
