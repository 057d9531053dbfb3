"""Pipeline parallelism (GPipe) over a mesh axis.

The reference has no pipeline parallelism (SURVEY §2.5: PP absent from apex);
the TPU framework provides it as a first-class axis alongside dp/tp/sp.

Design: the homogeneous stage stack is sharded over the ``pp`` axis (each
device holds one stage's params, passed as stacked leaves with a leading
stage dim). The GPipe schedule is a ``lax.scan`` over M + P - 1 ticks: stage
0 ingests a fresh microbatch each tick, every stage applies its layer to
whatever sits in its input buffer, and activations hop to the next stage with
``ppermute`` (one ICI neighbor transfer per tick). The backward pass needs no
hand-written schedule: autodiff transposes the scan and the ppermute, yielding
the reverse pipeline automatically.

Bubble fraction = (P-1)/(M+P-1), the standard GPipe tradeoff — pick
num_microbatches ≥ 4·P.

Round 2 adds **1F1B** (``pipeline_train_1f1b``): a manually-scheduled
one-forward-one-backward pipeline that bounds stashed activations at
O(P · microbatch) instead of GPipe's O(M · microbatch). The schedule is the
standard non-interleaved 1F1B in SPMD lockstep form: at tick t, stage s
forwards microbatch ``t - s`` and backwards microbatch ``t - 2(P-1) + s``
(the last stage backwards a microbatch the same tick it forwards it, earlier
stages progressively later), so the steady state alternates F and B with at
most 2(P-1) microbatches in flight. Backward recomputes the stage forward
from the stashed INPUT (remat — the memory/compute tradeoff every 1F1B
implementation makes) and uses ``jax.vjp`` for the stage pullback; activation
hops ride ``ppermute`` in both directions each tick.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from jax.lax import axis_size


def pipeline_apply(stage_fn: Callable, stage_params: Any, x: jax.Array,
                   axis_name: str = "pp",
                   num_microbatches: int = 4) -> jax.Array:
    """Run a P-stage pipeline over the ``axis_name`` mesh axis.

    Call INSIDE shard_map. ``stage_params``: this device's stage params (pass
    stacked params with in_specs=P('pp', ...) and squeeze the leading dim, or
    any per-device tree). ``stage_fn(params, x_micro) -> y_micro`` must
    preserve the microbatch shape (homogeneous stages). ``x``: the full local
    batch (B, ...), B divisible by num_microbatches; every device receives
    the same x (replicated in-specs) and stage 0 feeds it in.

    Returns the pipeline output (B, ...) — valid on every device (the last
    stage's results are broadcast back over the axis).
    """
    p = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    m = num_microbatches
    b = x.shape[0]
    assert b % m == 0, "num_microbatches must divide the batch size"
    mb = b // m
    micro = x.reshape(m, mb, *x.shape[1:])
    ticks = m + p - 1

    fwd_perm = [(i, i + 1) for i in range(p - 1)]

    def tick(buf, t):
        # stage 0 ingests microbatch t (clamped; garbage ticks are discarded)
        idx = jnp.clip(t, 0, m - 1)
        fresh = jax.lax.dynamic_index_in_dim(micro, idx, 0, keepdims=False)
        inp = jnp.where(my == 0, fresh, buf)
        out = stage_fn(stage_params, inp)
        nxt = jax.lax.ppermute(out, axis_name, fwd_perm)
        return nxt, out

    # initial carry = a real microbatch, NOT zeros: bubble ticks run stage_fn
    # on this buffer and discard the result, but a zeros input could produce
    # NaN primals (e.g. eps-free norms) that poison the scan VJP via
    # 0-cotangent × NaN. stage_fn must be finite on finite inputs.
    _, outs = jax.lax.scan(tick, micro[0], jnp.arange(ticks))
    # last stage's valid outputs are at ticks [p-1, p-1+m)
    valid = jax.lax.dynamic_slice_in_dim(outs, p - 1, m, axis=0)
    y = valid.reshape(b, *x.shape[1:])
    # broadcast the last stage's result to every device: zero elsewhere + psum
    y = jnp.where(my == p - 1, y, jnp.zeros_like(y))
    return jax.lax.psum(y, axis_name)


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack a list of per-stage param trees along a new leading axis, for
    sharding with in_specs=P('pp', ...)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def unstack_local(params: Any) -> Any:
    """Inside shard_map: squeeze the leading (local stage) dim of 1.

    Raises if more than one stage landed on this device (stage count must
    equal the pp axis size — silently using stage 0 of several would compute
    a wrong, shorter pipeline).
    """

    def squeeze(l):
        assert l.shape[0] == 1, (
            f"{l.shape[0]} stages per device: stack exactly axis_size stages "
            f"(stage count must equal the pp mesh axis size)")
        return l[0]

    return jax.tree_util.tree_map(squeeze, params)


def pipeline_train_1f1b(stage_fn: Callable, stage_params: Any,
                        shared_params: Any, x_template: jax.Array,
                        micro_args: tuple, num_microbatches: int,
                        axis_name: str = "pp"):
    """One fused forward+backward pipeline pass with the 1F1B schedule.

    Call INSIDE shard_map.

    ``stage_fn(stage_params, shared_params, x_act, *args_i) -> (y, loss_i)``
    is this device's stage: ``x_act`` is the incoming activation microbatch
    (same shape as the returned ``y``; the first stage ignores it and builds
    its input from ``args_i``, e.g. an embedding lookup), ``args_i`` are this
    microbatch's slices of ``micro_args`` (arrays with leading dim M — e.g.
    tokens/targets/mask). ``loss_i`` must be the microbatch loss on the LAST
    stage and any finite scalar elsewhere (it is discarded). stage_fn must be
    finite on finite inputs (bubble ticks run it on stale buffers).

    Returns ``(loss_sum, stage_grads, shared_grads, )`` where ``loss_sum`` is
    the sum of per-microbatch losses (valid on every device after a psum over
    the axis), ``stage_grads`` are THIS stage's param grads (local, not
    psum'd over pp), and ``shared_grads`` are psum'd over the pipeline axis.
    """
    p = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    m = num_microbatches
    depth = 2 * p  # stash ring: ≥ max microbatches in flight + 1
    ticks = m + 2 * (p - 1)
    fwd_perm = [(i, i + 1) for i in range(p - 1)]
    bwd_perm = [(i + 1, i) for i in range(p - 1)]
    is_last = my == p - 1

    zero_stage = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), stage_params)
    zero_shared = jax.tree_util.tree_map(
        lambda l: jnp.zeros(l.shape, jnp.float32), shared_params)

    def micro_at(t):
        return tuple(jax.lax.dynamic_index_in_dim(
            a, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            for a in micro_args)

    def tick(carry, t):
        in_buf, dy_buf, stash, g_stage, g_shared, loss_acc = carry

        # ---- forward sub-tick: microbatch fi = t - my
        fi = t - my
        valid_f = (fi >= 0) & (fi < m)
        args_f = micro_at(fi)
        y, loss_i = stage_fn(stage_params, shared_params, in_buf, *args_f)
        slot_f = jnp.clip(fi, 0, m - 1) % depth
        stash = jnp.where(
            valid_f,
            jax.lax.dynamic_update_index_in_dim(stash, in_buf, slot_f, 0),
            stash)
        loss_acc = loss_acc + jnp.where(valid_f & is_last, loss_i, 0.0)

        # ---- backward sub-tick: microbatch bi = t - 2(p-1) + my
        bi = t - 2 * (p - 1) + my
        valid_b = (bi >= 0) & (bi < m)
        args_b = micro_at(bi)
        x_b = jax.lax.dynamic_index_in_dim(
            stash, jnp.clip(bi, 0, m - 1) % depth, 0, keepdims=False)

        def f(sp, sh, xa):
            return stage_fn(sp, sh, xa, *args_b)

        _, pull = jax.vjp(f, stage_params, shared_params, x_b)
        # the last stage's cotangent enters through the loss output; earlier
        # stages take the ppermuted activation cotangent. Gate on valid_b so
        # bubble ticks contribute exact zeros.
        dy = jnp.where(valid_b & jnp.logical_not(is_last), dy_buf, 0.0)
        wl = jnp.where(valid_b & is_last, 1.0, 0.0)
        d_sp, d_sh, dx = pull((dy.astype(x_b.dtype), wl))
        # select (not multiply): bubble-tick pullbacks can contain non-finite
        # garbage; where() discards it exactly
        gate = lambda g: jnp.where(valid_b, g, 0.0)  # noqa: E731
        g_stage = jax.tree_util.tree_map(
            lambda a, g: a + gate(g), g_stage, d_sp)
        g_shared = jax.tree_util.tree_map(
            lambda a, g: a + gate(g), g_shared, d_sh)
        dx = jnp.where(valid_b, dx, 0.0)

        # ---- neighbor hops (one fwd + one bwd ppermute per tick)
        in_next = jax.lax.ppermute(y, axis_name, fwd_perm)
        dy_next = jax.lax.ppermute(dx, axis_name, bwd_perm)
        return (in_next, dy_next, stash, g_stage, g_shared, loss_acc), None

    stash0 = jnp.stack([x_template] * depth)
    carry0 = (x_template, jnp.zeros_like(x_template), stash0,
              zero_stage, zero_shared, jnp.float32(0.0))
    (_, _, _, g_stage, g_shared, loss_acc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(ticks))

    loss_sum = jax.lax.psum(loss_acc, axis_name)
    g_shared = jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, axis_name), g_shared)
    return loss_sum, g_stage, g_shared
