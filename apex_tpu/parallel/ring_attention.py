"""Ring attention — sequence/context parallelism for long sequences.

The reference has no ring attention (SURVEY §5: apex's closest artifacts are
the spatial halo exchangers and the 'generic' softmax that lifts the row-length
limit). The TPU framework builds the long-context story from the same two
primitives idiomatically: the Pallas flash kernel for the local block and
``ppermute`` neighbor exchange (the halo machinery generalized to a ring) for
the cross-device pass — K/V shards rotate around the ICI ring while each
device's Q stays resident, with online log-sum-exp merging of partial results.

Memory: O(local_seq · d) per device; comm: n-1 K/V hops (+ n dK/dV hops in the backward) of the local
shard per layer, riding ICI neighbor links (never DCN within a slice).

Two sharding layouts:

- **Contiguous** (``ring_self_attention``): device i holds global chunk i.
  Fine for non-causal. For causal it wastes ~2× FLOPs: ring steps whose
  source shard is entirely in the future must still be materialized in the
  scan (uniform step shape), and causal work is imbalanced across devices.
- **Zigzag** (``zigzag_ring_self_attention``, round-2, VERDICT item 6): the
  global sequence is split into 2n chunks; device i holds chunk i (the "low"
  half) and chunk 2n-1-i (the "high" half). Under causal masking every ring
  step then does exactly the same 2·c² work (c = chunk length): for a source
  shard earlier in the ring, all local queries attend its low chunk only;
  for a later source, only the local high queries attend its full shard. The
  step dispatches between those two equal-cost branches with ``lax.cond`` —
  no masked-and-discarded kernel invocations, total causal FLOPs ≈ S²/(2n)
  per device (the optimum), ~2× better than the contiguous layout.

Causal gating uses ``lax.cond``/``jnp.where`` selection — never multiplying
a possibly-non-finite partial by a 0/1 gate (a 0·inf there poisons dq/dk/dv
with NaN; advisor finding round-1).

Backward: a custom VJP runs the ring in the same direction once more — dK/dV
accumulators travel WITH the rotating K/V shards, each device adding its
block's contribution as the shard passes through, so after a full loop the
gradients arrive back at their owner. dQ accumulates locally. Each block's
contribution uses the Pallas flash backward kernels with the FINAL merged
logsumexp (P = exp(S - lse_final) is the exact global softmax probability).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from apex_tpu.ops.pallas.flash_attention import (flash_attention_bwd,
                                                 flash_attention_fwd)

_f32 = jnp.float32
_NEG = -1e30  # python scalar: no device-array creation at import time


def _merge(o1, lse1, o2, lse2):
    """Log-sum-exp merge of two partial attention results (o, lse)."""
    m = jnp.maximum(lse1, lse2)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    tot = w1 + w2
    safe = jnp.where(tot > 0, tot, 1.0)
    o = (o1 * w1[..., None] + o2 * w2[..., None]) / safe[..., None]
    lse = m + jnp.log(safe)
    lse = jnp.where(tot > 0, lse, _NEG)
    return o, lse



def _rotate(x, axis_name, perm, transport):
    """One +1 ring hop of ``x``. ``transport="rdma"`` issues the Pallas
    one-sided remote-DMA put (ops/pallas/remote_copy.peer_shift — an
    explicit peer copy over ICI); the default stays the compiler-scheduled
    ``ppermute``. Numerics are identical (parity-tested)."""
    if transport == "rdma":
        from apex_tpu.ops.pallas.remote_copy import peer_shift

        return peer_shift(x, axis_name, 1)
    return jax.lax.ppermute(x, axis_name, perm)


# ------------------------------------------------------- contiguous layout


def _ring_fwd_impl(q, k, v, axis_name, causal, s, block_q, block_k,
                   transport="collective"):
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    # step 0: diagonal block — causal within the local shard
    o, lse = flash_attention_fwd(q, k, v, scale=s, causal=causal,
                                 block_q=block_q, block_k=block_k)
    o = o.astype(_f32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def compute_step(o_acc, lse_acc, k_cur, v_cur, step):
        # at scan index `step` the carry holds the shard of device
        # (my - step - 1) mod n (it has made step+1 hops)
        src = (my - step - 1) % n
        o_i, lse_i = flash_attention_fwd(q, k_cur, v_cur, scale=s,
                                         causal=False, block_q=block_q,
                                         block_k=block_k)
        if causal:
            # mask whole contribution when the source shard is in my future
            lse_i = jnp.where(src < my, lse_i, _NEG)
        return _merge(o_acc, lse_acc, o_i.astype(_f32), lse_i)

    def body(carry, step):
        o_acc, lse_acc, k_cur, v_cur = carry
        # the hop for the NEXT step is dataflow-independent of this step's
        # flash compute, so XLA's latency-hiding scheduler overlaps the
        # collective with the matmuls (a head-of-body rotate would
        # serialize comm then compute)
        k_nxt = _rotate(k_cur, axis_name, perm, transport)
        v_nxt = _rotate(v_cur, axis_name, perm, transport)
        o_acc, lse_acc = compute_step(o_acc, lse_acc, k_cur, v_cur, step)
        return (o_acc, lse_acc, k_nxt, v_nxt), None

    if n > 1:
        # first hop issued here, overlapping the diagonal block's compute;
        # the LAST step is peeled out of the scan so no wasted (n-th) hop
        # is ever issued — exactly n-1 K/V rotations total
        k1 = _rotate(k, axis_name, perm, transport)
        v1 = _rotate(v, axis_name, perm, transport)
        if n > 2:
            (o, lse, k1, v1), _ = jax.lax.scan(
                body, (o, lse, k1, v1), jnp.arange(n - 2))
        o, lse = compute_step(o, lse, k1, v1, n - 2)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        axis_name: str, causal: bool = False,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        transport: str = "collective") -> jax.Array:
    """Ring attention over the ``axis_name`` mesh axis.

    q/k/v: LOCAL shards (b, h, s_local, d) of a sequence sharded contiguously
    along the axis. Returns the local output shard (b, h, s_local, d).
    Call inside shard_map/pjit with the sequence axis bound to ``axis_name``.
    For causal long-context training prefer ``zigzag_ring_self_attention``.
    """
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, _ = _ring_fwd_impl(q, k, v, axis_name, causal, s, block_q, block_k,
                          transport)
    return o


def _ring_vjp_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                  transport):
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, lse = _ring_fwd_impl(q, k, v, axis_name, causal, s, block_q, block_k,
                            transport)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, block_q, block_k, transport,
                  res, do):
    q, k, v, o, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    lse = lse.astype(_f32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    # diagonal contribution (own shard, still home)
    dq_acc, dk_cur, dv_cur, _ = flash_attention_bwd(
        q, k, v, o, lse, do, scale=s, causal=causal,
        block_q=block_q, block_k=block_k)
    dq_acc = dq_acc.astype(_f32)
    dk_cur = dk_cur.astype(_f32)
    dv_cur = dv_cur.astype(_f32)

    def compute_step(k_cur, v_cur, step):
        src = (my - step - 1) % n
        dq_j, dk_j, dv_j, _ = flash_attention_bwd(
            q, k_cur, v_cur, o, lse, do, scale=s, causal=False,
            block_q=block_q, block_k=block_k)
        if causal:
            # select, don't multiply: dq_j may contain inf/nan for masked
            # future shards (exp(s - lse) overflow) and 0 * inf = nan
            allowed = src < my
            dq_j = jnp.where(allowed, dq_j.astype(_f32), 0.0)
            dk_j = jnp.where(allowed, dk_j.astype(_f32), 0.0)
            dv_j = jnp.where(allowed, dv_j.astype(_f32), 0.0)
        return dq_j.astype(_f32), dk_j.astype(_f32), dv_j.astype(_f32)

    def body(carry, step):
        # carry holds the shard PRESENT on this device and its aligned
        # gradient accumulator; rotations sit at the TAIL of the body so
        # the k/v hop (independent of this step's compute) overlaps the
        # backward matmuls. The dk/dv hop necessarily follows the add —
        # that half of the comm is the ring-backward dependency chain.
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        dq_j, dk_j, dv_j = compute_step(k_cur, v_cur, step)
        dq_acc = dq_acc + dq_j
        dk_cur = dk_cur + dk_j
        dv_cur = dv_cur + dv_j
        k_nxt = _rotate(k_cur, axis_name, perm, transport)
        v_nxt = _rotate(v_cur, axis_name, perm, transport)
        dk_nxt = _rotate(dk_cur, axis_name, perm, transport)
        dv_nxt = _rotate(dv_cur, axis_name, perm, transport)
        return (dq_acc, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    if n > 1:
        # pre-rotate once (overlapping the diagonal backward above); the
        # last step is peeled: its k/v need no further hop (n-1 K/V hops
        # total) while dk/dv take their final homecoming hop (n total)
        k1 = _rotate(k, axis_name, perm, transport)
        v1 = _rotate(v, axis_name, perm, transport)
        dk1 = _rotate(dk_cur, axis_name, perm, transport)
        dv1 = _rotate(dv_cur, axis_name, perm, transport)
        if n > 2:
            (dq_acc, k1, v1, dk1, dv1), _ = jax.lax.scan(
                body, (dq_acc, k1, v1, dk1, dv1), jnp.arange(n - 2))
        dq_j, dk_j, dv_j = compute_step(k1, v1, n - 2)
        dq_acc = dq_acc + dq_j
        dk_cur = _rotate(dk1 + dk_j, axis_name, perm, transport)
        dv_cur = _rotate(dv1 + dv_j, axis_name, perm, transport)
    return (dq_acc.astype(q.dtype), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


ring_self_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None):
    """Alias with the conventional name."""
    return ring_self_attention(q, k, v, axis_name, causal, scale)


# ---------------------------------------------------------- zigzag layout


def zigzag_shard(x, n: int, axis: int = 2):
    """Reorder a GLOBAL sequence axis into zigzag layout.

    Splits the axis into 2n chunks and orders them so that a contiguous
    n-way shard gives device i chunks (i, 2n-1-i). Apply before sharding;
    ``zigzag_unshard`` inverts.
    """
    s = x.shape[axis]
    assert s % (2 * n) == 0, f"seq {s} must divide 2n={2 * n}"
    chunks = jnp.split(x, 2 * n, axis=axis)
    order = []
    for i in range(n):
        order += [chunks[i], chunks[2 * n - 1 - i]]
    return jnp.concatenate(order, axis=axis)


def zigzag_unshard(x, n: int, axis: int = 2):
    """Invert ``zigzag_shard``."""
    chunks = jnp.split(x, 2 * n, axis=axis)
    inv = [None] * (2 * n)
    for i in range(n):
        inv[i] = chunks[2 * i]
        inv[2 * n - 1 - i] = chunks[2 * i + 1]
    return jnp.concatenate(inv, axis=axis)


def _zz_fwd_impl(q, k, v, axis_name, s, block_q, block_k,
                 transport="collective"):
    """Causal zigzag ring forward. Local layout: [low chunk, high chunk]."""
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    c = q.shape[2] // 2

    # diagonal: local [lo, hi] preserves global order (all lo positions
    # precede all hi positions), so plain top-left causal flash is exact
    o, lse = flash_attention_fwd(q, k, v, scale=s, causal=True,
                                 block_q=block_q, block_k=block_k)
    o = o.astype(_f32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step_earlier(k_cur, v_cur):
        # src earlier in the ring: every local query attends src's LOW chunk
        # fully; src's high chunk is in everyone's future. cost: 2c × c
        o_i, lse_i = flash_attention_fwd(
            q, k_cur[:, :, :c], v_cur[:, :, :c], scale=s, causal=False,
            block_q=block_q, block_k=block_k)
        return o_i.astype(_f32), lse_i

    def step_later(k_cur, v_cur):
        # src later in the ring: only local HIGH queries attend, but they
        # attend src's full shard (both its chunks precede my high chunk).
        # cost: c × 2c — identical to the other branch: balanced ring.
        o_hi, lse_hi = flash_attention_fwd(
            q[:, :, c:], k_cur, v_cur, scale=s, causal=False,
            block_q=block_q, block_k=block_k)
        o_i = jnp.concatenate([jnp.zeros_like(o_hi), o_hi.astype(_f32)],
                              axis=2)
        lse_i = jnp.concatenate([jnp.full_like(lse_hi, _NEG), lse_hi],
                                axis=2)
        return o_i, lse_i

    def compute_step(o_acc, lse_acc, k_cur, v_cur, step):
        src = (my - step - 1) % n
        o_i, lse_i = jax.lax.cond(src < my, step_earlier, step_later,
                                  k_cur, v_cur)
        return _merge(o_acc, lse_acc, o_i, lse_i)

    def body(carry, step):
        o_acc, lse_acc, k_cur, v_cur = carry
        # tail rotation: the next hop is independent of this step's flash
        # compute, so the scheduler overlaps comm with the matmuls
        k_nxt = _rotate(k_cur, axis_name, perm, transport)
        v_nxt = _rotate(v_cur, axis_name, perm, transport)
        o_acc, lse_acc = compute_step(o_acc, lse_acc, k_cur, v_cur, step)
        return (o_acc, lse_acc, k_nxt, v_nxt), None

    if n > 1:
        # last step peeled: exactly n-1 hops, none wasted
        k1 = _rotate(k, axis_name, perm, transport)
        v1 = _rotate(v, axis_name, perm, transport)
        if n > 2:
            (o, lse, k1, v1), _ = jax.lax.scan(
                body, (o, lse, k1, v1), jnp.arange(n - 2))
        o, lse = compute_step(o, lse, k1, v1, n - 2)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def zigzag_ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                               axis_name: str,
                               scale: Optional[float] = None,
                               block_q: int = 128,
                               block_k: int = 128,
                               transport: str = "collective") -> jax.Array:
    """Causal ring attention in the balanced zigzag layout.

    q/k/v: LOCAL shards (b, h, s_local, d) where the GLOBAL sequence was
    reordered with ``zigzag_shard(x, n)`` before sharding, so this device
    holds [chunk i, chunk 2n-1-i]. Output is the local shard in the same
    layout (``zigzag_unshard`` recovers natural order). Always causal —
    for non-causal use ``ring_self_attention`` (already balanced).
    """
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, _ = _zz_fwd_impl(q, k, v, axis_name, s, block_q, block_k, transport)
    return o


def _zz_vjp_fwd(q, k, v, axis_name, scale, block_q, block_k, transport):
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, lse = _zz_fwd_impl(q, k, v, axis_name, s, block_q, block_k,
                          transport)
    return o, (q, k, v, o, lse)


def _zz_vjp_bwd(axis_name, scale, block_q, block_k, transport, res, do):
    q, k, v, o, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    n = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    c = q.shape[2] // 2
    lse = lse.astype(_f32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq_acc, dk_cur, dv_cur, _ = flash_attention_bwd(
        q, k, v, o, lse, do, scale=s, causal=True,
        block_q=block_q, block_k=block_k)
    dq_acc = dq_acc.astype(_f32)
    dk_cur = dk_cur.astype(_f32)
    dv_cur = dv_cur.astype(_f32)

    def bwd_earlier(k_cur, v_cur):
        dq_j, dk_lo, dv_lo, _ = flash_attention_bwd(
            q, k_cur[:, :, :c], v_cur[:, :, :c], o, lse, do, scale=s,
            causal=False, block_q=block_q, block_k=block_k)
        zeros_k = jnp.zeros((dk_lo.shape[0], dk_lo.shape[1], c,
                             dk_lo.shape[3]), _f32)
        dk_j = jnp.concatenate([dk_lo.astype(_f32), zeros_k], axis=2)
        dv_j = jnp.concatenate([dv_lo.astype(_f32), zeros_k], axis=2)
        return dq_j.astype(_f32), dk_j, dv_j

    def bwd_later(k_cur, v_cur):
        dq_hi, dk_j, dv_j, _ = flash_attention_bwd(
            q[:, :, c:], k_cur, v_cur, o[:, :, c:], lse[:, :, c:],
            do[:, :, c:], scale=s, causal=False,
            block_q=block_q, block_k=block_k)
        dq_j = jnp.concatenate([jnp.zeros_like(dq_hi, _f32),
                                dq_hi.astype(_f32)], axis=2)
        return dq_j, dk_j.astype(_f32), dv_j.astype(_f32)

    def compute_step(k_cur, v_cur, step):
        src = (my - step - 1) % n
        return jax.lax.cond(src < my, bwd_earlier, bwd_later, k_cur, v_cur)

    def body(carry, step):
        # tail rotations (see _ring_vjp_bwd): the k/v hop overlaps this
        # step's backward matmuls; the dk/dv hop follows the add
        dq_acc, k_cur, v_cur, dk_cur, dv_cur = carry
        dq_j, dk_j, dv_j = compute_step(k_cur, v_cur, step)
        dk_cur = dk_cur + dk_j
        dv_cur = dv_cur + dv_j
        k_nxt = _rotate(k_cur, axis_name, perm, transport)
        v_nxt = _rotate(v_cur, axis_name, perm, transport)
        dk_nxt = _rotate(dk_cur, axis_name, perm, transport)
        dv_nxt = _rotate(dv_cur, axis_name, perm, transport)
        return (dq_acc + dq_j, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    if n > 1:
        # last step peeled: k/v make n-1 hops, dk/dv their homecoming n-th
        k1 = _rotate(k, axis_name, perm, transport)
        v1 = _rotate(v, axis_name, perm, transport)
        dk1 = _rotate(dk_cur, axis_name, perm, transport)
        dv1 = _rotate(dv_cur, axis_name, perm, transport)
        if n > 2:
            (dq_acc, k1, v1, dk1, dv1), _ = jax.lax.scan(
                body, (dq_acc, k1, v1, dk1, dv1), jnp.arange(n - 2))
        dq_j, dk_j, dv_j = compute_step(k1, v1, n - 2)
        dq_acc = dq_acc + dq_j
        dk_cur = _rotate(dk1 + dk_j, axis_name, perm, transport)
        dv_cur = _rotate(dv1 + dv_j, axis_name, perm, transport)
    return (dq_acc.astype(q.dtype), dk_cur.astype(k.dtype),
            dv_cur.astype(v.dtype))


zigzag_ring_self_attention.defvjp(_zz_vjp_fwd, _zz_vjp_bwd)
