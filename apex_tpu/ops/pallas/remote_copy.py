"""Peer-to-peer device copies — Pallas TPU remote DMA.

The TPU materialization of the reference's peer-memory machinery
(``apex/contrib/peer_memory/peer_memory.py`` raw IPC buffers +
``peer_halo_exchanger_1d.py`` direct puts, and the ``nccl_p2p`` send/recv
pairs): ``pltpu.make_async_remote_copy`` issues a one-sided RDMA put over
ICI from this chip's buffer into a neighbor's, synchronized by DMA
semaphores — no collective, no host involvement. This is the same
hardware path XLA's ``ppermute`` lowers to, exposed as a kernel so halo
payloads can move while the surrounding kernel computes (the latency
hiding the reference's peer pools exist for).

Used by ``contrib.peer_memory`` / ``parallel.halo`` as the opt-in
``transport="rdma"`` path; the default XLA-collective path remains for
callers that prefer compiler-scheduled comm. Both are parity-tested
against each other on the virtual CPU mesh (interpret mode executes the
remote copies faithfully).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.lax import axis_size

from apex_tpu.utils.env import interpret_default


def _shift_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis_name, shift):
    my = jax.lax.axis_index(axis_name)
    n = axis_size(axis_name)
    dst = jax.lax.rem(my + shift + n, n)
    rdma = pltpu.make_async_remote_copy(
        src_ref=x_ref, dst_ref=o_ref, send_sem=send_sem, recv_sem=recv_sem,
        device_id=dst, device_id_type=pltpu.DeviceIdType.LOGICAL)
    rdma.start()
    rdma.wait()


def peer_shift(x: jax.Array, axis_name: str, shift: int = 1,
               interpret: bool | None = None) -> jax.Array:
    """Ring-shift ``x`` by ``shift`` positions along ``axis_name`` via a
    one-sided RDMA put (each device receives the shard of the device
    ``shift`` places behind it). Call inside ``shard_map``. Equivalent to
    ``jax.lax.ppermute`` with the ring permutation — implemented as an
    explicit peer copy, the ``nccl_p2p.nccl_send``/``nccl_recv`` pair."""
    if interpret is None:
        interpret = interpret_default()
    return pl.pallas_call(
        functools.partial(_shift_kernel, axis_name=axis_name, shift=shift),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(x)


def _tile_rows(dtype) -> int:
    """Minimum sublane (second-minor) tile for ``dtype`` — HBM memref
    slices must be tile-aligned on this axis (Mosaic rejects e.g. a 2-row
    f32 slice of an (8,128)-tiled ref; caught by tools/mosaic_aot.py)."""
    return {1: 32, 2: 16}.get(jnp.dtype(dtype).itemsize, 8)


def _halo_plan(rows: int, halo: int, dtype) -> tuple[int, bool, int]:
    """(send_rows, full, buf_rows) for a ``(rows, ...)`` halo exchange —
    the single source of the landing-buffer shape contract shared by
    ``halo_exchange_rdma`` and ``halo_buf_rows``."""
    t = _tile_rows(dtype)
    send_rows = -(-halo // t) * t  # halo rounded up to the sublane tile
    # whole-ref transfer when the shard is too small for an aligned edge
    # slice (also covers shards whose row count breaks the high-edge
    # slice's tile alignment)
    full = send_rows >= rows or rows % t != 0
    return send_rows, full, (rows if full else send_rows)


def halo_buf_rows(rows: int, halo: int, dtype) -> int:
    """Rows of the landing buffer ``halo_exchange_rdma`` uses for a
    ``(rows, ...)`` input — whole sublane tiles, or the full ref when the
    shard is small/unaligned. Exposed so callers (PeerMemoryPool) can
    pre-allocate aliasable landing buffers of the right shape."""
    return _halo_plan(rows, halo, dtype)[2]


def _halo_kernel(x_ref, lo_ref, hi_ref, slo, shi, rlo, rhi, *,
                 axis_name, send_rows, full):
    """Send my low edge to the LEFT neighbor's ``hi`` buffer and my high
    edge to the RIGHT neighbor's ``lo`` buffer (periodic ring; the wrapper
    zeroes wrap-around halos for non-periodic semantics).

    ``send_rows`` is the halo rounded UP to the dtype's sublane tile: HBM
    slices must be tile-aligned, so we over-send whole tiles and the
    wrapper slices the true halo out of the landed buffer. ``full`` ships
    the entire ref (no slice at all) when the shard is too small or not
    tile-aligned."""
    my = jax.lax.axis_index(axis_name)
    n = axis_size(axis_name)
    left = jax.lax.rem(my - 1 + n, n)
    right = jax.lax.rem(my + 1, n)
    if full:
        src_lo = src_hi = x_ref
    else:
        src_lo = x_ref.at[pl.ds(0, send_rows)]
        src_hi = x_ref.at[pl.ds(x_ref.shape[0] - send_rows, send_rows)]
    # my low-edge tiles -> left neighbor's hi_ref
    put_lo = pltpu.make_async_remote_copy(
        src_ref=src_lo, dst_ref=hi_ref,
        send_sem=slo, recv_sem=rhi,
        device_id=left, device_id_type=pltpu.DeviceIdType.LOGICAL)
    # my high-edge tiles -> right neighbor's lo_ref
    put_hi = pltpu.make_async_remote_copy(
        src_ref=src_hi, dst_ref=lo_ref, send_sem=shi, recv_sem=rlo,
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL)
    put_lo.start()
    put_hi.start()
    put_lo.wait()
    put_hi.wait()


def halo_exchange_rdma(x: jax.Array, axis_name: str, halo: int,
                       periodic: bool = False,
                       bufs=None, return_bufs: bool = False,
                       interpret: bool | None = None):
    """1-D halo exchange over leading axis via peer RDMA puts: returns
    ``(lo, hi)`` — the ``halo`` rows received from the left and right
    neighbors (≈ ``PeerHaloExchanger1d`` over a ``PeerMemoryPool``,
    peer_halo_exchanger_1d.py). ``periodic=False`` zeroes the wrap-around
    halos at the ring edges, matching the halo exchangers' boundary
    convention in ``parallel.halo``.

    ``bufs=(lo_buf, hi_buf)`` — optional pre-allocated landing buffers of
    shape ``(halo_buf_rows(rows, halo, dtype),) + x.shape[1:]`` (e.g. from
    a PeerMemoryPool arena). They are DONATED: the remote puts land in
    their storage via input/output aliasing instead of fresh HBM each
    call. ``return_bufs=True`` additionally returns the landed full
    buffers ``(lo_buf', hi_buf')`` so the caller can thread them into the
    next call (functional buffer reuse — the reference peer pool's
    no-per-iteration-allocation property, peer_memory.py:29-42, requires
    this threading; re-materializing views from the arena each call would
    allocate fresh storage and defeat the point)."""
    if interpret is None:
        interpret = interpret_default()
    rows = x.shape[0]
    send_rows, full, buf_rows = _halo_plan(rows, halo, x.dtype)
    kernel = functools.partial(_halo_kernel, axis_name=axis_name,
                               send_rows=send_rows, full=full)
    out_shape = [
        jax.ShapeDtypeStruct((buf_rows,) + x.shape[1:], x.dtype),
        jax.ShapeDtypeStruct((buf_rows,) + x.shape[1:], x.dtype),
    ]
    out_specs = [pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    sems = [pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA, pltpu.SemaphoreType.DMA]
    if bufs is not None:
        lo_in, hi_in = bufs
        want = (buf_rows,) + x.shape[1:]
        if lo_in.shape != want or hi_in.shape != want or \
                lo_in.dtype != x.dtype or hi_in.dtype != x.dtype:
            raise ValueError(
                f"landing buffers must be {want} {x.dtype} (use "
                f"halo_buf_rows); got {lo_in.shape}/{hi_in.shape} "
                f"{lo_in.dtype}")

        def kernel_aliased(x_ref, lo_in_ref, hi_in_ref, lo_ref, hi_ref,
                           *sems_):
            del lo_in_ref, hi_in_ref  # same storage as lo_ref/hi_ref
            kernel(x_ref, lo_ref, hi_ref, *sems_)

        lo_buf, hi_buf = pl.pallas_call(
            kernel_aliased,
            out_shape=out_shape,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=out_specs,
            scratch_shapes=sems,
            input_output_aliases={1: 0, 2: 1},
            interpret=interpret,
        )(x, lo_in, hi_in)
    else:
        lo_buf, hi_buf = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_specs,
            scratch_shapes=sems,
            interpret=interpret,
        )(x)
    # the landed buffers carry whole tiles; the true halo is the left
    # neighbor's LAST rows / right neighbor's FIRST rows
    lo = jax.lax.slice_in_dim(lo_buf, buf_rows - halo, buf_rows, axis=0)
    hi = jax.lax.slice_in_dim(hi_buf, 0, halo, axis=0)
    if return_bufs:
        out_bufs = (lo_buf, hi_buf)
    if not periodic:
        idx = jax.lax.axis_index(axis_name)
        n = axis_size(axis_name)
        lo = jnp.where(idx == 0, jnp.zeros_like(lo), lo)
        hi = jnp.where(idx == n - 1, jnp.zeros_like(hi), hi)
    if return_bufs:
        return lo, hi, out_bufs
    return lo, hi
