"""1-D halo exchange over ICI — TPU equivalent of the reference's halo stack:

- ``nccl_p2p_cuda.left_right_halo_exchange`` (apex/contrib/csrc/nccl_p2p/nccl_p2p.cpp:24-26)
- ``peer_memory_cuda.push_pull_halos_1d`` (apex/contrib/csrc/peer_memory/peer_memory.cpp:34)
- the pluggable exchangers of apex/contrib/bottleneck/halo_exchangers.py:28-201
  (``HaloExchangerNoComm`` :28, ``HaloExchangerAllGather`` :46,
  ``HaloExchangerSendRecv`` :95, ``HaloExchangerPeer`` :146)

TPU design: neighbor transfer is ``jax.lax.ppermute`` on a named mesh axis —
the compiler lowers it to direct ICI neighbor DMA, which *is* the peer-memory
push of the reference (SURVEY §2.5). All four reference exchanger flavors
collapse onto two implementations (ppermute, all_gather); the class zoo is kept
for API parity and for tests that exercise each. This module is also the
building block ring attention generalizes (SURVEY §5 long-context).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size


def left_right_halo_exchange(left_output_halo: jax.Array,
                             right_output_halo: jax.Array,
                             axis_name: str) -> Tuple[jax.Array, jax.Array]:
    """Send my left/right edge strips to my left/right neighbors; receive
    theirs. Returns ``(left_input_halo, right_input_halo)`` — what arrives
    from the left / right neighbor respectively (nccl_p2p.cpp:24 semantics,
    non-periodic: edge devices receive zeros).
    """
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    # right-going: my right halo → right neighbor's left input
    right_perm = [(i, i + 1) for i in range(n - 1)]
    left_in = jax.lax.ppermute(right_output_halo, axis_name, right_perm)
    # left-going: my left halo → left neighbor's right input
    left_perm = [(i + 1, i) for i in range(n - 1)]
    right_in = jax.lax.ppermute(left_output_halo, axis_name, left_perm)
    # non-edge devices got data; edges receive zeros (ppermute default)
    del idx
    return left_in, right_in


def halo_exchange_1d(x: jax.Array, halo: int, axis_name: str,
                     spatial_axis: int = 0) -> jax.Array:
    """Pad the sharded spatial axis with ``halo`` rows from each neighbor
    (the SpatialBottleneck pre-conv exchange, bottleneck.py:304+).

    Returns x extended to ``shape[spatial_axis] + 2*halo``; edge devices get
    zero padding on their outer side.
    """
    top = jax.lax.slice_in_dim(x, 0, halo, axis=spatial_axis)
    bottom_start = x.shape[spatial_axis] - halo
    bottom = jax.lax.slice_in_dim(x, bottom_start,
                                  x.shape[spatial_axis], axis=spatial_axis)
    left_in, right_in = left_right_halo_exchange(top, bottom, axis_name)
    return jnp.concatenate([left_in, x, right_in], axis=spatial_axis)


class HaloExchanger:
    """Base for the exchanger zoo (halo_exchangers.py:28-201 parity)."""

    def __init__(self, axis_name: str = "spatial"):
        self.axis_name = axis_name

    def left_right_halo_exchange(self, left_output_halo, right_output_halo):
        return left_right_halo_exchange(left_output_halo, right_output_halo,
                                        self.axis_name)

    def __call__(self, x, halo: int, spatial_axis: int = 0):
        return halo_exchange_1d(x, halo, self.axis_name, spatial_axis)


class HaloExchangerNoComm(HaloExchanger):
    """Correctness-ablation exchanger (halo_exchangers.py:28): returns zero
    halos without touching the fabric."""

    def left_right_halo_exchange(self, left_output_halo, right_output_halo):
        return (jnp.zeros_like(right_output_halo),
                jnp.zeros_like(left_output_halo))

    def __call__(self, x, halo: int, spatial_axis: int = 0):
        z_top = jnp.zeros_like(
            jax.lax.slice_in_dim(x, 0, halo, axis=spatial_axis))
        return jnp.concatenate([z_top, x, z_top], axis=spatial_axis)


class HaloExchangerAllGather(HaloExchanger):
    """all_gather-based exchange (halo_exchangers.py:46): gather every
    device's strips, pick the neighbors'. Costs world× bandwidth — kept for
    parity/testing like the reference."""

    def left_right_halo_exchange(self, left_output_halo, right_output_halo):
        n = axis_size(self.axis_name)
        idx = jax.lax.axis_index(self.axis_name)
        lefts = jax.lax.all_gather(left_output_halo, self.axis_name)
        rights = jax.lax.all_gather(right_output_halo, self.axis_name)
        left_in = jnp.where(idx > 0, rights[jnp.maximum(idx - 1, 0)],
                            jnp.zeros_like(right_output_halo))
        right_in = jnp.where(idx < n - 1,
                             lefts[jnp.minimum(idx + 1, n - 1)],
                             jnp.zeros_like(left_output_halo))
        return left_in, right_in


class HaloExchangerSendRecv(HaloExchanger):
    """p2p send/recv flavor (halo_exchangers.py:95) — on TPU identical to the
    ppermute base (ppermute IS the p2p primitive)."""


class HaloExchangerPeer(HaloExchanger):
    """CUDA-IPC peer-memory flavor (halo_exchangers.py:146). On TPU direct
    neighbor DMA over ICI is what ppermute compiles to, so this is the base
    implementation; the ``peer_pool`` argument of the reference has no analog
    (XLA owns buffers)."""
