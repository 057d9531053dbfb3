"""Halo exchange + ring attention on the 8-device CPU mesh — port of the
spatial-parallel tests (apex/contrib/test bottleneck/peer_memory patterns) and
the long-context story (SURVEY §5)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import (HaloExchangerAllGather, HaloExchangerNoComm,
                               HaloExchangerPeer, get_mesh, halo_exchange_1d,
                               left_right_halo_exchange, make_mesh,
                               ring_self_attention)
from apex_tpu.parallel.ring_attention import (zigzag_ring_self_attention,
                                              zigzag_shard, zigzag_unshard)
from apex_tpu.transformer import mha_reference

WORLD = 8


@pytest.fixture(scope="module")
def mesh():
    return get_mesh("sp")


class TestHaloExchange:
    def test_left_right_exchange(self, mesh):
        # device i holds rows [i*4, (i+1)*4); halos are 1-row strips
        x = jnp.arange(WORLD * 4 * 3, dtype=jnp.float32).reshape(WORLD * 4, 3)

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=(P("sp"), P("sp")), check_vma=False)
        def ex(xb):
            top = xb[:1]
            bottom = xb[-1:]
            l, r = left_right_halo_exchange(top, bottom, "sp")
            return l, r

        left_in, right_in = ex(x)
        left_in = np.asarray(left_in).reshape(WORLD, 1, 3)
        right_in = np.asarray(right_in).reshape(WORLD, 1, 3)
        xn = np.asarray(x).reshape(WORLD, 4, 3)
        for i in range(WORLD):
            if i > 0:  # left neighbor's bottom row
                np.testing.assert_array_equal(left_in[i, 0], xn[i - 1, 3])
            else:
                np.testing.assert_array_equal(left_in[i, 0], 0.0)
            if i < WORLD - 1:  # right neighbor's top row
                np.testing.assert_array_equal(right_in[i, 0], xn[i + 1, 0])
            else:
                np.testing.assert_array_equal(right_in[i, 0], 0.0)

    def test_halo_padded_conv_matches_full(self, mesh):
        """Spatially-sharded 1D conv with halo exchange == full conv
        (the SpatialBottleneck correctness property, bottleneck.py:833)."""
        H, C = WORLD * 8, 4
        x = jax.random.normal(jax.random.PRNGKey(0), (H, C))
        kern = jax.random.normal(jax.random.PRNGKey(1), (3, C))

        def conv_rows(xp):  # 'same' conv over rows via explicit halo
            return sum(xp[i:i + xp.shape[0] - 2] * kern[i]
                       for i in range(3))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=P("sp"), check_vma=False)
        def sharded(xb):
            xpad = halo_exchange_1d(xb, 1, "sp", spatial_axis=0)
            return conv_rows(xpad)

        got = sharded(x)
        xfull = jnp.pad(x, ((1, 1), (0, 0)))
        want = conv_rows(xfull)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_allgather_flavor_matches_ppermute(self, mesh):
        x = jax.random.normal(jax.random.PRNGKey(2), (WORLD * 4, 5))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=(P("sp"), P("sp")), check_vma=False)
        def both(xb):
            a = HaloExchangerPeer("sp")(xb, 1)
            b = HaloExchangerAllGather("sp")(xb, 1)
            return a, b

        a, b = both(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_nocomm_zero_halos(self, mesh):
        x = jnp.ones((WORLD * 2, 3))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=P("sp"), check_vma=False)
        def ex(xb):
            return HaloExchangerNoComm("sp")(xb, 1)

        out = np.asarray(ex(x)).reshape(WORLD, 4, 3)
        np.testing.assert_array_equal(out[:, 0], 0.0)
        np.testing.assert_array_equal(out[:, -1], 0.0)


class TestRingAttention:
    B, H, D = 1, 2, 32
    S = WORLD * 128  # 128 per device

    def _qkv(self, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        shape = (self.B, self.H, self.S, self.D)
        return tuple(jax.random.normal(k, shape) * 0.5 for k in ks)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device_reference(self, mesh, causal):
        q, k, v = self._qkv()

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), check_vma=False)
        def ring(q, k, v):
            return ring_self_attention(q, k, v, "sp", causal=causal)

        got = ring(q, k, v)
        want = mha_reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    def test_zigzag_shard_roundtrip(self):
        x = jnp.arange(WORLD * 4.0).reshape(1, 1, WORLD * 4, 1)
        y = zigzag_unshard(zigzag_shard(x, WORLD), WORLD)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_zigzag_matches_single_device_reference(self, mesh):
        """Balanced causal ring (VERDICT item 6) == full causal attention."""
        q, k, v = self._qkv(seed=4)
        qz, kz, vz = (zigzag_shard(t, WORLD) for t in (q, k, v))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(None, None, "sp", None), check_vma=False)
        def ring(q, k, v):
            return zigzag_ring_self_attention(q, k, v, "sp")

        got = zigzag_unshard(ring(qz, kz, vz), WORLD)
        want = mha_reference(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.slow
    def test_zigzag_differentiable(self, mesh):
        q, k, v = self._qkv(seed=5)
        qz, kz, vz = (zigzag_shard(t, WORLD) for t in (q, k, v))

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(), check_vma=False)
        def loss(q, k, v):
            o = zigzag_ring_self_attention(q, k, v, "sp")
            return jax.lax.psum(jnp.sum(o * o), "sp")

        gq, gk, gv = jax.grad(loss, (0, 1, 2))(qz, kz, vz)

        def ref_loss(q, k, v):
            return jnp.sum(mha_reference(q, k, v, True) ** 2)

        rq, rk, rv = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
        for g, r, name in zip((gq, gk, gv), (rq, rk, rv), "qkv"):
            np.testing.assert_allclose(
                np.asarray(zigzag_unshard(g, WORLD)), np.asarray(r),
                atol=5e-4, rtol=5e-4, err_msg=f"d{name}")

    @pytest.mark.slow
    def test_differentiable(self, mesh):
        q, k, v = self._qkv(seed=1)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, "sp", None),) * 3,
            out_specs=P(), check_vma=False)
        def loss(q, k, v):
            o = ring_self_attention(q, k, v, "sp", causal=True)
            return jax.lax.psum(jnp.sum(o * o), "sp")

        gq, gk, gv = jax.grad(loss, (0, 1, 2))(q, k, v)

        def ref_loss(q, k, v):
            return jnp.sum(mha_reference(q, k, v, True) ** 2)

        rq, rk, rv = jax.grad(ref_loss, (0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                                   atol=5e-4, rtol=5e-4)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                                   atol=5e-4, rtol=5e-4)
        np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                                   atol=5e-4, rtol=5e-4)


class TestUlysses:
    """Ulysses all-to-all sequence parallelism vs single-device flash."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        import functools
        from jax.sharding import PartitionSpec as P
        from apex_tpu.ops.pallas.flash_attention import flash_attention
        from apex_tpu.parallel import get_mesh, ulysses_self_attention

        mesh = get_mesh("sp")
        n = len(jax.devices())
        b, h, s, d = 2, n, n * 16, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(k_, (b, h, s, d), jnp.float32) * 0.3
                   for k_ in ks)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(None, None, "sp"),
            out_specs=P(None, None, "sp"), check_vma=False)
        def sharded(q, k, v):
            return ulysses_self_attention(q, k, v, "sp", causal)

        out = sharded(q, k, v)
        ref = flash_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.slow
    def test_grad_matches_full_attention(self):
        import functools
        from jax.sharding import PartitionSpec as P
        from apex_tpu.ops.pallas.flash_attention import flash_attention
        from apex_tpu.parallel import get_mesh, ulysses_self_attention

        mesh = get_mesh("sp")
        n = len(jax.devices())
        b, h, s, d = 1, n, n * 8, 64
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(k_, (b, h, s, d), jnp.float32) * 0.3
                   for k_ in ks)

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(None, None, "sp"),
            out_specs=P(), check_vma=False)
        def loss_sharded(q, k, v):
            o = ulysses_self_attention(q, k, v, "sp", True)
            return jax.lax.psum(jnp.sum(o.astype(jnp.float32) ** 2), "sp")

        g = jax.grad(lambda q: loss_sharded(q, k, v)[()])(q)
        g_ref = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, True).astype(jnp.float32) ** 2))(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   atol=5e-5)

    def test_rejects_h_not_divisible(self):
        import functools
        from jax.sharding import PartitionSpec as P
        from apex_tpu.parallel import get_mesh, ulysses_self_attention

        mesh = get_mesh("sp")
        n = len(jax.devices())
        if n < 2:
            pytest.skip("needs >1 device")
        q = jnp.zeros((1, n - 1, n * 8, 64))

        @functools.partial(
            shard_map, mesh=mesh, in_specs=P(None, None, "sp"),
            out_specs=P(None, None, "sp"), check_vma=False)
        def sharded(q):
            return ulysses_self_attention(q, q, q, "sp", False)

        with pytest.raises(Exception):
            sharded(q)


@pytest.mark.slow
class TestRdmaTransport:
    """Pallas remote-DMA peer transport (ops/pallas/remote_copy) vs the
    ppermute collective path — both must produce identical halos (the
    peer_memory push_pull_halos_1d capability, peer_memory.cpp:20-34).

    slow: interpret-mode RDMA emulation dominates tier-1 wall clock; the
    ppermute-collective equivalents above keep the semantics covered in the
    fast tier."""

    def test_peer_shift_matches_ppermute(self, mesh):
        from apex_tpu.ops.pallas.remote_copy import peer_shift
        x = jnp.arange(WORLD * 4 * 3, dtype=jnp.float32).reshape(WORLD * 4, 3)

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=P("sp"), check_vma=False)
        def rdma(x):
            return peer_shift(x, "sp", 1)

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=P("sp"), check_vma=False)
        def coll(x):
            perm = [(i, (i + 1) % WORLD) for i in range(WORLD)]
            return jax.lax.ppermute(x, "sp", perm)

        np.testing.assert_array_equal(np.asarray(rdma(x)),
                                      np.asarray(coll(x)))

    @pytest.mark.parametrize("halo", [1, 2])
    def test_halo_exchange_rdma_matches_collective(self, mesh, halo):
        from apex_tpu.contrib.peer_memory import PeerHaloExchanger1d
        x = jnp.arange(WORLD * 4 * 3, dtype=jnp.float32).reshape(
            1, WORLD * 4, 3)
        outs = {}
        for transport in ("collective", "rdma"):
            ex = PeerHaloExchanger1d(half_halo=halo, axis_name="sp",
                                     transport=transport)

            @functools.partial(shard_map, mesh=mesh, in_specs=P(None, "sp"),
                               out_specs=P(None, "sp"), check_vma=False)
            def body(x, ex=ex):
                return ex(x, spatial_axis=1)

            outs[transport] = np.asarray(body(x))
        np.testing.assert_array_equal(outs["collective"], outs["rdma"])

    def test_left_right_rdma_matches_collective(self, mesh):
        from apex_tpu.contrib.peer_memory import PeerHaloExchanger1d
        lo = jnp.arange(WORLD * 2 * 3, dtype=jnp.float32).reshape(
            WORLD * 2, 3)
        hi = lo * 10.0
        outs = {}
        for transport in ("collective", "rdma"):
            ex = PeerHaloExchanger1d(axis_name="sp", transport=transport)

            @functools.partial(shard_map, mesh=mesh,
                               in_specs=(P("sp"), P("sp")),
                               out_specs=(P("sp"), P("sp")),
                               check_vma=False)
            def body(lo, hi, ex=ex):
                return ex.left_right_halo_exchange(lo, hi)

            outs[transport] = [np.asarray(a) for a in body(lo, hi)]
        np.testing.assert_array_equal(outs["collective"][0], outs["rdma"][0])
        np.testing.assert_array_equal(outs["collective"][1], outs["rdma"][1])

    def test_halo_exchange_with_pool_landing_bufs(self, mesh):
        """Pool-backed landing buffers, threaded the honest way: arena
        views enter shard_map as ARGUMENTS, the puts land in their
        storage via input/output aliasing, and the returned landed
        buffers re-thread into the next call (allocation-free steady
        state). Halos must match the pool-less rdma path both calls."""
        from apex_tpu.contrib.peer_memory import PeerMemoryPool
        from apex_tpu.ops.pallas.remote_copy import (halo_buf_rows,
                                                     halo_exchange_rdma)

        halo = 2
        rows_per_dev = 8
        x = jnp.arange(WORLD * rows_per_dev * 128,
                       dtype=jnp.float32).reshape(WORLD * rows_per_dev, 128)
        br = halo_buf_rows(rows_per_dev, halo, jnp.float32)

        pool = PeerMemoryPool(static_size=1 << 20)
        # one buffer pair per device slot, entering shard_map sharded so
        # each device's slice is the kernel's (br, 128) landing contract
        lo_b = pool.allocate_peer_tensors((WORLD * br, 128), jnp.float32,
                                          False, False)[0]
        hi_b = pool.allocate_peer_tensors((WORLD * br, 128), jnp.float32,
                                          False, False)[0]

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("sp"), P("sp"), P("sp")),
                           out_specs=(P("sp"), P("sp"), (P("sp"), P("sp"))),
                           check_vma=False)
        def body(x, lo_in, hi_in):
            lo, hi, landed = halo_exchange_rdma(x, "sp", halo,
                                                bufs=(lo_in, hi_in),
                                                return_bufs=True)
            return lo, hi, landed

        @functools.partial(shard_map, mesh=mesh, in_specs=P("sp"),
                           out_specs=(P("sp"), P("sp")), check_vma=False)
        def plain(x):
            return halo_exchange_rdma(x, "sp", halo)

        want_lo, want_hi = (np.asarray(a) for a in plain(x))
        lo1, hi1, landed = jax.jit(body)(x, lo_b, hi_b)
        np.testing.assert_array_equal(np.asarray(lo1), want_lo)
        np.testing.assert_array_equal(np.asarray(hi1), want_hi)
        # steady state: re-thread the landed buffers into the next call
        lo2, hi2, _ = jax.jit(body)(x, *landed)
        np.testing.assert_array_equal(np.asarray(lo2), want_lo)
        np.testing.assert_array_equal(np.asarray(hi2), want_hi)
        # the pool really sub-allocated arena ranges for the buffers
        assert len(pool.allocations) == 2
        assert all(r["offset"] % pool.alignment == 0
                   for r in pool.allocations)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_attention_rdma_matches_collective(self, mesh, causal):
        b, h, s, d = 1, 2, WORLD * 16, 32
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d)) * 0.3 for kk in ks)
        outs, grads = {}, {}
        for transport in ("collective", "rdma"):
            @functools.partial(
                shard_map, mesh=mesh, in_specs=P(None, None, "sp"),
                out_specs=P(None, None, "sp"), check_vma=False)
            def body(q, k, v, transport=transport):
                return ring_self_attention(q, k, v, "sp", causal,
                                           transport=transport)

            outs[transport] = np.asarray(body(q, k, v))
            grads[transport] = np.asarray(jax.grad(
                lambda q: jnp.sum(body(q, k, v) ** 2))(q))
        np.testing.assert_allclose(outs["collective"], outs["rdma"],
                                   atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(grads["collective"], grads["rdma"],
                                   atol=1e-6, rtol=1e-6)

    def test_zigzag_rdma_matches_collective(self, mesh):
        b, h, s, d = 1, 2, WORLD * 16, 32
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (b, h, s, d)) * 0.3 for kk in ks)
        qz = zigzag_shard(q, WORLD)
        kz = zigzag_shard(k, WORLD)
        vz = zigzag_shard(v, WORLD)
        outs = {}
        for transport in ("collective", "rdma"):
            @functools.partial(
                shard_map, mesh=mesh, in_specs=P(None, None, "sp"),
                out_specs=P(None, None, "sp"), check_vma=False)
            def body(q, k, v, transport=transport):
                return zigzag_ring_self_attention(q, k, v, "sp",
                                                  transport=transport)

            outs[transport] = np.asarray(jax.grad(
                lambda q: jnp.sum(body(q, kz, vz) ** 2))(qz))
        np.testing.assert_allclose(outs["collective"], outs["rdma"],
                                   atol=1e-6, rtol=1e-6)
