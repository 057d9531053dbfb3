"""Pipeline (pp) + expert (ep) parallelism tests on the 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import make_mesh
from apex_tpu.parallel.moe import moe_ffn_ep, top1_dispatch
from apex_tpu.parallel.pipeline import (pipeline_apply, stack_stage_params,
                                        unstack_local)

D = 16


def _stage_fn(params, x):
    return jnp.tanh(x @ params["w"] + params["b"])


def _stages(p, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), p)
    return [{"w": jax.random.normal(k, (D, D)) * 0.5,
             "b": jnp.zeros((D,))} for k in ks]


class TestPipeline:
    @pytest.mark.parametrize("p,m", [(2, 4), (4, 8)])
    def test_matches_sequential(self, p, m):
        mesh = make_mesh([p], ["pp"])
        stages = _stages(p)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.PRNGKey(1), (m * 2, D))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("pp"), P()), out_specs=P(),
                           check_vma=False)
        def run(sp, x):
            return pipeline_apply(_stage_fn, unstack_local(sp), x, "pp", m)

        got = run(stacked, x)
        want = x
        for s in stages:
            want = _stage_fn(s, want)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.slow
    def test_backward_matches_sequential(self):
        p, m = 4, 8
        mesh = make_mesh([p], ["pp"])
        stages = _stages(p, seed=2)
        stacked = stack_stage_params(stages)
        x = jax.random.normal(jax.random.PRNGKey(3), (m, D))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("pp"), P()), out_specs=P(),
                           check_vma=False)
        def fwd(sp, x):
            return pipeline_apply(_stage_fn, unstack_local(sp), x, "pp", m)

        def ref_loss(stages, x):
            y = x
            for s in stages:
                y = _stage_fn(s, y)
            return jnp.sum(y * y)

        # grads THROUGH the pipelined shard_map (autodiff transposes the
        # GPipe schedule: reverse ppermutes, reverse scan)
        gx = jax.grad(lambda x: jnp.sum(fwd(stacked, x) ** 2))(x)
        gs = jax.grad(lambda sp: jnp.sum(fwd(sp, x) ** 2))(stacked)
        rx = jax.grad(lambda x: ref_loss(stages, x))(x)
        rs = [jax.grad(lambda s, i=i: ref_loss(
            stages[:i] + [s] + stages[i + 1:], x))(stages[i])
            for i in range(p)]
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=1e-4, rtol=1e-4)
        for i in range(p):
            np.testing.assert_allclose(np.asarray(gs["w"][i]),
                                       np.asarray(rs[i]["w"]),
                                       atol=1e-4, rtol=1e-4)


class TestMoE:
    def test_dispatch_respects_capacity(self):
        logits = jnp.array([[9., 0.], [9., 0.], [9., 0.], [0., 9.]])
        dispatch, combine = top1_dispatch(logits, 2, capacity=2)
        # three tokens want expert 0 but capacity is 2 → one dropped
        assert float(dispatch[:, 0].sum()) == 2.0
        assert float(dispatch[3, 1].sum()) == 1.0
        assert float(dispatch[2].sum()) == 0.0  # dropped token

    def test_ep_matches_single_device(self):
        """EP over 4 devices == same MoE computed densely on one device."""
        ep, e, d, h, t = 4, 8, 16, 32, 64
        mesh = make_mesh([ep], ["ep"])
        k = jax.random.split(jax.random.PRNGKey(0), 4)
        gate_w = jax.random.normal(k[0], (d, e)) * 0.5
        w1 = jax.random.normal(k[1], (e, d, h)) * 0.2
        w2 = jax.random.normal(k[2], (e, h, d)) * 0.2
        x = jax.random.normal(k[3], (t, d))
        cap = int(t / e * 1.25)

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(), P(), P("ep"), P("ep")),
                           out_specs=P(), check_vma=False)
        def run(x, gw, w1l, w2l):
            return moe_ffn_ep(x, gw, w1l, w2l, "ep")

        got = run(x, gate_w, w1, w2)

        # dense reference with identical routing
        logits = x @ gate_w
        dispatch, combine = top1_dispatch(logits, e, cap)
        exp_in = jnp.einsum("tec,td->ecd", dispatch, x)
        z = jax.nn.gelu(jnp.einsum("ecd,edh->ech", exp_in, w1))
        out = jnp.einsum("ech,ehd->ecd", z, w2)
        want = jnp.einsum("tec,ecd->td", combine, out)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4)

    @pytest.mark.slow
    def test_ep_differentiable(self):
        ep, e, d, h, t = 2, 4, 8, 16, 32
        mesh = make_mesh([ep], ["ep"])
        k = jax.random.split(jax.random.PRNGKey(1), 4)
        gate_w = jax.random.normal(k[0], (d, e)) * 0.5
        w1 = jax.random.normal(k[1], (e, d, h)) * 0.2
        w2 = jax.random.normal(k[2], (e, h, d)) * 0.2
        x = jax.random.normal(k[3], (t, d))

        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(), P(), P("ep"), P("ep")),
                           out_specs=P(), check_vma=False)
        def loss(x, gw, w1l, w2l):
            y = moe_ffn_ep(x, gw, w1l, w2l, "ep")
            return jnp.sum(y * y)

        g = jax.grad(loss, argnums=(0, 2))(x, gate_w, w1, w2)
        for leaf in g:
            assert bool(jnp.all(jnp.isfinite(leaf)))
