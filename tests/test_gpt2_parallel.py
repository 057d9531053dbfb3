"""Gradient parity for the dp×tp×sp parallel GPT-2 train step: grads computed
on a multi-device mesh must equal single-device autodiff (the review finding
that AdamW scale-invariance can mask a world-size factor — this pins it)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import PartitionSpec as P

from apex_tpu.models.gpt2 import GPT2Config
from apex_tpu.models.gpt2_parallel import (_forward_local, _grad_sync_specs,
                                           choose_mesh_shape, init_opt_state,
                                           init_params, make_train_step,
                                           param_specs)
from apex_tpu.parallel.mesh import make_mesh

CFG = GPT2Config(vocab_size=64, n_positions=256, n_embd=64, n_layer=1,
                 n_head=8)


def _data(batch=8):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 256), 0,
                                CFG.vocab_size, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
    return tokens, targets, mask


def _grads_on_mesh(params, data, dp, tp, sp):
    mesh = make_mesh([dp, tp, sp], ["dp", "tp", "sp"])
    pspecs = param_specs(CFG)
    sync_axes = _grad_sync_specs(CFG)

    def local(params, tokens, targets, mask):
        grads = jax.grad(
            lambda p: _forward_local(CFG, p, tokens, targets, mask))(params)
        n_total = (axis_size("dp") * axis_size("tp")
                   * axis_size("sp"))

        def sync(g, axes):
            for ax in axes.split("|"):
                g = jax.lax.psum(g, ax)
            return g / n_total

        return jax.tree_util.tree_map(sync, grads, sync_axes)

    f = shard_map(local, mesh=mesh,
                  in_specs=(pspecs, P("dp", "sp"), P("dp", "sp"),
                            P("dp", "sp")),
                  out_specs=pspecs, check_vma=False)
    return jax.jit(f)(params, *data)


# tier-1 runs the all-axes (2,2,2) cell (dp+tp+sp parity at once); the
# single-axis cells stay in the slow tier
@pytest.mark.parametrize("shape", [
    pytest.param((2, 1, 1), marks=pytest.mark.slow),
    pytest.param((1, 2, 1), marks=pytest.mark.slow),
    pytest.param((1, 1, 2), marks=pytest.mark.slow),
    (2, 2, 2)])
def test_parallel_grads_match_single_device(shape):
    params = init_params(CFG, jax.random.PRNGKey(0))
    data = _data()
    ref = _grads_on_mesh(params, data, 1, 1, 1)
    got = _grads_on_mesh(params, data, *shape)
    flat_r = jax.tree_util.tree_leaves(ref)
    flat_g = jax.tree_util.tree_leaves(got)
    for a, b in zip(flat_g, flat_r):
        # bf16 compute → reduction-order noise across shardings; the bound
        # still rules out any world-size scaling factor (2x would blow rtol)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-3, rtol=0.05)


@pytest.mark.slow
def test_train_step_descends_on_mesh():
    params = init_params(CFG, jax.random.PRNGKey(0))
    opt_state = init_opt_state(params)
    mesh = make_mesh([2, 2, 2], ["dp", "tp", "sp"])
    step_fn = make_train_step(CFG, mesh, lr=3e-3)
    tokens, targets, mask = _data()
    losses = []
    for i in range(5):
        params, opt_state, loss = step_fn(params, opt_state, tokens, targets,
                                          mask, jnp.int32(i + 1))
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_choose_mesh_shape():
    assert choose_mesh_shape(8) == (2, 2, 2)
    assert choose_mesh_shape(4) == (2, 2, 1)
    assert choose_mesh_shape(2) == (2, 1, 1)
    assert choose_mesh_shape(1) == (1, 1, 1)


@pytest.mark.slow
class TestPipelineComposed:
    """Round-2 pp/ep composition (VERDICT item 5): the 1F1B-pipelined model
    must match the non-pp model, and the 5-axis MoE variant must train."""

    def _data(self, batch=4):
        tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, 256), 0,
                                    CFG.vocab_size, jnp.int32)
        targets = jnp.roll(tokens, -1, axis=1)
        mask = jnp.ones(tokens.shape, jnp.float32).at[:, -1].set(0.0)
        return tokens, targets, mask

    def test_pp_loss_and_grads_match_non_pp(self):
        from apex_tpu.models.gpt2_parallel import (init_params_pp,
                                                   make_train_step_pp)
        cfg = GPT2Config(vocab_size=64, n_positions=256, n_embd=64,
                         n_layer=2, n_head=8)
        tokens, targets, mask = self._data()
        key = jax.random.PRNGKey(0)

        mesh_a = make_mesh([2, 2, 2], ["dp", "tp", "sp"])
        p_a = init_params(cfg, key)
        step_a = make_train_step(cfg, mesh_a, lr=1e-3)
        pa, sta, loss_a = step_a(p_a, init_opt_state(p_a), tokens, targets,
                                 mask, jnp.int32(1))

        mesh_b = make_mesh([1, 2, 2, 2, 1],
                           ["dp", "pp", "tp", "sp", "ep"])
        p_b = init_params_pp(cfg, key)
        step_b = make_train_step_pp(cfg, mesh_b, lr=1e-3,
                                    num_microbatches=2)
        pb, stb, loss_b = step_b(p_b, init_opt_state(p_b), tokens, targets,
                                 mask, jnp.int32(1))

        np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-5)
        # Adam first-moment state == grads at step 1 (up to (1-b1) scale):
        # the strongest cross-layout grad parity check
        m_a = np.stack([np.asarray(b["wq"]) for b in sta[0]["blocks"]])
        m_b = np.asarray(stb[0]["blocks"]["wq"])
        np.testing.assert_allclose(m_a, m_b, atol=2e-5, rtol=2e-2)
        wte_ma = np.asarray(sta[0]["wte"])
        wte_mb = np.asarray(stb[0]["shared"]["wte"])
        np.testing.assert_allclose(wte_ma, wte_mb, atol=2e-5, rtol=2e-2)

    def test_pp_descends_multiple_steps(self):
        from apex_tpu.models.gpt2_parallel import (init_params_pp,
                                                   make_train_step_pp)
        cfg = GPT2Config(vocab_size=64, n_positions=256, n_embd=64,
                         n_layer=2, n_head=8)
        tokens, targets, mask = self._data()
        mesh = make_mesh([1, 2, 2, 2, 1], ["dp", "pp", "tp", "sp", "ep"])
        p = init_params_pp(cfg, jax.random.PRNGKey(0))
        st = init_opt_state(p)
        step = make_train_step_pp(cfg, mesh, lr=3e-3, num_microbatches=4)
        losses = []
        for i in range(5):
            p, st, loss = step(p, st, tokens, targets, mask,
                               jnp.int32(1 + i))
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_moe_5axis_trains(self):
        from apex_tpu.models.gpt2_parallel import (init_params_pp,
                                                   make_train_step_pp)
        cfg = GPT2Config(vocab_size=64, n_positions=256, n_embd=64,
                         n_layer=2, n_head=8)
        tokens, targets, mask = self._data()
        mesh = make_mesh([1, 2, 2, 1, 2], ["dp", "pp", "tp", "sp", "ep"])
        p = init_params_pp(cfg, jax.random.PRNGKey(0), moe_experts=4)
        st = init_opt_state(p)
        step = make_train_step_pp(cfg, mesh, lr=3e-3, num_microbatches=2,
                                  moe_experts=4)
        losses = []
        for i in range(5):
            p, st, loss = step(p, st, tokens, targets, mask,
                               jnp.int32(1 + i))
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()


@pytest.mark.slow
def test_ulysses_strategy_matches_ring():
    """The composed dp×tp×sp step with sp_strategy='ulysses' computes the
    same loss trajectory as the ring strategy (same math, different comm).
    CFG has 8 heads, tp=2 → h_local=4, sp=2 divides it."""
    params = init_params(CFG, jax.random.PRNGKey(0))
    mesh = make_mesh([2, 2, 2], ["dp", "tp", "sp"])
    tokens, targets, mask = _data()

    losses = {}
    for strat in ("ring", "ulysses"):
        p = jax.tree_util.tree_map(lambda x: x, params)
        st = init_opt_state(p)
        step_fn = make_train_step(CFG, mesh, lr=3e-3, sp_strategy=strat)
        ls = []
        for i in range(3):
            p, st, loss = step_fn(p, st, tokens, targets, mask,
                                  jnp.int32(i + 1))
            ls.append(float(loss))
        losses[strat] = ls
    np.testing.assert_allclose(losses["ulysses"], losses["ring"],
                               rtol=2e-2, atol=2e-3)
    assert losses["ulysses"][-1] < losses["ulysses"][0]
