"""L1 integration: the amp cross-product matrix on a small conv model —
TPU port of tests/L1/common/run_test.sh:29-49 (opt levels O0-O3 ×
loss_scale {None, 1.0, 128.0, dynamic} × keep_batchnorm_fp32), with the
compare.py pattern: O1 vs O0 end states stay close; every cell trains.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models.resnet import ResNet18ish
from apex_tpu.optimizers import FusedAdam

STEPS = 4


def _data():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 16, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 4)
    return x, y


def _train(opt_level, loss_scale, keep_bn_fp32, steps=STEPS, lr=1e-3,
           return_opt=False):
    x, y = _data()
    policy = amp.Policy.from_opt_level(opt_level, loss_scale=loss_scale,
                                       keep_batchnorm_fp32=keep_bn_fp32)
    compute = jnp.float32 if opt_level == "O0" else jnp.bfloat16
    model = ResNet18ish(num_classes=4, compute_dtype=compute)
    variables = model.init(jax.random.PRNGKey(2), x)
    params = policy.cast_params(variables["params"]) \
        if opt_level in ("O2", "O3") else variables["params"]
    bstats = variables["batch_stats"]
    opt = FusedAdam(params, lr=lr, master_weights=policy.master_weights)
    scaler = policy.make_scaler()
    sstate = scaler.init() if scaler else None

    losses = []
    p = opt.parameters
    for step in range(steps):
        def loss_fn(p):
            logits, _ = model.apply({"params": p, "batch_stats": bstats},
                                    x, mutable=["batch_stats"])
            onehot = jax.nn.one_hot(y, 4)
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot,
                                     axis=-1))
            return scaler.scale(loss, sstate) if scaler else loss

        sl, grads = jax.value_and_grad(loss_fn)(p)
        if scaler:
            grads, found_inf = scaler.unscale(grads, sstate)
            p = opt.step(grads, found_inf=found_inf)
            sstate = scaler.update(sstate, found_inf)
            losses.append(float(sl) / float(sstate.scale))
        else:
            p = opt.step(grads)
            losses.append(float(sl))
    if return_opt:
        return losses, p, opt
    return losses, p


# The FULL reference matrix (tests/L1/common/run_test.sh:29-49): every
# opt-level × loss-scale × keep-bn cell, with the reference's own skip rule
# (O1 + an explicit keep_batchnorm flag is skipped, run_test.sh:67-71) —
# 40 cells, no sampling.
# the first cell of each opt level pays that level's full jit compile
# (fp32 for O0, fresh bf16 traces for O1/O2) — the three heaviest cells in
# the suite; they run in the slow tier.
_SLOW_CELLS = {("O0", None, None), ("O1", None, None), ("O2", None, None)}


def _tier1_cell(ol, ls, bn):
    """Tier-1 keeps ONE matrix row per DISTINCT code path — the dynamic
    scaler column at every opt level (the full scale/unscale/update
    machinery, and each level's first-trace warm-up has to land
    somewhere), the O3 no-scaler cell (amp without a scaler), and the O2
    cell that explicitly OPTS OUT of fp32 batchnorm under a static scale
    (keep_bn=False: master weights × the bn low-precision cast). The
    static 1.0/128.0 columns re-run the dynamic cells' policy machinery
    with a different constant (128.0 stays covered tier-1 by that O2 bn
    cell and test_o2_master_weights_are_fp32); keep-bn=True stays
    covered end to end by test_o1_close_to_o0's O1(dynamic, bn=True)
    run. Everything else rides the slow tier at ~4-8s/cell — the full
    40-cell matrix still runs without `-m 'not slow'` (tier-1 budget:
    ROADMAP.md)."""
    if bn is None:
        return ls == "dynamic" or (ol, ls) == ("O3", None)
    return (ol, ls, bn) == ("O2", 128.0, False)


MATRIX = [
    pytest.param(ol, ls, bn,
                 marks=[] if (ol, ls, bn) not in _SLOW_CELLS
                 and _tier1_cell(ol, ls, bn) else [pytest.mark.slow])
    for ol in ("O0", "O1", "O2", "O3")
    for ls in (None, 1.0, 128.0, "dynamic")
    for bn in (None, True, False)
    if not (ol == "O1" and bn is not None)
]
assert len(MATRIX) == 40


class TestAmpMatrix:
    @pytest.mark.parametrize("opt_level,loss_scale,keep_bn", MATRIX)
    def test_cell_trains(self, opt_level, loss_scale, keep_bn):
        losses, params = _train(opt_level, loss_scale, keep_bn)
        assert all(np.isfinite(l) for l in losses), losses
        # training moves: loss at end differs from start
        assert losses[-1] != losses[0]

    def test_o1_close_to_o0(self):
        """compare.py pattern: the O1 run tracks the fp32 run closely over a
        few steps (bf16 tolerance)."""
        l0, p0 = _train("O0", None, None)
        l1, p1 = _train("O1", "dynamic", True)
        assert abs(l0[-1] - l1[-1]) < 0.2 * abs(l0[0]) + 0.1

    def test_o2_master_weights_are_fp32(self):
        _, params, opt = _train("O2", 128.0, True, steps=1, return_opt=True)
        # model params low precision, optimizer masters fp32 (the O2 contract)
        for leaf in jax.tree_util.tree_leaves(params):
            assert leaf.dtype == jnp.bfloat16
        assert "master" in opt.state
        for leaf in jax.tree_util.tree_leaves(opt.state["master"]):
            assert leaf.dtype == jnp.float32


@pytest.mark.slow
class TestL1FullScale:
    """Round-2 scale-up (VERDICT item 9): the REAL ResNet-50 class at 64×64,
    20 steps — the reference L1 recipe shape (tests/L1/common/main_amp.py)
    at CI-tractable resolution. Marked slow: deselect with -m 'not slow'."""

    def test_resnet50_o1_trains(self):
        from apex_tpu.models.resnet import ResNet50
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 64, 3))
        y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 10)
        policy = amp.Policy.from_opt_level("O1", loss_scale="dynamic",
                                           keep_batchnorm_fp32=True)
        model = ResNet50(num_classes=10, compute_dtype=jnp.bfloat16)
        variables = model.init(jax.random.PRNGKey(2), x)
        params, bstats = variables["params"], variables["batch_stats"]
        opt = FusedAdam(params, lr=1e-3)
        scaler = policy.make_scaler()
        sstate = scaler.init()

        @jax.jit
        def fwd(p, bstats, sscale):
            def loss_fn(p):
                logits, upd = model.apply(
                    {"params": p, "batch_stats": bstats}, x,
                    mutable=["batch_stats"])
                onehot = jax.nn.one_hot(y, 10)
                loss = -jnp.mean(jnp.sum(
                    jax.nn.log_softmax(logits) * onehot, axis=-1))
                return loss * sscale, upd["batch_stats"]

            (sl, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(p)
            return sl, bs, grads

        losses = []
        p = opt.parameters
        for step in range(20):
            sl, bstats, grads = fwd(p, bstats, sstate.scale)
            grads, found_inf = scaler.unscale(grads, sstate)
            p = opt.step(grads, found_inf=found_inf)
            sstate = scaler.update(sstate, found_inf)
            losses.append(float(sl) / float(sstate.scale))
        assert np.isfinite(losses).all(), losses
        assert min(losses[10:]) < losses[0], losses


@pytest.mark.slow
class TestL1DistributedMatrix:
    """dp-sharded matrix variant ≈ tests/L1/common/run_test.sh:29-49
    distributed mode (cross_product_distributed/run.sh): DDP grad psum +
    SyncBatchNorm over the data axis, amp cells on the 8-device mesh."""

    @pytest.mark.parametrize("opt_level,loss_scale",
                             [("O1", "dynamic"), ("O2", 128.0)])
    def test_distributed_cell_trains(self, opt_level, loss_scale):
        import functools

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from apex_tpu.models.resnet import ResNet18ish
        from apex_tpu.optimizers.functional import adam_update
        from apex_tpu.parallel import get_mesh

        mesh = get_mesh("data")
        policy = amp.Policy.from_opt_level(opt_level,
                                           loss_scale=loss_scale,
                                           keep_batchnorm_fp32=True)
        model = ResNet18ish(num_classes=4, axis_name="data")
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 16, 3))
        y = jax.random.randint(jax.random.PRNGKey(1), (16,), 0, 4)
        variables = model.init(jax.random.PRNGKey(2), x[:2])
        params, bstats = variables["params"], variables["batch_stats"]
        m0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
        v0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)
        scaler = policy.make_scaler()
        sstate = scaler.init() if scaler else None
        scale_val = sstate.scale if scaler else jnp.float32(1.0)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P("data"), P("data"), P(), P()),
            out_specs=(P(), P(), P(), P(), P()), check_vma=False)
        def train_step(params, m, v, bstats, x, y, step, sscale):
            def loss_fn(p):
                logits, upd = model.apply(
                    {"params": p, "batch_stats": bstats}, x,
                    mutable=["batch_stats"])
                onehot = jax.nn.one_hot(y, 4)
                loss = -jnp.mean(jnp.sum(
                    jax.nn.log_softmax(logits) * onehot, axis=-1))
                return loss * sscale, upd["batch_stats"]

            (sl, bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params)
            # flat-bucket DDP allreduce (apex_C flatten capability)
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "data"), grads)
            inv = 1.0 / sscale
            grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
            found_inf = jnp.logical_not(jnp.all(jnp.stack([
                jnp.all(jnp.isfinite(g)) for g in
                jax.tree_util.tree_leaves(grads)])))
            params, m, v = adam_update(params, grads, m, v, step=step,
                                       lr=1e-3, found_inf=found_inf)
            return params, m, v, bs, jax.lax.pmean(sl, "data")

        losses = []
        state = (params, m0, v0, bstats)
        jit_step = jax.jit(train_step)
        for step in range(1, 5):
            *state, sl = jit_step(*state, x, y, jnp.int32(step),
                                  scale_val)
            state = tuple(state)
            if scaler:
                losses.append(float(sl) / float(scale_val))
            else:
                losses.append(float(sl))
        assert np.isfinite(losses).all(), losses
        assert losses[-1] != losses[0]
