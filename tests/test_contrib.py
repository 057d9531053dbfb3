"""Contrib package tests — the in-package test pattern of
apex/contrib/test/<pkg>/test_*.py (every package gets coverage; parity vs
python/torch references)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.contrib.clip_grad import clip_grad_norm_
from apex_tpu.contrib.conv_bias_relu import (conv_bias, conv_bias_mask_relu,
                                             conv_bias_relu)
from apex_tpu.contrib.focal_loss import focal_loss
from apex_tpu.contrib.group_norm import GroupNorm, group_norm_nhwc
from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC
from apex_tpu.contrib.index_mul_2d import index_mul_2d
from apex_tpu.contrib.layer_norm import FastLayerNorm
from apex_tpu.contrib.openfold_triton import FusedAdamSWA
from apex_tpu.contrib.optimizers import FP16_Optimizer
from apex_tpu.contrib.sparsity import ASP, create_mask
from apex_tpu.contrib.transducer import (TransducerJoint, transducer_joint,
                                         transducer_loss)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import get_mesh


class TestClipGrad:
    def test_vs_torch(self):
        grads = [jax.random.normal(jax.random.PRNGKey(i), (7, 5)) * 3
                 for i in range(3)]
        clipped, total = clip_grad_norm_(grads, 1.0)
        tg = [torch.nn.Parameter(torch.tensor(np.asarray(g)))
              for g in grads]
        for p, g in zip(tg, grads):
            p.grad = torch.tensor(np.asarray(g))
        tnorm = torch.nn.utils.clip_grad_norm_(tg, 1.0)
        np.testing.assert_allclose(float(total), float(tnorm), rtol=1e-5)
        for a, b in zip(clipped, tg):
            np.testing.assert_allclose(np.asarray(a), b.grad.numpy(),
                                       atol=1e-6)

    def test_no_clip_when_under(self):
        grads = [jnp.ones((4,)) * 0.01]
        clipped, total = clip_grad_norm_(grads, 10.0)
        np.testing.assert_allclose(np.asarray(clipped[0]),
                                   np.asarray(grads[0]), rtol=1e-6)


class TestFocalLoss:
    def test_matches_manual_sigmoid_focal(self):
        k = 5
        logits = jax.random.normal(jax.random.PRNGKey(0), (8, k))
        targets = jnp.array([0, 1, 2, -1, 5, 3, 0, 2])  # -1 ignore
        npos = jnp.float32(4.0)
        loss = focal_loss(logits, targets, npos, k, 0.25, 2.0, 0.0)
        # manual reference
        x = np.asarray(logits, np.float64)
        t = np.asarray(targets)
        onehot = np.zeros((8, k))
        for i, ti in enumerate(t):
            if ti >= 1:
                onehot[i, ti - 1] = 1.0
        p = 1 / (1 + np.exp(-x))
        ce = -(onehot * np.log(p) + (1 - onehot) * np.log(1 - p))
        pt = p * onehot + (1 - p) * (1 - onehot)
        at = 0.25 * onehot + 0.75 * (1 - onehot)
        per = at * (1 - pt) ** 2 * ce
        per[t < 0] = 0.0
        ref = per.sum() / 4.0
        np.testing.assert_allclose(float(loss), ref, rtol=1e-4)

    def test_grad_finite_and_zero_for_ignored(self):
        k = 4
        logits = jax.random.normal(jax.random.PRNGKey(1), (6, k))
        targets = jnp.array([1, -1, 2, 0, 4, -1])
        g = jax.grad(lambda x: focal_loss(x, targets, jnp.float32(3), k))(
            logits)
        assert bool(jnp.all(jnp.isfinite(g)))
        np.testing.assert_array_equal(np.asarray(g[1]), 0.0)
        np.testing.assert_array_equal(np.asarray(g[5]), 0.0)


class TestIndexMul2d:
    def test_forward_and_double_backward(self):
        in1 = jax.random.normal(jax.random.PRNGKey(0), (10, 4))
        in2 = jax.random.normal(jax.random.PRNGKey(1), (6, 4))
        idx = jnp.array([0, 3, 3, 9, 1, 0])
        out = index_mul_2d(in1, in2, idx)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(in1)[np.asarray(idx)]
                                   * np.asarray(in2), rtol=1e-6)
        # scatter-add grad for repeated indices
        g1 = jax.grad(lambda a: jnp.sum(index_mul_2d(a, in2, idx)))(in1)
        row0 = np.asarray(in2)[0] + np.asarray(in2)[5]  # idx 0 twice
        np.testing.assert_allclose(np.asarray(g1[0]), row0, rtol=1e-6)
        # double backward exists
        h = jax.hessian(
            lambda a: jnp.sum(index_mul_2d(a, in2, idx) ** 2))(in1[:2])
        assert np.all(np.isfinite(np.asarray(h)))


class TestGroupNorm:
    def test_vs_torch(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 16))
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (16,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
        y = group_norm_nhwc(x, 4, w, b)
        tx = torch.tensor(np.asarray(x)).permute(0, 3, 1, 2)
        ty = torch.nn.functional.group_norm(
            tx, 4, torch.tensor(np.asarray(w)), torch.tensor(np.asarray(b)))
        np.testing.assert_allclose(np.asarray(y),
                                   ty.permute(0, 2, 3, 1).numpy(),
                                   atol=1e-5)

    def test_fused_silu(self):
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 2, 8))
        y = group_norm_nhwc(x, 2, None, None, act="silu")
        y0 = group_norm_nhwc(x, 2, None, None)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(y0 * jax.nn.sigmoid(y0)), atol=1e-6)

    def test_module(self):
        m = GroupNorm(num_groups=2, num_channels=8, act="silu")
        x = jnp.ones((1, 2, 2, 8))
        v = m.init(jax.random.PRNGKey(0), x)
        assert m.apply(v, x).shape == x.shape


class TestFastLayerNorm:
    def test_matches_torch(self):
        m = FastLayerNorm(hidden_size=256)
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 256))
        v = m.init(jax.random.PRNGKey(1), x)
        y = m.apply(v, x)
        ty = torch.nn.functional.layer_norm(torch.tensor(np.asarray(x)),
                                            (256,))
        np.testing.assert_allclose(np.asarray(y), ty.numpy(), atol=1e-5)


class TestGroupBN:
    def test_bn_group_subsets(self):
        """bn_group=4 on an 8-device axis: stats reduced within each half
        (the test_groups.py scenario)."""
        mesh = get_mesh("data")
        C = 6
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 2, 2, C))
        bn = BatchNorm2d_NHWC(num_features=C, axis_name="data", bn_group=4,
                              world_size=8)
        v = bn.init(jax.random.PRNGKey(1), x[:2])

        @functools.partial(shard_map, mesh=mesh, in_specs=(P(), P("data")),
                           out_specs=P("data"), check_vma=False)
        def apply(v, xb):
            y, _ = bn.apply(v, xb, use_running_average=False,
                            mutable=["batch_stats"])
            return y

        y = apply(v, x)
        yn = np.asarray(y)
        # normalize first half with first-half stats == zero mean per group
        first = yn[:8].reshape(-1, C)
        np.testing.assert_allclose(first.mean(0), 0.0, atol=1e-4)

    def test_fuse_add_relu(self):
        C = 4
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 2, 2, C))
        z = jax.random.normal(jax.random.PRNGKey(3), (4, 2, 2, C))
        bn = BatchNorm2d_NHWC(num_features=C, fuse_relu=True)
        v = bn.init(jax.random.PRNGKey(4), x)
        y, _ = bn.apply(v, x, z, use_running_average=False,
                        mutable=["batch_stats"])
        assert float(np.asarray(y).min()) >= 0.0


class TestConvBiasReLU:
    def test_matches_composed(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 3))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 3, 8)) * 0.1
        b = jax.random.normal(jax.random.PRNGKey(2), (8,)) * 0.1
        y = conv_bias_relu(x, w, b, stride=1, padding=1)
        y0 = conv_bias(x, w, b, stride=1, padding=1)
        np.testing.assert_allclose(np.asarray(y),
                                   np.maximum(np.asarray(y0), 0), atol=1e-6)
        mask = (jax.random.uniform(jax.random.PRNGKey(3),
                                   y0.shape) > 0.5).astype(jnp.float32)
        ym = conv_bias_mask_relu(x, w, b, mask, stride=1, padding=1)
        np.testing.assert_allclose(
            np.asarray(ym), np.maximum(np.asarray(y0) * np.asarray(mask), 0),
            atol=1e-6)


class TestTransducer:
    def test_joint(self):
        f = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 4))
        g = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 4))
        h = transducer_joint(f, g)
        ref = np.asarray(f)[:, :, None, :] + np.asarray(g)[:, None, :, :]
        np.testing.assert_allclose(np.asarray(h), ref, atol=1e-6)
        hr = TransducerJoint(relu=True)(f, g)
        np.testing.assert_allclose(np.asarray(hr), np.maximum(ref, 0),
                                   atol=1e-6)

    def test_loss_matches_bruteforce(self):
        """Enumerate all monotone alignments for a tiny case."""
        T, U, V = 3, 3, 4  # 2 labels
        key = jax.random.PRNGKey(0)
        logits = jax.random.normal(key, (1, T, U, V))
        lp = jax.nn.log_softmax(logits, axis=-1)
        labels = jnp.array([[1, 2]])
        loss = transducer_loss(lp, labels, jnp.array([T]), jnp.array([U - 1]))

        lpn = np.asarray(lp[0], np.float64)
        lab = [1, 2]
        # brute force: paths of T blanks + U-1 labels
        import itertools
        total = -np.inf
        steps = ["B"] * T + ["L"] * (U - 1)
        for perm in set(itertools.permutations(steps)):
            t = u = 0
            logp = 0.0
            ok = True
            for s in perm:
                if s == "B":
                    if t >= T:
                        ok = False
                        break
                    logp += lpn[t, u, 0]
                    t += 1
                else:
                    if u >= U - 1 or t >= T:
                        ok = False
                        break
                    logp += lpn[t, u, lab[u]]
                    u += 1
            # must consume exactly T blanks ending at t==T (last blank from
            # (T-1, U-1)); standard RNNT: path ends after blank at (T-1,U-1)
            if ok and t == T and u == U - 1:
                total = np.logaddexp(total, logp)
        np.testing.assert_allclose(float(loss[0]), -total, rtol=1e-4)

    def test_loss_grad_finite(self):
        lp = jax.nn.log_softmax(
            jax.random.normal(jax.random.PRNGKey(1), (2, 4, 3, 5)), axis=-1)
        labels = jnp.array([[1, 2], [3, 4]])
        g = jax.grad(lambda x: jnp.sum(transducer_loss(
            x, labels, jnp.array([4, 3]), jnp.array([2, 2]))))(lp)
        assert bool(jnp.all(jnp.isfinite(g)))


class TestASP:
    def test_mask_is_2_of_4(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        m = create_mask(w, "m4n2_1d")
        mn = np.asarray(m).reshape(8, 4, 4)
        np.testing.assert_array_equal(mn.sum(-1), 2)
        # keeps the two largest magnitudes per group
        wn = np.abs(np.asarray(w)).reshape(8, 4, 4)
        kept = np.sort(np.where(mn, wn, 0).sum(-1))
        top2 = np.sort(np.sort(wn, axis=-1)[..., -2:].sum(-1))
        np.testing.assert_allclose(kept, top2, rtol=1e-6)

    def test_prune_and_optimizer_wrap(self):
        params = [jax.random.normal(jax.random.PRNGKey(0), (8, 8)),
                  jax.random.normal(jax.random.PRNGKey(1), (8,))]
        asp = ASP()
        opt = FusedAdam(params, lr=0.1)
        pruned = asp.prune_trained_model(params, opt)
        opt._params = pruned
        m = np.asarray(asp.masks[0])
        assert m.sum() == m.size // 2
        assert np.asarray(asp.masks[1]).all()  # 1-D not pruned
        p = opt.step([jnp.ones((8, 8)), jnp.ones((8,))])
        # pruned positions stay exactly zero after the step
        np.testing.assert_array_equal(np.asarray(p[0])[~m], 0.0)

    def test_checkpoint_roundtrip(self):
        params = [jax.random.normal(jax.random.PRNGKey(2), (4, 8))]
        asp = ASP()
        asp.init_model_for_pruning(params)
        asp.compute_sparse_masks(params)
        sd = asp.state_dict()
        asp2 = ASP()
        asp2.load_state_dict(sd)
        np.testing.assert_array_equal(np.asarray(asp.masks[0]),
                                      np.asarray(asp2.masks[0]))


class TestFusedAdamSWA:
    def test_ema_tracks_params(self):
        params = [jnp.ones((16,))]
        opt = FusedAdamSWA(params, lr=0.1, swa_decay_rate=0.5)
        for _ in range(5):
            opt.step([jnp.ones((16,))])
        p = float(np.asarray(opt.parameters[0])[0])
        s = float(np.asarray(opt.swa_parameters[0])[0])
        assert p < 1.0 and p < s < 1.0  # EMA lags the moving params


class TestFP16Optimizer:
    def test_dynamic_scaling_flow(self):
        params = [jnp.ones((8,), jnp.float32)]
        opt = FP16_Optimizer(FusedAdam(params, lr=0.1),
                             dynamic_loss_scale=True,
                             dynamic_loss_args={"init_scale": 64.0})
        scaled_grads = [jnp.full((8,), 64.0)]  # true grad 1.0
        p = opt.step(scaled_grads)
        assert not np.allclose(np.asarray(p[0]), 1.0)
        bad = [jnp.full((8,), jnp.inf)]
        p2 = opt.step(bad)
        np.testing.assert_array_equal(np.asarray(p2[0]), np.asarray(p[0]))
        assert opt.loss_scale == 32.0


class TestASPFlatOptimizers:
    def test_flat_fused_adam_respects_masks(self):
        params = [jax.random.normal(jax.random.PRNGKey(0), (8, 8))]
        asp = ASP()
        opt = FusedAdam(params, lr=0.1, use_flat=True)
        pruned = asp.prune_trained_model(params, opt)
        opt.set_parameters(pruned)
        m = np.asarray(asp.masks[0])
        p = opt.step([jnp.ones((8, 8))])
        np.testing.assert_array_equal(np.asarray(p[0])[~m], 0.0)
        # a second step keeps the internal flat master masked too
        p = opt.step([jnp.ones((8, 8))])
        np.testing.assert_array_equal(np.asarray(p[0])[~m], 0.0)

    def test_zero_adam_respects_masks(self):
        from apex_tpu.optimizers.distributed_fused_adam import (
            DistributedFusedAdam)
        mesh = get_mesh("data")
        params = [jax.random.normal(jax.random.PRNGKey(1), (8, 16))]
        asp = ASP()
        opt = DistributedFusedAdam(params, mesh, lr=0.1)
        pruned = asp.prune_trained_model(params, opt)
        opt.set_parameters(pruned)
        m = np.asarray(asp.masks[0])
        p = opt.step([jnp.ones((8, 16))])
        np.testing.assert_array_equal(np.asarray(p[0])[~m], 0.0)


class TestSpatialBottleneck:
    @pytest.mark.slow
    def test_matches_unsharded_bottleneck(self):
        """H-sharded SpatialBottleneck == Bottleneck on the full input
        (the reference's spatial-parallel correctness property)."""
        from apex_tpu.contrib.bottleneck import Bottleneck, SpatialBottleneck
        mesh = get_mesh("spatial")
        C_in, C_mid, C_out = 8, 4, 8
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8 * 4, 6, C_in),
                              jnp.float32)
        full = Bottleneck(C_in, C_mid, C_out, compute_dtype=jnp.float32)
        vfull = full.init(jax.random.PRNGKey(1), x)
        sp = SpatialBottleneck(C_in, C_mid, C_out,
                               compute_dtype=jnp.float32,
                               spatial_axis_name="spatial")
        # same param shapes/names → reuse the full variables
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P(), P(None, "spatial")),
                           out_specs=P(None, "spatial"), check_vma=False)
        def run(v, xb):
            y, _ = sp.apply(v, xb, use_running_average=False,
                            mutable=["batch_stats"])
            return y

        y_sp = run(vfull, x)
        y_full, _ = full.apply(vfull, x, use_running_average=False,
                               mutable=["batch_stats"])
        np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_full),
                                   atol=1e-4, rtol=1e-4)


class TestGroupNormPallas:
    def test_pallas_path_matches_jnp(self):
        from apex_tpu.contrib.group_norm import _gn_jnp, group_norm_nhwc
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 4, 32))  # HW=16
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (32,))
        for act in ("", "silu"):
            y = group_norm_nhwc(x, 8, w, b, act=act)  # pallas (16 % 8 == 0)
            ref = _gn_jnp(x, 8, w, b, 1e-5, act)
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)

    def test_pallas_grads_match_jnp(self):
        from apex_tpu.contrib.group_norm import _gn_jnp, group_norm_nhwc
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 4, 16))
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), (16,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(5), (16,))
        for act in ("", "silu"):
            gp = jax.grad(lambda x, w, b: jnp.sum(
                group_norm_nhwc(x, 4, w, b, act=act) ** 2), (0, 1, 2))(
                    x, w, b)
            gr = jax.grad(lambda x, w, b: jnp.sum(
                _gn_jnp(x, 4, w, b, 1e-5, act) ** 2), (0, 1, 2))(x, w, b)
            for a, r in zip(gp, gr):
                np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                           atol=1e-4, rtol=1e-4)

    def test_odd_hw_falls_back(self):
        from apex_tpu.contrib.group_norm import group_norm_nhwc
        x = jax.random.normal(jax.random.PRNGKey(6), (1, 3, 3, 8))  # HW=9
        y = group_norm_nhwc(x, 2)
        assert bool(jnp.all(jnp.isfinite(y)))


class TestGroupNormOnePass:
    """Round-3: one-pass algorithm + selection heuristic (VERDICT r2 item 8;
    reference one-pass group_norm_nhwc_one_pass_*.cu, selection
    group_norm.py:193-209)."""

    def test_one_pass_matches_two_pass_and_jnp(self):
        from apex_tpu.contrib.group_norm import _gn_jnp
        from apex_tpu.ops.pallas.group_norm_kernel import \
            group_norm_nhwc_pallas
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 64)) * 2 + 1
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
        for act in ("", "silu"):
            y1, m1, r1 = group_norm_nhwc_pallas(x, 8, w, b, act=act,
                                                algo="one_pass")
            y2, m2, r2 = group_norm_nhwc_pallas(x, 8, w, b, act=act,
                                                algo="two_pass")
            ref = _gn_jnp(x, 8, w, b, 1e-5, act)
            np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(y1), np.asarray(ref),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(m1), np.asarray(m2),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(np.asarray(r1), np.asarray(r2),
                                       atol=1e-4, rtol=1e-4)

    def test_one_pass_bf16_and_no_affine(self):
        from apex_tpu.ops.pallas.group_norm_kernel import \
            group_norm_nhwc_pallas
        x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 128),
                              jnp.bfloat16)
        y1, _, _ = group_norm_nhwc_pallas(x, 16, algo="one_pass")
        y2, _, _ = group_norm_nhwc_pallas(x, 16, algo="two_pass")
        assert y1.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(y1, np.float32), np.asarray(y2, np.float32),
            atol=2e-2, rtol=2e-2)

    def test_selection_heuristic(self):
        from apex_tpu.ops.pallas.group_norm_kernel import (
            _ONE_PASS_SLAB_ELEMS, one_pass_ok)
        assert one_pass_ok(2, 64, 256)               # small slab
        assert not one_pass_ok(2, 63, 256)           # sublane misaligned
        big_hw = _ONE_PASS_SLAB_ELEMS // 256 + 8
        big_hw -= big_hw % 8
        assert not one_pass_ok(2, big_hw, 256)       # slab too large

    def test_frontend_algo_override_and_grads(self):
        from apex_tpu.contrib.group_norm import group_norm_nhwc
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 4, 4, 32))
        w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (32,))
        b = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (32,))
        outs, grads = [], []
        for algo in ("one_pass", "two_pass"):
            outs.append(group_norm_nhwc(x, 8, w, b, act="silu", algo=algo))
            grads.append(jax.grad(lambda x: jnp.sum(group_norm_nhwc(
                x, 8, w, b, act="silu", algo=algo) ** 2))(x))
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grads[0]),
                                   np.asarray(grads[1]),
                                   atol=1e-4, rtol=1e-4)


class TestPermutationSearch:
    """Round-2 permutation-search parity (VERDICT item 10): the reference's
    bounded-exhaustive + greedy-swap phases (permutation_search_kernels/
    exhaustive_search.py, channel_swap.py) reimplemented vectorized."""

    def _adversarial(self, seed=0, rows=16, cols=16):
        """Matrix where the identity stripe grouping is provably bad: half
        the stripes are all-large (2:4 must drop two large values each),
        half all-small — regrouping to 2 large + 2 small per stripe keeps
        every large value."""
        rng = np.random.default_rng(seed)
        m = rng.normal(size=(rows, cols)) * 0.01
        for s in range(0, cols // 4, 2):
            m[:, s * 4:s * 4 + 4] += rng.normal(size=(rows, 4)) * 3.0
        return m

    def test_canonical_permutation_count(self):
        from apex_tpu.contrib.sparsity.permutation_lib import \
            canonical_window_permutations
        import math
        # P = C! / ((4!)^G * G!) — the reference's analytical count
        # (exhaustive_search.py predict_unique_combinations)
        for c in (8, 12):
            g = c // 4
            want = (math.factorial(c)
                    // (math.factorial(4) ** g * math.factorial(g)))
            assert canonical_window_permutations(c).shape == (want, c)

    def test_exhaustive_improves_adversarial(self):
        from apex_tpu.contrib.sparsity.permutation_lib import (
            exhaustive_search, sum_after_2_to_4)
        m = self._adversarial()
        base = sum_after_2_to_4(m)
        pm, perm = exhaustive_search(m)
        got = sum_after_2_to_4(pm)
        assert got > base * 1.05, (base, got)
        np.testing.assert_allclose(pm, m[:, perm])  # perm consistent
        assert sorted(perm.tolist()) == list(range(m.shape[1]))

    def test_exhaustive_matches_bruteforce_small(self):
        """On an 8-column matrix the window IS the whole matrix: the search
        must find the global optimum over all 35 canonical permutations."""
        from apex_tpu.contrib.sparsity.permutation_lib import (
            canonical_window_permutations, exhaustive_search,
            sum_after_2_to_4)
        rng = np.random.default_rng(3)
        m = rng.normal(size=(8, 8))
        best = max(sum_after_2_to_4(m[:, p])
                   for p in canonical_window_permutations(8))
        _, perm = exhaustive_search(m)
        np.testing.assert_allclose(sum_after_2_to_4(m[:, perm]), best,
                                   rtol=1e-12)

    def test_greedy_improves_and_converges(self):
        from apex_tpu.contrib.sparsity.permutation_lib import (
            greedy_channel_swaps, sum_after_2_to_4)
        m = self._adversarial(seed=5)
        base = sum_after_2_to_4(m)
        pm, perm = greedy_channel_swaps(m)
        assert sum_after_2_to_4(pm) > base
        # convergence: a second run from the result finds nothing
        pm2, perm2 = greedy_channel_swaps(pm)
        np.testing.assert_allclose(pm2, pm)

    def test_entry_point_strategies(self):
        from apex_tpu.contrib.sparsity.permutation_lib import (
            accelerated_search_for_good_permutation, sum_after_2_to_4)
        m = self._adversarial(seed=7)
        base = sum_after_2_to_4(m)
        for strat in ("exhaustive", "progressive channel swap"):
            pm, _ = accelerated_search_for_good_permutation(
                m, {"strategy": strat})
            assert sum_after_2_to_4(pm) >= base

    def test_asp_wrapper_preserves_function_contract(self):
        """permuted_w == w[:, perm] (so the producer's output permutation
        keeps the network function unchanged)."""
        from apex_tpu.contrib.sparsity.permutation_lib import \
            permute_channels_to_preserve_magnitude
        w = jnp.asarray(self._adversarial(seed=9), jnp.float32)
        pw, perm = permute_channels_to_preserve_magnitude(w)
        np.testing.assert_allclose(np.asarray(pw),
                                   np.asarray(w)[:, perm])


class TestASPCheckpointFlow:
    """The reference's two-part checkpointing flow
    (apex/contrib/sparsity/test/checkpointing_test_part1.py → part2):
    train dense → prune → train sparse → checkpoint; then restore into a
    FRESH model/optimizer/ASP and verify masks + sparsity survive continued
    training."""

    def _loss_grads(self, params, x):
        def loss(ps):
            h = x @ ps[0]
            return jnp.mean((h + ps[1]) ** 2)
        return jax.value_and_grad(loss)(params)

    def test_prune_checkpoint_restore_retrain(self, tmp_path):
        from apex_tpu.utils import checkpoint as ckpt

        x = jax.random.normal(jax.random.PRNGKey(9), (4, 16))
        params = [jax.random.normal(jax.random.PRNGKey(0), (16, 16)),
                  jnp.zeros((16,))]
        opt = FusedAdam(params, lr=0.05)
        # part 1: dense steps, then prune, then sparse steps
        p = opt.parameters
        for _ in range(2):
            _, g = self._loss_grads(p, x)
            p = opt.step(g)
        asp = ASP()
        pruned = asp.prune_trained_model(p, opt)
        opt.set_parameters(pruned)
        p = opt.parameters
        for _ in range(2):
            _, g = self._loss_grads(p, x)
            p = opt.step(g)
        m = np.asarray(asp.masks[0])
        np.testing.assert_array_equal(np.asarray(p[0])[~m], 0.0)
        # the string `pattern` field rides outside the array tree (the
        # reference stores it in the torch pickle; npz holds arrays only)
        ckpt.save_numpy(str(tmp_path / "part1.npz"),
                        {"params": p, "opt": opt.state_dict(),
                         "asp_masks": asp.state_dict()["masks"]})

        # part 2: fresh everything, restore, keep training sparse
        params2 = [jnp.zeros((16, 16)), jnp.zeros((16,))]
        tmpl = {"params": params2,
                "opt": FusedAdam(params2, lr=0.05).state_dict(),
                "asp_masks": ASP().init_model_for_pruning(
                    params2).state_dict()["masks"]}
        restored = ckpt.restore_numpy(str(tmp_path / "part1.npz"), tmpl)
        opt2 = FusedAdam(restored["params"], lr=0.05)
        opt2.load_state_dict(restored["opt"])
        asp2 = ASP()
        asp2.load_state_dict({"pattern": "m4n2_1d",
                              "masks": restored["asp_masks"]})
        opt2.set_parameters(jax.tree_util.tree_map(
            lambda q, mk: q * mk, restored["params"], asp2.masks))
        asp2.wrap_optimizer(opt2)  # part2 re-attaches ASP to the new opt
        np.testing.assert_array_equal(np.asarray(asp2.masks[0]), m)
        p2 = opt2.parameters
        for _ in range(3):
            _, g = self._loss_grads(p2, x)
            p2 = opt2.step(g)
        # sparsity maintained through post-restore training
        np.testing.assert_array_equal(np.asarray(p2[0])[~m], 0.0)


class TestPeerMemoryPool:
    """Real arena semantics (reference peer_memory.py:6-106): one device
    allocation, aligned bump sub-allocation, exhaustion asserts, dynamic
    reset, per-peer device views."""

    def test_allocation_accounting_and_views(self):
        from apex_tpu.contrib.peer_memory import PeerMemoryPool
        pool = PeerMemoryPool(static_size=4096, dynamic_size=4096,
                              peer_ranks=[0, 1, 2])
        ts = pool.allocate_peer_tensors((8, 16), jnp.float32,
                                        channels_last=False, dynamic=False)
        assert len(ts) == 3  # one view per peer rank
        assert ts[0].shape == (8, 16) and ts[0].dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(ts[0]), 0.0)
        # second static allocation starts at an aligned, disjoint offset
        t2 = pool.allocate_peer_tensors((4, 4), jnp.bfloat16,
                                        channels_last=True, dynamic=False)
        r0, r1 = pool.allocations
        assert r1["offset"] % pool.alignment == 0
        assert r1["offset"] >= r0["offset"] + r0["nbytes"]
        assert r1["channels_last"] is True
        assert t2[0].dtype == jnp.bfloat16
        # dynamic allocations live in the dynamic half and reset() drops
        # only them
        pool.allocate_peer_tensors((16,), jnp.int32, False, dynamic=True)
        assert pool.allocations[-1]["offset"] >= pool.static_size
        assert pool.dynamic_offset > 0
        pool.reset()
        assert pool.dynamic_offset == 0
        # records stay positionally stable: dynamic ones are marked freed
        # (cached indices keep resolving), statics stay live
        assert len(pool.allocations) == 3
        assert pool.allocations[2]["freed"]
        with pytest.raises(RuntimeError, match="freed by reset"):
            pool.view(2)
        pool.view(0)  # static index still valid after reset

    def test_exhaustion_asserts(self):
        from apex_tpu.contrib.peer_memory import PeerMemoryPool
        pool = PeerMemoryPool(static_size=1024, dynamic_size=512)
        with pytest.raises(AssertionError, match="Static"):
            pool.allocate_peer_tensors((1024,), jnp.float32, False, False)
        with pytest.raises(AssertionError, match="Dynamic"):
            pool.allocate_peer_tensors((512,), jnp.float32, False, True)

    def test_view_rematerializes(self):
        from apex_tpu.contrib.peer_memory import PeerMemoryPool
        pool = PeerMemoryPool(static_size=4096)
        t = pool.allocate_peer_tensors((8, 8), jnp.float32, False, False)[0]
        again = pool.view(0)
        assert again.shape == t.shape and again.dtype == t.dtype
        np.testing.assert_array_equal(np.asarray(again), np.asarray(t))

    def test_freed_pool_refuses(self):
        from apex_tpu.contrib.peer_memory import PeerMemoryPool
        pool = PeerMemoryPool(static_size=1024)
        pool.free()
        with pytest.raises(RuntimeError):
            pool.allocate_peer_tensors((4,), jnp.float32, False, False)
