"""Compiled-kernel parity checks — the kernel leg of ``chip_smoke.py``.

The test suite runs every Pallas kernel in interpret mode on a CPU mesh —
correct for semantics, blind to Mosaic compilation bugs (layout selection,
tiling, SMEM scalar plumbing). These checks run the COMPILED kernels on the
chip against plain-jnp references (the XLA-compiled math, a fully
independent lowering path), the TPU analog of the reference's on-device L0
tier (/root/reference/tests/L0/run_test.py:21-30).

Coverage: the five flat optimizer kernels (adam [+master, +L2 mode], sgd,
lamb, novograd, adagrad), LayerNorm/RMSNorm fwd+bwd (incl. the
memory-efficient recompute-from-output backward), GroupNorm NHWC (+SiLU),
the Pallas row-tile softmax fwd+bwd (causal + masked), flash attention
fwd+bwd (causal, arbitrary mask, ragged lengths, dropout determinism) and
the remote-DMA kernels on a one-device ring.

This module picks no backend and writes no file: ``chip_smoke.py`` owns
the process, pins the platform and calls :func:`run_checks`.
"""

from __future__ import annotations

import time

SMALL = False  # set by run_checks() for an interpret-mode rehearsal


def _cmp(got, want, tol):
    import jax.numpy as jnp
    import numpy as np

    g = np.asarray(got.astype(jnp.float32) if hasattr(got, "astype") else got,
                   np.float32)
    w = np.asarray(want.astype(jnp.float32)
                   if hasattr(want, "astype") else want, np.float32)
    err = float(np.max(np.abs(g - w))) if g.size else 0.0
    scale = float(np.max(np.abs(w))) + 1e-12
    return err, err <= tol * max(1.0, scale)


def _tree_cmp(got_tree, want_tree, tol):
    import jax

    errs, oks = [], []
    for g, w in zip(jax.tree_util.tree_leaves(got_tree),
                    jax.tree_util.tree_leaves(want_tree)):
        e, ok = _cmp(g, w, tol)
        errs.append(e)
        oks.append(ok)
    return max(errs), all(oks)


# --------------------------------------------------------------- checks


def check_adam_flat(jax, jnp):
    from apex_tpu.ops.pallas.fused_adam_kernel import (
        ADAM_MODE_L2, fused_adam_flat, fused_adam_flat_master)
    from apex_tpu.optimizers.functional import adam_update

    n = 8 * 1024 if SMALL else 64 * 1024
    key = jax.random.PRNGKey(0)
    p = jax.random.normal(key, (n,), jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.bfloat16)
    m = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1
    v = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (n,))) * 0.01
    kw = dict(lr=1e-3, weight_decay=0.01, step=3, inv_scale=0.5)
    out = {}
    # adamw mode
    pn, mn, vn = fused_adam_flat(p.copy(), g, m.copy(), v.copy(), **kw)
    rp, rm, rv = adam_update(p, g, m, v, **kw)
    e1, ok1 = _tree_cmp((pn, mn, vn), (rp, rm, rv), 2e-2)
    # L2 mode
    pn2, mn2, vn2 = fused_adam_flat(p.copy(), g, m.copy(), v.copy(), mode=ADAM_MODE_L2, **kw)
    rp2, rm2, rv2 = adam_update(p, g, m, v, adam_w_mode=False, **kw)
    e2, ok2 = _tree_cmp((pn2, mn2, vn2), (rp2, rm2, rv2), 2e-2)
    # found_inf skip must be exact
    pn3, mn3, vn3 = fused_adam_flat(p.copy(), g, m.copy(), v.copy(), found_inf=True, **kw)
    e3, ok3 = _tree_cmp((pn3, mn3, vn3), (p, m, v), 0.0)
    # master variant
    pm = p.astype(jnp.float32)
    pmn, plp, mn4, vn4 = fused_adam_flat_master(pm.copy(), g, m.copy(), v.copy(), **kw)
    rpm, rmm, rvm = adam_update(pm, g, m, v, **kw)
    e4, ok4 = _tree_cmp((pmn, mn4, vn4), (rpm, rmm, rvm), 1e-5)
    e5, ok5 = _cmp(plp, rpm.astype(jnp.bfloat16), 1e-2)
    return {"max_err": max(e1, e2, e3, e4, e5),
            "pass": ok1 and ok2 and ok3 and ok4 and ok5}


def check_sgd_flat(jax, jnp):
    from apex_tpu.ops.pallas.fused_sgd_kernel import fused_sgd_flat
    from apex_tpu.optimizers.functional import sgd_update

    n = 8 * 1024 if SMALL else 64 * 1024
    p = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.bfloat16)
    g = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.bfloat16)
    buf = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 0.1
    errs, oks = [], []
    for kw in (dict(momentum=0.9, weight_decay=1e-4),
               dict(momentum=0.9, nesterov=True),
               dict(momentum=0.9, weight_decay=1e-4, wd_after_momentum=True),
               dict(momentum=0.9, first_step=True)):
        pn, bn = fused_sgd_flat(p.copy(), g, buf.copy(), lr=0.1, inv_scale=2.0, **kw)
        rp, rb = sgd_update(p, g, buf, lr=0.1, inv_scale=2.0, **kw)
        e, ok = _tree_cmp((pn, bn), (rp, rb), 2e-2)
        errs.append(e)
        oks.append(ok)
    return {"max_err": max(errs), "pass": all(oks)}


def _opt_tree(jax, jnp):
    shapes = [(300,), (17, 129), (64, 64), (1000,)]
    p = [jax.random.normal(jax.random.PRNGKey(i), s) * 0.5
         for i, s in enumerate(shapes)]
    g = [jax.random.normal(jax.random.PRNGKey(10 + i), s)
         for i, s in enumerate(shapes)]
    return p, g


def check_lamb_flat(jax, jnp):
    from apex_tpu.ops.pallas.fused_opt_kernels import (fused_lamb_flat,
                                                       row_segment_ids)
    from apex_tpu.optimizers.functional import lamb_update
    from apex_tpu.utils.flatten import flat_spec, flatten, unflatten

    p, g = _opt_tree(jax, jnp)
    m = [jnp.zeros_like(x) for x in p]
    v = [jnp.zeros_like(x) for x in p]
    spec = flat_spec(p)
    fp = flatten(p, spec, dtype=jnp.float32, pad_to=1024)
    fg = flatten(g, spec, dtype=jnp.float32, pad_to=fp.size)
    fm = jnp.zeros_like(fp)
    fv = jnp.zeros_like(fp)
    rid = row_segment_ids(spec, fp.size)
    kw = dict(lr=1e-2, weight_decay=0.01, step=2, max_grad_norm=1.0)
    pn, mn, vn, gnorm = fused_lamb_flat(fp.copy(), fg, fm.copy(), fv.copy(), rid,
                                        num_tensors=spec.num_leaves, **kw)
    rp, rm, rv, rnorm = lamb_update(p, g, m, v, **kw)
    e1, ok1 = _tree_cmp(unflatten(pn, spec), rp, 1e-4)
    e2, ok2 = _cmp(gnorm, rnorm, 1e-5)
    return {"max_err": max(e1, e2), "pass": ok1 and ok2}


def check_novograd_flat(jax, jnp):
    from apex_tpu.ops.pallas.fused_opt_kernels import (fused_novograd_flat,
                                                       row_segment_ids)
    from apex_tpu.optimizers.functional import novograd_update
    from apex_tpu.utils.flatten import flat_spec, flatten, unflatten

    p, g = _opt_tree(jax, jnp)
    m = [jnp.zeros_like(x) for x in p]
    spec = flat_spec(p)
    fp = flatten(p, spec, dtype=jnp.float32, pad_to=1024)
    fg = flatten(g, spec, dtype=jnp.float32, pad_to=fp.size)
    fm = jnp.zeros_like(fp)
    rid = row_segment_ids(spec, fp.size)
    vt = jnp.zeros((spec.num_leaves,), jnp.float32)
    kw = dict(lr=1e-2, weight_decay=0.01, step=1)
    pn, mn, vn = fused_novograd_flat(fp.copy(), fg, fm.copy(), vt.copy(),
                                     rid, num_tensors=spec.num_leaves, **kw)
    # functional novograd keeps v as per-tensor tree of scalars
    rp, rm, rv = novograd_update(p, g, m, [jnp.float32(0.0)] * len(p), **kw)
    e1, ok1 = _tree_cmp(unflatten(pn, spec), rp, 1e-4)
    e2, ok2 = _tree_cmp(list(vn), rv, 1e-4)
    return {"max_err": max(e1, e2), "pass": ok1 and ok2}


def check_adagrad_flat(jax, jnp):
    from apex_tpu.ops.pallas.fused_opt_kernels import fused_adagrad_flat
    from apex_tpu.optimizers.functional import adagrad_update

    n = 8 * 1024 if SMALL else 64 * 1024
    p = jax.random.normal(jax.random.PRNGKey(0), (n,))
    g = jax.random.normal(jax.random.PRNGKey(1), (n,))
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (n,))) * 0.1
    kw = dict(lr=1e-2, weight_decay=1e-4)
    pn, hn = fused_adagrad_flat(p.copy(), g, h.copy(), **kw)
    rp, rh = adagrad_update(p, g, h, **kw)
    return dict(zip(("max_err", "pass"),
                    _tree_cmp((pn, hn), (rp, rh), 1e-5)))


def _ln_ref(jnp, x, w, b, eps=1e-5, rms=False):
    x32 = x.astype(jnp.float32)
    if rms:
        ms = jnp.mean(x32 * x32, -1, keepdims=True)
        y = x32 * jax_lax_rsqrt(jnp, ms + eps)
    else:
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean((x32 - mu) ** 2, -1, keepdims=True)
        y = (x32 - mu) * jax_lax_rsqrt(jnp, var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y


def jax_lax_rsqrt(jnp, x):
    return 1.0 / jnp.sqrt(x)


def check_layer_norm(jax, jnp):
    from apex_tpu.normalization.fused_layer_norm import (
        fused_layer_norm_affine, fused_rms_norm_affine)

    rows, hidden = (64, 256) if SMALL else (512, 1024)
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, hidden))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (hidden,))
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (hidden,))
    errs, oks = [], []
    for mem_eff in (False, True):
        y = fused_layer_norm_affine(x, w, b, hidden,
                                    memory_efficient=mem_eff)
        e, ok = _cmp(y, _ln_ref(jnp, x, w, b), 1e-4)
        errs.append(e)
        oks.append(ok)

        def loss(fn):
            return lambda x, w, b: jnp.sum(fn(x, w, b) ** 2)

        gf = jax.grad(
            lambda x, w, b: jnp.sum(fused_layer_norm_affine(
                x, w, b, hidden, memory_efficient=mem_eff) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(
            lambda x, w, b: jnp.sum(_ln_ref(jnp, x, w, b) ** 2),
            argnums=(0, 1, 2))(x, w, b)
        e, ok = _tree_cmp(gf, gr, 2e-3)
        errs.append(e)
        oks.append(ok)
        # RMSNorm
        yr = fused_rms_norm_affine(x, w, hidden, memory_efficient=mem_eff)
        e, ok = _cmp(yr, _ln_ref(jnp, x, w, None, rms=True), 1e-4)
        errs.append(e)
        oks.append(ok)
    # bf16 io
    xb = x.astype(jnp.bfloat16)
    yb = fused_layer_norm_affine(xb, w, b, hidden)
    e, ok = _cmp(yb, _ln_ref(jnp, xb, w, b).astype(jnp.bfloat16), 2e-2)
    errs.append(e)
    oks.append(ok)
    return {"max_err": max(errs), "pass": all(oks)}


def check_group_norm(jax, jnp):
    from apex_tpu.ops.pallas.group_norm_kernel import group_norm_nhwc_pallas

    n, h, w_, c, g = (1, 4, 4, 128, 16) if SMALL else (2, 8, 8, 256, 32)
    x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w_, c))
    wt = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (c,))
    bs = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (c,))
    errs, oks = [], []
    for act in ("", "silu"):
        for algo in ("one_pass", "two_pass"):
            y, mean, rstd = group_norm_nhwc_pallas(x, g, wt, bs, act=act,
                                                   algo=algo)
            x5 = x.reshape(n, h * w_, g, c // g).astype(jnp.float32)
            mu = jnp.mean(x5, axis=(1, 3), keepdims=True)
            var = jnp.mean((x5 - mu) ** 2, axis=(1, 3), keepdims=True)
            yr = ((x5 - mu) / jnp.sqrt(var + 1e-5)).reshape(n, h, w_, c)
            yr = yr * wt + bs
            if act == "silu":
                yr = yr * jax.nn.sigmoid(yr)
            e, ok = _cmp(y, yr, 1e-4)
            errs.append(e)
            oks.append(ok)
    return {"max_err": max(errs), "pass": all(oks)}


def check_softmax(jax, jnp):
    from apex_tpu.ops.pallas.softmax_kernel import (softmax_bwd_pallas,
                                                    softmax_fwd_pallas)

    B, sq, sk = (2, 128, 128) if SMALL else (8, 256, 256)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, sq, sk))
    errs, oks = [], []
    # causal
    y = softmax_fwd_pallas(x, None, scale=0.5, causal=True)
    mask = jnp.tril(jnp.ones((sq, sk), bool))
    ref = jax.nn.softmax(jnp.where(mask, x * 0.5, -1e30), axis=-1)
    e, ok = _cmp(y, ref, 1e-5)
    errs.append(e)
    oks.append(ok)
    # arbitrary mask (True = masked), per-batch shared across heads
    m3 = jax.random.bernoulli(jax.random.PRNGKey(1), 0.3, (B, sq, sk))
    ym = softmax_fwd_pallas(x, m3, scale=0.7, causal=False)
    refm_logits = jnp.where(m3, -1e30, x * 0.7)
    refm = jax.nn.softmax(refm_logits, axis=-1)
    # fully-masked rows yield zeros (megatron convention)
    all_masked = jnp.all(m3, axis=-1, keepdims=True)
    refm = jnp.where(all_masked, 0.0, refm)
    e, ok = _cmp(ym, refm, 1e-5)
    errs.append(e)
    oks.append(ok)
    # backward: dx = y * (dy - sum(dy * y)) * scale
    dy = jax.random.normal(jax.random.PRNGKey(2), (B, sq, sk))
    dx = softmax_bwd_pallas(y, dy, scale=0.5)
    dref = y * (dy - jnp.sum(dy * y, -1, keepdims=True)) * 0.5
    e, ok = _cmp(dx, dref, 1e-5)
    errs.append(e)
    oks.append(ok)
    return {"max_err": max(errs), "pass": all(oks)}


def _flash_ref(jax, jnp, q, k, v, causal=False, mask=None, scale=None):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    sq, sk = logits.shape[-2:]
    if causal:
        # top-left aligned: query i attends keys j <= i (kernel convention,
        # matching the megatron upper-triang softmax)
        cm = jnp.tril(jnp.ones((sq, sk), bool))
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, -1e30, logits)
    p = jax.nn.softmax(logits, axis=-1)
    if mask is not None:
        # fully-masked rows yield zero output (megatron generic-masked
        # softmax convention, matched by the flash kernel)
        fully = jnp.all(jnp.broadcast_to(mask, logits.shape), axis=-1,
                        keepdims=True)
        p = jnp.where(fully, 0.0, p)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def check_flash_attention(jax, jnp):
    from apex_tpu.ops.pallas.flash_attention import flash_attention

    b, h, s, d = (1, 1, 128, 64) if SMALL else (1, 2, 256, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, s, d)) * 0.3 for kk in ks)
    errs, oks = [], []
    # causal fwd
    y = flash_attention(q, k, v, True)
    ref = _flash_ref(jax, jnp, q, k, v, causal=True)
    e, ok = _cmp(y, ref, 2e-3)
    errs.append(e)
    oks.append(ok)
    # causal bwd
    gf = jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, True) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        _flash_ref(jax, jnp, q, k, v, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    e, ok = _tree_cmp(gf, gr, 5e-3)
    errs.append(e)
    oks.append(ok)
    # arbitrary mask fwd+bwd
    mask = jax.random.bernoulli(jax.random.PRNGKey(5), 0.25,
                                (b, 1, s, s))
    ym = flash_attention(q, k, v, mask=mask)
    refm = _flash_ref(jax, jnp, q, k, v, mask=mask)
    e, ok = _cmp(ym, refm, 2e-3)
    errs.append(e)
    oks.append(ok)
    gfm = jax.grad(lambda q: jnp.sum(
        flash_attention(q, k, v, mask=mask) ** 2))(q)
    grm = jax.grad(lambda q: jnp.sum(
        _flash_ref(jax, jnp, q, k, v, mask=mask) ** 2))(q)
    e, ok = _cmp(gfm, grm, 5e-3)
    errs.append(e)
    oks.append(ok)
    # ragged (non-multiple-of-block) lengths
    r1, r2 = (65, 93) if SMALL else (193, 217)
    qs, kss, vs = q[:, :, :r1], k[:, :, :r2], v[:, :, :r2]
    yr = flash_attention(qs, kss, vs, True)
    refr = _flash_ref(jax, jnp, qs, kss, vs, causal=True)
    e, ok = _cmp(yr, refr, 2e-3)
    errs.append(e)
    oks.append(ok)
    # dropout: deterministic per seed, differing across seeds, unbiased-ish
    y1 = flash_attention(q, k, v, True, dropout_p=0.3, dropout_seed=7)
    y2 = flash_attention(q, k, v, True, dropout_p=0.3, dropout_seed=7)
    y3 = flash_attention(q, k, v, True, dropout_p=0.3, dropout_seed=8)
    e, same = _cmp(y1, y2, 0.0)
    errs.append(e)
    oks.append(same)
    import numpy as np

    oks.append(bool(np.any(np.asarray(y1) != np.asarray(y3))))
    # dropout bwd executes (and is finite)
    gd = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, True, dropout_p=0.3, dropout_seed=7) ** 2))(q)
    oks.append(bool(np.all(np.isfinite(np.asarray(gd)))))
    return {"max_err": max(errs), "pass": all(oks)}


def check_remote_copy(jax, jnp):
    """Compile coverage for the Pallas remote-DMA kernels on a 1-device
    mesh: a self-ring peer_shift must be the identity, and the
    non-periodic halo exchange must return zero halos (the single device
    is both ring edges). Exercises make_async_remote_copy + DMA-semaphore
    lowering on the real chip (the multi-device semantics are
    parity-tested on the virtual CPU mesh)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.ops.pallas.remote_copy import (halo_exchange_rdma,
                                                 peer_shift)
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 256), jnp.float32)

    def body(x):
        y = peer_shift(x, "x", 1)
        lo, hi = halo_exchange_rdma(x, "x", 2)
        return y, lo, hi

    y, lo, hi = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("x"), out_specs=(P("x"), P("x"),
                                                     P("x")),
        check_vma=False))(x)
    e1, ok1 = _cmp(y, x, 0.0)
    e2, ok2 = _cmp(lo, jnp.zeros_like(lo), 0.0)
    e3, ok3 = _cmp(hi, jnp.zeros_like(hi), 0.0)

    # pool-backed landing buffers: the same exchange with donated
    # input/output-aliased buffers (PeerMemoryPool flow) must agree —
    # compiles the aliasing path on the real chip
    from apex_tpu.ops.pallas.remote_copy import halo_buf_rows

    br = halo_buf_rows(16, 2, x.dtype)
    bufs = (jnp.zeros((br, 256), x.dtype), jnp.zeros((br, 256), x.dtype))

    def body_pool(x, lo_in, hi_in):
        return halo_exchange_rdma(x, "x", 2, bufs=(lo_in, hi_in))

    lo2, hi2 = jax.jit(shard_map(
        body_pool, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
        out_specs=(P("x"), P("x")), check_vma=False))(x, *bufs)
    e4, ok4 = _cmp(lo2, jnp.zeros_like(lo2), 0.0)
    e5, ok5 = _cmp(hi2, jnp.zeros_like(hi2), 0.0)
    return {"max_err": max(e1, e2, e3, e4, e5),
            "pass": ok1 and ok2 and ok3 and ok4 and ok5}


CHECKS = [
    ("fused_adam_flat", check_adam_flat),
    ("fused_sgd_flat", check_sgd_flat),
    ("fused_lamb_flat", check_lamb_flat),
    ("fused_novograd_flat", check_novograd_flat),
    ("fused_adagrad_flat", check_adagrad_flat),
    ("layer_norm", check_layer_norm),
    ("group_norm", check_group_norm),
    ("softmax", check_softmax),
    ("flash_attention", check_flash_attention),
    ("remote_copy", check_remote_copy),
]


def run_checks(jax, jnp) -> dict:
    """Run every check on the already-initialized backend and return
    ``{name: {pass, max_err, wall_s}}``. On a TPU the kernels must be
    COMPILED (asserted); off-TPU (an explicit rehearsal) they run
    interpreted at small shapes. A check that raises propagates: nothing
    is caught and summarised."""
    global SMALL
    from apex_tpu.utils.env import interpret_default

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and interpret_default():
        raise RuntimeError("on a TPU the kernels must run compiled, but "
                           "interpret_default() is True")
    SMALL = not on_tpu
    mode = "interpreted" if interpret_default() else "compiled"
    results = {}
    for name, fn in CHECKS:
        t0 = time.perf_counter()
        r = fn(jax, jnp)
        r["wall_s"] = round(time.perf_counter() - t0, 1)
        results[name] = r
        print(f"[kernel] {name}: {mode}, "
              f"{'within tolerance' if r['pass'] else 'OUT OF TOLERANCE'} "
              f"(max_err {r['max_err']:.3g}, {r['wall_s']} s)", flush=True)
    return results
