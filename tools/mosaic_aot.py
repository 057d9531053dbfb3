"""Deviceless Mosaic AOT compile of the Pallas kernel zoo.

Interpret-mode parity (the test suite) is blind to Mosaic compile errors,
VMEM-budget violations, and layout problems. This tool closes that hole
WITHOUT the chip — a free pre-check before chip time is spent (the
compiled kernels' parity on silicon is chip_smoke.py's kernel leg): the
baked-in ``libtpu.so``
can build a compile-only PJRT client from a topology description
(``jax.experimental.topologies.get_topology_desc``), so every kernel is
lowered and compiled for a real v5e target with no attached device.

The reference compiles its kernel zoo in its build matrix
(/root/reference/tests/docker_extension_builds/run.sh:16-40); this is the
TPU analog.

Coverage mirrors chipcheck.py's 10 checks (same names, so the artifacts
line up), at the REAL bench shapes, fwd+bwd where the surface has a VJP,
plus the multi-device RDMA/ring paths compiled over a 4-device v5e:2x2
topology mesh (shard_map → Mosaic remote DMA — never compiled before).

Output: MOSAIC_AOT.json — per-kernel {compiled, tags: {tag: {ok, wall_s,
error?}}} + overall ``ok``. Exit 0 iff every tag compiled.
"""

from __future__ import annotations

import json
import os
import sys
import time

# shared compile-only scaffolding (env + CPU pin + cache) — must import
# before jax backend use
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _aot_common import (ROOT, atomic_write_json,  # noqa: E402
                         get_topology)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

OUT_PATH = os.environ.get("MOSAIC_AOT_OUT",
                          os.path.join(ROOT, "MOSAIC_AOT.json"))


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def build_cases(dev_sharding, mesh):
    """Return [(kernel_name, tag, fn, args)] at bench shapes."""
    s = dev_sharding
    cases = []

    def add(kernel, tag, fn, *args):
        cases.append((kernel, tag, fn, args))

    LANE = 128

    # ---- flat optimizer kernels at the 1B-element bench shape ----------
    rows = 999_999_488 // LANE
    pb = _struct((rows, LANE), jnp.bfloat16, s)
    gb = _struct((rows, LANE), jnp.bfloat16, s)
    mf = _struct((rows, LANE), jnp.float32, s)
    vf = _struct((rows, LANE), jnp.float32, s)
    pf = _struct((rows, LANE), jnp.float32, s)

    from apex_tpu.ops.pallas.fused_adam_kernel import (ADAM_MODE_L2,
                                                       fused_adam_flat,
                                                       fused_adam_flat_master)
    add("fused_adam_flat", "adamw_1b",
        lambda p, g, m, v: fused_adam_flat(p, g, m, v, lr=1e-3,
                                           weight_decay=0.01, step=3),
        pb, gb, mf, vf)
    add("fused_adam_flat", "l2_1b",
        lambda p, g, m, v: fused_adam_flat(p, g, m, v, lr=1e-3,
                                           weight_decay=0.01, step=3,
                                           mode=ADAM_MODE_L2,
                                           inv_scale=0.5),
        pb, gb, mf, vf)
    add("fused_adam_flat", "master_1b",
        lambda p, g, m, v: fused_adam_flat_master(p, g, m, v, lr=1e-3,
                                                  weight_decay=0.01, step=3),
        pf, gb, mf, vf)

    from apex_tpu.ops.pallas.fused_sgd_kernel import fused_sgd_flat
    add("fused_sgd_flat", "momentum_wd_1b",
        lambda p, g, b: fused_sgd_flat(p, g, b, lr=0.1, momentum=0.9,
                                       weight_decay=1e-4, inv_scale=2.0),
        pb, gb, mf)

    from apex_tpu.ops.pallas.fused_opt_kernels import (fused_adagrad_flat,
                                                       fused_lamb_flat,
                                                       fused_novograd_flat)
    # LAMB/NovoGrad: segment-summed per-tensor norms — the bench/BERT path
    # runs ~1e8 elements over hundreds of tensors; compile with a
    # representative segment map (structure, not data, is what Mosaic sees)
    lrows = 104_857_600 // LANE
    rid = _struct((lrows,), jnp.int32, s)
    lp = _struct((lrows, LANE), jnp.float32, s)
    add("fused_lamb_flat", "bert_scale",
        lambda p, g, m, v, r: fused_lamb_flat(
            p, g, m, v, r, num_tensors=400, lr=1e-2, weight_decay=0.01,
            step=2, max_grad_norm=1.0),
        lp, lp, lp, lp, rid)
    vt = _struct((400,), jnp.float32, s)
    add("fused_novograd_flat", "bert_scale",
        lambda p, g, m, v, r: fused_novograd_flat(
            p, g, m, v, r, num_tensors=400, lr=1e-2, weight_decay=0.01,
            step=1),
        lp, lp, lp, vt, rid)
    add("fused_adagrad_flat", "1b",
        lambda p, g, h: fused_adagrad_flat(p, g, h, lr=1e-2,
                                           weight_decay=1e-4),
        pf, _struct(gb.shape, jnp.float32, s), mf)

    # ---- LayerNorm / RMSNorm at the bench shape (8192x4096 bf16) -------
    from apex_tpu.normalization.fused_layer_norm import (
        fused_layer_norm_affine, fused_rms_norm_affine)
    xln = _struct((8192, 4096), jnp.bfloat16, s)
    wln = _struct((4096,), jnp.float32, s)
    bln = _struct((4096,), jnp.float32, s)
    add("layer_norm", "fwd_8192x4096_bf16",
        lambda x, w, b: fused_layer_norm_affine(x, w, b, 4096), xln, wln, bln)
    add("layer_norm", "bwd_8192x4096_bf16",
        lambda x, w, b: jax.grad(
            lambda x, w, b: jnp.sum(
                fused_layer_norm_affine(x, w, b, 4096)
                .astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(x, w, b),
        xln, wln, bln)
    add("layer_norm", "bwd_memeff",
        lambda x, w, b: jax.grad(
            lambda x, w, b: jnp.sum(
                fused_layer_norm_affine(x, w, b, 4096,
                                        memory_efficient=True)
                .astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(x, w, b),
        xln, wln, bln)
    add("layer_norm", "rms_fwd",
        lambda x, w: fused_rms_norm_affine(x, w, 4096), xln, wln)

    # ---- GroupNorm NHWC (both algos, SiLU epilogue) --------------------
    from apex_tpu.ops.pallas.group_norm_kernel import group_norm_nhwc_pallas
    xgn = _struct((8, 32, 32, 256), jnp.float32, s)
    wgn = _struct((256,), jnp.float32, s)
    add("group_norm", "one_pass_silu",
        lambda x, w, b: group_norm_nhwc_pallas(x, 32, w, b, act="silu",
                                               algo="one_pass"),
        xgn, wgn, wgn)
    add("group_norm", "two_pass",
        lambda x, w, b: group_norm_nhwc_pallas(x, 32, w, b,
                                               algo="two_pass"),
        xgn, wgn, wgn)

    # ---- Megatron softmax kernels at the bench shape -------------------
    from apex_tpu.ops.pallas.softmax_kernel import (softmax_bwd_pallas,
                                                    softmax_fwd_pallas)
    B, sq = 128, 1024  # b8·h16 fused softmax bench shape
    xs = _struct((B, sq, sq), jnp.float32, s)
    ms = _struct((B, sq, sq), jnp.bool_, s)
    add("softmax", "causal_chunked_fwd",
        lambda x: softmax_fwd_pallas(x, None, scale=0.5, causal=True), xs)
    add("softmax", "masked_fwd",
        lambda x, m: softmax_fwd_pallas(x, m, scale=0.7, causal=False),
        xs, ms)
    add("softmax", "bwd",
        lambda y, dy: softmax_bwd_pallas(y, dy, scale=0.5), xs, xs)

    # ---- Flash attention at the headline bench shape -------------------
    from apex_tpu.ops.pallas.flash_attention import flash_attention
    b, h, sl, d = 4, 16, 2048, 64
    qs = _struct((b, h, sl, d), jnp.bfloat16, s)
    add("flash_attention", "causal_fwd_b4h16s2048",
        lambda q, k, v: flash_attention(q, k, v, True), qs, qs, qs)
    add("flash_attention", "causal_bwd_b4h16s2048",
        lambda q, k, v: jax.grad(
            lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v),
        qs, qs, qs)
    mask = _struct((b, 1, sl, sl), jnp.bool_, s)
    add("flash_attention", "masked_fwd",
        lambda q, k, v, m: flash_attention(q, k, v, mask=m),
        qs, qs, qs, mask)
    add("flash_attention", "dropout_fwd",
        lambda q, k, v: flash_attention(q, k, v, True, dropout_p=0.1,
                                        dropout_seed=7), qs, qs, qs)
    rq = _struct((b, h, 1993, d), jnp.bfloat16, s)
    rk = _struct((b, h, 2017, d), jnp.bfloat16, s)
    add("flash_attention", "ragged_fwd",
        lambda q, k, v: flash_attention(q, k, v, True), rq, rk, rk)

    # ---- one-sided remote DMA over the 4-device topology mesh ----------
    # shard_map + make_async_remote_copy compiled by Mosaic for a REAL
    # multi-chip ring — the multi-device path has only ever run in
    # interpret mode on the CPU mesh
    from apex_tpu.ops.pallas.remote_copy import (halo_exchange_rdma,
                                                 peer_shift)
    ns = NamedSharding(mesh, P("x"))
    xr = _struct((64, 2048), jnp.float32, ns)

    def rdma_body(x):
        y = peer_shift(x, "x", 1)
        lo, hi = halo_exchange_rdma(x, "x", 2)
        return y, lo, hi

    add("remote_copy", "ring4_shift_halo",
        lambda x: shard_map(rdma_body, mesh=mesh, in_specs=P("x"),
                                out_specs=(P("x"), P("x"), P("x")),
                                check_vma=False)(x), xr)

    # pool-backed landing buffers: remote puts must alias into donated
    # storage (input_output_aliases through shard_map → Mosaic)
    from apex_tpu.ops.pallas.remote_copy import halo_buf_rows

    per_dev_rows = 64 // mesh.shape["x"]
    br = halo_buf_rows(per_dev_rows, 2, jnp.float32)
    buf = _struct((br * mesh.shape["x"], 2048), jnp.float32, ns)

    def rdma_pool_body(x, lo_in, hi_in):
        return halo_exchange_rdma(x, "x", 2, bufs=(lo_in, hi_in))

    add("remote_copy", "ring4_halo_pool_bufs",
        lambda x, lo, hi: shard_map(
            rdma_pool_body, mesh=mesh, in_specs=(P("x"), P("x"), P("x")),
            out_specs=(P("x"), P("x")), check_vma=False)(x, lo, hi),
        xr, buf, buf)

    # ---- beyond chipcheck: ring attention over the topology mesh -------
    from apex_tpu.parallel.ring_attention import ring_attention

    nring = mesh.shape["x"]
    qr = _struct((1, 8, nring * 1024, 64), jnp.bfloat16,
                 NamedSharding(mesh, P(None, None, "x", None)))
    add("ring_attention", f"collective_{nring}dev",
        lambda q, k, v: shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="x"),
            mesh=mesh,
            in_specs=P(None, None, "x", None),
            out_specs=P(None, None, "x", None),
            check_vma=False)(q, k, v),
        qr, qr, qr)
    return cases


def main():
    t0 = time.time()
    topo = get_topology()
    devs = topo.devices
    dev_sharding = SingleDeviceSharding(devs[0])
    nmesh = min(4, len(devs))
    mesh = Mesh(np.array(devs[:nmesh]).reshape(nmesh), ("x",))
    result = {"topology": os.environ.get("MOSAIC_AOT_TOPOLOGY", "v5e:2x2"),
              "device_kind": getattr(devs[0], "device_kind", "?"),
              "n_devices": len(devs),
              "jax": jax.__version__,
              "captured": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "kernels": {}}

    cases = build_cases(dev_sharding, mesh)
    ok_all = True
    for kernel, tag, fn, args in cases:
        rec = result["kernels"].setdefault(kernel,
                                           {"compiled": True, "tags": {}})
        t1 = time.time()
        try:
            compiled = jax.jit(fn).lower(*args).compile()
            entry = {"ok": True}
            try:  # best-effort: analysis failure is not a compile failure
                mem = compiled.memory_analysis()
                entry["hbm_args_bytes"] = int(mem.argument_size_in_bytes)
                entry["hbm_tmp_bytes"] = int(mem.temp_size_in_bytes)
            except Exception:
                pass
        except Exception as e:
            entry = {"ok": False,
                     "error": f"{type(e).__name__}: {str(e)[:1500]}"}
            rec["compiled"] = False
            ok_all = False
        entry["wall_s"] = round(time.time() - t1, 1)
        rec["tags"][tag] = entry
        print(f"[mosaic_aot] {kernel}:{tag} "
              f"{'OK' if entry['ok'] else 'FAIL ' + entry.get('error', '')}"
              f" ({entry['wall_s']}s)", file=sys.stderr, flush=True)
        # incremental write: a crash mid-run still leaves evidence
        result["ok"] = False
        result["wall_s"] = round(time.time() - t0, 1)
        atomic_write_json(OUT_PATH, result)

    result["ok"] = ok_all
    result["wall_s"] = round(time.time() - t0, 1)
    atomic_write_json(OUT_PATH, result)
    n_tags = sum(len(r["tags"]) for r in result["kernels"].values())
    print(json.dumps({"ok": ok_all, "kernels": len(result["kernels"]),
                      "tags": n_tags, "wall_s": result["wall_s"]}))
    sys.exit(0 if ok_all else 2)


if __name__ == "__main__":
    main()
