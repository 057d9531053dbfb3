"""Flash-attention block-size sweep on the real chip.

Measures fwd and fwd+bwd TFLOPs of ops/pallas/flash_attention.py across
(block_q, block_k) configurations at the bench shape (b4·h16·s2048·d64,
bf16, causal) plus a d=128 reference point, printing one JSON line per
config AS IT COMPLETES (a sweep cut short keeps its earlier lines). Needs
a TPU; run it through the chip tool with its output under chiprun_out/:

    python -u tools/tune_flash.py > chiprun_out/tune_flash.out

NOTE: the general successor is ``apex-tpu-tune`` (apex_tpu/tune), which
sweeps the same flash block set (registry._FA_BLOCKS), persists winners to
the shape-keyed tune cache that ``flash_attention`` consults at trace
time, and covers the rest of the kernel zoo; this script remains the
deep-dive harness (fwd+bwd TFLOPs, d=128 point, jax-pallas ceiling
comparator) whose findings inform the registry's candidate set.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_sweep(jax, jnp, out=sys.stdout):
    """Run the block sweep against an already-initialized backend, printing
    one JSON line per config to ``out`` as it completes."""
    from apex_tpu.ops.pallas.flash_attention import flash_attention
    from apex_tpu.utils.benchtime import measure_fetch_floor, timed_steps
    from apex_tpu.utils.prof import chip_peaks

    def emit(obj):
        print(json.dumps(obj), file=out, flush=True)

    backend = jax.default_backend()
    print(f"# backend={backend}", file=out, flush=True)
    on_tpu = backend == "tpu"
    peak = chip_peaks()["tflops"]
    floor_s = measure_fetch_floor()

    def measure(b, h, s, d, iters, attn_fn, **tag):
        """Time fwd and fwd+bwd of ``attn_fn(q, k, v)`` (causal) at the
        given shape; ``tag`` entries are merged into the result record.
        One timing/FLOPs implementation shared by our sweep configs AND
        the ceiling comparator, so they can never diverge."""
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(k_, (b, h, s, d), jnp.bfloat16) * 0.2
                   for k_ in ks)

        def fwd_step(i, q, k, v):
            return attn_fn(q, k, v).astype(q.dtype)

        ms_fwd = timed_steps(fwd_step, q, iters=iters, consts=(k, v),
                             floor_s=floor_s, donate=False)

        gradfn = jax.grad(lambda q, k, v: jnp.sum(
            attn_fn(q, k, v).astype(jnp.float32) ** 2))

        def bwd_step(i, q, k, v):
            return (q + 1e-3 * gradfn(q, k, v).astype(q.dtype)) \
                .astype(q.dtype)

        ms_fb = timed_steps(bwd_step, q, iters=iters, consts=(k, v),
                            floor_s=floor_s, donate=False)

        flops_fwd = 2 * 2 * b * h * s * s * d / 2  # causal
        # bwd ≈ 2.5x fwd FLOPs (dq, dk, dv + recompute); fwd+bwd total 3.5x
        tflops_fwd = flops_fwd / (ms_fwd / 1e3) / 1e12
        tflops_fb = 3.5 * flops_fwd / (ms_fb / 1e3) / 1e12
        return {"shape": f"b{b}h{h}s{s}d{d}", **tag,
                "fwd_ms": round(ms_fwd, 3), "fwd_tflops": round(tflops_fwd, 1),
                "fwd_mxu": round(tflops_fwd / peak, 3),
                "fb_ms": round(ms_fb, 3), "fb_tflops": round(tflops_fb, 1),
                "fb_mxu": round(tflops_fb / peak, 3)}

    def ours(bq, bk):
        return lambda q, k, v: flash_attention(q, k, v, True, block_q=bq,
                                               block_k=bk)

    b, h, s, d = (4, 16, 2048, 64) if on_tpu else (1, 2, 256, 64)
    iters = 20 if on_tpu else 2
    # (1024,2048)/(2048,1024)/(2048,2048) are excluded: their BACKWARD
    # exceeds v5e VMEM (proven deviceless — tools/flash_blocks_aot.json,
    # Mosaic RESOURCE_EXHAUSTED on the dkv transpose scratch); a sweep
    # winner must be usable for fwd AND bwd
    blocks = ([(256, 256), (256, 512), (512, 512), (512, 1024),
               (1024, 512), (1024, 1024), (2048, 512), (512, 2048),
               (256, 2048), (128, 1024), (128, 2048), (256, 1024),
               (128, 512)]
              if on_tpu else [(128, 128), (256, 128)])
    best = None
    for bq, bk in blocks:
        if bq > s or bk > s:
            continue
        try:
            t0 = time.perf_counter()
            r = measure(b, h, s, d, iters, ours(bq, bk), bq=bq, bk=bk)
            r["wall_s"] = round(time.perf_counter() - t0, 1)
            emit(r)
            if best is None or r["fwd_tflops"] > best["fwd_tflops"]:
                best = r
        except Exception as e:
            emit({"bq": bq, "bk": bk,
                  "error": f"{type(e).__name__}: {e}"})
    if on_tpu and best is not None:
        # d=128 reference point at the winning blocks
        try:
            r = measure(4, 8, 2048, 128, iters,
                        ours(best["bq"], best["bk"]),
                        bq=best["bq"], bk=best["bk"])
            emit(r)
        except Exception as e:
            emit({"shape": "d128", "error": str(e)})

    # ceiling comparator: jax's own Pallas TPU flash kernel at the same
    # shape — what a heavily-tuned kernel achieves on THIS chip. If ours
    # tracks it, the residual vs the MXU peak is platform, not our kernel.
    try:
        from jax.experimental.pallas.ops.tpu import flash_attention as jfa

        sm = 1.0 / (d ** 0.5)
        r = measure(b, h, s, d, iters,
                    lambda q, k, v: jfa.flash_attention(
                        q, k, v, causal=True, sm_scale=sm),
                    comparator="jax.experimental.pallas flash_attention")
        emit(r)
    except Exception as e:
        emit({"comparator": "jax pallas flash",
              "error": f"{type(e).__name__}: {e}"})
    # stamp the backend into the best record: block defaults must never
    # be derived from a CPU (interpret-mode) sweep line
    emit({"best": best, "backend": backend})
    return best


def main():
    import jax
    import jax.numpy as jnp

    if jax.default_backend() != "tpu":
        print(f"tune_flash needs a TPU; JAX found "
              f"{jax.default_backend()!r}", file=sys.stderr)
        sys.exit(3)
    run_sweep(jax, jnp)


if __name__ == "__main__":
    main()
