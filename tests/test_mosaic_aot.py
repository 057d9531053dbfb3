"""Deviceless Mosaic compile regression.

The interpret-mode suite is blind to Mosaic compile errors (layout, tiling,
VMEM budget) — tools/mosaic_aot.py compiles the whole kernel zoo against a
compile-only v5e topology built from the baked-in libtpu, no chip needed.
This test keeps that property green: every kernel tag must compile.

(The need was proven once: the RDMA halo kernel carried a tile-misaligned
HBM slice that interpret mode executed happily and Mosaic rejects
outright.)
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# slow: a full kernel-zoo AOT compile is a minutes-scale subprocess — far
# the heaviest single test — and belongs with the other long-running
# integration checks, not the fast CPU tier
@pytest.mark.slow
def test_kernel_zoo_compiles_for_v5e(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env["JAX_PLATFORMS"] = "cpu"
    out = tmp_path / "MOSAIC_AOT.json"
    env["MOSAIC_AOT_OUT"] = str(out)  # never clobber the committed artifact
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "mosaic_aot.py")],
        env=env, capture_output=True, text=True, timeout=850, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    art = json.load(open(out))
    assert art["ok"] is True
    failed = [
        f"{k}:{t}" for k, rec in art["kernels"].items()
        for t, e in rec["tags"].items() if not e["ok"]]
    assert not failed, failed
    # the multi-device RDMA ring and ring attention must be among them
    assert "remote_copy" in art["kernels"]
    assert "ring_attention" in art["kernels"]
    # memory-structure regressions the compile-only client can prove:
    # flash attention must stay O(s·d), far under the ~1.07 GB a
    # materialized (b4·h16) 2048x2048 fp32 score matrix would need
    fa = art["kernels"]["flash_attention"]["tags"]
    for tag in ("causal_fwd_b4h16s2048", "dropout_fwd"):
        tmp = fa[tag].get("hbm_tmp_bytes")
        if tmp is not None:
            assert tmp < 400e6, (tag, tmp)
    # the flat Adam kernel streams fully in place: zero temp HBM
    ad = art["kernels"]["fused_adam_flat"]["tags"]
    for tag, e in ad.items():
        if e.get("hbm_tmp_bytes") is not None:
            assert e["hbm_tmp_bytes"] < 1e6, (tag, e)
