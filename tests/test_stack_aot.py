"""Deviceless v5e compile regression for the distributed stack.

tools/stack_aot.py compiles the ZeRO optimizers (all state layouts, both
LAMB sync modes and clip points), the TP×SP and PP×TP(+MoE) GPT-2 train
steps, and the DDP/SyncBN/Ulysses shard_map paths against a compile-only
4-device v5e client. This test keeps every case green.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# slow: compiles the entire distributed stack AOT in a subprocess — a
# minutes-scale job that belongs with the long-running integration checks,
# not the fast CPU tier
@pytest.mark.slow
def test_distributed_stack_compiles_for_v5e(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env["JAX_PLATFORMS"] = "cpu"
    out = tmp_path / "STACK_AOT.json"
    env["STACK_AOT_OUT"] = str(out)  # never clobber the committed artifact
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "stack_aot.py")],
        env=env, capture_output=True, text=True, timeout=850, cwd=ROOT)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    art = json.load(open(out))
    assert art["ok"] is True
    failed = [n for n, e in art["cases"].items() if not e["ok"]]
    assert not failed, failed
    # every distributed case must actually contain collectives (a
    # partition-free compile would mean the sharding was silently dropped)
    for name, e in art["cases"].items():
        colls = e.get("collectives", {})
        assert sum(colls.values()) > 0, (name, colls)
    # the LAMB grad-sync modes must compile to DIFFERENT collective
    # structure on TPU, mirroring the CPU-mesh HLO test
    # (test_grad_sync_modes_different_collectives); grads are lowered
    # unpinned in the harness precisely so this distinction can surface
    rs = art["cases"]["dist_lamb_rs_ar"]["collectives"]
    fa = art["cases"]["dist_lamb_full_ar"]["collectives"]
    assert rs != fa, (rs, fa)
