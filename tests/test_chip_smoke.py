"""chip_smoke.py's refusals: what it must do where there is no chip.

The chip run itself cannot happen here; what tier-1 can hold is the other
half of the contract — with no accelerator the script fails before any
leg and prints no result, and outside a checkout it fails too. The
labelled CPU rehearsal of the full script is `slow`.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def _no_result(stdout):
    return not any(l.lstrip().startswith("{") and '"ok"' in l
                   for l in stdout.splitlines())


def test_no_accelerator_fails_before_any_leg():
    r = _run([], ROOT)
    assert r.returncode != 0
    assert _no_result(r.stdout), r.stdout[-500:]
    assert "[serve" not in r.stdout and "[kernel]" not in r.stdout
    assert "tpu" in r.stderr.lower()


def test_outside_a_checkout_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    # the rehearsal flag takes the platform check away, so what fails
    # here is the missing program itself
    r = _run(["--rehearse-cpu"], str(tmp_path))
    assert r.returncode != 0
    assert _no_result(r.stdout), r.stdout[-500:]
    assert "apex_tpu" in r.stderr


@pytest.mark.slow
def test_cpu_rehearsal_runs_every_leg_and_says_what_it_is():
    r = _run(["--rehearse-cpu"], ROOT, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "REHEARSAL" in r.stdout
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "rehearsal": True,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": last["device"]["count"]}}
    assert "streams identical across the two runs: yes" in r.stdout
    assert "10/10 within tolerance" in r.stdout
