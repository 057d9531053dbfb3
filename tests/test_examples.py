"""The examples/ entry points stay runnable (the reference ships runnable
examples/{simple,dcgan,imagenet}; a bit-rotted example is a broken
component). Subprocess smoke with tiny step counts on CPU."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("args", [
    ["examples/simple/main_amp.py", "--steps", "4"],
    # dcgan is the heaviest example subprocess (two compiled models); the
    # simple + lm_pretrain smokes keep the entry points covered in tier-1
    pytest.param(["examples/dcgan/main_amp.py", "--steps", "2",
                  "--batch", "4"], marks=pytest.mark.slow),
    # the Trainer seam this example migrated onto is exercised directly by
    # tests/test_train_elastic.py in tier-1; the subprocess rides slow
    pytest.param(["examples/lm_pretrain/main_fused_head.py", "--steps", "3",
                  "--vocab-chunk", "128"], marks=pytest.mark.slow),
    # the serve CLI smoke in tests/test_serve.py covers the same engine
    # path in tier-1; the example subprocess rides the slow tier
    pytest.param(["examples/serve/generate.py", "--requests", "3",
                  "--max-new-tokens", "3"], marks=pytest.mark.slow),
])
def test_example_runs(args):
    r = _run(args)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip(), "example produced no output"
