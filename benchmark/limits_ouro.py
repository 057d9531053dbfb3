"""Read the two ends an ``ouro`` cell's limit is set between, on the chip:

    python3 benchmark/limits_ouro.py --workload <cell> --seeds 1,2,3

``limits_deepseek_v3.py`` itself (the cell's own load through the timed
path, the program's ``logit_noise_share`` beside the int8 control's at the
same positions), with the engine ``drivers/serve_ouro.py`` builds, and a
process a seed: that tool keeps one engine for every seed and makes the
next seed's weights beside its pool, which 5.3 GB of weights beside a
6.5 GB pool leave no room for. This process never touches JAX when it is
given several seeds, so each child has the chip to itself. The benchmark's
own runs never run this.
"""

from __future__ import annotations

import subprocess
import sys
from unittest import mock

import limits_deepseek_v3
from drivers import serve_deepseek_v3, serve_ouro


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    at = argv.index("--seeds") + 1
    seeds = argv[at].split(",")
    if len(seeds) > 1:
        return max(subprocess.run(
            [sys.executable, __file__, *argv[:at], seed, *argv[at + 1:]]
        ).returncode for seed in seeds)
    with mock.patch.object(serve_deepseek_v3, "build", serve_ouro.build):
        return limits_deepseek_v3.main(argv)


if __name__ == "__main__":
    sys.exit(main())
