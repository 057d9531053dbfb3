"""Run a command and record when the whole machine stood still beside it.

    python3 tools/machine_pauses.py --out pauses.json -- <command> [args]

This process never touches JAX or the chip: it starts the command as a
child and, until the child ends, sleeps 5 ms at a time and notes every
sleep that took more than ``--longer-than`` seconds. A process that only
sleeps is late when the machine itself was stopped (PERF.md section 6,
PR 35: on the machines with a chip every process, an idle one too, stops
for about 0.11 s a few times a minute, and a server that waits for each
step before it launches the next loses what is left of the pause once
its step in flight has ended). The file holds the pauses as ``[seconds
after the command started, length in ms]``; a benchmark run's window lies
``setup_s`` after that start. The exit code is the command's.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time


def watch(child, longer_than: float, tick: float = 0.005) -> list:
    """``[(seconds since the start, ms)]`` of the sleeps that overran,
    until ``child`` has ended."""
    start = last = time.perf_counter()
    pauses = []
    while child.poll() is None:
        time.sleep(tick)
        t = time.perf_counter()
        if t - last > longer_than:
            pauses.append((round(last - start, 3),
                           round((t - last) * 1e3, 1)))
        last = t
    return pauses


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--longer-than", type=float, default=0.03)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    command = args.command[args.command[:1] == ["--"]:]
    if not command:
        ap.error("no command after --")
    t0 = time.perf_counter()
    child = subprocess.Popen(command)
    pauses = watch(child, args.longer_than)
    with open(args.out, "w") as f:
        json.dump({"command": command, "pauses": pauses,
                   "seconds": round(time.perf_counter() - t0, 3)}, f)
    return child.returncode


if __name__ == "__main__":
    sys.exit(main())
