"""Read the two ends a ``deepseek_v3`` cell's limit is set between, on the
chip, as ``limits_tool.py`` reads them for the first driver:

    python3 benchmark/limits_deepseek_v3.py --workload <cell> --seeds 1,2,3

One engine serves every seed (each seed's weights are swapped in, the pool
is reset). For each seed it drives the cell's own load through the timed
path for one window, draws the sample a run would, and prints what
``run.py`` would compare (the program's ``logit_noise_share``: the lower
reading) beside the same with the control, the reference in int8, in the
program's place at the same positions (the upper reading), and the window's
end-to-end numbers. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import numpy as np

import run
from readers import stamps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=run.ROOT)
    args = ap.parse_args(argv)
    cell = run.resolve(args.root, args.workload)
    device = run.find_device(cell.chips, args.rehearse)

    from drivers import serve, serve_deepseek_v3 as driver

    run.use_compile_cache()
    cfg, mix = cell.config, cell.traffic
    seeds = [int(s) for s in args.seeds.split(",")]
    engine, params, reference = driver.build(cell, seeds[0])
    for i, seed in enumerate(seeds):
        if i:
            engine.params = params = None
            gc.collect()
            engine.params = params = reference.make_params(cfg, seed)
            engine.reset()
        ring = driver.Ring(mix["kept_rows"], cfg["vocab_size"])
        kept = driver.KeptLogits(engine, ring, seed, mix["kept_share"])
        obs = serve.drive(cell, seed, args.seconds, False, engine,
                          time.perf_counter())
        kept.restore()
        chosen = serve.sample(driver.scorable(obs, ring), seed, mix)
        tokens, rows, served, counts = serve.layout(mix, chosen)
        got = driver.program_rows(chosen, ring, len(rows))
        ref = reference.forward_logits(cfg, params, tokens, rows)
        control = reference.forward_logits(cfg, params, tokens, rows, "int8")
        readings = {
            "program": serve.compare(ref, got, served, counts),
            "control_int8": serve.compare(
                ref, control, np.asarray(control.argmax(-1), np.int32),
                counts)}
        for r in obs["requests"]:
            del r["logits"]
        print(json.dumps({
            "workload": cell.name, "seed": seed, "kind": device["kind"],
            **readings, "requests": len(chosen), "tokens": int(counts.sum()),
            "failed": sum(r["failed"] for r in obs["requests"]),
            "memory_peak_bytes": obs["memory_peak_bytes"],
            **stamps.end_to_end(obs["requests"], obs["window"]),
            "longest": serve.longest_stalls(obs)}), flush=True)
        del ref, control, got
    return 0


if __name__ == "__main__":
    sys.exit(main())
