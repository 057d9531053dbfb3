"""Read the two ends a cell's limit is set between, on the chip:

    python3 benchmark/limits_tool.py --workload <cell> --seeds 1,2,3 --seconds 40

One engine serves every seed (its weights are an argument of its programs,
so each seed's weights are swapped in and the pool is reset). For each seed
it drives the cell's own load through the timed path for one window, draws
the sample a run would, and prints what ``run.py`` would compare (the lower
reading) beside the same numbers with the control, the reference in int8,
in the program's place (the upper reading), each with the ``correct`` that
the committed limits give it, and the reference with the other GELU in the
program's place, which sizes that departure of the program. A thread that
only sleeps 10 ms at a time says how late it woke at worst: if an engine
call stands still and the thread does not, the host was not what stood
still. ``--dump`` keeps each seed's stamps, from the window's opening, for
a look at other window lengths and at the phase in which a window closes.

    python3 benchmark/limits_tool.py --replay <dir> [<dir> ...]

needs no chip: it reads the dumps of each directory again and prints, for
each window, what ``readers/stamps.py`` makes of time to first token (the
count, mean, median, 90th percentile and longest of the sample that
``ttft_mean_ms`` is taken over, and the former ``ttft_p90_ms`` over its own
sample), and for each directory the spread of each of them over its
windows: the distance between the quartiles over the median, as a bound is
set from it. ``recorded/pr27`` keeps the twelve windows the bound of
``ttft_mean_ms`` was looked at on, without their token stamps. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import threading
import time

import run
from readers import stamps


def ticker(late: list, stop: threading.Event):
    while not stop.is_set():
        t = time.perf_counter()
        time.sleep(0.01)
        late.append((time.perf_counter() - t - 0.01, t))


def dumped(requests, window) -> dict:
    """What ``--dump`` keeps of one window: the request stamps, counted
    from its opening."""
    lo, hi = window
    return {"seconds": hi - lo, "requests": [
        {k: (r[k] - lo if r[k] is not None else None)
         for k in ("submit_t", "admit_t", "first_token_t", "done_t")}
        | {"token_t": [t - lo for t in r["token_t"]], "failed": r["failed"]}
        for r in requests]}


def spread(values) -> float:
    """The distance between the quartiles over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def replay(directory: str) -> dict:
    """Print one line for each dumped window of ``directory`` and one for
    the directory; returns the latter."""
    shapes = []
    for name in sorted(os.listdir(directory)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(directory, name)) as f:
            dump = json.load(f)
        shapes.append(stamps.ttft_shape(dump["requests"],
                                        (0.0, dump["seconds"])))
        print(json.dumps({"window": name[:-len(".json")], **shapes[-1]}),
              flush=True)
    if len(shapes) < 2:
        raise SystemExit(f"{directory}: {len(shapes)} dumped window(s), and "
                         f"a spread needs two")
    over = {"directory": directory, "windows": len(shapes)}
    for key in ("mean_ms", "median_ms", "p90_ms", "longest_ms",
                "former_p90_ms"):
        column = [s[key] for s in shapes]
        over[key] = {"median": statistics.median(column),
                     "least": min(column), "most": max(column),
                     "spread": spread(column)}
    print(json.dumps(over), flush=True)
    return over


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--replay", nargs="+", metavar="DIR", default=None)
    ap.add_argument("--workload")
    ap.add_argument("--seeds")
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--dump", default=None)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--root", default=run.ROOT)
    args = ap.parse_args(argv)
    if args.replay:
        for directory in args.replay:
            replay(directory)
        return 0
    if not (args.workload and args.seeds):
        ap.error("--workload and --seeds are needed, or --replay")
    cell = run.resolve(args.root, args.workload)
    device = run.find_device(cell.chips, args.rehearse)

    from drivers import serve

    run.use_compile_cache()
    cfg, mix = cell.config, cell.traffic
    other = dict(cfg, activation_function={"gelu_new": "gelu", "gelu":
                                           "gelu_new"}[cfg["activation_function"]])
    seeds = [int(s) for s in args.seeds.split(",")]
    engine, params, reference = serve.build(cell, seeds[0])
    late, stop = [], threading.Event()
    threading.Thread(target=ticker, args=(late, stop), daemon=True).start()
    for i, seed in enumerate(seeds):
        if i:
            engine.params = params = None
            gc.collect()
            engine.params = params = reference.make_params(cfg, seed)
            engine.reset()
        del late[:]
        obs = serve.drive(cell, seed, args.seconds, False, engine,
                          time.perf_counter())
        lo, hi = obs["window"]
        worst = max((x for x in late if lo <= x[1] < hi), default=(0, lo))
        chosen = serve.sample(obs, seed, mix)
        readings = {
            "program": serve.score(cfg, mix, params, reference, chosen),
            "control_int8": serve.score(cfg, mix, params, reference, chosen,
                                        "int8")}
        tokens, rows, served, counts = serve.layout(mix, chosen)
        readings["other_gelu_in_the_programs_place"] = serve.compare(
            reference.forward_logits(cfg, params, tokens, rows),
            reference.forward_logits(other, params, tokens, rows),
            served, counts)
        for r in readings.values():
            r["correct"] = (r["served_below_own_best"] == 0 and all(
                r[name] <= spec["limit"] for name, spec in cell.limits.items()))
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(args.dump, f"{cell.name}.{seed}.json"),
                      "w") as f:
                json.dump(dumped(obs["requests"], obs["window"]), f)
        for r in obs["requests"]:
            del r["logits"]
        print(json.dumps({
            "workload": cell.name, "seed": seed, "kind": device["kind"],
            **readings, "requests": len(chosen),
            "failed": sum(r["failed"] for r in obs["requests"]),
            "latest_wake_ms": [worst[0] * 1e3, worst[1] - lo],
            "longest": serve.longest_stalls(obs)}),
            flush=True)
    stop.set()
    return 0


if __name__ == "__main__":
    sys.exit(main())
