"""The benchmark's one command. Everything that belongs to one cell is found
by the names in ``BENCHMARK.json``:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

- the cell's configuration is the ``file`` of its ``configs`` entry;
- its traffic is ``<paths[0]>/traffic/<traffic>.json``, which names the
  driver, ``drivers/<driver>.py``;
- its limits for ``correct`` are ``<paths[0]>/limits/<cell>.json``;
- each per-layer metric is ``<paths[0]>/layer_metrics/<name>.json``, which
  names its reader, ``readers/<reader>.py``, and what to ask of it;
- the peaks are ``<paths[0]>/peaks.json``, by the ``device_kind`` JAX reports.

So a new configuration, mix, driver, reader or per-layer metric is new files
and new entries. ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, from a run in which a few seconds of the
window are traced. Without a TPU the command fails; ``--rehearse`` runs it
anyway on whatever JAX has, to debug the harness, and says so in its line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()             # set-up is counted from here

import argparse                            # noqa: E402
import importlib                           # noqa: E402
import json                                # noqa: E402
import os                                  # noqa: E402
import shutil                              # noqa: E402
import sys                                 # noqa: E402
import types                               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _path in (HERE, ROOT):                 # readers.*, drivers.*; apex_tpu
    if _path not in sys.path:
        sys.path.insert(0, _path)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(root: str, workload: str):
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no cell {workload!r}; there are {sorted(cells)}")
    entry = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    data = os.path.join(root, bench["paths"][0])

    def ours(metric):
        return workload in metric.get("workloads", [workload])

    return types.SimpleNamespace(
        name=workload, root=root, data=data, chips=entry["chips"],
        config=load_json(root, config["file"]),
        traffic=load_json(data, "traffic", entry["traffic"] + ".json"),
        limits=load_json(data, "limits", workload + ".json"),
        end_to_end=[m for m in bench["end_to_end"] if ours(m)],
        per_layer=[m for m in bench["per_layer"] if ours(m)])


def peaks_for(data: str, kind: str) -> dict:
    table = load_json(data, "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peaks for device_kind {kind!r} in peaks.json "
                         f"(it has {sorted(table)}): add the device, with "
                         f"its source")
    return table[kind]


def find_device(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it; exits unless it is a TPU with the
    chips the cell asks for (a rehearsal takes what there is)."""
    import jax

    if not rehearse:
        jax.config.update("jax_platforms", "tpu")   # never fall to the CPU
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"JAX found no accelerator: {e}")
    block = {"platform": devices[0].platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    if not rehearse and (block["platform"] != "tpu" or len(devices) < chips):
        raise SystemExit(f"the cell needs {chips} TPU chip(s), JAX has {block}")
    return block


def use_compile_cache():
    """``<checkout>/.jax_cache``, or where the environment says; every
    program goes in, however short its compile."""
    import jax
    from apex_tpu.utils.env import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def per_layer_metrics(cell, obs) -> dict:
    out = {}
    for metric in cell.per_layer:
        spec = load_json(cell.data, "layer_metrics", metric["name"] + ".json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        value = reader.read(spec, obs)
        if value is not None:             # nothing to read: left out, never 0
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU, to debug the harness; the "
                         "line says so and its numbers mean nothing")
    ap.add_argument("--control", default=None,
                    help="a precision below the stated one, in which the "
                         "reference stands in the program's place when "
                         "`correct` is decided: it has to come out false")
    args = ap.parse_args(argv)
    cell = resolve(root, args.workload)
    device = find_device(cell.chips, args.rehearse)

    use_compile_cache()
    driver = importlib.import_module(f"drivers.{cell.traffic['driver']}")
    obs = driver.run(cell, args.seed, args.seconds, bool(args.trace), T_START,
                     **({"control": args.control} if args.control else {}))

    result = {"correct": all(c["value"] <= c["limit"]
                             for c in obs["checks"].values()),
              "attempted": obs["attempted"], "failed": obs["failed"]}
    device["memory_peak_bytes"] = obs["memory_peak_bytes"]
    note = obs["note"]
    if args.trace:
        t_read = time.perf_counter()
        from readers import device_trace as trace

        obs["peaks"] = None if args.rehearse else peaks_for(cell.data,
                                                            device["kind"])
        result["metrics"] = per_layer_metrics(cell, obs)
        measured = trace.busy(obs)
        if measured:
            device["busy_s"], device["window_s"] = measured
        parts = trace.breakdown(obs)
        if parts:
            result["breakdown"] = parts
        if obs["trace_dir"]:              # read once; nothing big stays
            shutil.rmtree(obs["trace_dir"], ignore_errors=True)
        note += f"; trace read in {time.perf_counter() - t_read:.1f} s"
    else:
        result["metrics"] = {
            m["name"]: {"value": obs["end_to_end"][m["name"]],
                        "unit": m["unit"]} for m in cell.end_to_end}
    result["device"] = device
    if args.rehearse:
        result["rehearsal"] = True
    if args.control:
        result["control"] = args.control
    result["checks"] = obs["checks"]      # what was compared, last
    print(f"[{cell.name} seed {args.seed}] {note}", flush=True)
    for name, c in obs["checks"].items():
        print(f"compared {name}: {c['value']} against the limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
