"""``apex-tpu-tune`` — warm the shape-keyed kernel autotune cache.

Usage::

    apex-tpu-tune [--kernels layer_norm,flash_attention | all]
                  [--spec workload.json] [--cache PATH]
                  [--iters N] [--max-candidates N]
                  [--telemetry-jsonl PATH]

``--spec`` points at a JSON workload description — a list of
``{"kernel": ..., "shape": {...}, "dtype": "bfloat16"}`` entries; without
it, each selected kernel tunes its registry ``default_shapes`` (the bench
shapes). ``--cache`` names the cache file (else ``APEX_TPU_TUNE_CACHE``;
one of the two is required — there is no default location).

Every search publishes ``kernel_autotune`` events on the process event
bus; ``--telemetry-jsonl`` attaches a :class:`apex_tpu.monitor.Telemetry`
sink so those events (tuning provenance: key, winning params, timings)
land in a JSONL next to your training telemetry. One JSON line per tuned
(kernel, shape) is printed to stdout as it completes; the last line is a
summary ``{"tuned": N, "cache": PATH, ...}``.

Off-TPU the kernels run in interpret mode — the timings are meaningless
for real tuning (the CLI says so on stderr) but the full pipeline
(search → cache write → events) runs, which is what the CPU smoke test
exercises. Real warming happens on the chip (docs/performance.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List


def build_workload(args) -> List[Dict[str, Any]]:
    from apex_tpu.tune import registry

    if args.spec:
        with open(args.spec) as f:
            doc = json.load(f)
        if not isinstance(doc, list):
            raise SystemExit(f"--spec {args.spec}: expected a JSON list of "
                             "{kernel, shape, dtype?} entries")
        for entry in doc:
            registry.spec(entry["kernel"])  # fail fast on unknown kernels
            if not isinstance(entry.get("shape"), dict):
                raise SystemExit(f"--spec entry missing 'shape': {entry}")
        return doc

    if args.kernels in (None, "", "all"):
        names = list(registry.kernels())
    else:
        names = [k.strip() for k in args.kernels.split(",") if k.strip()]
    workload = []
    for name in names:
        spec = registry.spec(name)
        for shape in spec.default_shapes or ():
            workload.append({"kernel": name, "shape": dict(shape)})
    return workload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="apex-tpu-tune",
        description="warm the Pallas kernel autotune cache for a workload")
    ap.add_argument("--kernels", default="all",
                    help="comma-separated kernel subset (default: all)")
    ap.add_argument("--spec", default=None,
                    help="JSON workload file: [{kernel, shape, dtype?}]")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: APEX_TPU_TUNE_CACHE; one "
                         "of the two is required)")
    ap.add_argument("--iters", type=int, default=None,
                    help="timed steps per candidate (default: 10 on TPU, "
                         "2 off-TPU)")
    ap.add_argument("--max-candidates", type=int, default=None,
                    help="cap the per-shape candidate sweep")
    ap.add_argument("--telemetry-jsonl", default=None,
                    help="mirror kernel_autotune events into this JSONL "
                         "via apex_tpu.monitor.Telemetry")
    args = ap.parse_args(argv)

    if args.cache:
        os.environ["APEX_TPU_TUNE_CACHE"] = args.cache
    if not os.environ.get("APEX_TPU_TUNE_CACHE"):
        print("[apex-tpu-tune] no cache file: pass --cache PATH or set "
              "APEX_TPU_TUNE_CACHE", file=sys.stderr)
        return 2

    from apex_tpu.tune import cache as tune_cache
    from apex_tpu.tune.search import warm_cache

    tune_cache.invalidate()  # respect a just-set --cache path

    import jax

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu:
        print("[apex-tpu-tune] no TPU backend: kernels run in interpret "
              "mode — cache entries are smoke artifacts, not real tuning",
              file=sys.stderr)
    iters = args.iters if args.iters is not None else (10 if on_tpu else 2)

    workload = build_workload(args)
    if not workload:
        print("[apex-tpu-tune] empty workload", file=sys.stderr)
        return 2

    tel = None
    if args.telemetry_jsonl:
        from apex_tpu.monitor import Telemetry

        tel = Telemetry(args.telemetry_jsonl)

    failures = 0
    try:
        results = []
        for entry in workload:
            res = warm_cache([entry], iters=iters,
                             max_candidates=args.max_candidates)[0]
            results.append(res)
            line = {k: res.get(k) for k in
                    ("kernel", "key", "best", "best_ms", "default_ms",
                     "speedup_vs_default", "error") if res.get(k) is not None}
            print(json.dumps(line), flush=True)
            if "error" in res:
                failures += 1
    finally:
        if tel is not None:
            tel.close()

    path = tune_cache.default_cache().save()
    tune_cache.invalidate()  # consumers in this process reload the file
    print(json.dumps({"tuned": len(results) - failures,
                      "failed": failures,
                      "entries": len(tune_cache.default_cache()),
                      "backend": "tpu" if on_tpu else "interpret",
                      "cache": path}))
    return 1 if failures and failures == len(results) else 0


if __name__ == "__main__":
    sys.exit(main())
