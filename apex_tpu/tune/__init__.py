"""apex_tpu.tune — shape-keyed Pallas kernel autotuner.

Every kernel in the zoo picks its tile geometry through
:func:`tuned_params`: a cached winner for the exact
``(kernel, shape-bucket, dtype, chip, code-version)`` when the on-disk
cache has one, else today's hand-written heuristics (now shared in
``ops/pallas/tiling.py``) — and ALWAYS the heuristics in interpret mode,
so CPU tests and virtual meshes never depend on cache state.

The cache is warmed by timing real compiled calls
(:func:`~apex_tpu.tune.search.autotune_kernel`, the ``apex-tpu-tune``
CLI) and persists as the one JSON file ``APEX_TPU_TUNE_CACHE`` names
(unset: no cache, heuristics only). Selections and search results
publish ``kernel_autotune`` events on the monitor event bus, so tuning
provenance lands in the telemetry JSONL. The committed
``BENCH_BASELINE.json`` + ``tools/check_regression.py --suite`` close the
loop: warm cache → bench → commit baseline → CI gate
(docs/performance.md).
"""

from apex_tpu.tune.api import (pow2_bucket, record_tuned,  # noqa: F401
                               tuned_params)
from apex_tpu.tune.cache import (CODE_VERSIONS, TuneCache,  # noqa: F401
                                 cache_key, code_version, default_cache,
                                 default_cache_path, device_key, invalidate)

__all__ = [
    "tuned_params", "record_tuned", "pow2_bucket", "TuneCache",
    "cache_key", "code_version", "CODE_VERSIONS", "default_cache",
    "default_cache_path", "device_key", "invalidate",
]
