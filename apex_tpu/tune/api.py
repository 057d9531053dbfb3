"""``tuned_params()`` — the one lookup every Pallas kernel entry point
makes before choosing its tile geometry.

Contract (asserted in tests/test_tune.py):

- **interpret mode never consults the cache**: CPU tests and virtual
  meshes always get the hand-written heuristics, so numerics/grids there
  are independent of whatever cache file happens to exist;
- **empty cache == today's heuristics, bit for bit**: a miss returns the
  ``defaults`` dict unchanged;
- a hit merges ONLY keys already present in ``defaults`` (a cache entry
  cannot smuggle unknown kwargs into a kernel) and is optionally passed
  through a ``validate`` predicate — an entry tuned for a different shape
  in the same bucket that no longer satisfies the kernel's divisibility
  constraints falls back to the heuristics instead of crashing inside
  ``pallas_call``;
- every selection publishes ONE ``kernel_autotune`` event per (key,
  params) on the event bus (``utils.logging.publish_event``), so a
  :class:`~apex_tpu.monitor.telemetry.Telemetry` sink records tuning
  provenance in the run's JSONL.

Lookups happen at Python trace time (shapes are static), cost one dict
probe after the first call, and never touch the backend.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from apex_tpu.tune.cache import (cache_key, code_version, default_cache,
                                 device_key)
from apex_tpu.utils.env import interpret_default

# (key, frozen params) pairs already announced on the event bus — one
# kernel_autotune event per distinct selection per process, not per trace
_announced: set = set()


def pow2_bucket(n: int) -> int:
    """Shape-bucketing quantum for cache keys: next power of two. Nearby
    row counts share one tuned entry; the per-kernel ``validate`` hook
    rejects entries that stop dividing a particular member of the bucket."""
    from apex_tpu.ops.pallas.tiling import pow2_ceil

    return pow2_ceil(n)


def _announce(kernel: str, key: str, params: Dict[str, Any],
              source: str) -> None:
    from apex_tpu.utils.logging import publish_event

    tag = (key, tuple(sorted(params.items())))
    if tag in _announced:
        return
    _announced.add(tag)
    publish_event("kernel_autotune", kernel=kernel, key=key,
                  params=dict(params), source=source, emit=False)


def tuned_params(kernel: str, shape_key, defaults: Dict[str, Any], *,
                 dtype=None, interpret: Optional[bool] = None,
                 validate: Optional[Callable[[Dict[str, Any]], bool]] = None,
                 ) -> Dict[str, Any]:
    """Resolve a kernel's tile parameters: cached winner if one exists for
    this (kernel, shape-bucket, dtype, chip, code-version), else the
    hand-written ``defaults``.

    ``shape_key``: tuple of ``(name, value)`` pairs, pre-bucketed by the
    caller (``pow2_bucket`` for row-ish dims, exact for layout-defining
    dims like ``hidden``). ``interpret=None`` resolves via
    :func:`~apex_tpu.utils.env.interpret_default`; ``interpret=True``
    short-circuits to ``defaults`` without touching the cache.
    ``validate(params)`` may reject a merged candidate (fall back to
    defaults) when it violates the kernel's constraints for the CONCRETE
    shape at hand.
    """
    if interpret is None:
        interpret = interpret_default()
    if interpret:
        return dict(defaults)
    import os

    if os.environ.get("APEX_TPU_FORCE_COMPILED") == "1":
        # deviceless AOT compile (tools/mosaic_aot.py & co.): the jit
        # target is a topology client, not jax.devices() — device_key()
        # would name the HOST, so a stray cache file could silently change
        # the committed AOT artifacts. Heuristics only.
        return dict(defaults)
    cache = default_cache()
    if cache is None:
        return dict(defaults)
    key = cache_key(kernel, shape_key, dtype, device_key())
    entry = cache.get(key)
    if entry is None:
        return dict(defaults)
    params = entry.get("params", {})
    merged = dict(defaults)
    merged.update({k: params[k] for k in defaults if k in params})
    if merged == dict(defaults):
        return merged
    if validate is not None and not validate(merged):
        return dict(defaults)
    _announce(kernel, key, merged, source="cache")
    return merged


def record_tuned(kernel: str, shape_key, params: Dict[str, Any], *,
                 dtype=None, meta: Optional[Dict[str, Any]] = None,
                 device: Optional[str] = None, save: bool = True) -> str:
    """Store a tuning winner in the default cache (search results, or a
    hand-pinned config) and publish its ``kernel_autotune`` provenance
    event. Returns the cache key."""
    key = cache_key(kernel, shape_key, dtype, device or device_key(),
                    code_version(kernel))
    cache = default_cache()
    if cache is None:
        raise ValueError(
            "record_tuned needs a tune cache: set APEX_TPU_TUNE_CACHE")
    cache.put(key, params, meta=meta)
    if save:
        cache.save()
    _announce(kernel, key, dict(params), source="search")
    return key
