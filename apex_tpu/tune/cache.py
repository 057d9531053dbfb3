"""Persistent shape-keyed autotune cache.

One JSON file maps deterministic string keys —
``kernel|shape-bucket|dtype|device|code-version`` — to the winning kernel
parameters found by ``apex_tpu.tune.search`` (or pinned by hand). The file
is the durable half of the autotuner: warmed once per (chip, code-version)
by ``apex-tpu-tune``, then consulted at trace time by every kernel's
``tuned_params()`` lookup.

Durability rules (mirroring ``apex_tpu.resilience``'s conventions):

- writes are atomic (tmp + ``os.replace``) so a reader never sees a torn
  file;
- an unreadable / corrupt / wrong-schema cache file degrades to an EMPTY
  cache with one ``tune_cache_corrupt`` structured warning — a broken
  cache must never break training, it only loses tuning;
- keys are pure functions of their inputs (no timestamps, no dict order,
  no floats) so two processes tuning the same workload produce identical
  keys and can share one file.

The file is the one ``APEX_TPU_TUNE_CACHE`` names (tests point it at a
tmpdir; CI can point it at a committed warm cache). With the variable
unset there is NO cache: lookups return the heuristics, so block sizes
depend only on what the checkout holds — never on a file outside it
that two commits under comparison would share.
"""

from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, Optional, Tuple

SCHEMA_VERSION = 1

# per-kernel code-version: bump when a kernel's tiling semantics change so
# stale cache entries (tuned against the old kernel) stop applying. This is
# the ``code-version`` component of every cache key.
CODE_VERSIONS = {
    "layer_norm": 1,
    "softmax": 1,
    "softmax_causal_chunked": 1,
    "group_norm": 1,
    "flash_attention": 1,
    # v2: the paged KV pool added a page_size shape-key axis and the
    # block_k-divides-page constraint — entries tuned against the v1
    # slot-only geometry must not apply
    # v3: tensor-parallel serving added a tp_shards shape-key axis (the
    # per-shard head count changes the best block shapes) — v2 entries,
    # keyed without it, must invalidate rather than apply to a mesh
    # shape they were never timed on
    # v4: the slot-contiguous layout (page_size key 0) went; one page a
    # slot is keyed page_size == max_len — v3's slot-layout winners were
    # timed on another fetch and must not apply
    # v5: the chunks became a loop whose trip count follows the longest
    # slot, timed over slots of spread lengths — v4's winners were timed
    # on 16 unrolled chunks of full slots, where a smaller chunk cost no
    # trips
    "decode_attention": 5,
    "fused_adam": 1,
    "fused_sgd": 1,
    "fused_lamb": 1,
    "fused_novograd": 1,
    "fused_adagrad": 1,
}


def code_version(kernel: str) -> int:
    return CODE_VERSIONS.get(kernel, 0)


def default_cache_path() -> Optional[str]:
    """The tune-cache file ``APEX_TPU_TUNE_CACHE`` names, or None."""
    return os.environ.get("APEX_TPU_TUNE_CACHE") or None


def device_key(devices=None) -> str:
    """Stable chip identifier for cache keys: the detected generation
    (``v5e``/``v5p``/``v6e``), else the raw ``device_kind`` slug of a
    TPU not in the peak table, else ``cpu``."""
    from apex_tpu.utils.prof import detect_chip

    if devices is None:
        import jax

        devices = jax.devices()
    if not devices or getattr(devices[0], "platform", None) != "tpu":
        return "cpu"
    try:
        return detect_chip(devices)
    except ValueError:  # a key needs an identity, not published peaks
        kind = str(getattr(devices[0], "device_kind", "tpu"))
        return kind.lower().replace(" ", "-") or "tpu"


def cache_key(kernel: str, shape_key, dtype, device: str,
              version: Optional[int] = None) -> str:
    """Deterministic cache key.

    ``shape_key`` is a tuple of ``(name, value)`` pairs (already bucketed
    by the caller — see ``apex_tpu.tune.api.pow2_bucket``); ``dtype`` any
    jnp dtype / dtype-like / None. The rendering is canonical: pairs are
    sorted by name, values rendered with ``repr`` for ints/bools/strings
    only, so the same inputs produce the same key in every process.
    """
    parts = []
    for name, value in sorted(shape_key):
        if isinstance(value, bool):
            value = int(value)
        if not isinstance(value, (int, str)):
            raise TypeError(
                f"shape_key value for {name!r} must be int/bool/str, got "
                f"{type(value).__name__} (floats and arrays are not "
                f"deterministic key material)")
        parts.append(f"{name}={value}")
    if dtype is None:
        dt = "any"
    else:
        try:  # canonical name for jnp scalar types / np dtypes / strings
            import numpy as np

            dt = np.dtype(dtype).name
        except Exception:
            dt = str(getattr(dtype, "name", dtype))
    ver = code_version(kernel) if version is None else int(version)
    return f"{kernel}|{','.join(parts)}|{dt}|{device}|v{ver}"


class TuneCache:
    """On-disk JSON autotune cache with atomic writes and corrupt-file
    fallback. Thread-safe for the in-process mutation path."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_cache_path()
        if not self.path:
            raise ValueError(
                "no tune cache: set APEX_TPU_TUNE_CACHE (or pass a path)")
        self._lock = threading.Lock()
        self.entries: Dict[str, Dict[str, Any]] = {}
        self.load()

    def load(self) -> "TuneCache":
        """(Re)load entries from disk; corrupt or alien files degrade to an
        empty cache with one structured warning."""
        from apex_tpu.utils.logging import structured_warning

        entries: Dict[str, Dict[str, Any]] = {}
        if os.path.exists(self.path):
            try:
                with open(self.path) as f:
                    doc = json.load(f)
                if not isinstance(doc, dict) or \
                        not isinstance(doc.get("entries"), dict):
                    raise ValueError("not a tune-cache document")
                if doc.get("schema") != SCHEMA_VERSION:
                    raise ValueError(
                        f"schema {doc.get('schema')!r} != {SCHEMA_VERSION}")
                for key, entry in doc["entries"].items():
                    if isinstance(entry, dict) and \
                            isinstance(entry.get("params"), dict):
                        entries[key] = entry
            except (ValueError, OSError) as e:
                structured_warning(
                    "tune_cache_corrupt", path=self.path,
                    error=f"{type(e).__name__}: {e}",
                    action="falling back to heuristic tile choices")
                entries = {}
        with self._lock:
            self.entries = entries
        return self

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self.entries.get(key)

    def put(self, key: str, params: Dict[str, Any],
            meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        entry = {"params": dict(params)}
        if meta:
            entry["meta"] = dict(meta)
        with self._lock:
            self.entries[key] = entry
        return entry

    def save(self) -> str:
        """Atomic write (tmp + rename); creates parent dirs on demand."""
        doc = {"schema": SCHEMA_VERSION, "entries": self.entries}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        tmp = self.path + f".tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        return self.path

    def __len__(self) -> int:
        return len(self.entries)


# process-wide default cache, loaded lazily per path (the env var can move
# it between tests); invalidate() drops it so the next lookup reloads.
_default: Tuple[Optional[str], Optional[TuneCache]] = (None, None)
_default_lock = threading.Lock()


def default_cache() -> Optional[TuneCache]:
    """The cache ``APEX_TPU_TUNE_CACHE`` names, or None when unset."""
    global _default
    path = default_cache_path()
    if path is None:
        return None
    with _default_lock:
        cached_path, cache = _default
        if cache is None or cached_path != path:
            cache = TuneCache(path)
            _default = (path, cache)
        return cache


def invalidate() -> None:
    """Forget the process-wide cache so the next lookup reloads from disk
    (used after ``apex-tpu-tune`` writes, and by tests)."""
    global _default
    with _default_lock:
        _default = (None, None)
