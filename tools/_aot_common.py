"""Shared scaffolding for the deviceless AOT tools (mosaic/model/stack).

Importing this module (BEFORE anything else imports jax) puts the
process into compile-only mode: kernels lower via Mosaic rather than
interpret (APEX_TPU_FORCE_COMPILED), libtpu's host probing is quieted,
the host backend is pinned to CPU (the jit target is a compile-only
topology client, never an attached device), and the persistent compile
cache is enabled so artifact refreshes skip recompilation. One copy of
this setup — the three tools were drifting.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# kernels must pick the compiled (Mosaic) lowering even though the
# default backend is CPU — see apex_tpu/utils/env.py:interpret_default
os.environ["APEX_TPU_FORCE_COMPILED"] = "1"
# quiet libtpu's host-metadata probing (no real TPU VM here)
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # compile-only: no device used

from apex_tpu.utils.env import enable_compile_cache  # noqa: E402

enable_compile_cache()  # deviceless AOT compiles are cache-keyed

from bench import atomic_write_json  # noqa: E402,F401


def get_topology(default: str = "v5e:2x2"):
    """The compile-only topology (MOSAIC_AOT_TOPOLOGY overrides)."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        os.environ.get("MOSAIC_AOT_TOPOLOGY", default), "tpu")
