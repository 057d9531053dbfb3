"""Backend detection helpers.

Pallas TPU kernels run compiled on TPU and in interpreter mode everywhere else
(CPU test meshes, ``xla_force_host_platform_device_count`` virtual devices).
"""

import functools
import os

import jax


@functools.lru_cache(maxsize=None)
def platform_is_tpu() -> bool:
    """Whether the default backend is a TPU. A backend that cannot
    initialize (missing or busy chip under a pinned platform) RAISES
    here: answering False would silently turn every Pallas call into
    interpret mode on a machine that was meant to have a chip."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Whether pallas_call should run in interpret mode (True off-TPU).

    ``APEX_TPU_FORCE_COMPILED=1`` forces the compiled (Mosaic) lowering even
    when the default backend is CPU — used by tools/mosaic_aot.py to AOT-
    compile the kernel zoo against a deviceless TPU topology
    (jax.experimental.topologies), where the host backend is CPU but the
    jit target is a compile-only v5e client."""
    if os.environ.get("APEX_TPU_FORCE_COMPILED") == "1":
        return False
    return not platform_is_tpu()


def device_block() -> dict:
    """What ran this process, as JAX reports it: the block every CLI's
    final record and every bench capture carries, so a run that fell
    onto the CPU can never be read as a chip run (``device_kind`` is the
    identity check_regression's device gate compares). Raises when no
    backend is reachable: a record that cannot name its device must not
    be written."""
    devs = jax.devices()
    return {"platform": str(devs[0].platform),
            "device_kind": str(devs[0].device_kind),
            "device_count": len(devs),
            "interpret_mode": bool(interpret_default())}


def repo_root() -> str:
    """The checkout (or install) directory that holds ``apex_tpu/``."""
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ONE fixed place and
    return it — every entry point calls this before its first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    nothing is touched; otherwise the cache is ``<checkout>/.jax_cache``
    (git-ignored). The path is part of the cache key, so it carries no
    temp name, pid or time: a directory that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(repo_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def git_sha(cwd: str = None) -> str:
    """Short git sha of ``cwd`` (default: this repo checkout), or
    "unknown" (wheel installs have no .git)."""
    import subprocess

    if cwd is None:
        cwd = repo_root()
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=cwd,
            capture_output=True, text=True, timeout=10
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def capture_provenance() -> dict:
    """:func:`device_block` / git sha / timestamp — the
    stamp every bench capture carries so ``tools/check_regression.py``
    can refuse to gate a CPU-smoke/interpret capture against real-chip
    numbers (one builder; bench.py and apex-tpu-bench both use it)."""
    import time

    return {**device_block(),
            "git": git_sha(),
            "captured": time.strftime("%Y-%m-%dT%H:%M:%S")}
