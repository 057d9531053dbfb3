"""Test harness: run everything on a virtual 8-device CPU mesh.

TPU translation of the reference's multi-process harness
(``apex/distributed_testing/distributed_test_base.py:24-83`` spawns one process
per GPU); here multi-device = 8 virtual CPU devices via XLA_FLAGS, with Pallas
kernels in interpret mode (SURVEY §4 "TPU translation").

Tier-1 never touches a chip: the platform is pinned to the CPU through
jax.config before any backend initializes, whatever the environment says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Tier-1 neither reads nor writes a persistent compile cache, in this
# process or in the CLI subprocesses it starts: the entry points under test
# call enable_compile_cache(), which would otherwise fill
# <checkout>/.jax_cache and hand later tests reloaded XLA:CPU executables.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)


import pytest  # noqa: E402


def pytest_configure(config):
    # fault-injection suite (tests/test_resilience.py): deterministic,
    # CPU-only, fast — runs in tier-1; select alone with `-m fault`
    config.addinivalue_line(
        "markers",
        "fault: deterministic fault-injection resilience tests "
        "(fast, CPU-only, tier-1)")


@pytest.fixture(scope="session")
def tp_devices():
    """The multi-device CPU guarantee for sharded (tensor-parallel)
    tier-1: the early-env XLA_FLAGS hook at the top of this file — set
    BEFORE jax's backend initializes, the ``ThreadProcessGroup``
    fake-multihost precedent — forces an 8-device CPU host, so a
    ``tp=2`` serving mesh is always buildable and sharded tests never
    depend on real chips. Session-scoped and ASSERTING (not skipping):
    if the device pool ever shrinks below 2, the tensor-parallel
    acceptance suite must fail loudly, not silently vanish from tier-1.
    Returns the first two devices (the tp=2 mesh pool)."""
    devs = jax.devices()
    assert len(devs) >= 2, (
        f"the conftest xla_force_host_platform_device_count hook must "
        f"provide >= 2 CPU devices for the tp=2 mesh, got {len(devs)} — "
        f"was XLA initialized before this conftest imported?")
    return devs[:2]
