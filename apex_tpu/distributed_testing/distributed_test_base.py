"""Distributed test harness — TPU equivalent of
``apex/distributed_testing/distributed_test_base.py:24-131``.

The reference spawns one process per GPU (``MultiProcessTestCase``, world =
min(gpus, 4), file:// rendezvous, NCCL/UCC backends). On TPU a single process
drives all local devices, so the harness provides a mesh + shard_map context
instead of process spawning — and a CPU fallback mesh via
``xla_force_host_platform_device_count`` gives multi-"device" tests without
hardware, the fixture apex lacks (SURVEY §4).
"""

from __future__ import annotations

import functools
import unittest
from typing import Optional, Sequence

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.mesh import make_mesh


class DistributedTestBase(unittest.TestCase):
    """Subclass and use ``self.mesh`` / ``self.run_on_mesh``.

    ``world_size`` defaults to min(device_count, 8) — the analog of the
    reference's ``min(cuda.device_count(), 4)`` (:38-39).
    """

    axis_name = "data"
    max_world = 8

    @property
    def world_size(self) -> int:
        return min(jax.device_count(), self.max_world)

    @functools.cached_property
    def mesh(self) -> Mesh:
        return make_mesh([self.world_size], [self.axis_name])

    def run_on_mesh(self, fn, args, in_specs, out_specs):
        """shard_map + jit the per-device fn over the harness mesh."""
        f = shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
        return jax.jit(f)(*args)

    def skip_if_fewer_than(self, n: int):
        if jax.device_count() < n:
            self.skipTest(f"needs {n} devices, have {jax.device_count()}")


class NcclDistributedTestBase(DistributedTestBase):
    """Name-parity alias (:86): the TPU 'backend' is XLA-over-ICI."""


class UccDistributedTestBase(DistributedTestBase):
    """Name-parity alias (:99-131): no separate transport exists on TPU."""
