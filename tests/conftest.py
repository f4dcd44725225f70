"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's strategy of testing multi-device topologies on
CPU-only machines (tests/python/unittest/test_multi_device_exec.py uses
mx.cpu(0..3)); here XLA's host-platform device-count flag provides 8
virtual devices so mesh/sharding/collective paths are exercised without
TPU hardware (SURVEY.md §4.3).

The cpu platform is forced via jax.config *before any backend
initializes*, so the suite runs the same on a machine with a chip (only
tests/test_tpu_consistency.py, gated by MXTPU_TPU_TESTS=1, reaches for
one — from a child process, one at a time).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
