"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy tier 2 (SURVEY.md §4):
LocalQueryRunner-style in-process tests, multi-"node" via
xla_force_host_platform_device_count instead of real chips.

The suite is pinned to the CPU backend here, before the first backend
initialization, so it never reaches for a chip whatever the shell's
JAX_PLATFORMS says. The chip is exercised by ``chip_smoke.py`` and, for
compiles only, by ``tests/test_chip_compile.py``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import trino_tpu  # noqa: E402,F401  (enables x64)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: benchmark-grade tests excluded from the tier-1 run")
