"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Real-TPU execution is exercised by chip_smoke.py (the served path) and
bench.py (kernels); the test suite must be runnable anywhere, with
enough virtual devices to test the multi-chip sharding paths (SURVEY.md
section 7).  Pinning the CPU here is also the explicit opt-in that lets
the tests build ``backend="tpu"``/``"mesh"`` codecs without a chip
(ops/device.py): the device code paths run, on XLA:CPU.

jax.config.update pins the platform in-process as well, so a pytest
started without the environment variable behaves the same.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running subprocess tests (memory bounds, "
        "cluster harnesses)")


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _release_hot_read_caches():
    """Hot-read plane isolation: cached windows hold memory-governor
    charges (kind="cache") for as long as their layer lives, and many
    suites keep layers alive past their test (module fixtures, GC
    cycles).  Releasing every plane's cache after each test keeps the
    strict governor-settles-to-zero assertions sound without each
    suite knowing the plane exists."""
    yield
    from minio_tpu.objectlayer import hotread
    hotread.clear_all_planes()
