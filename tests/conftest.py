"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4 tier-2 analog: a
deterministic in-process multi-"node" runtime), whatever devices the
machine has: the platform is forced to the CPU here, before any backend
initializes. The chip is covered by chip_smoke.py, run on hardware, and
by the compiles for a described v5e in tests/test_tpu_compile.py.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running out-of-core / subprocess tests")


class Clock:
    """Injectable manual clock shared by coordination-plane tests."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t
