"""Test environment: an 8-device virtual CPU mesh.

Multi-chip sharding paths are tested on virtual CPU devices; the chip is
driven by chip_smoke.py (and timed by bench.py) only, never by the tests.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)

import numpy as np
import pytest


# --------------------------------------------------------------------------
# VM-mapping pressure guard.
#
# Every XLA:CPU compile mmaps JIT code regions that stay mapped for the
# executable's lifetime. One full tier-1 run compiles thousands of distinct
# programs in ONE process, and the kernel caps a process's mappings at
# vm.max_map_count (65530 default). At the cliff the next mmap inside
# LLVM's JIT fails and XLA SEGFAULTS (observed deterministically at ~65.5k
# maps, two-thirds through the suite) instead of raising. jax.clear_caches()
# drops compiled executables (and their mappings); later tests simply
# recompile. Clearing is keyed on MEASURED pressure, not a test count, so
# small runs never pay a recompile and full runs stay far from the cliff.
# --------------------------------------------------------------------------

_MAPS_CHECK_EVERY = 20  # tests between /proc/self/maps size probes
_MAPS_SOFT_LIMIT = 40_000  # clear compiled-program caches beyond this
_tests_done = 0


def pytest_runtest_teardown(item, nextitem):
    global _tests_done
    _tests_done += 1
    if _tests_done % _MAPS_CHECK_EVERY:
        return
    try:
        with open("/proc/self/maps", "rb") as f:
            n_maps = sum(1 for _ in f)
    except OSError:  # non-Linux: no map cap to defend against
        return
    if n_maps >= _MAPS_SOFT_LIMIT:
        jax.clear_caches()


@pytest.fixture
def rng():
    return np.random.default_rng(42)
