"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's fixed 6-rank MPI test fixture
(reference: test/include/dlaf_test/comm_grids/grids_6_ranks.h:26-60) — we use
8 virtual devices so square-ish (2x4, 4x2), degenerate (1x1, 2x1) and
non-divisible grids are all exercised on one host.  Must set XLA flags before
jax initializes its backends, hence module-level os.environ mutation here.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_ENABLE_X64", "true")
# No persistent XLA compile cache in tests: serializing the largest 8-device
# shard_map executables (distributed D&C) segfaults inside the cache backend
# (observed on both the read and the write path); the suite gains little from
# cross-run persistence and must not die on it.  miniapps/bench keep theirs.
os.environ["DLAF_TPU_COMPILE_CACHE"] = ""

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402

from dlaf_tpu.comm.grid import Grid  # noqa: E402
from dlaf_tpu.common.index import Size2D  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Default tier keeps the suite inside a CI window; the slow tier
    (medium-N pipeline coverage, compile-heavy sweeps) runs with
    DLAF_TPU_RUN_SLOW=1 or -m slow (see .github/workflows/ci.yml)."""
    if os.environ.get("DLAF_TPU_RUN_SLOW") or config.option.markexpr:
        return
    skip = pytest.mark.skip(reason="slow tier: set DLAF_TPU_RUN_SLOW=1 or -m slow")
    for it in items:
        if "slow" in it.keywords:
            it.add_marker(skip)


def _grids():
    """Grid fixture set: analogue of CommGridsEnvironment's {3x2 row-major,
    2x3 col-major, 3x1, 1x2, 1x1} on 6 ranks — here on 8 devices."""
    devs = jax.devices()
    shapes = [(2, 4), (4, 2), (2, 2), (1, 2), (2, 1), (1, 1)]
    return [Grid.create(Size2D(*s), devs) for s in shapes]


@pytest.fixture(scope="session")
def comm_grids():
    return _grids()


@pytest.fixture(scope="session")
def grid_2x4():
    return Grid.create(Size2D(2, 4), jax.devices())


@pytest.fixture(scope="session")
def grid_1x1():
    return Grid.create(Size2D(1, 1), jax.devices()[:1])
