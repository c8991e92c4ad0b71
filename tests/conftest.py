"""Test harness config: force an 8-virtual-device CPU mesh.

Both variables are read at jax's first backend initialisation, which has
not happened yet: the suite runs on the CPU wherever it is started (the
tier-1 command sets JAX_PLATFORMS=cpu itself; a chip host exports
"tpu,cpu").
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# The persistent compilation cache is ON for the suite, through the same
# FLAGS_persistent_compilation_cache default as everywhere, at the fixed
# <checkout>/.jax_cache with jax's default floors (only compiles over a
# second are stored). Warm tier-1 runs on jax 0.9.0 load those entries
# without trouble (PR 21). Do NOT zero the entry floors for the suite: on
# jax 0.4.37 XLA:CPU segfaulted deserializing the shard_map/donated
# TrainStep executables (tests/test_elastic_reshard.py) and nobody has
# retried that on 0.9.0.

import gc  # noqa: E402

import pytest  # noqa: E402

# Every compiled executable pins ~6 mmap'd regions for the life of the
# process. A full single-process tier-1 run accumulates past the kernel's
# vm.max_map_count (65530 default) and XLA's next allocation SEGFAULTS the
# interpreter (reproduced deterministically around tests/test_utils_longtail
# at ~64k regions). Between modules, when the region count nears the limit,
# drop every compiled-executable cache and collect. Only ever fires near the
# ceiling, so cross-module compile reuse is kept until it has to go; clearing
# at a module BOUNDARY cannot perturb in-module trace/retrace-count gates.
_MAP_GUARD_THRESHOLD = 35_000


def _mapped_regions():
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no /proc, and no 65530 ceiling either
        return 0


@pytest.fixture(autouse=True, scope="module")
def _vm_map_guard():
    if _mapped_regions() > _MAP_GUARD_THRESHOLD:
        jax.clear_caches()
        gc.collect()
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running benches excluded from the tier-1 '-m not slow' "
        "gate")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


def _primitives(jaxpr, inside=False):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside
        under = inside or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub, under)


@pytest.fixture(scope="session")
def primitives():
    """``primitives(jaxpr)``: (primitive name, whether it lies under a
    ``cond`` branch) of every equation, nested jaxprs included — what the
    gates on the serving steps' sampling tail walk."""
    return _primitives
