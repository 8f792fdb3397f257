"""Test fixtures: force an 8-device virtual CPU platform BEFORE jax
import so every test can exercise real mesh shardings without TPU
hardware (the driver's dryrun does the same trick)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("HOROVOD_LOG_LEVEL", "warning")

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# A compile cache of the tests' own, set before jax is imported and
# inherited by every process a test spawns: the entry points' CPU
# programs (use_compile_cache() honours the variable) stay out of
# <checkout>/.jax_cache, which the chip runs fill and read.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    _ROOT, ".jax_cache_tests")
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import pytest  # noqa: E402

# Belt and braces with the env var above: tier-1 never touches a chip.
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'`; register the marker so long-running
    # benchmarks (e.g. the serve mixed-trace comparison) can opt out
    # without tripping --strict-markers or unknown-marker warnings.
    config.addinivalue_line(
        "markers",
        "slow: long-running benchmark/soak tests excluded from tier-1")


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    from horovod_tpu.parallel import build_mesh
    return build_mesh(dp=8)


@pytest.fixture()
def mesh2x4(devices):
    from horovod_tpu.parallel import build_mesh
    return build_mesh(dp=2, tp=4)
