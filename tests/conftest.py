"""Test harness: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is not available in CI; collective semantics are
tested on 8 virtual CPU devices (the same XLA collectives, different
interconnect), mirroring the reference's localhost `mpirun -np 2` strategy
(SURVEY §4)."""

import atexit
import os
import shutil
import tempfile

# Must be set before the first backend initialization.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " + _flag).strip()

# The compile cache (horovod_tpu/compile/cache.py) lives where this
# variable says, else in the checkout. A test session writes into neither
# the checkout (the chip tool would copy it with the tree) nor the home
# directory: each pytest process — each xdist worker — gets a temporary
# one, set before jax reads the variable and before hvd.init() arms it,
# and inherited by every child process a test starts.
_cache_dir = tempfile.mkdtemp(prefix="hvd-test-compile-cache-")
os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import horovod_tpu as hvd  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from the tier-1 "
        "'not slow' set")
    config.addinivalue_line(
        "markers", "chaos: fault-injection (chaos) robustness test — "
        "see docs/robustness.md and scripts/chaos_soak.py")
    config.addinivalue_line(
        "markers", "serve: continuous-batching generation engine test "
        "(horovod_tpu/serve/) — see docs/serving.md")


@pytest.fixture(scope="session", autouse=True)
def _hvd_init():
    hvd.init()
    yield


@pytest.fixture()
def mesh():
    return hvd.mesh()
