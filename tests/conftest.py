"""Test configuration: run the whole suite on a virtual 8-device CPU mesh.

Mirrors the reference's "real runtime, fake scale" test philosophy
(SURVEY.md §4: launcher-local multi-process tests): JAX host-platform
device multiplexing stands in for a TPU pod slice, so sharding/collective
paths execute for real without TPU hardware.
"""
import os

# Force CPU with 8 virtual devices, whatever platform the environment
# names: set the env var for child processes and jax.config for this one,
# before any backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _fixed_seed():
    """Reference pattern: tests/python/unittest/common.py with_seed()."""
    import mxnet_tpu as mx
    seed = int(os.environ.get("MXNET_TEST_SEED", "42"))
    mx.random.seed(seed)
    np.random.seed(seed)
    yield


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    """Under MXNET_ENGINE_SANITIZE=1 every test asserts at teardown
    that no framework thread (engine.make_thread) survived its owner's
    stop — the runtime twin of mxlint's thread-lifecycle pass.  Zero
    cost when the sanitizer is off (the tier-1 default): both calls
    are no-ops behind the module-level _SANITIZE bool."""
    from mxnet_tpu import engine
    yield
    engine.check_thread_leaks()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 `-m 'not slow'` budget run "
        "(ROADMAP.md); the full suite still runs them")
