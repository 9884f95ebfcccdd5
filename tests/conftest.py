"""Shared test configuration: CPU JAX, deterministic seeds, markers.

The tests run on the CPU, where the ``ops`` wrappers run the Pallas
kernels in interpret mode; pinning the platform here gives every test
module those defaults without per-file boilerplate (and keeps a host that
ships libtpu from probing for a chip).  What the chip's compiler accepts
is checked in ``tests/test_tpu_compile.py``; runs on the chip go through
``chip_smoke.py``.
"""

import os

import numpy as np
import pytest

# pin the platform before jax initializes any backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (deselect with -m 'not slow')")


@pytest.fixture(autouse=True)
def _deterministic_numpy_seed():
    """Reset the legacy numpy global RNG per test for reproducibility."""
    np.random.seed(0)
    yield


@pytest.fixture(autouse=True, scope="module")
def _drop_jit_caches_between_modules():
    """Release compiled executables when a test module finishes.

    The suite compiles hundreds of interpret-mode kernel programs; the jit
    caches keep every executable alive for the whole run, and on the CPU
    backend that accumulation eventually segfaults XLA's backend_compile on
    a later large program (deterministically ~320 tests in).  Per-module
    cache drops bound the live set; within-module caching (the no-retrace
    and single-pack-event tests) is unaffected.
    """
    yield
    jax.clear_caches()
