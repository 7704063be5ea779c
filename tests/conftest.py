"""Test harness: force an 8-device CPU platform BEFORE jax initialises.

SURVEY.md §4: the honest JAX analogue of the reference's "localhost PS
cluster" smoke tests is a single-host fake mesh via
``--xla_force_host_platform_device_count``. Everything in tests/ runs on
CPU so the suite is hermetic and fast; TPU-only paths (real Pallas
lowering) are exercised by chip_smoke.py on hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# A plugin may have imported jax before this file ran, in which case the
# env vars above came too late for platform selection; jax.config still
# works, and the CPU client is created lazily so the forced host device
# count applies.
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

assert jax.device_count() == 8, (
    f"expected 8 forced CPU devices, got {jax.devices()}")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True, scope="module")
def _collect_between_modules():
    """A batch_iterator that died inside a traceback (an injected OOM,
    a capped validation sweep) keeps its fm-build pool until the cycle
    collector runs. Collect at each module boundary so one module's
    crash-path test cannot leak live threads into another module's
    no-thread-leak assertions (seen once in three full runs, PR 21)."""
    import gc
    gc.collect()
    yield
