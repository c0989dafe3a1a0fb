"""Test conftest: force an 8-device CPU mesh before jax initializes.

Mirrors the reference's device-backend test strategy (survey §4): CPU-parity
op tests + multi-device tests on a virtual mesh without real chips.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# tests leave the persistent compilation cache off, here and in every
# process they start (paddle_tpu/_compile_cache.py turns it on otherwise)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(102)
    np.random.seed(102)
    yield


#: the upstream source tree whose ``__all__`` lists the API-parity gates
#: read; not part of this repository, and absent from some sandboxes
REFERENCE_TREE = "/root/reference"


def pytest_collection_modifyitems(config, items):
    """``@pytest.mark.needs_reference``: skip, with the reason, where the
    reference tree is absent — such a test can only fail on the missing
    file, and a run that cannot end rc 0 hides a real failure."""
    if os.path.isdir(REFERENCE_TREE):
        return
    skip = pytest.mark.skip(
        reason=f"reads the reference tree, and {REFERENCE_TREE} is absent")
    for item in items:
        if "needs_reference" in item.keywords:
            item.add_marker(skip)
