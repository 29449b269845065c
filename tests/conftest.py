"""Test fixtures for mxnet_tpu.

Mirrors the reference's test infra (reference: conftest.py:38+ seed
reporting, tests/python/unittest/common.py with_seed): every test runs with
a reproducible seed that is printed on failure.

Sharding/collective tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) — the TPU-build analogue of
the reference's `--launcher local` fake cluster (SURVEY.md §4).
"""
import os
import random

# Force a virtual 8-device CPU platform so multi-chip sharding paths are
# exercised without TPU hardware; jax reads both variables when it is
# first imported. Set MXNET_TEST_ON_TPU=1 to run the suite against the
# real chip instead.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
if not os.environ.get("MXNET_TEST_ON_TPU"):
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def seed_all(request):
    """Seed python/numpy/mxnet RNGs per test; report the seed on failure
    (reference: conftest.py seeding + common.py:155 with_seed)."""
    seed = int(os.environ.get("MXNET_TEST_SEED",
                              np.random.randint(0, 2**31)))
    random.seed(seed)
    np.random.seed(seed)
    import mxnet_tpu as mx
    mx.ndarray.random.seed(seed)
    yield
    if request.node.rep_call.failed if hasattr(request.node, "rep_call") else False:
        print(f"\nTest failed with MXNET_TEST_SEED={seed} — "
              f"set this env var to reproduce.")


@pytest.hookimpl(tryfirst=True, hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    setattr(item, "rep_" + rep.when, rep)
