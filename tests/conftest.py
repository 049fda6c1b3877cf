"""Test harness: force an 8-device virtual CPU mesh before jax initialises.

Mirrors the reference's multi-node-without-a-cluster testing approach
(reference: scripts/tests/run-integration-tests.sh runs N processes on
127.0.0.1); here N virtual XLA CPU devices stand in for N TPU chips.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

from testutil import claim_port_window  # noqa: E402

claim_port_window(os.environ)

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process scenario (chaos matrix, ...); "
        "skipped unless KFT_SLOW_TESTS=1 — tier-1 keeps one smoke "
        "member instead")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("KFT_SLOW_TESTS", "") in ("1", "true", "yes"):
        return
    skip = pytest.mark.skip(reason="slow tier (set KFT_SLOW_TESTS=1)")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    ds = jax.devices()
    assert len(ds) >= 8, f"expected 8 virtual devices, got {len(ds)}"
    return ds
