"""Test harness: force an 8-device virtual CPU platform BEFORE jax import
so every sharding/mesh test runs without TPU hardware, and apply the
aggressive test settings profile (reference utils/utils.py:39-57)."""

import os

# Must happen before jax is imported: JAX_PLATFORMS is read at import,
# XLA_FLAGS at backend init.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

from tpfl.settings import Settings  # noqa: E402


@pytest.fixture(autouse=True)
def _test_settings():
    snap = Settings.snapshot()
    Settings.set_test_settings()
    yield
    Settings.restore(snap)


@pytest.fixture
def two_partition_mnist():
    """Small synthetic MNIST split in two — shared by node/learner tests."""
    from tpfl.learning.dataset.synthetic import synthetic_mnist
    from tpfl.learning.dataset.partition_strategies import RandomIIDPartitionStrategy

    ds = synthetic_mnist(n_train=400, n_test=100, seed=0)
    return ds.generate_partitions(2, RandomIIDPartitionStrategy, seed=0)
