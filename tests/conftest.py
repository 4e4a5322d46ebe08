"""Test harness config.

Forces JAX onto the CPU backend with 8 virtual devices BEFORE jax is
imported anywhere, so SPMD/sharding tests get real 8-device semantics
without TPU hardware (SURVEY.md §4.2 note: this beats the reference's
`local[n]` SparkContext trick because the collectives actually run).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# keep in-process template trains from writing bucket caches into the
# real ~/.pio_tpu; cache-specific tests re-enable it in subprocess envs
os.environ["PIO_BUCKET_CACHE"] = "0"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def memory_storage():
    """A fresh in-memory Storage wired as the process singleton."""
    from predictionio_tpu.storage.registry import SourceConfig, Storage, StorageConfig

    src = SourceConfig(name="TEST", type="memory")
    storage = Storage(StorageConfig(metadata=src, modeldata=src, eventdata=src))
    Storage.reset(storage)
    yield storage
    storage.close()
    Storage.reset(None)
