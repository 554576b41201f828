"""Test config: force an 8-device virtual CPU platform BEFORE jax imports.

Mirrors the reference's testing stance (deterministic in-process multi-"node"
simulation, `ydb/library/actors/testlib/test_runtime.h`): all sharding /
collective paths are exercised on a virtual 8-device mesh in one process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # force: the ambient env may point at a TPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the suite is XLA-compile dominated; the persistent compile cache is
# safe within one host (identical CPU features process-to-process, the
# cross-machine caveat in ydb_tpu/__init__.py doesn't apply) and makes
# warm reruns materially faster. A directory given from outside wins.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache"))

import jax  # noqa: E402

# cache mid-size executables too (default only >1s compiles) — the suite
# compiles hundreds of 0.3-1s programs
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.25)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long soaks excluded from the tier-1 run "
        "(-m 'not slow')")


@pytest.fixture
def rng():
    return np.random.default_rng(1234)
