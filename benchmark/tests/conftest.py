"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Tier-1's command collects `tests/` only, so these are not in its count.
The platform requirement is relaxed here by monkeypatching
`devices.REQUIRED_PLATFORM`, the way `chip_smoke.REQUIRED_PLATFORM` is in
the repo's own tests; no option or variable that ships reaches it.
"""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402

SMALL = {"sf": 0.01}


@pytest.fixture
def on_cpu(monkeypatch):
    import devices
    monkeypatch.setattr(devices, "REQUIRED_PLATFORM", "cpu")
