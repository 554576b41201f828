"""Virtual-mesh self-provisioning for CPU proxies of multi-chip runs.

A CPU leg that needs N devices (`scripts/ici_gate.py`,
`bench.py --multichip`) re-executes itself in a subprocess with an
N-device virtual CPU platform. The flag merge lives HERE once: the
child runs with `JAX_PLATFORMS=cpu` and adds
`--xla_force_host_platform_device_count=N` without clobbering any
XLA_FLAGS the operator already set. Four real chips are driven in one
process instead (`chip_smoke.py --chips 4`).
"""

from __future__ import annotations

import os


def virtual_mesh_env(ndev: int, base: dict = None) -> dict:
    """Environment for a subprocess that must see an `ndev`-device
    virtual CPU mesh. Existing XLA_FLAGS are preserved; an explicit
    device-count flag already present wins."""
    env = dict(os.environ if base is None else base)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={ndev}"
        ).strip()
    return env
