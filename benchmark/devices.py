"""The device a run is on: the requirement, the facts, the published peaks.

A measuring run needs the cell's chips as TPUs and fails before it loads
anything otherwise. Tests rehearse on the CPU by monkeypatching
`REQUIRED_PLATFORM`; no option or environment variable reaches it.
"""

from __future__ import annotations

REQUIRED_PLATFORM = "tpu"

# published peaks by `device_kind` (Google Cloud documentation, "TPU v5e":
# 16 GB HBM2e at 819 GB/s, 197 TFLOP/s bf16). A kind that is not here is
# an error, never a default.
_V5E = {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
        "bf16_flops_per_s": 197e12}
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


class NoDevice(Exception):
    pass


def require(chips: int) -> dict:
    """The device as JAX reports it; raises unless it is `chips` TPUs."""
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != REQUIRED_PLATFORM:
        raise NoDevice(f"platform is {dev['platform']!r}, need "
                       f"{REQUIRED_PLATFORM!r}: the benchmark measures "
                       "nothing else")
    if dev["count"] < chips:
        raise NoDevice(f"{dev['count']} device(s), the cell needs {chips}")
    return dev


def versions() -> str:
    import jax
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                          # noqa: BLE001 — report only
        libtpu = "not installed"
    return f"jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu {libtpu}"


def peak(kind: str, what: str) -> float:
    if kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {kind!r}: add it "
                       "to benchmark/devices.py PEAKS with its source")
    return PEAKS[kind][what]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips the cell used."""
    import jax
    worst = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        worst = max(worst, int(stats.get("peak_bytes_in_use", 0)))
    return worst


def on_required_platform(arr) -> bool:
    return all(d.platform == REQUIRED_PLATFORM for d in arr.devices())
