"""The chip a run measures: its peaks (bench/peaks.json, keyed by the
``device_kind`` JAX reports), the refusal of anything that is not an
accelerator, and the device block of the result line."""

from __future__ import annotations

import json
from typing import Any, Dict

from harness.spec import BENCH


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of `device_kind`. A kind that the table
    does not hold is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def accelerators(chips: int):
    """The first `chips` devices, which must be accelerators: a CPU,
    or fewer chips than asked for, raises NoAccelerator."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoAccelerator(f"JAX found no accelerator, only "
                            f"{devs[0].device_kind}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips; JAX found "
                            f"{len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend
    reports none)."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def describe(devs, memory_peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": memory_peak_bytes}
