"""The chip a run stands on: the refusal without one, the peak table,
and the device record of the result line."""
from __future__ import annotations

import json
import os

from harness.spec import BENCH


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks."""


def require_chips(n: int):
    """The first ``n`` TPU devices; `NoChip` on any other platform or
    with fewer devices.  Import JAX only after the caller has placed
    the compilation cache."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {platform!r}, "
                     f"{len(devs)} device(s)); the benchmark measures "
                     f"the chip and has no CPU fallback")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def peaks(device_kind: str, path: str = None) -> dict:
    """The published peaks of ``device_kind`` (`peaks.json`); an unknown
    kind is an error, never a default."""
    path = path or os.path.join(BENCH, "peaks.json")
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(path)}; known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}
