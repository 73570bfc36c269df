"""The chip: refusal of any other platform, the table of peaks, and what
the result line says about the device."""
from __future__ import annotations

import json
import os

from bench.harness.manifest import BENCH_DIR

PEAKS = os.path.join(BENCH_DIR, "peaks.json")


def require_tpu(n_chips: int):
    """The first ``n_chips`` TPU devices, or SystemExit naming what JAX
    found: the benchmark measures nothing on another platform."""
    import jax
    devices = jax.devices()
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"bench: no TPU; JAX found platform {platform!r} "
                         f"({len(devices)} x {devices[0].device_kind})")
    if len(devices) < n_chips:
        raise SystemExit(f"bench: the cell needs {n_chips} TPU chips, "
                         f"JAX found {len(devices)}")
    return devices[:n_chips]


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """Published peaks of one chip; a kind missing from the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{os.path.basename(path)}; known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
