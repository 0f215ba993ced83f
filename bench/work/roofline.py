"""Least time of a piece of work at the chip's peaks, and shares of it.

Work is ``{"ops": {op_class: count}, "bytes": n}``. Each operation class runs
at its own peak (``peaks.json``: a bf16 x ±1 matmul at the bf16 peak, a
±1 x ±1 XNOR dot at the int8 peak), so the compute time is the sum over
classes; the least time is the larger of that and bytes over HBM bandwidth.
"""
from __future__ import annotations

import json
import os
from typing import Optional

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def load_peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device the table lacks is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path} "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def work(ops: Optional[dict] = None, nbytes: float = 0.0) -> dict:
    return {"ops": dict(ops or {}), "bytes": float(nbytes)}


def add(*works: dict) -> dict:
    out = work()
    for w in works:
        for c, v in w["ops"].items():
            out["ops"][c] = out["ops"].get(c, 0.0) + v
        out["bytes"] += w["bytes"]
    return out


def least_seconds(w: dict, peaks: dict) -> tuple[float, str]:
    """(least seconds, "compute" | "memory")."""
    compute = sum(v / peaks["ops_per_s"][c] for c, v in w["ops"].items())
    memory = w["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def share_percent(least_s: float, device_s: float) -> Optional[float]:
    """Least time over measured device time, in percent; None when nothing
    was measured (a share is never reported as 0 for want of a reading)."""
    if device_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / device_s
