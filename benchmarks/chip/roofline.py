"""Peak table and least-bytes arithmetic of the benchmark's problems."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    """The device kind has no row in ``peaks.json``."""


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}")
    return table[device_kind]


def least_bytes(n: int, steps: int, space: int = 2, itemsize: int = 4) -> int:
    """The HBM traffic a time-stepped stencil cannot avoid: each of the
    ``steps`` sweeps reads the ``n**space`` grid once and writes it once."""
    return 2 * n ** space * itemsize * steps


def hbm_roofline_pct(nbytes: float, seconds: float, peak: dict) -> float:
    """Share (%) of the HBM roofline: least time at peak bandwidth over the
    measured device time."""
    return 100.0 * nbytes / (peak["hbm_bytes_per_s"] * seconds)
