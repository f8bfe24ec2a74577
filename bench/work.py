"""Work counts and the table of peaks.

The kernel roofline measures the work a client asks for, not the traffic
any one implementation happens to move: every field is read once and
written once per unit of work (one timed call of an integration, one
job in serving), at the published Z, with no lane padding. No
implementation can move less, so a share computed from these bytes can
not pass 100% however the kernel is rewritten. The bound is HBM alone:
the v5e publishes no vector-unit peak, so there is no compute side.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
N_FIELDS = 3          # u, v, w


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of `device_kind`; an unknown kind
    is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def field_bytes(X: int, Y: int, Z: int, itemsize: int = 4) -> int:
    """One field of an (X, Y, Z) domain at the published Z."""
    return X * Y * Z * itemsize


def min_hbm_bytes(X: int, Y: int, Z: int, itemsize: int = 4) -> int:
    """Least HBM traffic of one unit of work on an (X, Y, Z) domain:
    each of the three fields read once and written once."""
    return 2 * N_FIELDS * field_bytes(X, Y, Z, itemsize)


def cell_substeps(X: int, Y: int, Z: int, substeps: int) -> int:
    """Useful cell-updates: every cell of the domain, once per substep."""
    return X * Y * Z * substeps


def least_seconds(hbm_bytes: int, device_kind: str, n_chips: int = 1) -> float:
    """The least time `n_chips` chips could take to move `hbm_bytes`."""
    return hbm_bytes / (peaks(device_kind)["hbm_bytes_per_s"] * n_chips)
