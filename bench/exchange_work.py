"""The halo exchange's least work, and the table of interconnect peaks.

Like the kernel roofline's (`bench/work.py`), the exchange's bytes are
the client's, not what one implementation sends: across each internal
face of the mesh, each side needs the other's depth-T band of every
field, at the published Z and no lane padding. The global walls need no
band. The few columns a chip needs from its diagonal neighbours are left
out too, so the count stays below what any implementation must move,
and a share computed from these bytes cannot pass 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

ICI_PEAKS_FILE = Path(__file__).resolve().parent / "ici_peaks.json"
N_FIELDS = 3          # u, v, w


def ici_peaks(device_kind: str) -> dict:
    """The published interconnect peak of one chip of `device_kind`; an
    unknown kind is an error, never a default."""
    table = json.loads(ICI_PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no ICI peak for device kind {device_kind!r} in "
                       f"{ICI_PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def least_exchange_bytes(X: int, Y: int, Z: int, mesh, T: int,
                         itemsize: int = 4) -> int:
    """Least bytes of one block's depth-`T` halo exchange of an (X, Y, Z)
    grid split over an (nx, ny) `mesh`, over the whole mesh: both
    directions across every internal face, every field."""
    nx, ny = mesh
    planes = (nx - 1) * Y + (ny - 1) * X      # cells of a face, summed
    return 2 * T * planes * Z * N_FIELDS * itemsize


def least_ici_seconds(n_bytes: int, device_kind: str) -> float:
    """The least time one chip's links could take to move `n_bytes`."""
    return n_bytes / ici_peaks(device_kind)["ici_bytes_per_s"]
