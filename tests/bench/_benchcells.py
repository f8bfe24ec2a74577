"""The benchmark's cells, cut to sizes a CPU test can run, with the
Pallas kernels in interpret mode. Only the sizes change: each cell keeps
its driver, its traffic's shape, its limits and its check."""
from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness as H  # noqa: E402

SEED = 2 ** 31 + 7               # larger than 32 signed bits hold


def shrink(cell: H.Cell) -> H.Cell:
    """The same cell at sizes a CPU test holds."""
    kind = cell.traffic["driver"]
    if kind == "integration":
        cell.config.update(X=16 * cell.config["mesh"][0], Y=32, Z=8,
                           y_tile=8)
    elif kind == "ensemble_backlog":
        cell.config.update(slot=[16, 32, 8], batch_size=3, y_tile=8,
                           n_steps=2)
        cell.traffic.update(members=4, warmup_calls=1, sample_per_call=4,
                            check_batch=4)
    else:
        raise ValueError(f"no small size for traffic kind {kind!r}")
    return cell


def small_cell(name: str) -> H.Cell:
    """A cell of BENCHMARK.json at sizes a CPU test holds."""
    return shrink(H.load_cell(name))


def run_small(cell: H.Cell, seconds: float = 0.5, *, devices=None,
              control: bool = False, seed: int = SEED) -> H.Outcome:
    """One run of `cell` on the CPU, the look for a chip skipped."""
    import jax
    devices = devices or jax.devices()[:cell.chips]
    return H.driver(cell.traffic["driver"]).run(
        cell, seed, seconds, False, time.perf_counter(), devices=devices,
        control=control)
