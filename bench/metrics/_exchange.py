"""Readings of the halo exchange's remote-DMA kernels in the traced window.

`stencil.distributed` runs each block's two-phase exchange as Mosaic
kernels named `halo_band_exchange_dma_x` and `halo_band_exchange_dma_y`
(earlier programs name both phases `halo_band_exchange_dma`); a device
trace holds each run of one as an `XLA Ops` event whose HLO text leads
with that name. The readers load the capture that `harness.window` left
under `bench/out/trace/<cell>`, once per file, and sum each chip's DMA
kernel time clipped to the window. A run that was not traced, ran no
block or ran no such kernel (a 1x1 mesh exchanges nothing) gives no
reading: None, never 0.
"""
from __future__ import annotations

import functools
from pathlib import Path

from bench import exchange_work as EW
from bench import harness as H
from bench import trace as TR

PREFIX = "halo_band_exchange_dma"


def dma_seconds(events, devices):
    """Seconds of DMA kernel time in the window on each chip of
    `devices`, by device id."""
    lo, hi = TR.window_of(events)
    per = {d: 0.0 for d in devices}
    planes = {f"/device:TPU:{d}": d for d in devices}
    for e in events:
        d = planes.get(e.plane)
        if (d is not None and e.line == TR.OPS_LINE
                and TR.op_name(e.name).startswith(PREFIX)):
            per[d] += TR.length(TR.clip([(e.start_ns, e.end_ns)], lo, hi))
    return {d: ns * 1e-9 for d, ns in per.items()}


@functools.lru_cache(maxsize=4)
def _load(xplane: str, mtime_ns: int, devices):
    return dma_seconds(TR.load_events(Path(xplane)), devices)


def window_dma_seconds(ctx):
    """DMA kernel seconds per chip in the cell's traced window; None if
    not traced, no block ran, or no DMA kernel ran."""
    if ctx.trace is None or ctx.counters.get("blocks", 0) <= 0:
        return None
    try:
        path = TR.Capture(H.OUT_DIR / "trace" / ctx.cell.name).xplane()
    except FileNotFoundError:
        return None
    per = _load(str(path), path.stat().st_mtime_ns,
                tuple(ctx.trace.devices))
    return per if sum(per.values()) > 0 else None


def exchange_dma_ms(ctx):
    """Milliseconds of DMA kernel time a block, averaged over the chips."""
    per = window_dma_seconds(ctx)
    if per is None:
        return None
    return 1e3 * sum(per.values()) / len(per) / ctx.counters["blocks"]


def exchange_dma_roofline(ctx):
    """Per cent of the DMA kernels' device time, summed over the chips,
    that moving the window's least exchange bytes at one chip's
    interconnect peak would take (`bench/exchange_work.py`)."""
    per = window_dma_seconds(ctx)
    if per is None:
        return None
    cfg = ctx.cell.config
    work = ctx.counters["blocks"] * EW.least_exchange_bytes(
        cfg["X"], cfg["Y"], cfg["Z"], cfg["mesh"], cfg["T"])
    return 100.0 * EW.least_ici_seconds(work, ctx.device_kind) / sum(
        per.values())
