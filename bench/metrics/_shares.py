"""Readings shared by several per-layer metrics, from a reduced trace."""
from __future__ import annotations

from bench import work as W


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the
    device, averaged over the chips used."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * ctx.trace.idle_share


def roofline_share(ctx):
    """Per cent of the Mosaic kernels' device time that moving the
    window's least HBM traffic at the chip's peak would take. There is
    nothing to read where no kernel ran or no work was counted."""
    if ctx.trace is None:
        return None
    kernel_s = ctx.trace.kernel_s                  # summed over the chips
    work = ctx.counters.get("work_bytes", 0)
    if kernel_s <= 0 or work <= 0:
        return None
    return 100.0 * W.least_seconds(work, ctx.device_kind) / kernel_s
