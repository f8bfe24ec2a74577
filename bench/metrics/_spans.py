"""Readings of the serving engine's host spans in the traced window.

`StencilServingEngine` opens `TraceAnnotation` spans on the calling
thread (`engine.run` > `engine.megastep` > `engine.upload`, ...); the
profiler records them on the host plane beside the benchmark's own, on
the device trace's clock. The readers take the spans with no engine span
inside them (`LEAVES`), so a span's duration is its self time. They read
the capture that `harness.window` left under `bench/out/trace/<cell>`,
once per file. A program or a cell without the spans gives no reading:
None, never 0.
"""
from __future__ import annotations

import functools
from pathlib import Path

from bench import harness as H
from bench import trace as TR

LEAVES = ("engine.upload", "engine.device", "engine.download",
          "engine.snapshot", "engine.prime", "engine.crop")


def in_window(events):
    """Each leaf span's (start, end) intervals, clipped to the window."""
    lo, hi = TR.window_of(events)
    spans = {}
    for e in events:
        if e.plane == TR.HOST_PLANE and e.name in LEAVES:
            spans.setdefault(e.name, []).extend(
                TR.clip([(e.start_ns, e.end_ns)], lo, hi))
    return spans


@functools.lru_cache(maxsize=4)
def _load(xplane: str, mtime_ns: int):
    return in_window(TR.load_events(Path(xplane)))


def window_spans(ctx):
    """The leaf spans of the cell's traced window (None if not traced)."""
    if ctx.trace is None:
        return None
    try:
        path = TR.Capture(H.OUT_DIR / "trace" / ctx.cell.name).xplane()
    except FileNotFoundError:
        return None
    return _load(str(path), path.stat().st_mtime_ns)


def phase_ms(spans, name: str, megasteps: float):
    """Milliseconds of span `name` per mega-step; None where no span of
    that name was in the window or no mega-step ran."""
    ivs = (spans or {}).get(name)
    if not ivs or megasteps <= 0:
        return None
    return 1e-6 * sum(e - s for s, e in ivs) / megasteps


def idle_outside(spans, idle):
    """Per cent of the idle gaps `idle` (merged ns intervals) that no leaf
    span covers; None where there are no spans or no idle time."""
    total = TR.length(idle)
    if not spans or total <= 0:
        return None
    covered = TR.union(iv for ivs in spans.values() for iv in ivs)
    return 100.0 * TR.length(TR.subtract(idle, covered)) / total


def _phase(name: str):
    def read(ctx):
        return phase_ms(window_spans(ctx), name,
                        ctx.counters.get("megasteps", 0))
    return read


upload_ms = _phase("engine.upload")
device_ms = _phase("engine.device")
download_ms = _phase("engine.download")
snapshot_ms = _phase("engine.snapshot")
prime_ms = _phase("engine.prime")
crop_ms = _phase("engine.crop")


def idle_outside_spans(ctx):
    """Per cent of chip 0's idle time in the window under no leaf span."""
    spans = window_spans(ctx)
    if not spans:
        return None
    chip0 = next(iter(ctx.trace.devices.values()))
    return idle_outside(spans, chip0.idle)
