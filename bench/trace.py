"""Device traces: capture one window with the JAX profiler, and reduce it.

The reduction works on a flat list of `Event`s, so it can be checked on
a small recorded or synthetic trace. What a TPU trace holds, as read by
hand from a v5e trace of this benchmark (`bench/inspect_trace.py`):

- one plane per chip, named ``/device:TPU:<n>``; its line ``XLA Ops``
  holds one event per HLO operation run, named by the instruction's whole
  HLO text (``%copy.16 = f32[7,64,256,64]{...} copy(...)``); the line
  ``Async XLA Ops`` holds the asynchronous copies that overlap them, and
  ``XLA Modules`` one event per program run;
- the host plane ``/host:CPU`` holds, on the Python thread's line, the
  benchmark's own `TraceAnnotation` spans (`SPANS`), on the same clock.

A Mosaic kernel shows up as an HLO ``custom-call`` whose text names the
``tpu_custom_call`` target. Kernels carry no stable names yet, so the
reducer matches that, and every Mosaic kernel of a program counts.
Busy time is the union of the ``XLA Ops`` events, leaving out control
flow (a ``while`` event spans its body's events and the gaps between).
"""
from __future__ import annotations

import dataclasses
import re
import shutil
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW = "window"
# the benchmark's own host spans, by what the host is doing in them
SPANS = ("submit", "wait", "run() call", "generator")
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("collective-permute", "all-reduce", "all-gather",
               "all-to-all", "reduce-scatter", "send", "recv")
CONTROL_FLOW = ("while", "conditional", "call")
_HLO = re.compile(r"^%?(?P<name>[^ ]+) = ")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events
    opts.host_tracer_level = 2        # keeps TraceAnnotation spans
    return opts


class Capture:
    """Trace what runs inside the `with` block into `out_dir` (emptied
    first); `events()` reads it back afterwards."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)

    def __enter__(self):
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True)
        jax.profiler.start_trace(str(self.out_dir),
                                 profiler_options=profiler_options())
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()
        return False

    def xplane(self) -> Path:
        found = sorted(self.out_dir.glob("plugins/profile/*/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return found[-1]

    def events(self) -> List[Event]:
        return load_events(self.xplane())


def load_events(path: Path) -> List[Event]:
    """Every event of a recorded trace, device and host planes."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in data.planes:
        if not (DEVICE_PLANE.match(plane.name) or plane.name == HOST_PLANE):
            continue
        for line in plane.lines:
            out.extend(Event(plane.name, line.name, ev.name,
                             float(ev.start_ns), float(ev.duration_ns))
                       for ev in line.events)
    return out


# -- interval arithmetic -------------------------------------------------
def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def length(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b) -> List[Tuple[float, float]]:
    """The parts of merged intervals `a` not covered by merged `b`."""
    out = []
    for s, e in a:
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    return subtract([(lo, hi)], busy)


# -- HLO event names ------------------------------------------------------
def _skip_shape(text: str, i: int) -> int:
    """Index just past the result shape that starts at `text[i]`."""
    if text.startswith("(", i):
        depth = 0
        for j in range(i, len(text)):
            depth += {"(": 1, ")": -1}.get(text[j], 0)
            if depth == 0:
                return j + 1
        return len(text)
    j = text.find(" ", i)
    return len(text) if j < 0 else j


def op_name(text: str) -> str:
    """The instruction's name from an event's HLO text."""
    m = _HLO.match(text)
    return m.group("name") if m else text


def opcode(text: str) -> str:
    """The instruction's opcode from an event's HLO text ('' if none)."""
    m = _HLO.match(text)
    if not m:
        return ""
    i = _skip_shape(text, m.end())
    j = text.find("(", i)
    return text[i:j].strip() if j > i else ""


def is_kernel(ev: Event) -> bool:
    return opcode(ev.name) == "custom-call" and KERNEL_TARGET in ev.name


def is_collective(ev: Event) -> bool:
    op = opcode(ev.name)
    return any(op == c or op.startswith(c + "-") for c in COLLECTIVES)


def label(ev: Event) -> str:
    """A short name for an event: instruction name and opcode, with the
    kernel target for a Mosaic kernel."""
    op = opcode(ev.name)
    if not op:
        return ev.name[:80]
    kind = "tpu_custom_call" if is_kernel(ev) else op
    return f"{op_name(ev.name)} ({kind})"


# -- the reduction -------------------------------------------------------


@dataclasses.dataclass
class DeviceTime:
    busy_s: float
    kernel_s: float
    collective_s: float
    exposed_collective_s: float
    idle: List[Tuple[float, float]]        # gaps, ns, in the window


@dataclasses.dataclass
class Summary:
    """What a traced window shows, per chip and over the chips used."""
    window_s: float
    devices: Dict[int, DeviceTime]
    top_ops: List[Tuple[str, float]]       # per-chip mean seconds, by name
    idle_by_span: List[Tuple[str, float]]  # chip 0's idle, by host span

    @property
    def busy_s(self) -> float:
        return sum(d.busy_s for d in self.devices.values()) / len(self.devices)

    @property
    def kernel_s(self) -> float:
        """Summed over the chips used."""
        return sum(d.kernel_s for d in self.devices.values())

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    spans = [e for e in events if e.plane == HOST_PLANE and e.name == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span in the trace, "
                         f"found {len(spans)}")
    return spans[0].start_ns, spans[0].end_ns


def reduce(events: Sequence[Event], devices: Sequence[int]) -> Summary:
    """Reduce a traced window to device time on the chips `devices`."""
    lo, hi = window_of(events)
    per_dev: Dict[int, DeviceTime] = {}
    op_time: Dict[str, float] = {}
    for d in devices:
        plane = f"/device:TPU:{d}"
        # a while loop's event spans its body's; the body's ops count
        ops = [e for e in events if e.plane == plane and e.line == OPS_LINE
               and opcode(e.name) not in CONTROL_FLOW]
        if not ops:
            raise ValueError(f"no {OPS_LINE!r} events on {plane}")
        busy = clip(union((e.start_ns, e.end_ns) for e in ops), lo, hi)
        coll = clip(union((e.start_ns, e.end_ns) for e in ops
                          if is_collective(e)), lo, hi)
        compute = clip(union((e.start_ns, e.end_ns) for e in ops
                             if not is_collective(e)), lo, hi)
        kernel_ns = sum(length(clip([(e.start_ns, e.end_ns)], lo, hi))
                        for e in ops if is_kernel(e))
        for e in ops:
            ns = length(clip([(e.start_ns, e.end_ns)], lo, hi))
            op_time[label(e)] = op_time.get(label(e), 0.0) + ns
        per_dev[d] = DeviceTime(
            busy_s=length(busy) * 1e-9, kernel_s=kernel_ns * 1e-9,
            collective_s=length(coll) * 1e-9,
            exposed_collective_s=length(subtract(coll, compute)) * 1e-9,
            idle=gaps(busy, lo, hi))
    top = sorted(((n, t * 1e-9 / len(devices)) for n, t in op_time.items()),
                 key=lambda kv: -kv[1])[:10]
    return Summary(window_s=(hi - lo) * 1e-9, devices=per_dev, top_ops=top,
                   idle_by_span=attribute(per_dev[devices[0]].idle, events))


def attribute(idle, events: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each idle gap goes to the host span that overlaps it most ("no
    span" where none does); returns seconds of idle per span name."""
    spans = [e for e in events if e.plane == HOST_PLANE and e.name in SPANS]
    total: Dict[str, float] = {}
    for s, e in idle:
        best, best_ns = "no span", 0.0
        for sp in spans:
            ov = min(e, sp.end_ns) - max(s, sp.start_ns)
            if ov > best_ns:
                best, best_ns = sp.name, ov
        total[best] = total.get(best, 0.0) + (e - s) * 1e-9
    return sorted(total.items(), key=lambda kv: -kv[1])[:10]


def breakdown(summary: Optional[Summary]) -> Optional[dict]:
    if summary is None:
        return None
    return {"device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_by_span]}
