"""What every cell shares: finding a cell's files by name, the look for
the chips, spans, and the result line.

A cell is one `workloads` entry of `BENCHMARK.json`. Its configuration is
the file the `configs` entry names; its traffic mix is
`bench/traffic/<traffic>.json`, whose `driver` names the general
generator `bench/drivers/<driver>.py`; each metric it reports in a traced
run is read by `bench/metrics/<metric>.py` (see `metric_reader`). A
new cell is new files and a new `workloads` entry: nothing here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / "out"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: Optional[Dict[str, Any]] = None,
              root: Path = ROOT) -> Cell:
    """The cell `name` with its configuration, traffic and metrics."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"]
                  if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in end_to_end}
    # a per-layer metric without a list of cells is read in every cell
    # that reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=end_to_end, per_layer=per_layer)


def _load_file(path: Path, module_name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str) -> ModuleType:
    """The traffic generator of one kind: `bench/drivers/<kind>.py`."""
    return _load_file(BENCH_DIR / "drivers" / f"{kind}.py",
                      f"bench_driver_{kind}")


def metric_reader(name: str):
    """`read(ctx)` of one per-layer metric: `bench/metrics/<name>.py`.

    One quantity can move several end-to-end metrics, one per kind of
    cell, and so has one `per_layer` entry for each (`device_idle_share`,
    `device_idle_share.serve`). Where `<name>.py` is not there, the reader
    of the name before its last dot reads it."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    if not path.is_file() and "." in name:
        path = BENCH_DIR / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    mod = _load_file(path, "bench_metric_" + path.stem.replace(".", "_"))
    return mod.read


def require_chips(n: int):
    """The first `n` TPU chips JAX sees; `NoChip` otherwise."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s), found {len(devices)}")
    return devices[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it
    (`$JAX_COMPILATION_CACHE_DIR`, else `.jax_cache` in the checkout),
    for every program however quick to compile; returns its directory."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def span(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def window(cell_name: str, traced: bool):
    """The measured window; traced, it is captured by the profiler under
    one host span, and the capture is yielded (None otherwise)."""
    if not traced:
        yield None
        return
    from bench import trace as TR
    cap = TR.Capture(OUT_DIR / "trace" / cell_name)
    with cap, span(TR.WINDOW):
        yield cap


def reduce_trace(cap, devices):
    """The traced window reduced to device time on `devices`."""
    if cap is None:
        return None
    from bench import trace as TR
    return TR.reduce(cap.events(), [d.id for d in devices])


@contextlib.contextmanager
def uncached():
    """Compile what runs inside without the persistent compilation cache.

    A program whose arguments or results have a layout of their own (the
    kernels' row-major `Format`) comes back from JAX 0.9's persistent
    cache with XLA's default layouts on TPU, and its next call then
    refuses its own outputs; such programs compile afresh in each run."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def memory_peak_bytes(devices) -> int:
    """`peak_bytes_in_use` of the fullest of `devices`."""
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return max(peaks)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Check:
    """One number compared with its limit: correct while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return (not math.isnan(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run of a cell."""
    attempted: int
    failed: int
    end_to_end: Dict[str, float]          # metric name -> value
    counters: Dict[str, float]            # read by per-layer metrics
    checks: List[Check]
    devices: list
    memory_peak_bytes: int
    trace: Any = None                     # trace.Summary of the window
    control: Optional[Dict[str, float]] = None

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may look at."""
    cell: Cell
    trace: Any                            # trace.Summary
    counters: Dict[str, float]
    device_kind: str
    n_chips: int


def result(cell: Cell, out: Outcome, traced: bool) -> Tuple[dict, List[str]]:
    """The result line and the lines that show each compared number."""
    from bench import trace as TR

    d0 = out.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(out.devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    metrics = {}
    if traced:
        device["busy_s"] = out.trace.busy_s
        device["window_s"] = out.trace.window_s
        ctx = Context(cell=cell, trace=out.trace, counters=out.counters,
                      device_kind=d0.device_kind, n_chips=len(out.devices))
        for m in cell.per_layer:
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if traced:
        line["breakdown"] = TR.breakdown(out.trace)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    shown = [f"check {c.name} {c.value!r} limit {c.limit!r} "
             f"{'ok' if c.ok else 'FAILED'}" for c in out.checks]
    return line, shown
