"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is a `workloads` entry of `BENCHMARK.json`. With `--trace 0` the
last line of standard output is the result with the cell's end-to-end
metrics; with `--trace 1` the window is traced and the line carries the
per-layer metrics, the device's busy time and a breakdown instead. The
numbers compared for `correct` end standard error, each beside its limit.
Exits nonzero, with no result, where JAX finds no TPU or fewer chips than
the cell asks for.

`setup_s` counts from the moment JAX holds the chips: everything of the
system under test (its imports, the inputs made from the seed, compiling
and warming up) comes after it. The TPU runtime's own start before it
(8 to 19 s on a one-chip TPU v5e host) varies from machine to machine
and holds nothing of this repository; it is logged, and not counted.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness as H

    cell = H.load_cell(args.workload)
    try:
        devices = H.require_chips(cell.chips)
    except H.NoChip as e:
        H.log(str(e))
        return 1
    t0 = time.perf_counter()            # set-up counts from here
    H.log(f"{len(devices)} x {devices[0].device_kind} reached "
          f"{t0 - T_START:.3f} s after start")

    H.log(f"compile cache: {H.enable_cache()}")

    out = H.driver(cell.traffic["driver"]).run(
        cell, args.seed, args.seconds, bool(args.trace), t0, devices=devices)
    line, shown = H.result(cell, out, bool(args.trace))
    for s in shown:
        H.log(s)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
