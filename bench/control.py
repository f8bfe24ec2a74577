"""Read a cell's compared numbers for the program and for its control.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed, in one process, runs the cell as `run.py` does and then
puts the reference, computed in bfloat16 (one step below the float32 the
configurations state), in the program's place over the same work: the
program's reading sets the lower end of each limit, the control's the
upper end. The control's readings then go through the same checks as a
run's, and `control_correct` is what a run that produced them would
report: it has to be false. Prints one JSON line per seed. The
benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def control_outcome(out):
    """`out` with the control's reading of each compared number in the
    program's place; a number the control does not read stays as the
    program's run left it."""
    from bench import harness as H
    checks = [H.Check(c.name, out.control.get(c.name, c.value), c.limit)
              for c in out.checks]
    return dataclasses.replace(out, checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness as H

    cell = H.load_cell(args.workload)
    try:
        devices = H.require_chips(cell.chips)
    except H.NoChip as e:
        H.log(str(e))
        return 1
    H.enable_cache()

    drive = H.driver(cell.traffic["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = drive.run(cell, seed, args.seconds, False, time.perf_counter(),
                        devices=devices, control=True)
        ctrl = control_outcome(out)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "program_correct": out.correct,
            "program": {c.name: c.value for c in out.checks},
            "control_correct": ctrl.correct,
            "control": {c.name: c.value for c in ctrl.checks},
            "limits": {c.name: c.limit for c in out.checks}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
