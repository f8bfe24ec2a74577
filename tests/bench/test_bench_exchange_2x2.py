"""The 2x2 remote-DMA cell, cut to a CPU size, run through
`bench/drivers/integration.py` on four host devices: the program is
`correct`, its bfloat16 control is not, and neither is a planted fault.
On the CPU the exchange runs the DMA schedule's ppermute emulation, with
the fused kernel in interpret mode; the rest of a run (its window, its
reference and its comparison) runs as on the chip. The four devices
exist only in a child interpreter, so one child runs every case and the
tests read what it wrote."""
import json
import sys
import textwrap

import pytest

from _benchcells import ROOT

sys.path.insert(0, str(ROOT / "tests"))
from _subproc import run_ok  # noqa: E402

CELL = "integ-537m-2x2-dma"

CHILD = textwrap.dedent("""
    import json, os, sys, time
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/tests/bench"]
    import jax
    from _benchcells import run_small, small_cell
    from bench.control import control_outcome
    from repro.stencil import distributed as D

    devices = jax.devices()[:4]
    assert len(devices) == 4, devices
    cell = small_cell({cell!r})
    exchanged = []
    emulated = D._exchange_remote_dma_emulated

    def counted(*args, **kwargs):
        exchanged.append(args[3])           # the phase's dim
        return emulated(*args, **kwargs)
    D._exchange_remote_dma_emulated = counted

    out = run_small(cell, devices=devices, control=True)
    res = {{"config": cell.config, "program": out.correct,
            "checks": {{c.name: c.value for c in out.checks}},
            "control": control_outcome(out).correct,
            "control_checks": out.control, "attempted": out.attempted,
            "dims": sorted(set(exchanged))}}

    real = D.make_distributed_run
    for fault in ("unchanged", "altered"):
        def make(*args, _fault=fault, **kwargs):
            run = real(*args, **kwargs)

            def broken(u, v, w):
                if _fault == "unchanged":
                    time.sleep(0.01)
                    return u, v, w
                u, v, w = run(u, v, w)
                return u.at[1, 1, 1].add(1.0), v, w
            return broken
        D.make_distributed_run = make
        res[fault] = run_small(cell, devices=devices).correct
    D.make_distributed_run = real
    with open({out!r}, "w") as f:
        json.dump(res, f)
    print("OK")
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("exchange_2x2") / "runs.json"
    run_ok(CHILD.format(root=str(ROOT), cell=CELL, out=str(out)))
    return json.loads(out.read_text())


def test_the_cell_is_the_2x2_remote_dma_run_and_exchanges_both_axes(runs):
    cfg = runs["config"]
    assert cfg["mesh"] == [2, 2] and cfg["exchange"] == "remote_dma"
    assert cfg["donate"] and cfg["local_kernel"] == "fused"
    assert runs["attempted"] >= 1
    # both phases ran the DMA schedule (x planes, then y rows)
    assert runs["dims"] == [0, 1]


def test_program_is_correct(runs):
    assert runs["program"], runs["checks"]
    assert runs["checks"]["max_rel_err"] <= runs["config"][
        "limit_max_rel_err"]


def test_bf16_control_is_not_correct(runs):
    assert not runs["control"], runs["control_checks"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_planted_fault_is_not_correct(runs, fault):
    assert runs[fault] is False
