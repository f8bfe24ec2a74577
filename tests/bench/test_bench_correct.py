"""`correct` comes out true for the program and false for its control and
for each fault the cells can have, at sizes a CPU test holds: the timed
path is broken underneath, and the rest of a run (its window, its
reference and its comparison) runs as on the chip."""
import time

import jax.numpy as jnp
import pytest

from _benchcells import run_small, small_cell
from bench.control import control_outcome


@pytest.fixture
def integ():
    return small_cell("integ-268m-1chip")


@pytest.fixture
def ens():
    return small_cell("ens-members-1m")


def test_integration_program_passes_and_its_control_fails(integ):
    out = run_small(integ, control=True)
    assert out.correct, out.checks
    assert not control_outcome(out).correct, out.control


def test_serving_program_passes_and_its_control_fails(ens):
    out = run_small(ens, control=True)
    assert out.correct, out.checks
    assert out.attempted >= ens.traffic["members"] and out.failed == 0
    assert not control_outcome(out).correct, out.control


def _broken_run(monkeypatch, fault):
    from repro.stencil import distributed as D
    real = D.make_distributed_run

    def make(*args, **kwargs):
        run = real(*args, **kwargs)

        def broken(u, v, w):
            if fault == "unchanged":
                time.sleep(0.01)         # a call that takes time, as one does
                return u, v, w
            u, v, w = run(u, v, w)
            return u.at[1, 1, 1].add(1.0), v, w        # one answer altered
        return broken
    monkeypatch.setattr(D, "make_distributed_run", make)


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_integration_fault_is_not_correct(integ, monkeypatch, fault):
    _broken_run(monkeypatch, fault)
    assert not run_small(integ).correct


def _broken_kernel(monkeypatch, fault):
    from repro.kernels.advection import advection as K
    real = K.advect_fused_batched

    def broken(u, v, w, p, **kw):
        B, X = u.shape[:2]
        if fault == "unchanged":
            return u, v, w, jnp.ones((B, X), jnp.float32)
        ou, ov, ow, gf = real(u, v, w, p, **kw)
        if fault == "half_batch":            # the second half not advanced
            keep = (jnp.arange(B) < B // 2)[:, None, None, None]
            ou, ov, ow = (jnp.where(keep, o, i)
                          for o, i in ((ou, u), (ov, v), (ow, w)))
        else:                                # one answer altered
            ou = ou.at[0, 1, 1, 1].add(1.0)
        return ou, ov, ow, gf
    monkeypatch.setattr(K, "advect_fused_batched", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_serving_fault_is_not_correct(ens, monkeypatch, fault):
    _broken_kernel(monkeypatch, fault)
    assert not run_small(ens).correct


def test_result_line_carries_the_cells_metrics_and_ends_with_the_checks(ens):
    from bench import harness as H
    out = run_small(ens)
    line, shown = H.result(ens, out, traced=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {m["name"] for m in ens.end_to_end}
    assert line["device"]["count"] == 1
    assert line["checks"]["max_rel_err"]["limit"] == \
        ens.config["limit_max_rel_err"]
    assert all(s.startswith("check ") and s.endswith(" ok") for s in shown)
