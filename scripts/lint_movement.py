#!/usr/bin/env python
"""Static data-movement lint: every registered analysis pass driven over
the ladder's representative configs, written to ``BENCH_analysis.json``.

Nothing here executes a kernel — every rung is traced (`jax.make_jaxpr`
inside the passes) and audited statically, so the whole lint is a
build-time gate: it catches an unpriced byte category, a leaked static
config value, an over-budget VMEM ring or a broken Pallas tiling
contract before anything compiles.

Row families and their gates (every gate an explicit ``SystemExit`` —
``python -O`` safe):

  * ``ledger[]``   — `MovementLedger` totals per rung (fused /
    grid-tiled / distributed x {collective, remote_dma, fused local
    kernel} / verified / spec-driven verified / batched serving), each
    with the analytic claims (`hbm_bytes_model`,
    `halo_wire_bytes_model`, `integrity_bytes_model`,
    `guard_bytes_model_parts`) the model-coverage pass holds them to.
    GATE: `check_model_coverage` passes — every nonzero category is
    claimed EXACTLY and no claim is stale (`pallas_control` is the one
    documented unpriced category: scalar pipeline plumbing).
  * ``retrace[]``  — the retrace detector over `make_distributed_step`
    / `make_distributed_run` knobs (`dma_block_index` parity and
    `n_blocks` must NOT change the trace; `y_tile` MUST), plus the
    fixture pair: the deliberately-broken static-parity driver must be
    flagged (red) and its traced-parity fix must not (green). GATE:
    real drivers retrace-free, fixture flagged with a "leak" finding.
  * ``vmem[]``     — the static VMEM plans of each rung's rings/slabs
    vs `roofline.VMEM_PER_CORE`. GATE: every shipped config fits, and
    a deliberately oversized plan RAISES `VmemBudgetExceeded` naming
    its largest buffer.
  * ``tiling[]``   — `lint_tiling` over every Pallas-backed rung.
    GATE: zero errors (warnings — e.g. interpret-mode grids below the
    (8, 128) tile — are recorded, not fatal).

``--quick`` / ``BENCH_SMOKE=1`` skips the rungs marked slow; every
family keeps its quick rows FIRST so ``benchmarks/baselines.json``
paths resolve in both modes. ``--list`` prints the pass registry.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# Force 4 host devices BEFORE jax imports: the distributed rungs trace
# on a real 2x2 mesh.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax
import jax.numpy as jnp

from repro.analysis import (Perturbation, VmemBudgetExceeded, available,
                            get_pass, make_static_parity_driver,
                            make_traced_parity_driver)
from repro.analysis.vmem import (distributed_block_plan, fused_ring_plan,
                                 serving_ring_plan)
from repro.core import roofline as R
from repro.kernels.advection.advection import (advect_fused,
                                               advect_fused_batched,
                                               hbm_bytes_model)
from repro.kernels.advection.ref import AdvectParams, default_params
from repro.launch.mesh import make_stencil_mesh
from repro.stencil import spec as SP
from repro.stencil.distributed import (make_distributed_run,
                                       make_distributed_step)

GRID = (8, 16, 128)        # single-device rungs (lane-aligned Z)
DGRID = (8, 8, 128)        # distributed rungs on the 2x2 mesh
T = 2
ITEM = 4
BATCH = 2


def _fields(shape, n, salt=0):
    key = jax.random.PRNGKey(7)
    return tuple(jax.random.normal(jax.random.fold_in(key, salt + i),
                                   shape, jnp.float32) * 0.01
                 for i in range(n))


def _ledger_rungs(mesh, p, spec):
    """(name, slow, fn, args, claims) per rung. Claims are the analytic
    model terms the coverage pass holds the counted bytes to."""
    X, Y, Z = GRID
    DX, DY, DZ = DGRID
    nx = ny = 2
    Xl, Yl = DX // nx, DY // ny
    F = _fields(GRID, 3)
    G = _fields(DGRID, 3, salt=10)
    S = _fields(DGRID, spec.n_fields, salt=20)
    BF = tuple(jnp.stack([f] * BATCH) for f in F)
    pb = AdvectParams(*[jnp.stack([leaf] * BATCH) for leaf in p])
    sd = spec.halo(1)

    wire = R.halo_wire_bytes_model(DX, DY, DZ, ITEM, nx=nx, ny=ny, T=T)
    guard = R.guard_bytes_model_parts(X, Y, Z, batch=BATCH)
    rungs = [
        ("fused", False,
         lambda u, v, w: advect_fused(u, v, w, p, T=T, interpret=True),
         F, {"pallas_hbm": hbm_bytes_model(X, Y, Z, ITEM, "fused", T=T)}),
        ("grid_tiled", False,
         lambda u, v, w: advect_fused(u, v, w, p, T=T, interpret=True,
                                      y_tile=8),
         F, {"pallas_hbm": hbm_bytes_model(X, Y, Z, ITEM, "fused", T=T)}),
        ("dist_collective", False,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=T),
         G, {"ppermute_wire": wire}),
        ("dist_fused", False,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                               local_kernel="fused"),
         # the fused local kernel streams the HALO-EXTENDED slab
         G, {"ppermute_wire": wire,
             "pallas_hbm": hbm_bytes_model(Xl + 2 * T, Yl + 2 * T, DZ,
                                           ITEM, "fused", T=T)}),
        ("verified", False,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                               verify_integrity=True),
         G, {"ppermute_wire": wire,
             "integrity_words": R.integrity_bytes_model(
                 DX, DY, DZ, nx=nx, ny=ny, T=T)}),
        ("spec_verified", False,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=1,
                               spec=spec, spec_params=p,
                               local_kernel="fused", verify_integrity=True),
         S, {"ppermute_wire": R.halo_wire_bytes_model(
                 DX, DY, DZ, ITEM, nx=nx, ny=ny, T=1,
                 n_fields=spec.n_fields, depth=sd),
             "integrity_words": R.integrity_bytes_model(
                 DX, DY, DZ, nx=nx, ny=ny, T=1,
                 n_fields=spec.n_fields, depth=sd),
             "pallas_hbm": hbm_bytes_model(
                 Xl + 2 * sd, Yl + 2 * sd, DZ, ITEM, "fused", T=1,
                 n_fields=spec.n_fields, halo_depth=sd)}),
        ("serving_batched", False,
         lambda u, v, w: advect_fused_batched(u, v, w, pb, T=T,
                                              interpret=True, guard=True),
         BF, {"pallas_hbm": BATCH * hbm_bytes_model(X, Y, Z, ITEM,
                                                    "fused", T=T),
              "guard_field_reads": guard["field_reads"],
              "guard_flag_words": guard["flag_words"]}),
        # slow tail (skipped by --quick; keep AFTER the quick rows so
        # baselines.json paths resolve in both modes)
        ("dist_remote_dma", True,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                               exchange="remote_dma"),
         G, {"ppermute_wire": wire}),
        ("dist_run_fused", True,
         make_distributed_run(mesh, p, n_blocks=3, axis="y", x_axis="x",
                              T=T, local_kernel="fused"),
         # ONE traced block (lax.fori_loop) — the run's per-block bytes
         # equal the single step's, whatever n_blocks
         G, {"ppermute_wire": wire,
             "pallas_hbm": hbm_bytes_model(Xl + 2 * T, Yl + 2 * T, DZ,
                                           ITEM, "fused", T=T)}),
    ]
    return rungs


def _ledger_rows(mesh, p, spec, smoke):
    ledger_pass = get_pass("movement-ledger")
    coverage_pass = get_pass("model-coverage")
    rows = []
    for name, slow, fn, args, claims in _ledger_rungs(mesh, p, spec):
        if smoke and slow:
            continue
        led = ledger_pass.run(fn, *args)
        report = coverage_pass.run(fn, *args, claims=claims)
        if not report.ok:
            raise SystemExit(
                f"lint gate: model coverage failed on rung {name!r}:\n  "
                + "\n  ".join(str(f) for f in report.failures))
        totals = {k: v for k, v in led.totals().items() if v}
        print(f"ledger.{name}: {totals}")
        rows.append({"rung": name, "categories": totals, "claims": claims,
                     "grand_total": led.grand_total(),
                     "coverage_ok": report.ok})
    return rows


def _retrace_rows(mesh, p, smoke):
    retrace_pass = get_pass("retrace")
    G = _fields(DGRID, 3, salt=10)
    rows = []

    def step_factory(dma_block_index=0):
        fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                                   exchange="remote_dma",
                                   dma_block_index=dma_block_index)
        return fn, G

    def run_factory(n_blocks=2, y_tile=None):
        fn = make_distributed_run(mesh, p, n_blocks=n_blocks, axis="y",
                                  x_axis="x", T=T, local_kernel="fused",
                                  y_tile=y_tile)
        return fn, G

    def green_driver(name, factory, perts):
        report = retrace_pass.run(factory, perts)
        for f in report.findings:
            print(f"retrace.{name}: {f}")
        if not report.ok:
            raise SystemExit(
                f"lint gate: retrace detector flagged {name}:\n  "
                + "\n  ".join(str(f) for f in report.findings))
        print(f"retrace.{name}: clean over "
              f"{[pt.knob for pt in perts]}")
        rows.append({"driver": name, "knobs": [pt.knob for pt in perts],
                     "findings": 0, "ok": True})

    green_driver("make_distributed_run", run_factory,
                 [Perturbation("n_blocks", (2, 3), expect="shared"),
                  Perturbation("y_tile", (2, 4), expect="distinct")])

    # the fixture pair: broken driver RED, fixed driver GREEN
    red = retrace_pass.run(
        make_static_parity_driver,
        [Perturbation("block_index", (0, 1), expect="shared")])
    if red.ok or not any(f.kind == "leak" for f in red.findings):
        raise SystemExit(
            "lint gate: the deliberately-broken static-parity fixture was "
            "NOT flagged — the retrace detector lost the PR 5 bug class")
    print(f"retrace.static_parity_fixture: flagged as expected "
          f"({red.findings[0].kind})")
    rows.append({"driver": "static_parity_fixture", "knobs": ["block_index"],
                 "findings": len(red.findings), "ok": False,
                 "expected_red": True})
    green = retrace_pass.run(
        make_traced_parity_driver,
        [Perturbation("block_index", (0, 1), expect="shared")])
    if not green.ok:
        raise SystemExit(
            "lint gate: the FIXED traced-parity fixture was flagged:\n  "
            + "\n  ".join(str(f) for f in green.findings))
    print("retrace.traced_parity_fixture: clean as expected")
    rows.append({"driver": "traced_parity_fixture", "knobs": ["block_index"],
                 "findings": 0, "ok": True})
    # slow tail (full mode only; AFTER the quick rows for path stability)
    if not smoke:
        green_driver("make_distributed_step[remote_dma]", step_factory,
                     [Perturbation("dma_block_index", (0, 1),
                                   expect="shared")])
    return rows


def _vmem_rows():
    budget_pass = get_pass("vmem-budget")
    X, Y, Z = GRID
    DX, DY, DZ = DGRID
    plans = [
        fused_ring_plan(Y, Z, T=T, itemsize=ITEM, y_tile=8, halo=T,
                        context="fused rung rings"),
        distributed_block_plan((DX // 2, DY // 2, DZ), T=T, itemsize=ITEM,
                               local_kernel="fused", nx=2, ny=2,
                               context="distributed fused rung"),
        serving_ring_plan(Y, Z, batch=BATCH, T=T, itemsize=ITEM, y_tile=8,
                          n_fields=3, context="serving rung slot rings"),
    ]
    rows = []
    for plan in plans:
        budget_pass.run(plan)   # raises VmemBudgetExceeded on overflow
        print(f"vmem.{plan.context}: {plan.total()} B of {plan.budget} B "
              f"({len(plan.buffers)} buffers)")
        rows.append({"context": plan.context, "total_bytes": plan.total(),
                     "budget": plan.budget, "headroom": plan.headroom(),
                     "n_buffers": len(plan.buffers), "fits": plan.fits()})
    # the negative gate: an untiled ring on a tall slab MUST be refused,
    # and the refusal must name the offending buffer
    big = fused_ring_plan(16384, 128, T=8, itemsize=ITEM, y_tile=None,
                          halo=8, context="deliberately oversized ring")
    try:
        budget_pass.run(big)
    except VmemBudgetExceeded as e:
        if "ring" not in str(e):
            raise SystemExit(
                f"lint gate: VmemBudgetExceeded did not name the "
                f"offending buffer: {e}")
        print(f"vmem.oversized: refused as expected ({big.total()} B)")
        rows.append({"context": big.context, "total_bytes": big.total(),
                     "budget": big.budget, "headroom": big.headroom(),
                     "n_buffers": len(big.buffers), "fits": big.fits(),
                     "expected_overflow": True})
    else:
        raise SystemExit(
            f"lint gate: oversized plan ({big.total()} B vs "
            f"{big.budget} B budget) was NOT refused")
    return rows


def _tiling_rows(mesh, p, spec, smoke):
    tiling_pass = get_pass("tiling-contract")
    F = _fields(GRID, 3)
    G = _fields(DGRID, 3, salt=10)
    S = _fields(DGRID, spec.n_fields, salt=20)
    BF = tuple(jnp.stack([f] * BATCH) for f in F)
    pb = AdvectParams(*[jnp.stack([leaf] * BATCH) for leaf in p])
    rungs = [
        ("fused", False,
         lambda u, v, w: advect_fused(u, v, w, p, T=T, interpret=True,
                                      y_tile=8), F),
        ("dist_fused", False,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                               local_kernel="fused"), G),
        ("serving_batched", False,
         lambda u, v, w: advect_fused_batched(u, v, w, pb, T=T,
                                              interpret=True, guard=True),
         BF),
        ("spec_fused", True,
         make_distributed_step(mesh, p, axis="y", x_axis="x", T=1,
                               spec=spec, spec_params=p,
                               local_kernel="fused"), S),
    ]
    rows = []
    for name, slow, fn, args in rungs:
        if smoke and slow:
            continue
        report = tiling_pass.run(fn, *args)
        for issue in report.errors:
            print(f"tiling.{name}: ERROR {issue}")
        if report.errors:
            raise SystemExit(
                f"lint gate: tiling contract errors on rung {name!r}:\n  "
                + "\n  ".join(str(i) for i in report.errors))
        print(f"tiling.{name}: {report.kernels} kernels, "
              f"0 errors, {len(report.warnings)} warnings")
        rows.append({"rung": name, "kernels": report.kernels,
                     "errors": 0, "warnings": len(report.warnings)})
    return rows


def run(smoke: bool = None) -> None:
    if smoke is None:
        smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    if jax.device_count() < 4:
        raise SystemExit(
            f"lint gate: needs 4 forced host devices, got "
            f"{jax.device_count()} — is XLA_FLAGS overridden?")
    mesh = make_stencil_mesh(2, 2)
    p = default_params(GRID[2])
    spec = SP.tracer_advection_spec()
    payload = {
        "passes": [{"name": n, "summary": s} for n, s in available()],
        "ledger": _ledger_rows(mesh, p, spec, smoke),
        "retrace": _retrace_rows(mesh, p, smoke),
        "vmem": _vmem_rows(),
        "tiling": _tiling_rows(mesh, p, spec, smoke),
        "contract": "every nonzero ledger category claimed EXACTLY by an "
                    "analytic model term (pallas_control unpriced by "
                    "design); real drivers retrace-free with the broken "
                    "fixture flagged; every shipped VMEM plan within "
                    "VMEM_PER_CORE with oversized plans refused by name; "
                    "zero Pallas tiling-contract errors",
    }
    out_path = os.path.join(os.getcwd(), "BENCH_analysis.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"analysis lint: json written to {out_path}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow rungs (CI smoke mode)")
    ap.add_argument("--list", action="store_true",
                    help="print the registered analysis passes and exit")
    ns = ap.parse_args(argv)
    if ns.list:
        for name, summary in available():
            print(f"{name}: {summary}")
        return
    run(smoke=ns.quick or None)


if __name__ == "__main__":
    main()
