"""VMEM budget pass: every shipped config's on-chip buffers statically
summed against `roofline.VMEM_PER_CORE` BEFORE anything compiles.

All fast tier (1-device): plan arithmetic pinned to the kernel sizing
formula (`fused_register_bytes`), `check()` raising a
`VmemBudgetExceeded` that NAMES the largest buffer, `plan_max_batch` ==
`roofline.serving_max_batch` (the pass and the serving-only bound can
never drift), and the two trace/alloc-time integration points: an
over-budget fused distributed config refused while TRACING (before
compile), and the serving engine's `_alloc` refusing an over-budget
batch at construction.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import (VmemBudgetExceeded, VmemBuffer, VmemPlan,
                            plan_max_batch)
from repro.analysis.vmem import (distributed_block_plan, fused_ring_plan,
                                 serving_ring_plan)
from repro.core import roofline as R
from repro.kernels.advection.advection import fused_register_bytes
from repro.kernels.advection.ref import default_params
from repro.launch.mesh import make_stencil_mesh
from repro.serving.stencil_engine import StencilServingEngine
from repro.stencil.advection import AdvectionDomain
from repro.stencil.distributed import make_distributed_step
from repro.stencil.spec import tracer_advection_spec


def test_plan_arithmetic_and_table():
    plan = VmemPlan((VmemBuffer("a", 100), VmemBuffer("b", 50, "why")),
                    budget=200, context="unit")
    assert plan.total() == 150
    assert plan.headroom() == 50
    assert plan.fits()
    assert plan.check() is plan
    assert "TOTAL" in plan.table() and "why" in plan.table()


def test_check_raises_naming_largest_buffer():
    plan = VmemPlan((VmemBuffer("small ring", 100),
                     VmemBuffer("huge recv slab", 10 ** 9, "depth=64")),
                    budget=2 ** 20, context="unit-overflow")
    assert not plan.fits() and plan.headroom() < 0
    with pytest.raises(VmemBudgetExceeded) as ei:
        plan.check()
    msg = str(ei.value)
    assert "huge recv slab" in msg and "unit-overflow" in msg
    assert "small ring" in msg            # full table rides the error


def test_fused_ring_plan_matches_register_bytes():
    plan = fused_ring_plan(64, 128, T=4, y_tile=8, halo=4)
    assert plan.total() == fused_register_bytes(4, 64, 128, 4, 8, 4)
    # batch multiplies the slot ring
    b4 = fused_ring_plan(64, 128, T=4, y_tile=8, halo=4, batch=4)
    assert b4.total() == 4 * plan.total()
    assert "batch=4" in b4.buffers[0].name


def test_serving_ring_plan_and_max_batch_agree():
    Y, Z, T = 64, 128, 4
    per_slot = fused_register_bytes(T, Y, Z, 4, None)
    assert serving_ring_plan(Y, Z, batch=1, T=T).total() == per_slot
    mb = plan_max_batch(Y, Z, T=T)
    assert mb == R.serving_max_batch(per_slot, vmem_budget=R.VMEM_PER_CORE)
    # the plan at max batch fits; one slot past it does not
    assert serving_ring_plan(Y, Z, batch=mb, T=T).fits()
    assert not serving_ring_plan(Y, Z, batch=mb + 1, T=T).fits()


def test_distributed_block_plan_fused_and_dma_slabs():
    shard = (8, 16, 128)
    # fused local kernel on a y-decomposed mesh: ring over the
    # halo-extended rows
    p = distributed_block_plan(shard, T=2, local_kernel="fused", ny=4)
    assert p.total() == fused_register_bytes(2, 16 + 2 * 2, 128, 4, None)
    assert p.buffers[0].name.startswith("fused shift-register ring")
    # the remote-DMA engine moves its bands HBM to HBM: with the jnp
    # reference loop a 2D block holds no VMEM at all
    d = distributed_block_plan(shard, T=2, local_kernel="reference",
                               nx=2, ny=2)
    assert d.total() == 0 and not d.buffers


def test_distributed_block_plan_spec_geometry():
    spec = tracer_advection_spec()
    shard = (8, 16, 128)
    T = 2
    p = distributed_block_plan(shard, T=T, local_kernel="fused", ny=4,
                               spec=spec)
    depth = spec.halo(T)
    want = fused_register_bytes(T, 16 + 2 * depth, 128, 4, None,
                                depth, n_fields=spec.n_fields,
                                n_slots=2 * spec.radius + 1,
                                n_levels=spec.stages * T)
    assert p.total() == want


def test_oversized_distributed_build_refused_at_trace_time():
    # an untiled fused ring over a tall shard must be refused while
    # TRACING the step — before compile, naming the ring buffer
    mesh = make_stencil_mesh(1, 1)
    p = default_params(128)
    big = jnp.zeros((8, 16384, 128), jnp.float32)
    step = make_distributed_step(mesh, p, axis="y", x_axis=None, T=8,
                                 local_kernel="fused")
    with pytest.raises(VmemBudgetExceeded, match="shift-register ring"):
        jax.make_jaxpr(lambda u, v, w: step(u, v, w))(big, big, big)
    # the tiled equivalent of the same config traces fine
    tiled = make_distributed_step(mesh, p, axis="y", x_axis=None, T=8,
                                  local_kernel="fused", y_tile=8)
    jax.make_jaxpr(lambda u, v, w: tiled(u, v, w))(big, big, big)


def test_serving_engine_alloc_checks_budget():
    # a modest domain constructs fine...
    eng = StencilServingEngine(
        AdvectionDomain(6, 16, 12, variant="fused", fuse_T=2, dt=0.005),
        batch_size=2)
    assert eng is not None
    # ...an over-budget slot ring is refused at construction, naming the
    # batched rings (the untiled Y makes each slot ring Y-proportional)
    with pytest.raises(VmemBudgetExceeded, match="slot rings"):
        StencilServingEngine(
            AdvectionDomain(8, 65536, 128, variant="fused", fuse_T=8,
                            dt=0.005),
            batch_size=8)
