"""In-grid (y_tile, x) 2D tiling: the equivalence + budget suite.

Grid-tiled outputs must be BITWISE equal to the untiled kernel and to the
retained host-tiled path (`_y_tiled_host`, `tiling="host"`) across
(y_tile, T, dtype, edge-remainder Y) sweeps; the fused-update v1-v3 rungs
must reproduce sources + host Euler exactly; `advect_wide` gains a
lane-aligned tiled path; and the VMEM register stays inside the budget the
updated `fused_register_bytes` promises.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.advection.advection import (_y_tiled_host, advect_blocked,
                                               advect_dataflow, advect_fused,
                                               advect_wide,
                                               fused_register_bytes,
                                               hbm_bytes_model,
                                               vmem_halo_bytes_model)
from repro.kernels.advection.ref import default_params, pw_advect_ref

DT = 0.01


def fields(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype) for _ in range(3))


def assert_bitwise(a_tuple, b_tuple, ctx):
    for a, b in zip(a_tuple, b_tuple):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(ctx))


# --- grid == untiled == host, across the sweep -----------------------------

SOURCE_KERNELS = [("blocked", advect_blocked), ("dataflow", advect_dataflow)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("y_tile", [3, 4, 5])
@pytest.mark.parametrize("name,fn", SOURCE_KERNELS)
def test_grid_tiled_sources_bitmatch_untiled_and_host(name, fn, y_tile,
                                                      dtype):
    """Y=14 is not a multiple of any swept tile except 7-adjacent sizes, so
    every sweep exercises the edge-remainder tile."""
    shape = (5, 14, 16)
    u, v, w = fields(shape, dtype, seed=11)
    p = default_params(shape[2])
    full = fn(u, v, w, p)
    grid = fn(u, v, w, p, y_tile=y_tile, tiling="grid")
    host = fn(u, v, w, p, y_tile=y_tile, tiling="host")
    assert_bitwise(grid, full, (name, y_tile, dtype, "grid vs untiled"))
    assert_bitwise(grid, host, (name, y_tile, dtype, "grid vs host"))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,y_tile", [(1, 4), (2, 5), (2, 7), (4, 3)])
def test_grid_tiled_fused_bitmatch_untiled_and_host(T, y_tile, dtype):
    shape = (5, 17, 12)   # 17 = prime: every y_tile leaves a remainder tile
    u, v, w = fields(shape, dtype, seed=12)
    p = default_params(shape[2])
    full = advect_fused(u, v, w, p, T=T, dt=DT)
    grid = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile, tiling="grid")
    host = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile, tiling="host")
    assert_bitwise(grid, full, (T, y_tile, dtype, "grid vs untiled"))
    assert_bitwise(grid, host, (T, y_tile, dtype, "grid vs host"))


def test_host_tiler_retained_under_new_name():
    """The renamed `_y_tiled_host` is the same halo-overlap/trim/concat loop
    the kernels' `tiling="host"` dispatches to."""
    shape = (5, 14, 16)
    u, v, w = fields(shape, seed=13)
    p = default_params(shape[2])
    direct = _y_tiled_host(lambda a, b, c: advect_dataflow(a, b, c, p),
                           u, v, w, y_tile=4, halo=1)
    via_kw = advect_dataflow(u, v, w, p, y_tile=4, tiling="host")
    assert_bitwise(direct, via_kw, "host path dispatch")


def test_rejects_bad_tiling_and_y_tile():
    u, v, w = fields((4, 8, 8))
    p = default_params(8)
    with pytest.raises(ValueError):
        advect_dataflow(u, v, w, p, tiling="diagonal")
    with pytest.raises(ValueError):
        advect_fused(u, v, w, p, y_tile=0)


# --- fuse_update: the Euler step folded into the v1-v3 kernels -------------

@pytest.mark.parametrize("y_tile", [None, 4, 5])
@pytest.mark.parametrize("name,fn", SOURCE_KERNELS)
def test_fuse_update_equals_sources_plus_euler(name, fn, y_tile):
    shape = (6, 14, 16)
    u, v, w = fields(shape, seed=9)
    p = default_params(shape[2])
    su, sv, sw = fn(u, v, w, p)
    expect = (u + DT * su, v + DT * sv, w + DT * sw)
    out = fn(u, v, w, p, fuse_update=True, dt=DT, y_tile=y_tile)
    assert_bitwise(out, expect, (name, y_tile))


def test_fuse_update_wide():
    u, v, w = fields((4, 16, 128), seed=10)
    p = default_params(128)
    su, sv, sw = advect_wide(u, v, w, p)
    expect = (u + DT * su, v + DT * sv, w + DT * sw)
    out = advect_wide(u, v, w, p, fuse_update=True, dt=DT)
    assert_bitwise(out, expect, "wide fuse_update")


def test_ops_wrapper_fuse_update_and_tiling():
    from repro.kernels.advection.ops import pw_advect
    u, v, w = fields((5, 14, 16), seed=14)
    p = default_params(16)
    base = pw_advect(u, v, w, p, variant="dataflow")
    tiled = pw_advect(u, v, w, p, variant="dataflow", y_tile=4,
                      tiling="grid")
    assert_bitwise(base, tiled, "ops grid tiling")
    stepped = pw_advect(u, v, w, p, variant="dataflow", fuse_update=True,
                        dt=DT)
    expect = tuple(f + DT * s for f, s in zip((u, v, w), base))
    assert_bitwise(stepped, expect, "ops fuse_update")
    ref_step = pw_advect(u, v, w, p, variant="reference", fuse_update=True,
                         dt=DT)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(ref_step, expect))
    assert err < 1e-6


# --- wide: the lane-aligned tiled path (previously raised) -----------------

def test_wide_grid_tiled_y1024_class():
    """Fig. 8 shapes (Y=1024) now tile under the (8,128) contract: the
    in-grid slab carries a sublane-rounded (8-row) halo, so tile row counts
    and element offsets stay multiples of 8."""
    u, v, w = fields((3, 1024, 128), seed=4)
    p = default_params(128)
    full = advect_wide(u, v, w, p)
    tiled = advect_wide(u, v, w, p, y_tile=256)
    assert_bitwise(tiled, full, "wide Y=1024 y_tile=256")


def test_wide_tiling_contract_checks():
    u, v, w = fields((4, 32, 128), seed=5)
    p = default_params(128)
    with pytest.raises(ValueError):           # host path: contract-breaking
        advect_wide(u, v, w, p, y_tile=8, tiling="host")
    with pytest.raises(ValueError):           # non-sublane tile
        advect_wide(u, v, w, p, y_tile=12)
    full = advect_wide(u, v, w, p)
    tiled = advect_wide(u, v, w, p, y_tile=8)  # 8 + 2*8 <= 32: tiles
    assert_bitwise(tiled, full, "wide y_tile=8 on Y=32")


# --- y_interior_mask: the distributed-composition hook ---------------------

def test_fused_y_interior_mask_matches_masked_reference_loop():
    """The kernel's per-substep row mask reproduces the distributed halo
    semantics: masked rows are frozen walls; grid tiling does not change a
    bit of it."""
    X, Y, Z, T = 6, 20, 12, 3
    u, v, w = fields((X, Y, Z), seed=6)
    p = default_params(Z)
    gy = -T + np.arange(Y)
    mask = ((gy >= 1) & (gy <= 40)).astype(np.float32)
    us, vs, ws = u, v, w
    m = jnp.asarray(mask)[None, :, None] > 0
    for _ in range(T):
        su, sv, sw = pw_advect_ref(us, vs, ws, p)
        us = us + DT * jnp.where(m, su, 0.0)
        vs = vs + DT * jnp.where(m, sv, 0.0)
        ws = ws + DT * jnp.where(m, sw, 0.0)
    ref = (us, vs, ws)
    base = advect_fused(u, v, w, p, T=T, dt=DT,
                        y_interior_mask=jnp.asarray(mask))
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(ref, base))
    assert err < 1e-6, err          # kernel vs jnp loop: FMA-level noise
    for y_tile in (6, 7):
        tiled = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile,
                             y_interior_mask=jnp.asarray(mask))
        assert_bitwise(tiled, base, ("masked grid tiling", y_tile))
    with pytest.raises(ValueError):  # host tiling cannot slice the mask
        advect_fused(u, v, w, p, T=T, dt=DT, y_tile=6, tiling="host",
                     y_interior_mask=jnp.asarray(mask))
    with pytest.raises(ValueError):  # mask shape must match Y
        advect_fused(u, v, w, p, T=T, dt=DT,
                     y_interior_mask=jnp.ones((Y + 1,)))


def test_distributed_step_fused_local_kernel_single_shard():
    """Cheap in-process wiring check of local_kernel="fused" (the 4-shard
    equivalence lives in the slow distributed suite): one self-wrapping
    shard must match the global T-substep oracle."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)
    X, Y, Z = 6, 20, 12
    u, v, w = fields((X, Y, Z), seed=7)
    p = default_params(Z)
    mesh = jax.make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P(None, "data", None))
    for T, y_tile in ((1, None), (2, 6)):
        fn = make_distributed_step(mesh, p, T=T, dt=DT,
                                   local_kernel="fused", y_tile=y_tile)
        out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref = reference_global_step(u, v, w, p, T=T, dt=DT)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, ref))
        assert err < 1e-5, (T, y_tile, err)
    with pytest.raises(ValueError):
        make_distributed_step(mesh, p, local_kernel="magic")


# --- VMEM budget: the in-grid register honours fused_register_bytes --------

VMEM_BUDGET_BYTES = 8 * 1024 * 1024


@pytest.mark.parametrize("Y", [1024, 65536])
@pytest.mark.parametrize("T", [1, 4, 8])
def test_grid_tiled_register_budget(Y, T):
    """The in-grid slab ring is (T, 3, y_tile+2T, Z) x 3 fields — exactly
    what fused_register_bytes prices, flat in Y and under budget."""
    Z, item, y_tile = 64, 4, 128
    b = fused_register_bytes(T, Y, Z, item, y_tile=y_tile)
    assert b == 3 * 3 * T * (y_tile + 2 * T) * Z * item
    assert b == fused_register_bytes(T, 8 * Y, Z, item, y_tile=y_tile)
    assert b <= VMEM_BUDGET_BYTES, (Y, T, b)
    # wide's grid-tiled ring carries the sublane-rounded 8-row halo instead
    bw = fused_register_bytes(1, Y, 128, item, y_tile=y_tile, halo=8)
    assert bw == 3 * 3 * (y_tile + 16) * 128 * item
    assert bw <= VMEM_BUDGET_BYTES


def test_domain_grid_tiling_accounting():
    from repro.stencil.advection import AdvectionDomain
    dom = AdvectionDomain(16, 65536, 64, variant="fused", fuse_T=4,
                          y_tile=128)
    host = AdvectionDomain(16, 65536, 64, variant="fused", fuse_T=4,
                          y_tile=128, tiling="host")
    assert dom.tiling == "grid"
    assert dom.hbm_bytes_per_step() < host.hbm_bytes_per_step()
    assert dom.hbm_bytes_per_step() == hbm_bytes_model(16, 65536, 64, 4,
                                                       "fused", T=4)
    assert dom.vmem_halo_bytes_per_step() > 0
    assert host.vmem_halo_bytes_per_step() == 0
    assert dom.vmem_register_bytes() <= VMEM_BUDGET_BYTES
    wide = AdvectionDomain(16, 1024, 128, variant="wide", y_tile=128)
    assert wide.vmem_register_bytes() \
        == fused_register_bytes(1, 1024, 128, 4, y_tile=128, halo=8)


def test_domain_fuse_update_fast_path():
    from repro.stencil.advection import AdvectionDomain
    dom = AdvectionDomain(5, 14, 16, variant="dataflow", fuse_update=True,
                          dt=DT, y_tile=4)
    u, v, w = dom.init()
    base = AdvectionDomain(5, 14, 16, variant="dataflow", dt=DT)
    expect = base.step(u, v, w)
    out = dom.step(u, v, w)
    assert_bitwise(out, expect, "domain fuse_update")
    with pytest.raises(ValueError):
        dom.sources(u, v, w)
    with pytest.raises(ValueError):
        dom.step(u, v, w, dt=0.5)   # dt is baked into the fused-update kernel
    # the unfused-update model charges the extra full-field pass
    assert dom.hbm_bytes_per_step() < base.hbm_bytes_per_step()
