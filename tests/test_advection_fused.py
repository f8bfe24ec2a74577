"""v4 `fused` kernel: interpret-mode equivalence vs the multi-step f64
oracle, Y-tiling equivalence (including non-multiple tile sizes), and the
VMEM-budget contract of the Y-tiled shift register."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.advection import advection as K
from repro.kernels.advection.advection import (advect_dataflow, advect_fused,
                                               advect_fused_batched,
                                               fused_register_bytes,
                                               hbm_bytes_model)
from repro.kernels.advection.ref import (default_params, pw_multistep_ref_f64,
                                         pw_step_ref)

DT = 0.01


def fields(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), dtype) for _ in range(3))


def max_err(out, oracle):
    return max(float(np.max(np.abs(np.asarray(a, np.float64) - b)))
               for a, b in zip(out, oracle))


@pytest.mark.parametrize("T", [1, 2, 4])
def test_fused_matches_multistep_f64_oracle(T):
    shape = (6, 10, 12)
    u, v, w = fields(shape)
    p = default_params(shape[2])
    oracle = pw_multistep_ref_f64(u, v, w, p, T, DT)
    out = advect_fused(u, v, w, p, T=T, dt=DT)
    assert max_err(out, oracle) < 1e-4, T


def test_fused_t1_equals_one_euler_step():
    """T=1 degenerates to dataflow + Euler update (same f32 arithmetic)."""
    shape = (5, 8, 8)
    u, v, w = fields(shape)
    p = default_params(shape[2])
    su, sv, sw = advect_dataflow(u, v, w, p)
    expect = (u + DT * su, v + DT * sv, w + DT * sw)
    out = advect_fused(u, v, w, p, T=1, dt=DT)
    assert max_err(out, [np.asarray(e, np.float64) for e in expect]) < 1e-6


@pytest.mark.parametrize("tiling", ["grid", "host"])
def test_fused_ytiled_matches_untiled_nonmultiple_tiles(tiling):
    """y_tile that does NOT divide Y (17 = 3*5 + 2) and degenerate tiles
    still restitch to the exact untiled result, on both the in-grid and the
    retained host-tiled path."""
    shape = (5, 17, 12)
    T = 2
    u, v, w = fields(shape, seed=3)
    p = default_params(shape[2])
    full = advect_fused(u, v, w, p, T=T, dt=DT)
    for y_tile in (5, 7, 64):
        tiled = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile,
                             tiling=tiling)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(full, tiled))
        assert err == 0.0, (tiling, y_tile, err)


def test_fused_boundary_cells_frozen():
    """Zero-source boundaries: edge cells keep their initial values for all
    T substeps (the oracle's contract, streamed through the ring)."""
    shape = (6, 9, 10)
    u, v, w = fields(shape, seed=1)
    out = advect_fused(u, v, w, default_params(shape[2]), T=3, dt=DT)
    for f0, fT in zip((u, v, w), out):
        np.testing.assert_array_equal(np.asarray(fT[0]), np.asarray(f0[0]))
        np.testing.assert_array_equal(np.asarray(fT[-1]), np.asarray(f0[-1]))
        np.testing.assert_array_equal(np.asarray(fT[:, 0]),
                                      np.asarray(f0[:, 0]))
        np.testing.assert_array_equal(np.asarray(fT[:, :, -1]),
                                      np.asarray(f0[:, :, -1]))


def test_fused_rejects_bad_T():
    u, v, w = fields((4, 8, 8))
    with pytest.raises(ValueError):
        advect_fused(u, v, w, default_params(8), T=0)


def test_ops_wrapper_fused():
    from repro.kernels.advection.ops import pw_advect, pw_advect_fused
    shape = (5, 8, 8)
    u, v, w = fields(shape, seed=2)
    p = default_params(shape[2])
    oracle = pw_multistep_ref_f64(u, v, w, p, 2, DT)
    out = pw_advect_fused(u, v, w, p, T=2, dt=DT)
    assert max_err(out, oracle) < 1e-4
    with pytest.raises(ValueError):
        pw_advect(u, v, w, p, variant="fused")


def test_domain_fused_step_and_advance():
    from repro.stencil.advection import AdvectionDomain
    dom = AdvectionDomain(5, 8, 8, variant="fused", fuse_T=2, dt=DT)
    u, v, w = dom.init()
    p = dom.params
    out = dom.step(u, v, w)
    ru, rv, rw = u, v, w
    for _ in range(2):
        ru, rv, rw = pw_step_ref(ru, rv, rw, p, DT)
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(out, (ru, rv, rw)))
    assert err < 1e-4
    assert dom.substeps_per_step() == 2
    out4 = dom.advance(u, v, w, 4)
    assert out4[0].shape == u.shape
    with pytest.raises(ValueError):
        dom.advance(u, v, w, 3)   # not a multiple of fuse_T
    with pytest.raises(ValueError):
        dom.step(u, v, w, dt=0.5)  # fused bakes dt into the kernel
    with pytest.raises(ValueError):
        dom.sources(u, v, w)


# --- x_interior_mask: the 2D-decomposition hook ----------------------------

def test_fused_x_interior_mask_matches_masked_reference_loop():
    """The kernel's per-slice x mask reproduces the 2D distributed halo
    semantics: masked planes are frozen walls, exactly like the y row mask;
    grid tiling does not change a bit of it; all-ones is a bitwise no-op."""
    from repro.kernels.advection.ref import pw_advect_ref
    X, Y, Z, T = 8, 12, 10, 3
    u, v, w = fields((X, Y, Z), seed=8)
    p = default_params(Z)
    base = advect_fused(u, v, w, p, T=T, dt=DT)
    ones = advect_fused(u, v, w, p, T=T, dt=DT,
                        x_interior_mask=jnp.ones((X,)))
    for a, b in zip(base, ones):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    xm = np.ones((X,), np.float32)
    xm[:3] = 0.0                     # e.g. wrapped x-halo planes of a shard
    m = jnp.asarray(xm)[:, None, None] > 0
    us, vs, ws = u, v, w
    for _ in range(T):
        su, sv, sw = pw_advect_ref(us, vs, ws, p)
        us = us + DT * jnp.where(m, su, 0.0)
        vs = vs + DT * jnp.where(m, sv, 0.0)
        ws = ws + DT * jnp.where(m, sw, 0.0)
    out = advect_fused(u, v, w, p, T=T, dt=DT, x_interior_mask=jnp.asarray(xm))
    err = max(float(jnp.max(jnp.abs(a - b)))
              for a, b in zip(out, (us, vs, ws)))
    assert err < 1e-6, err
    tiled = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=4,
                         x_interior_mask=jnp.asarray(xm))
    for a, b in zip(tiled, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_x_interior_mask_contract_checks():
    X, Y, Z = 6, 18, 8
    u, v, w = fields((X, Y, Z), seed=9)
    p = default_params(Z)
    with pytest.raises(ValueError):   # shape must match X
        advect_fused(u, v, w, p, T=2, x_interior_mask=jnp.ones((X + 1,)))
    with pytest.raises(ValueError):   # host tiling cannot thread the mask
        advect_fused(u, v, w, p, T=2, y_tile=6, tiling="host",
                     x_interior_mask=jnp.ones((X,)))


# --- the lane-full ring: sources by rotation on the whole slab ------------

def _shard_walls(X, Y):
    """Interior masks of a 2x2-decomposed shard: wrapped halo planes and
    rows frozen on both sides, as `make_distributed_run` passes them."""
    xm = np.ones((X,), np.float32)
    xm[:2] = xm[-1:] = 0.0
    ym = np.ones((Y,), np.float32)
    ym[:3] = ym[-2:] = 0.0
    return jnp.asarray(xm), jnp.asarray(ym)


def _masked_f32_ref(u, v, w, p, T, xm, ym):
    """T plain f32 Euler steps of `pw_advect_ref`, each source applied only
    where both interior masks are nonzero."""
    from repro.kernels.advection.ref import pw_advect_ref
    m = ((xm[:, None] > 0) & (ym[None, :] > 0))[:, :, None]
    for _ in range(T):
        su, sv, sw = pw_advect_ref(u, v, w, p)
        u, v, w = (f + DT * jnp.where(m, s, 0.0)
                   for f, s in ((u, su), (v, sv), (w, sw)))
    return u, v, w


def _abs_err(got, want):
    return max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(got, want))


@pytest.mark.parametrize("Z", [64, 128])
@pytest.mark.parametrize("T", [1, 4])
def test_fused_rolled_ring_matches_f32_reference(Z, T):
    """The 2x2 path's local block — y-tiled, both interior masks — and the
    batched serving launch against the plain f32 reference."""
    X, Y = 6, 24
    u, v, w = fields((X, Y, Z), seed=11)
    p = default_params(Z)
    xm, ym = _shard_walls(X, Y)
    want = _masked_f32_ref(u, v, w, p, T, xm, ym)
    got = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=8,
                       x_interior_mask=xm, y_interior_mask=ym)
    assert _abs_err(got, want) < 1e-6, (Z, T)
    # two slots, one with the shard's walls and one without
    u2, v2, w2 = fields((X, Y, Z), seed=12)
    ones = (jnp.ones((X,)), jnp.ones((Y,)))
    bxm, bym = jnp.stack([xm, ones[0]]), jnp.stack([ym, ones[1]])
    out = advect_fused_batched(jnp.stack([u, u2]), jnp.stack([v, v2]),
                               jnp.stack([w, w2]), p, T=T, dt=DT, y_tile=8,
                               x_interior_mask=bxm, y_interior_mask=bym)
    want2 = _masked_f32_ref(u2, v2, w2, p, T, *ones)
    assert _abs_err([o[0] for o in out], want) < 1e-6, (Z, T)
    assert _abs_err([o[1] for o in out], want2) < 1e-6, (Z, T)


@pytest.mark.parametrize("Z", [64, 100])
@pytest.mark.parametrize("T,y_tile", [(1, None), (4, 8)])
def test_fused_lane_padded_ring_in_interpret_mode(monkeypatch, Z, T,
                                                  y_tile):
    """The compiled kernel's ring, forced on the CPU: Z padded to 128
    lanes. The interpreter fills scratch with NaN, so a pad lane or a
    wrapped rotation leaking into any output would show; none does, and
    the result is the unpadded ring's, bit for bit."""
    X, Y = 6, 24
    u, v, w = fields((X, Y, Z), seed=13)
    p = default_params(Z)
    xm, ym = _shard_walls(X, Y)
    kw = dict(T=T, dt=DT, y_tile=y_tile, x_interior_mask=xm,
              y_interior_mask=ym)
    base = advect_fused(u, v, w, p, **kw)
    monkeypatch.setattr(K, "_ring_lanes", lambda Z, interpret: 128)
    padded = advect_fused(u, v, w, p, **kw)
    for a, b in zip(padded, base):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = _masked_f32_ref(u, v, w, p, T, xm, ym)
    assert _abs_err(padded, want) < 1e-6, (Z, T, y_tile)


@pytest.mark.parametrize("lanes", [None, 128])
@pytest.mark.parametrize("y_tile", [None, 4])
def test_fused_wall_cells_bitwise_unchanged(monkeypatch, lanes, y_tile):
    """Every face of the domain — x, y and z, first and last — keeps its
    initial values through T substeps, on the plain and the 128-lane ring,
    tiled or not: where the rotations wrap, the mask holds."""
    if lanes is not None:
        monkeypatch.setattr(K, "_ring_lanes", lambda Z, interpret: lanes)
    shape = (6, 13, 10)
    u, v, w = fields(shape, seed=14)
    out = advect_fused(u, v, w, default_params(shape[2]), T=3, dt=DT,
                       y_tile=y_tile)
    faces = (np.s_[0], np.s_[-1], np.s_[:, 0], np.s_[:, -1],
             np.s_[:, :, 0], np.s_[:, :, -1])
    for f0, fT in zip((u, v, w), out):
        assert not np.array_equal(np.asarray(fT), np.asarray(f0))
        for face in faces:
            np.testing.assert_array_equal(np.asarray(fT)[face],
                                          np.asarray(f0)[face])


# --- VMEM budget: the Y-tiled register is bounded irrespective of Y --------

VMEM_BUDGET_BYTES = 8 * 1024 * 1024   # half a v5e's 16 MiB VMEM, for head-
                                      # room against double-buffered slices


@pytest.mark.parametrize("Y", [1024, 4096, 65536])
@pytest.mark.parametrize("T", [1, 2, 4, 8])
def test_ytiled_register_stays_under_vmem_budget(Y, T):
    """Fig. 8 contract: at fixed (y_tile, Z) the register size is constant
    in Y — the paper's 67M/268M grids fit the same VMEM as the 1M grid."""
    Z, item, y_tile = 64, 4, 128
    b = fused_register_bytes(T, Y, Z, item, y_tile=y_tile)
    assert b == fused_register_bytes(T, 1024, Z, item, y_tile=y_tile)
    assert b <= VMEM_BUDGET_BYTES, (Y, T, b)
    # untiled at Y=65536 would blow the budget for T>=2 — tiling is load-
    # bearing, not decorative
    if T >= 2:
        assert fused_register_bytes(T, 65536, Z, item) > VMEM_BUDGET_BYTES


def test_domain_vmem_accounting():
    from repro.stencil.advection import AdvectionDomain
    dom = AdvectionDomain(16, 65536, 64, variant="fused", fuse_T=4,
                          y_tile=128)
    assert dom.vmem_register_bytes() <= VMEM_BUDGET_BYTES
    assert dom.hbm_bytes_per_step() < hbm_bytes_model(
        16, 65536, 64, 4, "dataflow", T=4)


@pytest.mark.slow
@pytest.mark.parametrize("shape,T,y_tile", [
    ((12, 32, 128), 4, 8),
    ((8, 24, 40), 8, 6),
    ((5, 8, 256), 2, None),
])
def test_fused_large_shapes_slow(shape, T, y_tile):
    u, v, w = fields(shape, seed=4)
    p = default_params(shape[2])
    oracle = pw_multistep_ref_f64(u, v, w, p, T, DT)
    out = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile)
    assert max_err(out, oracle) < 1e-4, (shape, T, y_tile)
