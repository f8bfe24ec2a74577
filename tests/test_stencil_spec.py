"""Differential oracle suite for the stencil-spec frontend.

Two gate families keep the generalised engine honest:

  * BITWISE — the spec-driven `stencil_fused` must reproduce the
    hand-written `advect_fused` bit for bit when given the
    Piacsek-Williams spec (swept over T, y_tile, dtype), and the
    spec-driven distributed step must reproduce the legacy 3-field
    distributed path bit for bit. The frontend is a generalisation of the
    v4 ladder, not a fork.
  * f64 ORACLE — every new operator (tracer advection, 3D diffusion) and
    the in-ring RK2 integrator, differenced against
    `spec_multistep_ref_f64` (genuine float64 intermediates) under a
    per-dtype tolerance ladder.

`benchmarks/stencil_sweep.py` re-runs the same gates as explicit
SystemExit raises and prices counted-vs-modelled bytes per operator.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_ok

from repro.kernels.advection.advection import (advect_fused, stencil_fused,
                                               stencil_fused_batched)
from repro.kernels.advection.ref import default_params
from repro.stencil import spec as SP
from repro.stencil.advection import stratus_fields

DT = 0.01
SHAPE = (8, 10, 8)

# per-dtype tolerance ladder (relative to the operator's field scale)
TOL_REL = {"float32": 2e-5, "bfloat16": 0.02}


def _max_err_f64(out, oracle):
    return max(float(np.max(np.abs(np.asarray(a, np.float64) - b)))
               for a, b in zip(out, oracle))


def _bitwise(a_fields, b_fields, ctx=""):
    for a, b in zip(a_fields, b_fields):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(ctx))


def _operator(key, dtype=jnp.float32):
    X, Y, Z = SHAPE
    if key in ("pw", "pw_rk2"):
        spec = SP.pw_advection_spec("rk2" if key.endswith("rk2")
                                    else "euler")
        return spec, default_params(Z), stratus_fields(X, Y, Z,
                                                       dtype=dtype), DT
    if key in ("tracer", "tracer_rk2"):
        spec = SP.tracer_advection_spec("rk2" if key.endswith("rk2")
                                        else "euler")
        fields = stratus_fields(X, Y, Z, dtype=dtype) + (
            SP.tracer_field(X, Y, Z, dtype=dtype),)
        return spec, default_params(Z), fields, DT
    spec = SP.diffusion_spec("rk2" if key.endswith("rk2") else "euler")
    return spec, SP.default_diffusion_params(Z), (
        SP.diffusion_field(X, Y, Z, dtype=dtype),), 1e-3


# ---------------------------------------------------------------------------
# bitwise: spec frontend == hand-written v4 kernel for the PW spec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T", [1, 2, 3])
@pytest.mark.parametrize("y_tile", [None, 5])
def test_pw_spec_bitwise_vs_advect_fused(T, y_tile):
    X, Y, Z = SHAPE
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    ref = advect_fused(u, v, w, p, T=T, dt=DT, y_tile=y_tile)
    got = stencil_fused((u, v, w), p, SP.pw_advection_spec(), T=T, dt=DT,
                        y_tile=y_tile)
    _bitwise(got, ref, (T, y_tile))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pw_spec_bitwise_dtype_sweep(dtype):
    X, Y, Z = SHAPE
    u, v, w = stratus_fields(X, Y, Z, dtype=dtype)
    p = default_params(Z)
    ref = advect_fused(u, v, w, p, T=2, dt=DT, y_tile=4)
    got = stencil_fused((u, v, w), p, SP.pw_advection_spec(), T=2, dt=DT,
                        y_tile=4)
    _bitwise(got, ref, dtype)


def test_pw_spec_bitwise_with_interior_masks():
    """The distributed rung's mask arguments thread through identically."""
    X, Y, Z = SHAPE
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    xm = (np.arange(X) % 5 != 0).astype(np.float32)
    ym = (np.arange(Y) % 4 != 0).astype(np.float32)
    ref = advect_fused(u, v, w, p, T=2, dt=DT, x_interior_mask=xm,
                       y_interior_mask=ym)
    got = stencil_fused((u, v, w), p, SP.pw_advection_spec(), T=2, dt=DT,
                        x_interior_mask=xm, y_interior_mask=ym)
    _bitwise(got, ref)


# ---------------------------------------------------------------------------
# f64 oracle ladder: the new operators and the in-ring RK2
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("key", ["tracer", "diffusion", "pw_rk2",
                                 "tracer_rk2", "diffusion_rk2"])
def test_operator_matches_f64_oracle(key, dtype):
    T = 2
    spec, params, fields, dt = _operator(key, dtype)
    oracle = SP.spec_multistep_ref_f64(fields, params, spec, T, dt)
    out = stencil_fused(fields, params, spec, T=T, dt=dt)
    scale = max(1.0, max(float(np.max(np.abs(b))) for b in oracle))
    tol = TOL_REL[jnp.dtype(dtype).name] * scale
    err = _max_err_f64(out, oracle)
    assert err <= tol, (key, jnp.dtype(dtype).name, err, tol)


@pytest.mark.parametrize("key", ["tracer", "diffusion_rk2"])
def test_operator_tiled_matches_untiled_bitwise(key):
    """In-grid y-tiling restitches to the exact untiled result for the
    generalised ring too (deeper rk2 halos included)."""
    T = 2
    spec, params, fields, dt = _operator(key)
    full = stencil_fused(fields, params, spec, T=T, dt=dt)
    for y_tile in (3, 5, 64):
        tiled = stencil_fused(fields, params, spec, T=T, dt=dt,
                              y_tile=y_tile)
        _bitwise(tiled, full, (key, y_tile))


def test_tracer_velocities_bitwise_equal_pw():
    """The tracer spec's u/v/w outputs are the PW spec's outputs exactly:
    the fourth field rides the rings without perturbing the carriers."""
    spec, params, fields, dt = _operator("tracer")
    out4 = stencil_fused(fields, params, spec, T=2, dt=dt)
    out3 = stencil_fused(fields[:3], params, SP.pw_advection_spec(), T=2,
                         dt=dt)
    _bitwise(out4[:3], out3)


def test_spec_boundary_cells_frozen():
    """zero_source walls: the outermost `radius` cells never change, for
    every operator and integrator."""
    for key in ("tracer", "diffusion_rk2"):
        spec, params, fields, dt = _operator(key)
        out = stencil_fused(fields, params, spec, T=3, dt=dt)
        r = spec.radius
        for f0, fT in zip(fields, out):
            f0, fT = np.asarray(f0), np.asarray(fT)
            np.testing.assert_array_equal(fT[:r], f0[:r])
            np.testing.assert_array_equal(fT[-r:], f0[-r:])
            np.testing.assert_array_equal(fT[:, :r], f0[:, :r])
            np.testing.assert_array_equal(fT[:, :, -r:], f0[:, :, -r:])


def test_spec_batched_matches_sequential_bitwise():
    X, Y, Z = SHAPE
    B = 3
    spec, params, _, dt = _operator("tracer")
    rng = np.random.default_rng(11)
    fields = tuple(jnp.asarray(rng.normal(size=(B, X, Y, Z)), jnp.float32)
                   for _ in range(spec.n_fields))
    batched = stencil_fused_batched(fields, params, spec, T=2, dt=dt)
    for b in range(B):
        one = stencil_fused(tuple(f[b] for f in fields), params, spec,
                            T=2, dt=dt)
        _bitwise([g[b] for g in batched], one, b)


# ---------------------------------------------------------------------------
# build-time contracts
# ---------------------------------------------------------------------------


def test_stencil_fused_rejects_bad_args():
    spec, params, fields, dt = _operator("tracer")
    with pytest.raises(ValueError, match="T must be"):
        stencil_fused(fields, params, spec, T=0)
    with pytest.raises(ValueError, match="got 3 arrays"):
        stencil_fused(fields[:3], params, spec, T=1)
    with pytest.raises(ValueError, match="shape"):
        bad = fields[:3] + (fields[3][:, :-1],)
        stencil_fused(bad, params, spec, T=1)


# ---------------------------------------------------------------------------
# distributed: spec path bitwise vs the legacy 3-field path (4 host devices)
# ---------------------------------------------------------------------------

DIST_CODE = """
import os
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import numpy as np
import jax
import jax.numpy as jnp
from repro.launch.mesh import make_stencil_mesh
from repro.stencil import spec as SP
from repro.stencil import distributed as D
from repro.stencil.advection import stratus_fields
from repro.kernels.advection.ref import default_params

X, Y, Z = 8, 12, 8
p = default_params(Z)
u, v, w = stratus_fields(X, Y, Z)
q = SP.tracer_field(X, Y, Z)
mesh = make_stencil_mesh(2, 2)
pw = SP.pw_advection_spec()
for T in (1, 2):
    legacy = D.make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                                     dt=0.01)(u, v, w)
    via_spec = D.make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                                       dt=0.01, spec=pw,
                                       spec_params=p)(u, v, w)
    for a, b in zip(legacy, via_spec):
        assert np.array_equal(np.asarray(a), np.asarray(b)), T

# tracer: fused local kernel bitwise vs reference; run == sequential steps
tr = SP.tracer_advection_spec()
st_r = D.make_distributed_step(mesh, p, axis="y", x_axis="x", T=2, dt=0.01,
                               spec=tr, spec_params=p)
st_f = D.make_distributed_step(mesh, p, axis="y", x_axis="x", T=2, dt=0.01,
                               spec=tr, spec_params=p,
                               local_kernel="fused", y_tile=4)
outr, outf = st_r(u, v, w, q), st_f(u, v, w, q)
for a, b in zip(outr, outf):
    assert np.array_equal(np.asarray(a), np.asarray(b))
run = D.make_distributed_run(mesh, p, n_blocks=2, axis="y", x_axis="x",
                             T=2, dt=0.01, spec=tr, spec_params=p)
seq = st_r(*st_r(u, v, w, q))
for a, b in zip(run(u, v, w, q), seq):
    assert np.array_equal(np.asarray(a), np.asarray(b))

# rk2 diffusion: deeper exchange vs the single-device oracle
mesh1 = jax.make_mesh((4,), ("data",))
dspec = SP.diffusion_spec("rk2")
dp = SP.default_diffusion_params(Z)
phi = SP.diffusion_field(X, Y, Z)
out = D.make_distributed_step(mesh1, p, axis="data", T=2, dt=1e-3,
                              spec=dspec, spec_params=dp)(phi)
ref = D.reference_global_spec_step((phi,), dp, dspec, T=2, dt=1e-3)
err = float(jnp.max(jnp.abs(out[0] - ref[0])))
assert err < 1e-5, err
print("OK")
"""


def test_distributed_spec_path_bitwise_and_oracle():
    run_ok(DIST_CODE)
