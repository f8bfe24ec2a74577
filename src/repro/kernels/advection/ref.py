"""Pure-jnp oracle for the Piacsek-Williams advection stencil (MONC).

The paper's kernel: depth-1 3D stencil computing momentum source terms
(su, sv, sw) for wind fields (u, v, w) — "53 double precision operations per
grid cell" (21 add/sub + 32 mul). The exact MONC discretisation is not listed
in the paper; this is the standard PW centred form on the MONC grid, with the
z metric terms (tzc1/tzc2) carried per-level exactly as MONC does. Our op
count is measured from the jaxpr in tests and reported alongside the paper's.

Boundary cells (first/last index in each dim) are zero, matching the paper's
kernel which computes k in [1, size_in_z) with halo-exchanged y/x edges.

TPU adaptation: f32 instead of f64 (the paper names reduced precision as its
own further-work item); the f64 numpy oracle in tests bounds the error.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class AdvectParams(NamedTuple):
    tcx: jax.Array   # scalar: 0.25 / dx
    tcy: jax.Array   # scalar: 0.25 / dy
    tzc1: jax.Array  # (Z,): 0.25 * rdz[k] * rho ratios (level-dependent)
    tzc2: jax.Array  # (Z,)


def default_params(Z: int, dx: float = 100.0, dy: float = 100.0,
                   dz: float = 40.0, dtype=jnp.float32) -> AdvectParams:
    k = np.arange(Z, dtype=np.float64)
    rdz = 1.0 / (dz * (1.0 + 0.001 * k))       # slightly stretched grid
    tzc1 = 0.25 * rdz * (1.0 - 0.002 * k)
    tzc2 = 0.25 * rdz * (1.0 + 0.002 * k)
    return AdvectParams(
        jnp.asarray(0.25 / dx, dtype), jnp.asarray(0.25 / dy, dtype),
        jnp.asarray(tzc1, dtype), jnp.asarray(tzc2, dtype))


def _interior_slices(x):
    """c = centre view (X-2, Y-2, Z-2); offsets index into the full array."""
    return x[1:-1, 1:-1, 1:-1]


def pw_advect_ref(u, v, w, p: AdvectParams):
    """Reference PW advection. u,v,w: (X,Y,Z). Returns (su, sv, sw) same shape,
    interior computed, boundary zero."""
    def sh(f, di, dj, dk):
        return f[1 + di:f.shape[0] - 1 + di,
                 1 + dj:f.shape[1] - 1 + dj,
                 1 + dk:f.shape[2] - 1 + dk]

    tzc1 = p.tzc1[1:-1]
    tzc2 = p.tzc2[1:-1]

    def source(f):
        """PW flux form: d(uf)/dx + d(vf)/dy + d(wf)/dz, centred."""
        fx = p.tcx * (sh(u, -1, 0, 0) * (sh(f, 0, 0, 0) + sh(f, -1, 0, 0))
                      - sh(u, 1, 0, 0) * (sh(f, 0, 0, 0) + sh(f, 1, 0, 0)))
        fy = p.tcy * (sh(v, 0, -1, 0) * (sh(f, 0, 0, 0) + sh(f, 0, -1, 0))
                      - sh(v, 0, 1, 0) * (sh(f, 0, 0, 0) + sh(f, 0, 1, 0)))
        fz = (tzc1 * sh(w, 0, 0, -1) * (sh(f, 0, 0, 0) + sh(f, 0, 0, -1))
              - tzc2 * sh(w, 0, 0, 1) * (sh(f, 0, 0, 0) + sh(f, 0, 0, 1)))
        return fx + fy + fz

    out = []
    for f in (u, v, w):
        s = source(f)
        out.append(jnp.pad(s, ((1, 1), (1, 1), (1, 1))))
    return tuple(out)


def _with_f64(fn, fields, p: AdvectParams):
    """Run ``fn(u64, v64, w64, p64)`` on genuinely-f64 jnp inputs.

    The jnp.asarray conversions must happen INSIDE the enable_x64 context —
    outside it they silently downcast f64 to f32 and the "oracle" stops
    being one.
    """
    f_np = [np.asarray(t, np.float64) for t in fields]
    p_np = [np.asarray(t, np.float64) for t in p]
    with jax.enable_x64(True):
        f64 = [jnp.asarray(t) for t in f_np]
        p64 = AdvectParams(*(jnp.asarray(t) for t in p_np))
        return fn(*f64, p64)


def pw_advect_ref_f64(u, v, w, p: AdvectParams):
    """f64 oracle (the paper's double-precision ground truth)."""
    return _with_f64(pw_advect_ref, (u, v, w), p)


def pw_step_ref(u, v, w, p: AdvectParams, dt: float = 1.0):
    """One explicit-Euler advection step: f <- f + dt * source(f)."""
    su, sv, sw = pw_advect_ref(u, v, w, p)
    return u + dt * su, v + dt * sv, w + dt * sw


def pw_multistep_ref_f64(u, v, w, p: AdvectParams, T: int, dt: float = 1.0):
    """T explicit-Euler steps in f64 — the oracle for the fused (v4) kernel.

    Every intermediate field is held in double precision, so this bounds the
    accumulated f32 error of ``advect_fused(T=...)`` from above.
    """
    def run(u64, v64, w64, p64):
        for _ in range(T):
            u64, v64, w64 = pw_step_ref(u64, v64, w64, p64, dt)
        return tuple(np.asarray(t, np.float64) for t in (u64, v64, w64))

    return _with_f64(run, (u, v, w), p)


def flops_per_cell() -> int:
    """Measured add/sub/mul count per interior cell (reported in EXPERIMENTS)."""
    import collections
    X = Y = Z = 4
    p = default_params(Z)
    args = [jnp.zeros((X, Y, Z), jnp.float32)] * 3
    jaxpr = jax.make_jaxpr(lambda u, v, w: pw_advect_ref(u, v, w, p))(*args)
    counts = collections.Counter(str(e.primitive) for e in jaxpr.jaxpr.eqns)
    cells = (X - 2) * (Y - 2) * (Z - 2)
    # every add/sub/mul in the jaxpr operates elementwise on interior views
    total = sum(counts[k] for k in ("add", "sub", "mul"))
    return total  # per-cell by construction (all ops are per-cell elementwise)
