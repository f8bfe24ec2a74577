"""The plain reference that decides `correct`.

Piacsek-Williams advection written out in `jax.numpy`, one explicit Euler
substep at a time: f <- f + dt * (d(u f)/dx + d(v f)/dy + d(w f)/dz) in
centred flux form, on every interior cell; the outermost cell of each
axis is a wall and never changes. It imports nothing of the system under
test. `dtype` sets the precision it computes in: the configuration's own
(float32) for the reference, one step below (bfloat16) for the control.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P


def _source(f, u, v, w, tcx, tcy, tzc1, tzc2):
    """The PW flux-form source of field `f` on the interior cells."""
    def sh(g, di, dj, dk):
        X, Y, Z = g.shape
        return g[1 + di:X - 1 + di, 1 + dj:Y - 1 + dj, 1 + dk:Z - 1 + dk]

    c = sh(f, 0, 0, 0)
    fx = tcx * (sh(u, -1, 0, 0) * (c + sh(f, -1, 0, 0))
                - sh(u, 1, 0, 0) * (c + sh(f, 1, 0, 0)))
    fy = tcy * (sh(v, 0, -1, 0) * (c + sh(f, 0, -1, 0))
                - sh(v, 0, 1, 0) * (c + sh(f, 0, 1, 0)))
    fz = (tzc1[1:-1] * sh(w, 0, 0, -1) * (c + sh(f, 0, 0, -1))
          - tzc2[1:-1] * sh(w, 0, 0, 1) * (c + sh(f, 0, 0, 1)))
    return fx + fy + fz


def euler_substep(fields, coeffs, dt, mask=None):
    """One substep of (u, v, w) of shape (X, Y, Z). `mask`, broadcast
    against the interior, freezes the cells where it is False."""
    u, v, w = fields
    dt = jnp.asarray(dt, u.dtype)
    out = []
    for f in fields:
        s = dt * _source(f, u, v, w, *coeffs)
        if mask is not None:
            s = jnp.where(mask, s, jnp.zeros_like(s))
        out.append(f.at[1:-1, 1:-1, 1:-1].add(s))
    return tuple(out)


def _cast(fields, coeffs, dtype):
    return (tuple(f.astype(dtype) for f in fields),
            tuple(jnp.asarray(c, dtype) for c in coeffs))


def _slab_substep(fields, coeffs, dt, X: int):
    """One substep of the X-slab this device holds: the neighbouring
    slabs' edge planes come by `ppermute`, and the planes at the global
    walls stay fixed."""
    n = jax.lax.axis_size("r")
    me = jax.lax.axis_index("r")
    Xl = fields[0].shape[0]
    fwd = [(j, (j + 1) % n) for j in range(n)]
    bwd = [(j, (j - 1) % n) for j in range(n)]
    ext = [jnp.concatenate([jax.lax.ppermute(f[-1:], "r", fwd), f,
                            jax.lax.ppermute(f[:1], "r", bwd)], axis=0)
           for f in fields]
    gx = me * Xl + jnp.arange(Xl)
    live = ((gx >= 1) & (gx <= X - 2))[:, None, None]
    dt = jnp.asarray(dt, fields[0].dtype)
    out = []
    for own, f in zip(fields, ext):
        s = dt * _source(f, *ext, *coeffs)
        out.append(own.at[:, 1:-1, 1:-1].add(
            jnp.where(live, s, jnp.zeros_like(s))))
    return tuple(out)


@lru_cache(maxsize=None)
def _integrator(mesh, X: int, dtype):
    spec = P("r", None, None)

    def body(fields, coeffs, dt, n_substeps):
        fields, coeffs = _cast(fields, coeffs, dtype)
        out = jax.lax.fori_loop(
            0, n_substeps, lambda _, fs: _slab_substep(fs, coeffs, dt, X),
            fields)
        return tuple(f.astype(jnp.float32) for f in out)

    fn = jax.shard_map(body, mesh=mesh, in_specs=((spec,) * 3, P(), P(), P()),
                       out_specs=(spec,) * 3)
    return jax.jit(fn, donate_argnums=(0,))


def integrate(fields, coeffs, dt, n_substeps, *, mesh, dtype=jnp.float32):
    """`n_substeps` substeps of one grid, computed in `dtype` and
    returned in float32. The grid is split in X over the devices of the
    one-axis `mesh` (axis "r"), as `fields` are placed."""
    fn = _integrator(mesh, int(fields[0].shape[0]), jnp.dtype(dtype))
    return fn(tuple(fields), tuple(jnp.asarray(c) for c in coeffs),
              jnp.float32(dt), jnp.int32(n_substeps))


@partial(jax.jit, static_argnames=("max_substeps", "dtype"))
def integrate_jobs(fields, coeffs, dt, extents, n_substeps, *,
                   max_substeps, dtype=jnp.float32):
    """Jobs of different extents, each held at the origin of one padded
    (X, Y, Z) array: `fields` are (J, X, Y, Z), `extents` (J, 2) the
    jobs' (Xr, Yr), `n_substeps` (J,) their substep counts. A job's cells
    outside its extent, and its own outermost ring, never change."""
    fields, coeffs = _cast(fields, coeffs, dtype)
    J, X, Y, _ = fields[0].shape
    ix = jnp.arange(1, X - 1)[None, :]
    iy = jnp.arange(1, Y - 1)[None, :]
    xin = ix < (extents[:, 0:1] - 1)                       # (J, X-2)
    yin = iy < (extents[:, 1:2] - 1)                       # (J, Y-2)
    inside = xin[:, :, None, None] & yin[:, None, :, None]

    def body(k, fs):
        live = (k < n_substeps)[:, None, None, None]
        return jax.vmap(lambda a, b, c, m: euler_substep(
            (a, b, c), coeffs, dt, m))(*fs, inside & live)

    out = jax.lax.fori_loop(0, max_substeps, body, fields)
    return tuple(f.astype(jnp.float32) for f in out)


@jax.jit
def _gap(a, b):
    return jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(b))


def max_rel_err(got, want) -> float:
    """The widest gap between a result and the reference, over every
    cell of every field, as a share of that field's largest magnitude in
    the reference: max_f max|got_f - want_f| / max|want_f|."""
    worst = 0.0
    for g, w in zip(got, want):
        w = jnp.asarray(w, jnp.float32)
        if not isinstance(g, jax.Array):
            g = jax.device_put(np.asarray(g, np.float32), w.sharding)
        gap, scale = (float(x) for x in _gap(g, w))
        if not np.isfinite(gap):
            return float("inf")
        worst = max(worst, gap / max(scale, np.finfo(np.float32).tiny))
    return worst


def max_rel_err_host(got, want) -> float:
    """`max_rel_err` on the host, for results of many shapes."""
    worst = 0.0
    for g, w in zip(got, want):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        if g.shape != w.shape:
            return float("inf")
        gap = float(np.max(np.abs(g - w)))
        if not np.isfinite(gap):
            return float("inf")
        worst = max(worst, gap / max(float(np.max(np.abs(w))), 1e-30))
    return worst
