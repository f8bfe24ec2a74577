"""Stencil-spec frontend: the operator-parameterised temporal-blocking engine.

The fused ring kernel, band schedule, masks and exchange engines were all
hard-wired to one Piacsek-Williams advection operator; the MONC port
(arXiv:2010.01545) shows advection was only the first and hottest of many
cloud-model kernels needing the same data-movement machinery, and the
follow-up study (arXiv:2107.13500) confirms the dataflow recast transfers
when the operator is parameterised. `StencilSpec` is that parameterisation:

  - per-field stencil offsets (the dependence star; `radius` = max |offset|
    component bounds the ring width and the halo growth per substep),
  - a boundary condition (``zero_source``: the outermost `radius` cells
    never receive a source — exactly the wall behaviour of the hand-written
    ladder),
  - a source-term callback `source(sh, pv)` written against an abstract
    accessor `sh(field_index, dx, dy, dz)`, so the SAME arithmetic runs on
    3-D array views (the jnp reference below) and on the fused kernel's
    2-D VMEM ring slices (`kernels.advection.stencil_fused`),
  - an integrator (`euler` or midpoint `rk2` — RK2 runs INSIDE the ring:
    two ring levels per substep, so the halo deepens at `radius * 2` per
    step and `spec.halo(T) = radius * stages * T` is the single number the
    kernel ring depth, the analytic byte models and the distributed
    exchange depth all consume).

The Piacsek-Williams spec (`pw_advection_spec`) reproduces the hand-written
`advect_fused` BITWISE (gated in tests/test_stencil_spec.py and
benchmarks/stencil_sweep.py): its callback mirrors `_source_slices`
term-by-term, so the spec frontend is a generalisation, not a fork.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.advection.ref import AdvectParams

INTEGRATORS = ("euler", "rk2")
BOUNDARIES = ("zero_source",)


def _check_offset(field: str, off) -> Tuple[int, int, int]:
    if not (isinstance(off, tuple) and len(off) == 3):
        raise ValueError(
            f"field {field!r}: offset {off!r} must be a 3-tuple of ints")
    for c in off:
        # bools are ints in Python; reject them (an offset of True is a bug)
        if not isinstance(c, int) or isinstance(c, bool):
            raise ValueError(
                f"field {field!r}: offset {off!r} must be a 3-tuple of ints "
                f"(component {c!r} is {type(c).__name__})")
    return off


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """One stencil operator: what the temporal-blocking engine needs to know.

    `source(sh, pv)` returns one interior source slab per field, where
    `sh(fi, dx, dy, dz)` yields field `fi` shifted by the offset (views
    trimmed by `radius` on every axis — 3-D in the reference, (rows, Z)
    2-D ring slices in the kernel) and `pv` is `pack_params(params)`: a
    tuple of 1-D vectors broadcast along the LAST (z) axis only, so the
    identical callback traces in both worlds. Offsets are declarative
    metadata validated here; the accessor re-checks every `sh` call stays
    within the declared radius.
    """
    name: str
    fields: Tuple[str, ...]
    offsets: Mapping[str, Tuple[Tuple[int, int, int], ...]]
    source: Callable
    pack_params: Callable
    boundary: str = "zero_source"
    integrator: str = "euler"

    def __post_init__(self):
        if not self.fields or not isinstance(self.fields, tuple):
            raise ValueError(
                f"fields must be a non-empty tuple of names, "
                f"got {self.fields!r}")
        seen = set()
        for f in self.fields:
            if not isinstance(f, str) or not f:
                raise ValueError(f"field name {f!r} must be a non-empty str")
            if f in seen:
                raise ValueError(f"duplicate field name {f!r}")
            seen.add(f)
        for f in self.fields:
            if f not in self.offsets:
                raise ValueError(f"field {f!r} has no stencil offsets")
        for f in self.offsets:
            if f not in seen:
                raise ValueError(
                    f"offsets name unknown field {f!r} "
                    f"(declared fields: {self.fields})")
        for f, offs in self.offsets.items():
            if not offs:
                raise ValueError(f"field {f!r}: offsets must be non-empty")
            for off in offs:
                _check_offset(f, off)
        if self.boundary not in BOUNDARIES:
            raise ValueError(
                f"boundary must be one of {BOUNDARIES}, "
                f"got {self.boundary!r}")
        if self.integrator not in INTEGRATORS:
            raise ValueError(
                f"integrator must be one of {INTEGRATORS}, "
                f"got {self.integrator!r}")
        if not callable(self.source):
            raise ValueError("source must be callable")
        if not callable(self.pack_params):
            raise ValueError("pack_params must be callable")
        if self.radius < 1:
            raise ValueError(
                "spec must have at least one nonzero offset (radius >= 1); "
                "a pointwise operator needs no ring")

    @property
    def n_fields(self) -> int:
        return len(self.fields)

    @property
    def radius(self) -> int:
        """Max |offset component| over every field — the ring half-width."""
        return max(abs(c) for offs in self.offsets.values()
                   for off in offs for c in off)

    @property
    def stages(self) -> int:
        """Ring levels consumed per substep (1 euler, 2 rk2)."""
        return 2 if self.integrator == "rk2" else 1

    def halo(self, T: int) -> int:
        """Halo/exchange/contamination depth of T fused substeps.

        Each ring level advances the dependence cone by `radius`; the
        integrator spends `stages` levels per substep — so T substeps
        need `radius * stages * T` halo cells, the single depth that the
        fused kernel's startup masks, `_band_schedule`'s exchange bands
        and the analytic byte models all share.
        """
        if T < 1:
            raise ValueError(f"T must be >= 1, got {T}")
        return self.radius * self.stages * T


def checked_accessor(spec: StencilSpec, raw_sh: Callable) -> Callable:
    """Wrap an `sh` accessor with the spec's declared-radius contract:
    a callback reaching past `spec.radius` on any axis is a spec bug, and
    the error names the field and the offending offset."""
    r = spec.radius

    def sh(fi, dx, dy, dz):
        if max(abs(dx), abs(dy), abs(dz)) > r:
            raise ValueError(
                f"field {spec.fields[fi]!r}: source reads offset "
                f"({dx}, {dy}, {dz}) beyond the declared radius {r}")
        return raw_sh(fi, dx, dy, dz)

    return sh


# ---------------------------------------------------------------------------
# full-array jnp reference (the oracle the kernels are differenced against)
# ---------------------------------------------------------------------------


def spec_sources(fields, params, spec: StencilSpec):
    """Full-array source terms: one (X, Y, Z) array per field, interior
    computed, outermost `radius` cells zero (the ``zero_source`` wall)."""
    fields = tuple(fields)
    if len(fields) != spec.n_fields:
        raise ValueError(
            f"spec {spec.name!r} has {spec.n_fields} fields "
            f"({spec.fields}), got {len(fields)} arrays")
    r = spec.radius
    X, Y, Z = fields[0].shape

    def raw_sh(fi, dx, dy, dz):
        f = fields[fi]
        return f[r + dx:X - r + dx, r + dy:Y - r + dy, r + dz:Z - r + dz]

    pv = spec.pack_params(params)
    srcs = spec.source(checked_accessor(spec, raw_sh), pv)
    if len(srcs) != spec.n_fields:
        raise ValueError(
            f"spec {spec.name!r} source returned {len(srcs)} slabs for "
            f"{spec.n_fields} fields")
    return tuple(jnp.pad(s, ((r, r), (r, r), (r, r))) for s in srcs)


def spec_step(fields, params, spec: StencilSpec, dt: float = 1.0):
    """One integrator step of the spec: euler `f + dt*S(f)` or midpoint
    rk2 `f + dt*S(f + (dt/2)*S(f))`, sources walled to zero at the
    boundary ring exactly as the fused kernel's masks do."""
    fields = tuple(fields)
    if spec.integrator == "euler":
        srcs = spec_sources(fields, params, spec)
        return tuple(f + dt * s for f, s in zip(fields, srcs))
    half = 0.5 * dt
    g = tuple(f + half * s for f, s in
              zip(fields, spec_sources(fields, params, spec)))
    srcs = spec_sources(g, params, spec)
    return tuple(f + dt * s for f, s in zip(fields, srcs))


def spec_multistep(fields, params, spec: StencilSpec, T: int,
                   dt: float = 1.0):
    fields = tuple(fields)
    for _ in range(T):
        fields = spec_step(fields, params, spec, dt)
    return fields


def spec_multistep_ref_f64(fields, params, spec: StencilSpec, T: int,
                           dt: float = 1.0):
    """T spec steps in genuine float64 — the oracle bounding every lower
    dtype's accumulated error (the jnp.asarray conversions happen INSIDE
    enable_x64; outside they silently downcast, cf. ref._with_f64)."""
    f_np = [np.asarray(t, np.float64) for t in fields]
    p_np = jax.tree_util.tree_map(lambda t: np.asarray(t, np.float64),
                                  params)
    with jax.enable_x64(True):
        f64 = tuple(jnp.asarray(t) for t in f_np)
        p64 = jax.tree_util.tree_map(jnp.asarray, p_np)
        out = spec_multistep(f64, p64, spec, T, dt)
        return tuple(np.asarray(t, np.float64) for t in out)


def spec_flops_per_cell(spec: StencilSpec, params) -> int:
    """Jaxpr-measured add/sub/mul per interior cell of one source pass
    (all ops are per-cell elementwise by construction; `params` must be
    built for the probe Z below)."""
    import collections
    n = _PROBE_N
    args = [jnp.zeros((n, n, n), jnp.float32)] * spec.n_fields
    jaxpr = jax.make_jaxpr(
        lambda *fs: spec_sources(fs, params, spec))(*args)
    counts = collections.Counter(str(e.primitive) for e in jaxpr.jaxpr.eqns)
    return sum(counts[k] for k in ("add", "sub", "mul"))


_PROBE_N = 4  # probe grid edge for spec_flops_per_cell (>= 2*radius + 2)


# ---------------------------------------------------------------------------
# operator specs
# ---------------------------------------------------------------------------

_STAR = ((0, 0, 0), (-1, 0, 0), (1, 0, 0),
         (0, -1, 0), (0, 1, 0), (0, 0, -1), (0, 0, 1))


def _pw_pack(p: AdvectParams):
    """Pack scalars + z-metrics into (Z+2,) vectors — the exact layout the
    hand-written kernels stream, so the spec path's param traffic and
    arithmetic are identical to theirs."""
    t1 = jnp.concatenate([p.tcx[None], p.tcy[None], p.tzc1])
    t2 = jnp.concatenate([p.tcx[None], p.tcy[None], p.tzc2])
    return (t1, t2)


def _pw_flux_source(sh, pv, n_out: int):
    """PW flux-form sources for fields 0..n_out-1 advected by the velocity
    fields 0/1/2 — mirrors `_source_slices` term-by-term (operand order
    included) so the spec-driven kernel is BITWISE-equal to the
    hand-written one for the 3-velocity case."""
    t1, t2 = pv
    tcx = 0.0 + t1[0]
    tcy = t1[1]
    tzc1 = t1[2:][1:-1]
    tzc2 = t2[2:][1:-1]

    def source(fi):
        fx = tcx * (sh(0, -1, 0, 0) * (sh(fi, 0, 0, 0) + sh(fi, -1, 0, 0))
                    - sh(0, 1, 0, 0) * (sh(fi, 0, 0, 0) + sh(fi, 1, 0, 0)))
        fy = tcy * (sh(1, 0, -1, 0) * (sh(fi, 0, 0, 0) + sh(fi, 0, -1, 0))
                    - sh(1, 0, 1, 0) * (sh(fi, 0, 0, 0) + sh(fi, 0, 1, 0)))
        fz = (tzc1 * sh(2, 0, 0, -1) * (sh(fi, 0, 0, 0) + sh(fi, 0, 0, -1))
              - tzc2 * sh(2, 0, 0, 1) * (sh(fi, 0, 0, 0) + sh(fi, 0, 0, 1)))
        return fx + fy + fz

    return tuple(source(fi) for fi in range(n_out))


def _pw_source(sh, pv):
    return _pw_flux_source(sh, pv, 3)


def _tracer_source(sh, pv):
    return _pw_flux_source(sh, pv, 4)


def pw_advection_spec(integrator: str = "euler") -> StencilSpec:
    """The Piacsek-Williams momentum advection operator — the paper's
    kernel, as a spec. With `integrator="euler"` the fused spec kernel is
    gated bitwise-equal to the hand-written `advect_fused`."""
    return StencilSpec(
        name="pw_advection" if integrator == "euler"
        else f"pw_advection_{integrator}",
        fields=("u", "v", "w"),
        offsets={"u": _STAR, "v": _STAR, "w": _STAR},
        source=_pw_source, pack_params=_pw_pack,
        integrator=integrator)


def tracer_advection_spec(integrator: str = "euler") -> StencilSpec:
    """Scalar-tracer advection riding the velocity rings: a fourth field
    `q` advected by (u, v, w) in the same PW flux form — the MONC
    multi-kernel amortisation story's first extra passenger (one exchange
    and one HBM pass now serve FOUR fields)."""
    return StencilSpec(
        name="tracer_advection" if integrator == "euler"
        else f"tracer_advection_{integrator}",
        fields=("u", "v", "w", "q"),
        offsets={"u": _STAR, "v": _STAR, "w": _STAR, "q": _STAR},
        source=_tracer_source, pack_params=_pw_pack,
        integrator=integrator)


class DiffusionParams(NamedTuple):
    kx: jax.Array   # scalar: nu / dx^2
    ky: jax.Array   # scalar: nu / dy^2
    kz: jax.Array   # (Z,): per-level nu / dz(k)^2 (stretched grid)


def default_diffusion_params(Z: int, dx: float = 100.0, dy: float = 100.0,
                             dz: float = 40.0, nu: float = 50.0,
                             dtype=jnp.float32) -> DiffusionParams:
    k = np.arange(Z, dtype=np.float64)
    dzk = dz * (1.0 + 0.001 * k)
    return DiffusionParams(
        jnp.asarray(nu / dx ** 2, dtype), jnp.asarray(nu / dy ** 2, dtype),
        jnp.asarray(nu / dzk ** 2, dtype))


def _diff_pack(p: DiffusionParams):
    return (jnp.concatenate([p.kx[None], p.ky[None], p.kz]),)


def _diff_source(sh, pv):
    (t,) = pv
    kx = t[0]
    ky = t[1]
    kz = t[2:][1:-1]
    c = sh(0, 0, 0, 0)
    lap = (kx * (sh(0, -1, 0, 0) - 2.0 * c + sh(0, 1, 0, 0))
           + ky * (sh(0, 0, -1, 0) - 2.0 * c + sh(0, 0, 1, 0))
           + kz * (sh(0, 0, 0, -1) - 2.0 * c + sh(0, 0, 0, 1)))
    return (lap,)


def diffusion_spec(integrator: str = "euler") -> StencilSpec:
    """3D diffusion (7-point Laplacian, per-level z metric): one field —
    the n_fields=1 end of the frontier the engine must span."""
    return StencilSpec(
        name="diffusion3d" if integrator == "euler"
        else f"diffusion3d_{integrator}",
        fields=("phi",),
        offsets={"phi": _STAR},
        source=_diff_source, pack_params=_diff_pack,
        integrator=integrator)


# ---------------------------------------------------------------------------
# deterministic initial fields for the new operators (hash-pinned in tests)
# ---------------------------------------------------------------------------


def tracer_field(X: int, Y: int, Z: int, seed: int = 3,
                 dtype=jnp.float32):
    """Deterministic smooth tracer blob + seeded noise (the q companion to
    `stratus_fields`; content-hash pinned by tests/test_seed_determinism)."""
    rng = np.random.default_rng(seed)
    kx = np.linspace(0, 2 * np.pi, X)[:, None, None]
    ky = np.linspace(0, 2 * np.pi, Y)[None, :, None]
    kz = np.linspace(0, np.pi, Z)[None, None, :]
    q = 1.0 + 0.5 * np.sin(kx) * np.sin(ky + 0.2) * np.cos(kz)
    q += 0.01 * rng.normal(size=q.shape)
    return jnp.asarray(q, dtype)


def diffusion_field(X: int, Y: int, Z: int, seed: int = 7,
                    dtype=jnp.float32):
    """Deterministic initial temperature-like field for the diffusion
    operator (content-hash pinned by tests/test_seed_determinism)."""
    rng = np.random.default_rng(seed)
    kx = np.linspace(0, 2 * np.pi, X)[:, None, None]
    ky = np.linspace(0, 2 * np.pi, Y)[None, :, None]
    kz = np.linspace(0, np.pi, Z)[None, None, :]
    phi = 300.0 + 2.0 * np.cos(kx + 0.1) * np.sin(ky) * np.sin(kz + 0.3)
    phi += 0.01 * rng.normal(size=phi.shape)
    return jnp.asarray(phi, dtype)
