"""Stencil-spec frontend sweep: every operator the spec layer opens —
PW advection, scalar-tracer advection, 3D diffusion, each under euler and
the in-ring RK2 — priced counted-vs-modelled and differenced against the
f64 oracle, written to ``BENCH_stencils.json``.

Row families and their gates (every gate an explicit ``SystemExit`` —
``python -O`` safe):

  * ``bitwise[]``     — the spec-driven `stencil_fused` vs the hand-written
    `advect_fused` for the Piacsek-Williams spec over a (T, y_tile, dtype)
    sweep. GATE: max |diff| == 0.0 — the frontend is a generalisation of
    the v4 kernel, not a fork.
  * ``oracle[]``      — every operator x dtype vs `spec_multistep_ref_f64`
    (genuine float64). GATE: max err <= per-dtype tolerance x operator
    scale (the tolerance ladder: f32 tight, bf16 loose).
  * ``hbm[]``         — `count_pallas_hbm_bytes` of the spec kernel on a
    lane-aligned grid vs ``hbm_bytes_model(..., "fused",
    n_fields=spec.n_fields, halo_depth=spec.halo(T))``. GATE: equal
    EXACTLY — one compulsory read+write per field per T steps, whatever
    the operator (the MONC multi-kernel amortisation claim, priced).
  * ``halo[]``        — `_band_schedule(L, spec.halo(T))` partition checks.
    GATE: the per-hop band counts sum to exactly ``radius * stages * T``.
  * ``distributed[]`` — a subprocess on 4 forced host devices builds the
    spec-driven `make_distributed_step` per operator/mesh, GATES counted
    ppermute bytes == ``halo_wire_bytes_model(depth=spec.halo(T),
    n_fields=spec.n_fields)`` exactly, fused local kernel bitwise-equal
    to the reference one, and the sharded result vs the single-device
    oracle.
  * ``ai[]``          — jaxpr-counted `spec_flops_per_cell` feeding
    `stencil_arithmetic_intensity` / `stencil_ridge_T` per operator (the
    fusion depth each operator needs to reach the ridge).

``--quick`` / ``BENCH_SMOKE=1`` runs a prefix of each sweep (row 0 of
every family is identical in both modes, so the trend-gate baselines in
``benchmarks/baselines.json`` resolve either way).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

try:                        # package context (benchmarks.run / -m)
    from benchmarks import _bootstrap
except ImportError:         # script context: benchmarks/ is sys.path[0]
    import _bootstrap

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import roofline as R
from repro.kernels.advection import advection as K
from repro.kernels.advection.ref import default_params
from repro.stencil import spec as SP
from repro.stencil.advection import stratus_fields

ITEM = 4  # f32
GRID = (10, 12, 8)           # interpret-mode compute grid
HBM_GRID = (8, 16, 128)      # lane-aligned trace-only grid (Z % 128 == 0)

# per-dtype relative tolerance ladder for the f64-oracle gate
TOL_REL = {"float32": 2e-5, "bfloat16": 0.02}


def _operators(Z: int, dtype=jnp.float32):
    """(key, spec, kernel params, packed-spec params, fields, dt) per
    operator; the velocity fields double as the tracer's carriers."""
    X, Y = GRID[0], GRID[1]
    p = default_params(Z)
    dp = SP.default_diffusion_params(Z)
    u, v, w = stratus_fields(X, Y, Z, dtype=dtype)
    q = SP.tracer_field(X, Y, Z, dtype=dtype)
    phi = SP.diffusion_field(X, Y, Z, dtype=dtype)
    return [
        ("pw", SP.pw_advection_spec(), p, (u, v, w), 0.01),
        ("pw_rk2", SP.pw_advection_spec("rk2"), p, (u, v, w), 0.01),
        ("tracer", SP.tracer_advection_spec(), p, (u, v, w, q), 0.01),
        ("diffusion", SP.diffusion_spec(), dp, (phi,), 1e-3),
        ("diffusion_rk2", SP.diffusion_spec("rk2"), dp, (phi,), 1e-3),
    ]


def _bitwise_rows(smoke: bool):
    """Spec-driven kernel == hand-written `advect_fused`, bit for bit."""
    X, Y, Z = GRID
    p = default_params(Z)
    pw = SP.pw_advection_spec()
    combos = [(2, None, jnp.float32)]
    if not smoke:
        combos += [(1, 5, jnp.float32), (3, 5, jnp.float32),
                   (4, None, jnp.float32), (2, 4, jnp.bfloat16)]
    rows = []
    for T, y_tile, dtype in combos:
        u, v, w = stratus_fields(X, Y, Z, dtype=dtype)
        ref = K.advect_fused(u, v, w, p, T=T, dt=0.01, y_tile=y_tile)
        got = K.stencil_fused((u, v, w), p, pw, T=T, dt=0.01, y_tile=y_tile)
        diff = max(float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                         - jnp.asarray(b, jnp.float32))))
                   for a, b in zip(got, ref))
        if diff != 0.0:
            raise SystemExit(
                f"stencils gate: spec-driven kernel differs from "
                f"advect_fused by {diff} at T={T}, y_tile={y_tile}, "
                f"dtype={jnp.dtype(dtype).name} — the frontend must be "
                f"bitwise-equal for the PW spec")
        rows.append({"T": T, "y_tile": y_tile,
                     "dtype": jnp.dtype(dtype).name,
                     "max_bitwise_diff": diff})
        emit(f"stencils.bitwise.T{T}.yt{y_tile}.{jnp.dtype(dtype).name}",
             0.0, f"diff={diff}")
    return rows


def _oracle_rows(smoke: bool):
    """Every operator vs the genuine-f64 reference, per-dtype ladder."""
    T = 2
    dtypes = [jnp.float32] if smoke else [jnp.float32, jnp.bfloat16]
    rows = []
    for dtype in dtypes:
        dname = jnp.dtype(dtype).name
        for key, spec, params, fields, dt in _operators(GRID[2], dtype):
            ref = SP.spec_multistep_ref_f64(fields, params, spec, T, dt)
            got = K.stencil_fused(fields, params, spec, T=T, dt=dt)
            err = max(float(np.max(np.abs(np.asarray(a, np.float64) - b)))
                      for a, b in zip(got, ref))
            scale = max(1.0, max(float(np.max(np.abs(b))) for b in ref))
            tol = TOL_REL[dname] * scale
            if err > tol:
                raise SystemExit(
                    f"stencils gate: {key} ({dname}) err {err} vs the f64 "
                    f"oracle exceeds the tolerance ladder ({tol})")
            rows.append({"operator": key, "dtype": dname, "T": T,
                         "max_err": err, "tolerance": tol})
            emit(f"stencils.oracle.{key}.{dname}", 0.0,
                 f"err={err:.2e};tol={tol:.2e}")
    return rows


def _hbm_rows():
    """Counted Pallas HBM bytes == the n_fields/halo-generalised model."""
    from repro.stencil.distributed import count_pallas_hbm_bytes

    X, Y, Z = HBM_GRID
    T = 2
    p = default_params(Z)
    dp = SP.default_diffusion_params(Z)
    rows = []
    for key, spec, params in (
            ("pw", SP.pw_advection_spec(), p),
            ("pw_rk2", SP.pw_advection_spec("rk2"), p),
            ("tracer", SP.tracer_advection_spec(), p),
            ("diffusion", SP.diffusion_spec(), dp)):
        F = spec.n_fields
        fields = tuple(jnp.zeros((X, Y, Z), jnp.float32) for _ in range(F))

        def fn(*fs, _p=params, _s=spec):
            return K.stencil_fused(fs, _p, _s, T=T, interpret=True)

        counted = count_pallas_hbm_bytes(fn, *fields)
        model = K.hbm_bytes_model(X, Y, Z, ITEM, "fused", T=T,
                                  grid_tiled=True, n_fields=F,
                                  halo_depth=spec.halo(T))
        if counted != model:
            raise SystemExit(
                f"stencils gate: {key} counted HBM bytes {counted} != "
                f"modelled {model} (n_fields={F}, "
                f"halo_depth={spec.halo(T)})")
        ring = K.fused_register_bytes(
            T, Y, Z, ITEM, y_tile=8, halo=spec.halo(T), n_fields=F,
            n_slots=2 * spec.radius + 1, n_levels=spec.stages * T)
        vmem_halo = K.vmem_halo_bytes_model(
            X, Y, Z, ITEM, "fused", T=T, y_tile=8, n_fields=F,
            halo_depth=spec.halo(T))
        rows.append({"operator": key, "T": T, "n_fields": F,
                     "halo_depth": spec.halo(T),
                     "counted_hbm_bytes": counted,
                     "modelled_hbm_bytes": model,
                     "ring_vmem_bytes": ring,
                     "vmem_halo_bytes": vmem_halo})
        emit(f"stencils.hbm.{key}", 0.0,
             f"hbm_B={counted};model_exact=True;ring_B={ring}")
    return rows


def _halo_rows():
    """`_band_schedule` partitions exactly `spec.halo(T)` rows per side."""
    rows = []
    for key, spec in (("pw", SP.pw_advection_spec()),
                      ("pw_rk2", SP.pw_advection_spec("rk2")),
                      ("tracer", SP.tracer_advection_spec()),
                      ("diffusion_rk2", SP.diffusion_spec("rk2"))):
        for T in (1, 2, 3):
            D = spec.halo(T)
            for L in (2, 3, 5):
                sched = K._band_schedule(L, D)
                moved = sum(cnt for _, cnt, _, _ in sched)
                if moved != D:
                    raise SystemExit(
                        f"stencils gate: {key} T={T} band schedule over "
                        f"local extent {L} moves {moved} rows, not "
                        f"spec.halo(T)={D}")
                if len(sched) != -(-D // L):
                    raise SystemExit(
                        f"stencils gate: {key} T={T} L={L}: "
                        f"{len(sched)} hops != ceil({D}/{L})")
            rows.append({"operator": key, "T": T, "halo_depth": D,
                         "radius": spec.radius, "stages": spec.stages})
            emit(f"stencils.halo.{key}.T{T}", 0.0,
                 f"depth={D}=r{spec.radius}*s{spec.stages}*T{T}")
    return rows


_SUB_CODE = textwrap.dedent("""
    import json, os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core.roofline import halo_wire_bytes_model
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil import spec as SP
    from repro.stencil.advection import stratus_fields
    from repro.stencil.distributed import (count_exchange_wire_bytes,
                                           make_distributed_step,
                                           reference_global_spec_step)

    cfg = json.loads(sys.argv[1])
    X, Y, Z = cfg["grid"]
    p = default_params(Z)
    dp = SP.default_diffusion_params(Z)
    u, v, w = stratus_fields(X, Y, Z)
    q = SP.tracer_field(X, Y, Z)
    phi = SP.diffusion_field(X, Y, Z)
    OPS = {
        "pw": (SP.pw_advection_spec(), p, (u, v, w), 0.01),
        "tracer": (SP.tracer_advection_spec(), p, (u, v, w, q), 0.01),
        "diffusion_rk2": (SP.diffusion_spec("rk2"), dp, (phi,), 1e-3),
    }
    rows = []
    for key, nx, ny, T, exchange in cfg["cases"]:
        spec, sp_params, fields, dt = OPS[key]
        if nx > 1:
            mesh = make_stencil_mesh(nx, ny)
            kw = dict(axis="y", x_axis="x")
        else:
            mesh = jax.make_mesh((ny,), ("y",))
            kw = dict(axis="y")
        ref_step = make_distributed_step(mesh, p, T=T, dt=dt, spec=spec,
                                         spec_params=sp_params,
                                         exchange=exchange, **kw)
        fus_step = make_distributed_step(mesh, p, T=T, dt=dt, spec=spec,
                                         spec_params=sp_params,
                                         local_kernel="fused", y_tile=4,
                                         exchange=exchange, **kw)
        out_r = ref_step(*fields)
        out_f = fus_step(*fields)
        bitwise = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(out_r, out_f))
        oracle = reference_global_spec_step(fields, sp_params, spec,
                                            T=T, dt=dt)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(out_r, oracle))
        counted = count_exchange_wire_bytes(ref_step, *fields)
        model = halo_wire_bytes_model(X, Y, Z, 4, nx=nx, ny=ny, T=T,
                                      n_fields=spec.n_fields,
                                      depth=spec.halo(T))
        rows.append({"operator": key, "mesh": [nx, ny], "T": T,
                     "exchange": exchange,
                     "halo_depth": spec.halo(T),
                     "n_fields": spec.n_fields,
                     "counted_wire_bytes": counted,
                     "modelled_wire_bytes": model,
                     "fused_vs_reference_diff": bitwise,
                     "max_err_vs_oracle": err})
    print(json.dumps({"rows": rows}))
""")


def _distributed_rows(smoke: bool):
    """Spec-driven distributed step on 4 forced host devices: counted
    wire bytes vs the depth-generalised model, fused-vs-reference local
    kernels bitwise, shards vs the single-device oracle."""
    cases = [["tracer", 2, 2, 2, "collective"],
             ["diffusion_rk2", 1, 4, 2, "collective"]]
    if not smoke:
        cases += [["pw", 2, 2, 1, "collective"],
                  ["tracer", 1, 4, 3, "remote_dma"],
                  ["diffusion_rk2", 2, 2, 1, "remote_dma"]]
    cfg = {"grid": [12, 16, 8], "cases": cases}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "PYTHONPATH": os.pathsep.join(
            [os.path.join(root, "src"), root,
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep),
    })
    r = subprocess.run([sys.executable, "-c", _SUB_CODE, json.dumps(cfg)],
                       capture_output=True, text=True, cwd=root, env=env,
                       timeout=900)
    if r.returncode != 0:
        raise SystemExit(f"stencils subprocess failed:\n{r.stderr[-3000:]}")
    rows = json.loads(r.stdout.strip().splitlines()[-1])["rows"]
    for row in rows:
        if row["counted_wire_bytes"] != row["modelled_wire_bytes"]:
            raise SystemExit(
                f"stencils gate: counted wire bytes "
                f"{row['counted_wire_bytes']} != modelled "
                f"{row['modelled_wire_bytes']} for {row}")
        if row["fused_vs_reference_diff"] != 0.0:
            raise SystemExit(
                f"stencils gate: fused local kernel differs from the "
                f"reference one by {row['fused_vs_reference_diff']} "
                f"for {row}")
        if row["max_err_vs_oracle"] > 1e-5:
            raise SystemExit(
                f"stencils gate: distributed spec step err "
                f"{row['max_err_vs_oracle']} vs oracle for {row}")
        emit(f"stencils.dist.{row['operator']}"
             f".{row['mesh'][0]}x{row['mesh'][1]}.T{row['T']}", 0.0,
             f"wire_B={row['counted_wire_bytes']};model_exact=True;"
             f"depth={row['halo_depth']}")
    return rows


def _ai_rows():
    """Per-operator arithmetic intensity and the ridge fusion depth."""
    n = SP._PROBE_N
    p = default_params(n)
    dp = SP.default_diffusion_params(n)
    rows = []
    for key, spec, params in (
            ("pw", SP.pw_advection_spec(), p),
            ("tracer", SP.tracer_advection_spec(), p),
            ("diffusion", SP.diffusion_spec(), dp)):
        flops = SP.spec_flops_per_cell(spec, params)
        bytes_pass = 2 * spec.n_fields * ITEM   # one read + write per field
        ai1 = R.stencil_arithmetic_intensity(flops * spec.stages,
                                             bytes_pass)
        ridge_T = R.stencil_ridge_T(flops * spec.stages, bytes_pass)
        rows.append({"operator": key, "flops_per_cell": flops,
                     "stages": spec.stages,
                     "bytes_per_cell_pass": bytes_pass,
                     "ai_T1": ai1, "ridge_T": ridge_T})
        emit(f"stencils.ai.{key}", 0.0,
             f"flops={flops};ai_T1={ai1:.3f};ridge_T={ridge_T}")
    return rows


def run(smoke: bool = None) -> None:
    if smoke is None:
        smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    bitwise = _bitwise_rows(smoke)
    oracle = _oracle_rows(smoke)
    hbm = _hbm_rows()
    halo = _halo_rows()
    distributed = _distributed_rows(smoke)
    ai = _ai_rows()
    payload = {
        "bitwise": bitwise, "oracle": oracle, "hbm": hbm, "halo": halo,
        "distributed": distributed, "ai": ai, "itemsize": ITEM,
        "contract": "spec-driven fused kernel bitwise-equal to "
                    "advect_fused for the PW spec; every operator within "
                    "the per-dtype tolerance of the f64 oracle; counted "
                    "Pallas HBM bytes == hbm_bytes_model(n_fields, "
                    "halo_depth) exactly; band schedules partition "
                    "radius*stages*T; counted distributed wire bytes == "
                    "halo_wire_bytes_model(depth=spec.halo(T)) exactly",
    }
    out_path = os.path.join(os.getcwd(), "BENCH_stencils.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    emit("stencils.json_written", 0.0, out_path)


if __name__ == "__main__":
    run(smoke=_bootstrap.smoke_arg())
