"""2D (x, y) mesh decomposition sweep: weak/strong scaling of the fused
distributed step — the Fig. 8 endgame that unlocks the 268M-cell
(4096, 1024, 64) grid.

Three row families, written to ``BENCH_scaling2d.json``:

  * ``strong[]``  — the 268M grid on growing (nx, ny) meshes: per-shard HBM
    bytes (`AdvectionDomain.hbm_bytes_per_shard_step`, the halo'd-slab
    kernel pass), per-shard wire bytes (`roofline.halo_wire_bytes_model`,
    the ONE depth-T two-phase exchange per T substeps), and the resulting
    three-term roofline (`RooflineTerms`, exchange bytes feeding
    ``collective_s``). GATE: per-shard HBM bytes fall STRICTLY as the
    device count grows.
  * ``weak[]``    — fixed per-shard slab, growing mesh: per-shard HBM and
    wire bytes must be CONSTANT (gated) — the flat-line that makes the
    decomposition scale-free.
  * ``counted[]`` / ``measured[]`` — a subprocess on 4 forced host CPU
    devices builds the real `make_distributed_step` per mesh shape, walks
    its jaxpr with `count_exchange_wire_bytes`, and GATES counted ==
    modelled wire bytes EXACTLY (the x-then-y corner contract: phase-2
    operands are x-extended); it also runs the fused step in interpret
    mode for wallclock + equivalence vs `reference_global_step`.

Every gate is an explicit ``SystemExit`` raise (never ``assert``), so the
CI `benchmark-smoke` job keeps failing under ``python -O`` /
``PYTHONOPTIMIZE``. ``--quick`` / ``BENCH_SMOKE=1`` shrinks the subprocess
part for CI.
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

from benchmarks.common import emit
from repro.core.roofline import HBM_PER_CHIP
from repro.stencil.advection import PAPER_GRIDS, AdvectionDomain

ITEM = 4  # f32

STRONG_GRID = PAPER_GRIDS["268M"]               # (4096, 1024, 64)
STRONG_MESHES = [(1, 1), (2, 1), (2, 2), (4, 2), (4, 4), (8, 4),
                 (8, 8), (16, 8), (16, 16)]     # devices: 1 .. 256
WEAK_SHARD = (256, 128, 64)
WEAK_MESHES = [(2, 2), (2, 4), (4, 4), (8, 4), (8, 8), (16, 16)]
T_SWEEP = (4, 8)
Y_TILE = 128


def _domain(X, Y, Z, nx, ny, T):
    return AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T,
                           y_tile=Y_TILE, mesh_nx=nx, mesh_ny=ny)


def _row(dom, nx, ny, T):
    X, Y, Z = dom.X, dom.Y, dom.Z
    n_dev = nx * ny
    shard_hbm = dom.hbm_bytes_per_shard_step()
    wire = dom.halo_wire_bytes_per_step()
    terms = dom.roofline_terms()
    Xl, Yl = dom.shard_shape()
    # steady-state HBM residency per shard: fields in+out + the VMEM ring's
    # HBM shadow is negligible; the point is the 268M grid fitting
    resident = 2 * 3 * Xl * Yl * Z * ITEM
    return {
        "grid": [X, Y, Z], "mesh": [nx, ny], "devices": n_dev, "T": T,
        "y_tile": Y_TILE,
        "shard_shape": [Xl, Yl],
        "hbm_bytes_per_shard_step": shard_hbm,
        "halo_wire_bytes_per_step": wire,
        "wire_bytes_per_substep": wire / T,
        "memory_s": terms.memory_s,
        "collective_s": terms.collective_s,
        "compute_s": terms.compute_s,
        "step_time_s": terms.step_time_s,
        "bound": terms.bound,
        "hbm_resident_frac": resident / HBM_PER_CHIP,
    }


def _strong_rows():
    X, Y, Z = STRONG_GRID
    rows = []
    for T in T_SWEEP:
        prev = None
        for nx, ny in STRONG_MESHES:
            r = _row(_domain(X, Y, Z, nx, ny, T), nx, ny, T)
            # the acceptance gate: growing the mesh must STRICTLY cut the
            # per-shard HBM pass — otherwise the decomposition isn't
            # unlocking anything. Explicit raise: python -O safe.
            if prev is not None and r["hbm_bytes_per_shard_step"] >= prev:
                raise SystemExit(
                    f"scaling2d gate: per-shard HBM bytes "
                    f"{r['hbm_bytes_per_shard_step']} did not fall below "
                    f"{prev} at mesh ({nx}, {ny}), T={T}")
            prev = r["hbm_bytes_per_shard_step"]
            emit(f"scaling2d.strong.268M.T{T}.{nx}x{ny}",
                 r["step_time_s"] * 1e6,
                 f"shard_hbm_B={r['hbm_bytes_per_shard_step']};"
                 f"wire_B={r['halo_wire_bytes_per_step']};"
                 f"bound={r['bound']}")
            rows.append(r)
    return rows


def _weak_rows():
    Xl, Yl, Z = WEAK_SHARD
    rows = []
    for T in T_SWEEP:
        base = None
        for nx, ny in WEAK_MESHES:
            r = _row(_domain(Xl * nx, Yl * ny, Z, nx, ny, T), nx, ny, T)
            key = (r["hbm_bytes_per_shard_step"],
                   r["halo_wire_bytes_per_step"])
            if base is None:
                base = key
            elif key != base:
                raise SystemExit(
                    f"scaling2d gate: weak-scaling per-shard bytes "
                    f"{key} drifted from {base} at mesh ({nx}, {ny}), "
                    f"T={T} — the decomposition is not scale-free")
            emit(f"scaling2d.weak.T{T}.{nx}x{ny}",
                 r["step_time_s"] * 1e6,
                 f"shard_hbm_B={r['hbm_bytes_per_shard_step']};"
                 f"wire_B={r['halo_wire_bytes_per_step']}")
            rows.append(r)
    return rows


_SUB_CODE = textwrap.dedent("""
    import json, os, sys, time
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.roofline import halo_wire_bytes_model
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil.advection import stratus_fields
    from repro.stencil.distributed import (count_exchange_wire_bytes,
                                           make_distributed_step,
                                           reference_global_step)

    cfg = json.loads(sys.argv[1])
    X, Y, Z = cfg["grid"]
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    counted, measured = [], []
    for nx, ny in cfg["meshes"]:
        mesh = make_stencil_mesh(nx, ny)
        sh = NamedSharding(mesh, P("x", "y", None))
        args = [jax.device_put(t, sh) for t in (u, v, w)]
        for T in cfg["T"]:
            for lk, ov in (("reference", False), ("fused", True)):
                fn = make_distributed_step(mesh, p, axis="y", x_axis="x",
                                           T=T, dt=0.01, local_kernel=lk,
                                           overlap=ov)
                got = count_exchange_wire_bytes(fn, u, v, w)
                model = halo_wire_bytes_model(X, Y, Z, 4, nx=nx, ny=ny, T=T)
                counted.append({"mesh": [nx, ny], "T": T,
                                "local_kernel": lk, "overlap": ov,
                                "counted_wire_bytes": got,
                                "modelled_wire_bytes": model})
            fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=T,
                                       dt=0.01, local_kernel="fused",
                                       y_tile=cfg["y_tile"])
            out = fn(*args)
            ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(out, ref))
            ts = []
            for _ in range(cfg["iters"]):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                ts.append(time.perf_counter() - t0)
            measured.append({"mesh": [nx, ny], "T": T,
                             "y_tile": cfg["y_tile"],
                             "interpret_us": sorted(ts)[len(ts) // 2] * 1e6,
                             "max_err_vs_oracle": err})
    print(json.dumps({"counted": counted, "measured": measured}))
""")


def _subprocess_rows(smoke: bool):
    """Counted wire bytes + interpret-mode equivalence on 4 forced host
    devices. Subprocess because the device count must be fixed by XLA_FLAGS
    before jax initialises (tests/test_distributed_stencil.py idiom)."""
    cfg = {"grid": [8, 8, 16], "y_tile": 3, "iters": 1 if smoke else 3,
           "meshes": [[2, 2], [1, 4]] if smoke else [[2, 2], [1, 4], [4, 1]],
           "T": [2] if smoke else [1, 2]}
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
        raise SystemExit(f"scaling2d subprocess failed:\n{r.stderr[-3000:]}")
    payload = json.loads(r.stdout.strip().splitlines()[-1])
    for row in payload["counted"]:
        if row["counted_wire_bytes"] != row["modelled_wire_bytes"]:
            raise SystemExit(
                f"scaling2d gate: counted wire bytes "
                f"{row['counted_wire_bytes']} != modelled "
                f"{row['modelled_wire_bytes']} for {row}")
        emit(f"scaling2d.counted.{row['mesh'][0]}x{row['mesh'][1]}"
             f".T{row['T']}.{row['local_kernel']}", 0.0,
             f"wire_B={row['counted_wire_bytes']};model_exact=True")
    for row in payload["measured"]:
        if row["max_err_vs_oracle"] > 1e-4:
            raise SystemExit(
                f"scaling2d gate: 2D fused step err "
                f"{row['max_err_vs_oracle']} vs oracle for {row}")
        emit(f"scaling2d.measured.{row['mesh'][0]}x{row['mesh'][1]}"
             f".T{row['T']}", row["interpret_us"],
             f"err={row['max_err_vs_oracle']:.2e}")
    return payload["counted"], payload["measured"]


def run(smoke: bool = None) -> None:
    if smoke is None:
        smoke = os.environ.get("BENCH_SMOKE", "") == "1"
    strong = _strong_rows()
    weak = _weak_rows()
    counted, measured = _subprocess_rows(smoke)
    payload = {
        "strong": strong, "weak": weak,
        "counted": counted, "measured": measured,
        "itemsize": ITEM,
        "contract": "strong: per-shard HBM bytes strictly fall with mesh "
                    "size; weak: per-shard HBM+wire bytes constant; "
                    "counted ppermute bytes == halo_wire_bytes_model "
                    "exactly; 2D fused step matches the global oracle",
    }
    out_path = os.path.join(os.getcwd(), "BENCH_scaling2d.json")
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    emit("scaling2d.json_written", 0.0, out_path)


if __name__ == "__main__":
    run(smoke=_bootstrap.smoke_arg())
