"""Chip smoke test: drive the stencil system's main paths once, compiled,
on TPU, and check what comes out.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --four-chips # a 2x2 host: the decomposed run only

Phase A is one long integration of the paper's 268M-cell grid
(4096x1024x64 f32) through `make_distributed_run` on a 1x1 mesh with the
compiled fused kernel, checked against the jnp reference on the same
chip. Phase B serves eight mixed-extent forecast jobs through
`StencilServingEngine` and checks two of them bitwise against the
per-domain compiled kernel. `--four-chips` runs the same grid
strong-scaled on a 2x2 mesh with both exchange engines, checks them
against each other bitwise and against the jnp reference on that mesh,
and checks that the state is spread over the four devices.

Timings printed on the way are smoke timings of one run, not benchmark
numbers. The last line is one JSON object naming the device. The script
exits nonzero, without that line, when JAX finds no TPU, when any phase
fails, and when a kernel on the path would run interpreted.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GRID = (4096, 1024, 64)        # stencil.advection.PAPER_GRIDS["268M"]
T, N_BLOCKS, DT = 4, 3, 0.01
Y_TILE = 128                   # in-grid y tile of the 268M slab
TOL = 1e-5                     # max |fused - reference|, as the tests use
SERVE_SLOT = (64, 256, 64)     # launch/serve.py --stencil slot shape
SERVE_T, SERVE_DT, SERVE_BATCH, SERVE_JOBS = 4, 0.005, 4, 8


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def n_kernels(fn, *args) -> int:
    """Mosaic kernels in `fn`'s lowered program (lowered from the
    arguments' shapes, never run): a kernel that would run in the Pallas
    interpreter lowers to plain HLO instead."""
    import jax
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=getattr(a, "sharding", None)),
        args)
    return jax.jit(fn).lower(*shapes).as_text().count("tpu_custom_call")


def require_kernels(name: str, fn, *args, at_least: int) -> None:
    n = n_kernels(fn, *args)
    if n < at_least:
        raise RuntimeError(f"{name}: {n} compiled Mosaic kernel(s) in the "
                           f"program, expected >= {at_least}")
    log(f"{name}: {n} compiled Mosaic kernel(s) in the program")


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def timed(fn, *args):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def max_err(a, b) -> float:
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(a, b))


def to_host(fields):
    return tuple(np.asarray(f) for f in fields)


def phase_integration(seed: int) -> None:
    """Phase A: 268M cells, one chip, N_BLOCKS blocks of T fused steps."""
    import jax

    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil import distributed as D
    from repro.stencil.advection import stratus_fields_host

    X, Y, Z = GRID
    t0 = time.perf_counter()
    host = stratus_fields_host(X, Y, Z, seed=seed)
    log(f"A: built the {X}x{Y}x{Z} stratus fields on the host in "
        f"{time.perf_counter() - t0:.1f}s")
    p = default_params(Z)
    mesh = make_stencil_mesh(1, 1)
    fmt = D.field_formats(mesh, axis="y", x_axis="x")
    run = D.make_distributed_run(mesh, p, n_blocks=N_BLOCKS, axis="y",
                                 x_axis="x", T=T, dt=DT,
                                 local_kernel="fused", y_tile=Y_TILE,
                                 donate=True)
    fields = tuple(jax.device_put(f, fmt) for f in host)
    require_kernels("A: make_distributed_run", run, *fields, at_least=1)
    out, first_s = timed(run, *fields)       # donates `fields`
    fused = to_host(out)
    if not all(np.isfinite(f).all() for f in fused):
        raise RuntimeError("A: the fused integration produced non-finite "
                           "values")
    out, warm_s = timed(run, *out)           # continues, donating `out`
    del out, fields
    log(f"A: smoke timings (one run, not a benchmark): first call "
        f"{first_s:.2f}s (compile + run), warm call {warm_s:.2f}s for "
        f"{N_BLOCKS * T} steps, compile ~{first_s - warm_s:.2f}s")
    log(f"A: peak_bytes_in_use {peak_bytes()}")

    # the reference on the same chip, once the fused buffers are gone
    ref_step = jax.jit(lambda u, v, w: D.reference_global_step(
        u, v, w, p, T=T, dt=DT))
    ref = tuple(jax.device_put(f) for f in host)
    for _ in range(N_BLOCKS):
        ref = ref_step(*ref)
    err = max_err(fused, to_host(ref))
    log(f"A: max |fused - reference| over {N_BLOCKS * T} steps = {err:.3e} "
        f"(limit {TOL:g})")
    if not err < TOL:
        raise RuntimeError(f"A: fused run differs from the reference by "
                           f"{err:.3e} >= {TOL:g}")


def phase_serving(seed: int) -> None:
    """Phase B: the serving engine at the launch/serve.py slot shape."""
    import jax

    from repro.kernels.advection import advection as K
    from repro.serving.stencil_engine import (StencilRequest,
                                              StencilServingEngine)
    from repro.stencil.advection import AdvectionDomain, stratus_fields_host

    X, Y, Z = SERVE_SLOT
    dom = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=SERVE_T,
                          dt=SERVE_DT)
    engine = StencilServingEngine(dom, batch_size=SERVE_BATCH)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(SERVE_JOBS):
        Xr, Yr = int(rng.integers(4, X + 1)), int(rng.integers(4, Y + 1))
        u, v, w = stratus_fields_host(Xr, Yr, Z, seed=seed + i)
        reqs.append(StencilRequest(uid=i, u=u, v=v, w=w,
                                   n_steps=int(rng.integers(1, 5))))
    B = SERVE_BATCH
    slot = jax.ShapeDtypeStruct((B, X, Y, Z), np.float32)
    params = jax.tree.map(lambda a: jax.ShapeDtypeStruct((B,) + a.shape,
                                                         a.dtype), dom.params)
    require_kernels(
        "B: serving mega-step",
        lambda u, v, w, pp, xm, ym: K.advect_fused_batched(
            u, v, w, pp, T=SERVE_T, dt=SERVE_DT, x_interior_mask=xm,
            y_interior_mask=ym, guard=True),
        slot, slot, slot, params,
        jax.ShapeDtypeStruct((B, X), np.float32),
        jax.ShapeDtypeStruct((B, Y), np.float32), at_least=2)
    t0 = time.perf_counter()
    done = engine.run(reqs)
    wall = time.perf_counter() - t0
    log(f"B: smoke timing (one run, not a benchmark): {len(done)} jobs in "
        f"{wall:.2f}s including compilation")
    log(f"B: peak_bytes_in_use {peak_bytes()}")
    bad = {u: r.status for u, r in done.items() if r.status != "done"}
    if len(done) != SERVE_JOBS or bad:
        raise RuntimeError(f"B: jobs not done: {bad} ({len(done)} returned)")
    h = engine.health()
    if (h["faults_injected"] or h["degradations"] or h["quarantines"]
            or h["quarantined_uids"] or h["exchange"] != dom.exchange):
        raise RuntimeError(f"B: unhealthy engine: {h}")
    step = jax.jit(lambda u, v, w: K.advect_fused(u, v, w, dom.params,
                                                  T=SERVE_T, dt=SERVE_DT))
    for req in reqs[:2]:
        f = (req.u, req.v, req.w)
        for _ in range(req.n_steps):
            f = step(*f)
        if not all(np.array_equal(a, np.asarray(b))
                   for a, b in zip(done[req.uid].out, f)):
            raise RuntimeError(f"B: job {req.uid} differs from the "
                               f"per-domain compiled kernel")
        log(f"B: job {req.uid} ({req.u.shape[0]}x{req.u.shape[1]}, "
            f"{req.n_steps} steps) bitwise equal to per-domain advect_fused")


def phase_four_chips(seed: int) -> None:
    """The 268M grid strong-scaled on a 2x2 mesh, both exchange engines."""
    import jax

    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil import distributed as D
    from repro.stencil.advection import stratus_fields_host

    X, Y, Z = GRID
    host = stratus_fields_host(X, Y, Z, seed=seed)
    p = default_params(Z)
    mesh = make_stencil_mesh(2, 2)
    fields = tuple(jax.device_put(f, D.field_formats(mesh, axis="y",
                                                      x_axis="x"))
                   for f in host)
    results = {}
    for exchange, lk in (("collective", "fused"), ("remote_dma", "fused"),
                         ("collective", "reference")):
        run = D.make_distributed_run(mesh, p, n_blocks=N_BLOCKS, axis="y",
                                     x_axis="x", T=T, dt=DT,
                                     local_kernel=lk, y_tile=Y_TILE,
                                     exchange=exchange)
        name = f"4 chips: {exchange} engine, {lk} local kernel"
        if lk == "fused":
            require_kernels(name, run, *fields,
                            at_least=1 + 2 * (exchange == "remote_dma"))
        out, first_s = timed(run, *fields)
        _, warm_s = timed(run, *fields)
        log(f"{name}: smoke timings (not a benchmark): first call "
            f"{first_s:.2f}s, warm call {warm_s:.2f}s")
        shards = out[0].addressable_shards
        devices = {s.device for s in shards}
        shapes = {s.data.shape for s in shards}
        if len(devices) != 4 or shapes != {(X // 2, Y // 2, Z)}:
            raise RuntimeError(f"{name}: state on {len(devices)} device(s) "
                               f"with shard shapes {shapes}")
        results[(exchange, lk)] = to_host(out)
        del out
    log(f"4 chips: state spans 4 devices, shard shape "
        f"{(X // 2, Y // 2, Z)}")
    col = results[("collective", "fused")]
    dma = results[("remote_dma", "fused")]
    if not all(np.array_equal(a, b) for a, b in zip(col, dma)):
        raise RuntimeError(f"4 chips: engines differ by "
                           f"{max_err(col, dma):.3e}")
    log("4 chips: collective and remote_dma engines bitwise equal")
    err = max_err(col, results[("collective", "reference")])
    log(f"4 chips: max |fused - reference| = {err:.3e} (limit {TOL:g})")
    if not err < TOL:
        raise RuntimeError(f"4 chips: fused differs from the reference by "
                           f"{err:.3e}")
    log(f"4 chips: peak_bytes_in_use on device 0 {peak_bytes()}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 decomposed integration")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    import jax

    log(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"no TPU: JAX found {devices[0].platform!r} devices")
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        log(f"needs {want} TPU chip(s), found {len(devices)}")
        return 1
    log(f"device: {devices[0].device_kind} x{len(devices)}")
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_integration(args.seed)
        phase_serving(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
