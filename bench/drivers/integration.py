"""Traffic kind `integration`: one long integration of one grid.

The client calls `make_distributed_run` back to back, each call running
`n_blocks` blocks of T fused substeps and donating the state it got from
the call before, and waits for each call as a user stepping a forecast
does. Set-up makes the grid on the device from the seed and makes
`warmup_calls` calls, which compile; the window then calls until
`seconds` have passed. The grid's whole state after the window is
compared with the reference run from the same fields for the same
number of substeps.

Traffic keys: `n_blocks`, `warmup_calls`.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import fields as F
from bench import harness as H
from bench import reference as REF
from bench import work as W


def _program(cfg, traffic, devices):
    """The system under test: its mesh, where its fields live, its run."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.advection.ref import AdvectParams
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil import distributed as D

    nx, ny = cfg["mesh"]
    mesh = make_stencil_mesh(nx, ny)
    if devices[0].platform == "tpu":
        where = D.field_formats(mesh, axis="y", x_axis="x")
    else:
        where = NamedSharding(mesh, P("x", "y", None))
    coeffs = F.coefficients(cfg["Z"], **cfg["spacing"])
    run = D.make_distributed_run(
        mesh, AdvectParams(*(jnp.asarray(c) for c in coeffs)),
        n_blocks=traffic["n_blocks"], axis="y", x_axis="x", T=cfg["T"],
        dt=cfg["dt"], local_kernel=cfg["local_kernel"],
        y_tile=cfg["y_tile"], exchange=cfg["exchange"],
        donate=cfg["donate"])
    return where, run


def _reference_mesh(devices):
    """The reference's own layout: the grid split in X over the chips."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("r",))
    return mesh, NamedSharding(mesh, P("r", None, None))


def run(cell, seed: int, seconds: float, traced: bool, t0: float, *,
        devices, control: bool = False) -> H.Outcome:
    cfg, traffic = cell.config, cell.traffic
    shape = (cfg["X"], cfg["Y"], cfg["Z"])
    substeps_per_call = traffic["n_blocks"] * cfg["T"]

    where, program = _program(cfg, traffic, devices)
    with H.uncached():
        state = F.make_grid(seed, shape, out_shardings=where)
        state = jax.block_until_ready(program(*state))
    for _ in range(traffic["warmup_calls"] - 1):
        state = jax.block_until_ready(program(*state))
    calls = traffic["warmup_calls"]

    start = time.perf_counter()
    setup_s = start - t0
    n = 0
    with H.window(cell.name, traced) as cap:
        while True:
            with H.span("submit"):
                state = program(*state)
            with H.span("wait"):
                jax.block_until_ready(state)
            n += 1
            if time.perf_counter() - start >= seconds:
                break
    window_s = time.perf_counter() - start
    peak = H.memory_peak_bytes(devices)
    calls += n
    H.log(f"window: {n} calls of {substeps_per_call} substeps in "
          f"{window_s:.3f} s; set-up {setup_s:.3f} s")

    # the reference runs once the program's state is off the device
    t_ref = time.perf_counter()
    got = [np.asarray(f) for f in state]
    del state, program
    gc.collect()
    coeffs = F.coefficients(cfg["Z"], **cfg["spacing"])
    mesh, sharding = _reference_mesh(devices)
    substeps = calls * substeps_per_call
    want = REF.integrate(F.make_grid(seed, shape, out_shardings=sharding),
                         coeffs, cfg["dt"], substeps, mesh=mesh)
    err = REF.max_rel_err(got, want)
    H.log(f"check: {substeps} reference substeps, the state moved and "
          f"compared, in {time.perf_counter() - t_ref:.3f} s")
    ctrl = None
    if control:
        low = REF.integrate(F.make_grid(seed, shape, out_shardings=sharding),
                            coeffs, cfg["dt"], substeps, mesh=mesh,
                            dtype=jnp.bfloat16)
        ctrl = {"max_rel_err": REF.max_rel_err(low, want)}
    del want, got

    cells = W.cell_substeps(*shape, substeps_per_call) * n
    return H.Outcome(
        attempted=n, failed=0,
        end_to_end={"setup_s": setup_s,
                    "cell_updates_per_s": cells / window_s / 1e9,
                    "peak_hbm_gib": peak / 2 ** 30},
        counters={"calls": n, "blocks": n * traffic["n_blocks"],
                  "window_s": window_s,
                  "work_bytes": n * W.min_hbm_bytes(*shape)},
        checks=[H.Check("max_rel_err", err, cfg["limit_max_rel_err"])],
        devices=devices, memory_peak_bytes=peak,
        trace=H.reduce_trace(cap, devices), control=ctrl)
