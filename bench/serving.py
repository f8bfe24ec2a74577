"""What the serving drivers share: the engine as the configuration states
it, requests, and the check of finished jobs.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import numpy as np

from bench import fields as F
from bench import reference as REF
from bench import work as W


def make_engine(cfg):
    """`StencilServingEngine` over the configuration's padded slot; the
    finite guard and per-step snapshots stay at the engine's defaults."""
    from repro.serving.stencil_engine import StencilServingEngine
    from repro.stencil.advection import AdvectionDomain

    X, Y, Z = cfg["slot"]
    dom = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=cfg["T"],
                          dt=cfg["dt"], y_tile=cfg["y_tile"])
    return StencilServingEngine(dom, batch_size=cfg["batch_size"])


def request(uid: int, u, v, w, n_steps: int):
    from repro.serving.stencil_engine import StencilRequest
    return StencilRequest(uid=uid, u=u, v=v, w=w, n_steps=n_steps)


def warm_up(engine, reqs) -> None:
    """Serve `reqs` and drop the answers as the window does; every one has
    to come back done."""
    done = engine.run(reqs)
    bad = [r.uid for r in reqs
           if r.uid not in done or done[r.uid].status != "done"]
    if bad:
        raise RuntimeError(f"warm-up jobs not done: {bad}")
    for r in reqs:
        r.states = None


@dataclasses.dataclass
class Job:
    """A finished job kept for the check: its inputs and its answer."""
    u: np.ndarray
    v: np.ndarray
    w: np.ndarray
    n_steps: int
    out: Tuple[np.ndarray, np.ndarray, np.ndarray]


def useful_cells(u, n_steps: int, T: int) -> int:
    Xr, Yr, Z = u.shape
    return W.cell_substeps(Xr, Yr, Z, n_steps * T)


def check(jobs: Sequence[Job], cfg, *, max_steps: int, batch: int,
          control: bool = False) -> Tuple[float, Optional[float]]:
    """The widest relative gap between the finished jobs and the
    reference (and, with `control`, between the bfloat16 reference and
    the float32 one), over every field of every job. Jobs are padded to
    the slot, `batch` to a reference call."""
    import jax.numpy as jnp

    X, Y, Z = cfg["slot"]
    T = cfg["T"]
    coeffs = F.coefficients(Z, **cfg["spacing"])
    if not jobs:                     # nothing compared is never correct
        return float("inf"), (float("inf") if control else None)
    worst, worst_ctrl = 0.0, (0.0 if control else None)
    for lo in range(0, len(jobs), batch):
        chunk = list(jobs[lo:lo + batch])
        chunk += [chunk[-1]] * (batch - len(chunk))
        pad = [np.zeros((batch, X, Y, Z), np.float32) for _ in range(3)]
        for j, job in enumerate(chunk):
            Xr, Yr, _ = job.u.shape
            for dst, src in zip(pad, (job.u, job.v, job.w)):
                dst[j, :Xr, :Yr] = src
        extents = np.array([job.u.shape[:2] for job in chunk], np.int32)
        nsub = np.array([job.n_steps * T for job in chunk], np.int32)
        args = (tuple(jnp.asarray(p) for p in pad), coeffs, cfg["dt"],
                jnp.asarray(extents), jnp.asarray(nsub))
        want = [np.asarray(f) for f in REF.integrate_jobs(
            *args, max_substeps=max_steps * T)]
        low = ([np.asarray(f) for f in REF.integrate_jobs(
            *args, max_substeps=max_steps * T, dtype=jnp.bfloat16)]
            if control else None)
        for j, job in enumerate(chunk):
            Xr, Yr, _ = job.u.shape
            ref = [f[j, :Xr, :Yr] for f in want]
            worst = max(worst, REF.max_rel_err_host(job.out, ref))
            if control:
                worst_ctrl = max(worst_ctrl, REF.max_rel_err_host(
                    [f[j, :Xr, :Yr] for f in low], ref))
    return worst, worst_ctrl
