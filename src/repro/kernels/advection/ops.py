"""Jit'd public wrappers for the PW-advection kernel ladder.

`pw_advect(..., variant=...)` selects the Fig. 3 rung; `interpret` toggles
Pallas interpret mode (CPU validation) vs compiled TPU execution, and
defaults to compiled on a TPU backend, interpret elsewhere. `y_tile`
runs the in-grid 2D `(y_tile, x)` tiling by default (`tiling="grid"`, one
kernel launch, no HBM halo restaging); `tiling="host"` keeps the retained
per-block host loop for comparison. `fuse_update=True` makes the v1-v3
rungs return advanced fields (`f + dt*s` fused in-kernel) instead of raw
sources. `pw_advect_fused` is the v4 temporal-blocking entry point: it
always returns the *advanced fields* after `T` fused Euler steps.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.advection import advection as K
from repro.kernels.advection import ref as REF

# source-computing rungs dispatchable via pw_advect; the v4 `fused` rung
# advances whole steps instead and has its own entry point, pw_advect_fused
VARIANTS = {
    "reference": None,
    "blocked": K.advect_blocked,
    "dataflow": K.advect_dataflow,
    "wide": K.advect_wide,
}


@functools.partial(jax.jit, static_argnames=("variant", "interpret", "y_tile",
                                             "tiling", "fuse_update", "dt"))
def pw_advect(u, v, w, params: REF.AdvectParams, *, variant: str = "dataflow",
              interpret: Optional[bool] = None,
              y_tile: Optional[int] = None,
              tiling: str = "grid",
              fuse_update: bool = False,
              dt: float = 1.0
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Momentum sources (or advanced fields with `fuse_update=True`) via the
    selected ladder rung (v1-v3 + reference)."""
    if variant == "fused":
        raise ValueError("fused advances fields, not sources; "
                         "use pw_advect_fused")
    if variant == "reference":
        if fuse_update:
            return REF.pw_step_ref(u, v, w, params, dt)
        return REF.pw_advect_ref(u, v, w, params)
    fn = VARIANTS[variant]
    return fn(u, v, w, params, interpret=interpret, y_tile=y_tile,
              tiling=tiling, fuse_update=fuse_update, dt=dt)


@functools.partial(jax.jit,
                   static_argnames=("T", "dt", "interpret", "y_tile",
                                    "tiling"))
def pw_advect_fused(u, v, w, params: REF.AdvectParams, *, T: int = 4,
                    dt: float = 1.0, interpret: Optional[bool] = None,
                    y_tile: Optional[int] = None,
                    tiling: str = "grid"
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Advance (u, v, w) by T fused Euler steps in one HBM pass (v4)."""
    return K.advect_fused(u, v, w, params, T=T, dt=dt, interpret=interpret,
                          y_tile=y_tile, tiling=tiling)


def traffic_model(shape, itemsize: int, variant: str, *, T: int = 1,
                  y_tile: Optional[int] = None, grid_tiled: bool = True,
                  fuse_update: bool = True) -> int:
    X, Y, Z = shape
    return K.hbm_bytes_model(X, Y, Z, itemsize,
                             "pointwise" if variant == "reference" else variant,
                             T=T, y_tile=y_tile, grid_tiled=grid_tiled,
                             fuse_update=fuse_update)
