"""Inputs made from the seed, on the device.

The wind fields follow the stratus test case's formula (smooth sines plus
1% Gaussian noise), computed in the served dtype inside one jitted call
and written straight into the sharding and layout the caller asks for, so
set-up never builds a grid on the host. The PW coefficients follow the
repository's stretched-grid formula; both are copies kept with the
benchmark, so the reference and the program are fed from here and the
reference takes nothing the program made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

NOISE = 0.01


def seed_key(seed: int):
    """A PRNG key from any whole number up to 64 bits: the low and high
    32-bit words are folded in separately."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def coefficients(Z: int, *, dx: float, dy: float, dz: float,
                 dtype=np.float32):
    """(tcx, tcy, tzc1, tzc2) of the PW stencil as host arrays: 0.25/dx,
    0.25/dy and the per-level z metric terms of a grid stretched by 0.1%
    per level, with a 0.2% density ratio either side."""
    k = np.arange(Z, dtype=np.float64)
    rdz = 1.0 / (dz * (1.0 + 0.001 * k))
    tzc1 = 0.25 * rdz * (1.0 - 0.002 * k)
    tzc2 = 0.25 * rdz * (1.0 + 0.002 * k)
    return (np.asarray(0.25 / dx, dtype), np.asarray(0.25 / dy, dtype),
            tzc1.astype(dtype), tzc2.astype(dtype))


def stratus(key, X: int, Y: int, Z: int, dtype=jnp.float32):
    """(u, v, w) of shape (X, Y, Z): the stratus formula in `dtype`."""
    f = jnp.float32
    kx = jnp.linspace(0, 2 * math.pi, X, dtype=f)[:, None, None]
    ky = jnp.linspace(0, 2 * math.pi, Y, dtype=f)[None, :, None]
    kz = jnp.linspace(0, math.pi, Z, dtype=f)[None, None, :]
    smooth = (5.0 * jnp.sin(kx + 0.5) * jnp.cos(ky) * jnp.sin(kz + 0.1),
              4.0 * jnp.cos(kx) * jnp.sin(ky + 0.3) * jnp.sin(kz),
              0.5 * jnp.sin(kx) * jnp.sin(ky) * jnp.cos(kz))
    keys = jax.random.split(key, 3)
    return tuple((s + NOISE * jax.random.normal(k, (X, Y, Z), f)).astype(dtype)
                 for s, k in zip(smooth, keys))


def make_grid(seed: int, shape, *, out_shardings=None, dtype=jnp.float32):
    """The stratus fields of one grid, generated where `out_shardings`
    places them (a sharding or a layout `Format`, or None)."""
    X, Y, Z = shape
    fn = jax.jit(lambda key: stratus(key, X, Y, Z, dtype),
                 out_shardings=out_shardings)
    return fn(seed_key(seed))


def make_members(seed: int, n: int, shape, *, spread: float,
                 dtype=jnp.float32):
    """`n` ensemble members of one domain as (n, X, Y, Z) arrays: the
    stratus fields of the seed, each member perturbed by its own Gaussian
    noise of standard deviation `spread`."""
    X, Y, Z = shape

    def gen(key):
        base = stratus(jax.random.fold_in(key, 0), X, Y, Z, jnp.float32)
        keys = jax.random.split(jax.random.fold_in(key, 1), 3)
        return tuple(
            (b[None] + spread * jax.random.normal(k, (n, X, Y, Z),
                                                  jnp.float32)).astype(dtype)
            for b, k in zip(base, keys))

    return jax.jit(gen)(seed_key(seed))

