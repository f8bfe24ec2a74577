"""Production mesh construction (assignment-prescribed shapes).

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis Auto: the drivers here place arrays
    with `NamedSharding` / `with_sharding_constraint` and run `shard_map`,
    which assume Auto axes rather than the Explicit default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_stencil_mesh(nx: int, ny: int, *, x_axis: str = "x",
                      y_axis: str = "y"):
    """(nx, ny) device mesh for the 2D-decomposed stencil step: each shard
    owns an (X/nx, Y/ny, Z) slab under
    `stencil.distributed.make_distributed_step(axis=y_axis, x_axis=x_axis)`.
    The mesh takes the first nx*ny of this process's devices.
    """
    return _auto_mesh((nx, ny), (x_axis, y_axis),
                      devices=jax.devices()[:nx * ny])


def ring_neighbor(idx, n: int, delta: int):
    """Logical ring coordinate of the `delta`-away neighbour on an n-shard
    mesh axis (wraps periodically — wrapped halo data must be frozen by the
    caller's global-interior mask, exactly as for the ppermute engine).

    Pure index math, usable both host-side and on traced values (Python %
    on a traced value follows jnp.mod's sign-of-divisor semantics, so
    delta=-1 at coordinate 0 wraps to n-1): the remote-DMA exchange kernel
    computes its `make_async_remote_copy` `device_id` mesh coordinates
    through `dma_neighbor_coords`, which builds the full coordinate tuple.
    """
    if n < 1:
        raise ValueError(f"axis size must be >= 1, got {n}")
    return (idx + delta) % n


def dma_neighbor_coords(mesh_axes, my_coords, axis: str, delta: int,
                        n: int):
    """Mesh-coordinate tuple addressing the `delta`-away ring neighbour
    along `axis` (an n-shard ring), holding every other axis coordinate
    fixed — the `device_id` (``DeviceIdType.MESH``) the in-kernel
    remote-DMA exchange kernel (`_kernel_band_dma`) sends its boundary
    bands to. `mesh_axes`/`my_coords` are parallel over the mesh's axis
    order; coordinates may be traced values."""
    if axis not in mesh_axes:
        raise ValueError(f"axis {axis!r} not in mesh axes {tuple(mesh_axes)}")
    return tuple(
        ring_neighbor(c, n, delta) if a == axis else c
        for a, c in zip(mesh_axes, my_coords))


def resize_stencil_mesh(nx: int, ny: int, *, x_axis: str = "x",
                        y_axis: str = "y"):
    """Elastic rebuild of the stencil mesh: the device-loss recovery path
    (`serving.faults.resilient_distributed_run`) gathers to host, calls
    this to lay out the survivors (shrink) or the returned fleet
    (regrow), and re-shards onto the result. Same shape contract as
    `make_stencil_mesh`, plus a CLEAR error when the requested shape
    exceeds what this process can see — the failure mode of resharding
    UP after a loss that was real."""
    if nx < 1 or ny < 1:
        raise ValueError(f"mesh shape must be >= 1, got ({nx}, {ny})")
    avail = len(jax.devices())
    if nx * ny > avail:
        raise ValueError(
            f"cannot build a ({nx}, {ny}) stencil mesh: needs {nx * ny} "
            f"devices, {avail} available to this process")
    return make_stencil_mesh(nx, ny, x_axis=x_axis, y_axis=y_axis)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(*, model: int = 1):
    """Whatever this host offers (smoke tests / examples on CPU)."""
    n = len(jax.devices())
    data = n // model
    return _auto_mesh((data, model), ("data", "model"))


def tp_degree(mesh) -> int:
    return mesh.shape.get("model", 1)
