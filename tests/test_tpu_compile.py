"""Compile the main-path kernels for a TPU v5e at the sizes the chip runs.

Nothing here runs on a chip: a `v5e:2x2` topology is described (the TPU
compiler is installed alongside JAX), and each program is lowered and
compiled for it from shapes alone, in Mosaic, with `interpret=False`. A
compile that passes says the chip's compiler accepts the kernel's block
layouts, VMEM and HBM footprint; it says nothing about results or time
(`chip_smoke.py` checks those on the chip).

Covered, at real size: `advect_fused` on the 268M-cell grid with the
y-tile `chip_smoke.py` uses, `finite_guard` at 268M, the serving
mega-step (`advect_fused_batched`, guard on) at the `launch/serve.py
--stencil` slot shape, the serving engine's donated mega-step, slot
write and crop at the ensemble cell's slot shape, the 1x1
`make_distributed_run` with donated fields, the 2x2 `make_distributed_run` with both exchange engines, and the 2x2
remote-DMA cell's 4096x2048x64 run with donated fields.
Each program's text also names its kernels (`name=` on every
`pallas_call`) and the run's block phases (`jax.named_scope`), the names
a device trace shows. The topology is described inside a fixture, so
only the worker that runs this file loads the TPU library, and the
persistent compilation cache is off while these compile (a compile for a
described chip cannot be read back from it).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.kernels.advection import advection as K
from repro.kernels.advection.ref import default_params
from repro.stencil import distributed as D

GRID = (4096, 1024, 64)      # PAPER_GRIDS["268M"]
Y_TILE = 128                 # chip_smoke.py's y-tile for this grid
T = 4
HBM_BYTES = 16 * 2 ** 30     # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mesh(topo, nx, ny):
    devs = np.array(topo.devices[:nx * ny]).reshape(nx, ny)
    return Mesh(devs, ("x", "y"), axis_types=(AxisType.Auto,) * 2)


def _params(sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        default_params(GRID[2]))


def _hbm(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _n_kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _names(compiled, *names) -> bool:
    """Every one of `names` is in the compiled program's text (a kernel's
    `name=` becomes its instruction's name and its op_name)."""
    text = compiled.as_text()
    return all(n in text for n in names)


def _fused_rings(traced):
    """The ring scratch of every `advect_fused` kernel in a traced program:
    one ``(T, 3, S, lanes)`` shape per field, in order."""
    from repro.analysis.jaxpr import walk_jaxpr
    rings = []

    def visit(eqn):
        if (eqn.primitive.name == "pallas_call"
                and "advect_fused" in str(eqn.params.get("name"))):
            n = eqn.params["grid_mapping"].num_scratch_operands
            scratch = eqn.params["jaxpr"].invars[-n:]
            rings.extend(tuple(v.aval.shape) for v in scratch[:3])

    walk_jaxpr(traced.jaxpr.jaxpr, visit)
    return rings


# the compiled ring: slab rows 128 + 2 x the T=4 halo rounded up to the
# 8-row sublane tile; Z=64 widened to the 128-lane vreg
RING_268M = (T, 3, Y_TILE + 2 * 8, 128)


def test_fused_268m_compiles(one_chip):
    fmt = K.field_format(one_chip)
    f = jax.ShapeDtypeStruct(GRID, jnp.float32, sharding=fmt)
    tr = jax.jit(
        lambda u, v, w, p: K.advect_fused(u, v, w, p, T=T, dt=0.01,
                                          interpret=False, y_tile=Y_TILE),
        out_shardings=(fmt,) * 3,
    ).trace(f, f, f, _params(one_chip))
    assert _fused_rings(tr) == [RING_268M] * 3
    c = tr.lower().compile()
    assert _n_kernels(c) == 1
    assert _names(c, "%advect_fused", "advect_fused/pallas_call")
    # fields in the kernel's layout: three in, three out, nothing else
    assert c.memory_analysis().temp_size_in_bytes == 0
    assert _hbm(c) <= HBM_BYTES


def test_finite_guard_268m_compiles(one_chip):
    f = jax.ShapeDtypeStruct(GRID, jnp.float32, sharding=one_chip)
    c = jax.jit(lambda u, v, w: K.finite_guard(u, v, w, interpret=False)
                ).lower(f, f, f).compile()
    assert _n_kernels(c) == 1
    assert _names(c, "%finite_guard", "finite_guard/pallas_call")
    assert c.memory_analysis().output_size_in_bytes == GRID[0] * 4


def test_serving_mega_step_compiles(one_chip):
    B, X, Y, Z = 4, 64, 256, 64
    f = jax.ShapeDtypeStruct((B, X, Y, Z), jnp.float32, sharding=one_chip)
    p = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((B,) + a.shape, a.dtype,
                                       sharding=one_chip),
        default_params(Z))
    xm = jax.ShapeDtypeStruct((B, X), jnp.float32, sharding=one_chip)
    ym = jax.ShapeDtypeStruct((B, Y), jnp.float32, sharding=one_chip)
    tr = jax.jit(
        lambda u, v, w, p, xm, ym: K.advect_fused_batched(
            u, v, w, p, T=T, dt=0.005, interpret=False, x_interior_mask=xm,
            y_interior_mask=ym, guard=True),
    ).trace(f, f, f, p, xm, ym)
    assert _fused_rings(tr) == [(T, 3, Y, 128)] * 3    # untiled slab
    c = tr.lower().compile()
    assert _n_kernels(c) == 2          # the fused mega-launch + the guard
    assert _names(c, "%vmap_advect_fused_", "vmap(advect_fused)/pallas_call",
                  "%finite_guard", "finite_guard/pallas_call")


def test_serving_device_batch_is_updated_in_place(one_chip):
    """At the ensemble cell's size (13 slots of 16x1024x64, y_tile 128)
    the engine's mega-step writes its fields over the donated batch, a
    prime writes one slot of it in place, and a crop makes one slot's
    fields alone: the device holds one batch besides the step's own
    working set."""
    from repro.serving import stencil_engine as E
    from repro.stencil.advection import AdvectionDomain

    B, X, Y, Z = 13, 16, 1024, 64
    eng = E.StencilServingEngine(
        AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, dt=0.005,
                        y_tile=128, interpret=False), batch_size=B)
    fields = 3 * B * X * Y * Z * 4
    f = jax.ShapeDtypeStruct((B, X, Y, Z), jnp.float32, sharding=one_chip)
    p = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((B,) + a.shape, a.dtype,
                                       sharding=one_chip),
        default_params(Z))
    xm = jax.ShapeDtypeStruct((B, X), jnp.float32, sharding=one_chip)
    ym = jax.ShapeDtypeStruct((B, Y), jnp.float32, sharding=one_chip)
    step = eng._build_step().lower(f, f, f, p, xm, ym).compile()
    assert _n_kernels(step) == 2
    assert step.memory_analysis().alias_size_in_bytes == fields
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    job = jax.ShapeDtypeStruct((X, Y, Z), jnp.float32, sharding=one_chip)
    write = E._write_slot.lower((f,) * 3, slot, (job,) * 3).compile()
    assert write.memory_analysis().alias_size_in_bytes == fields
    crop = E._crop_slot.lower((f,) * 3, slot, (X, Y)).compile()
    # one slot's three fields, and the output tuple's small table
    out = crop.memory_analysis().output_size_in_bytes
    assert fields // B <= out < fields // B + 4096


def test_run_268m_one_chip_fits(topo):
    mesh = _mesh(topo, 1, 1)
    fmt = D.field_formats(mesh, axis="y", x_axis="x")
    run = D.make_distributed_run(mesh, default_params(GRID[2]), n_blocks=3,
                                 axis="y", x_axis="x", T=T, dt=0.01,
                                 local_kernel="fused", y_tile=Y_TILE,
                                 donate=True)
    f = jax.ShapeDtypeStruct(GRID, jnp.float32, sharding=fmt)
    c = jax.jit(run, in_shardings=(fmt,) * 3, out_shardings=(fmt,) * 3,
                donate_argnums=(0, 1, 2)).lower(f, f, f).compile()
    assert _n_kernels(c) == 1
    # one block's compute phase, named, inside the loop
    assert _names(c, "%advect_fused", "block/compute/advect_fused")
    # donated fields carry the loop state: 6 GiB in, 6 GiB out, at Z=64
    # padded to the 128-lane row
    assert _hbm(c) <= 13 * 2 ** 30 < HBM_BYTES


@pytest.mark.parametrize("exchange", ["collective", "remote_dma"])
def test_run_268m_2x2_compiles(topo, exchange):
    mesh = _mesh(topo, 2, 2)
    fmt = D.field_formats(mesh, axis="y", x_axis="x")
    run = D.make_distributed_run(mesh, default_params(GRID[2]), n_blocks=3,
                                 axis="y", x_axis="x", T=T, dt=0.01,
                                 local_kernel="fused", y_tile=Y_TILE,
                                 exchange=exchange)
    f = jax.ShapeDtypeStruct(GRID, jnp.float32, sharding=fmt)
    c = jax.jit(run, in_shardings=(fmt,) * 3, out_shardings=(fmt,) * 3
                ).lower(f, f, f).compile()
    text = c.as_text()
    # the fused kernel, plus one remote-DMA kernel per exchange phase
    assert _n_kernels(c) == (3 if exchange == "remote_dma" else 1)
    assert ("collective-permute" in text) == (exchange == "collective")
    assert _names(c, "block/exchange_x/", "block/exchange_y/",
                  "block/compute/advect_fused")
    assert ("halo_band_exchange_dma" in text) == (exchange == "remote_dma")
    assert _hbm(c) <= HBM_BYTES


def test_run_537m_2x2_remote_dma_cell_fits(topo):
    """The `integ-537m-2x2-dma` cell's program: 4096x2048x64 split 2x2,
    remote-DMA exchange, donated, 3 blocks a call. Each phase's DMA
    kernel carries its own name, so a device trace tells them apart."""
    grid = (4096, 2048, 64)
    mesh = _mesh(topo, 2, 2)
    fmt = D.field_formats(mesh, axis="y", x_axis="x")
    run = D.make_distributed_run(mesh, default_params(grid[2]), n_blocks=3,
                                 axis="y", x_axis="x", T=T, dt=0.01,
                                 local_kernel="fused", y_tile=Y_TILE,
                                 exchange="remote_dma", donate=True)
    f = jax.ShapeDtypeStruct(grid, jnp.float32, sharding=fmt)
    tr = jax.jit(run, in_shardings=(fmt,) * 3, out_shardings=(fmt,) * 3,
                 donate_argnums=(0, 1, 2)).trace(f, f, f)
    # each chip's 1032-row extended slab runs the 268M grid's tiles
    assert _fused_rings(tr) == [RING_268M] * 3
    c = tr.lower().compile()
    assert _n_kernels(c) == 3
    assert _names(c, "%halo_band_exchange_dma_x", "%halo_band_exchange_dma_y",
                  "block/compute/advect_fused")
    # per chip: 3 GiB of fields in (aliased out), ~6 GiB of extended slabs
    assert _hbm(c) <= HBM_BYTES
