"""The remote-DMA exchange engine vs the collective oracle: interpret-mode
outputs must be BITWISE equal across (nx, ny, T, dtype, overlap,
local_kernel) — the two engines assemble the same extended slab through
different transports — and the engine's counted wire bytes must match
`halo_wire_bytes_model` exactly. Multi-device sweeps use the subprocess
idiom (`tests/_subproc.run_ok`, JAX_PLATFORMS=cpu pinned); fast-tier cases
cover wiring, ring-neighbour math and the multi-hop trace contract of the
compiled DMA kernel (one `make_async_remote_copy` per `_band_schedule`
hop; the pipelined K-block driver rides tests/test_pipeline_driver.py).
"""
import textwrap

import pytest

from _subproc import run_ok as _run


# --- fast tier: wiring + pure helpers --------------------------------------

def test_remote_dma_wiring_single_device():
    """(1, 1) 'mesh': the engine dispatch, masks and trim run with no
    exchange; both engines must agree with the global oracle and each
    other. Covers both dma_block_index parities."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil.advection import stratus_fields
    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)

    X, Y, Z = 6, 10, 8
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    mesh = make_stencil_mesh(1, 1)
    sh = NamedSharding(mesh, P("x", "y", None))
    args = [jax.device_put(t, sh) for t in (u, v, w)]
    ref = reference_global_step(u, v, w, p, T=2, dt=0.01)
    for block in (0, 1):
        fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=2,
                                   dt=0.01, local_kernel="fused",
                                   overlap=True, exchange="remote_dma",
                                   dma_block_index=block)
        out = fn(*args)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(out, ref))
        assert err < 1e-5, (block, err)


def test_band_schedule_partitions_halo():
    """Hop offsets/counts tile the hi and lo halo regions exactly —
    the recv-slab addresses the DMA kernel and emulation share."""
    from repro.stencil.distributed import _band_schedule

    for L, depth in ((8, 3), (4, 4), (4, 6), (4, 10), (3, 14), (5, 1)):
        sched = _band_schedule(L, depth)
        hi = sorted((off, off + cnt) for _, cnt, off, _ in sched)
        lo = sorted((off, off + cnt) for _, cnt, _, off in sched)
        covered = [r for span in hi for r in range(*span)]
        assert covered == list(range(depth)), (L, depth, hi)
        covered = [r for span in lo for r in range(*span)]
        assert covered == list(range(depth + L, 2 * depth + L)), (L, depth)
        assert sum(cnt for _, cnt, _, _ in sched) == depth


def test_ring_neighbor_math():
    from repro.launch.mesh import dma_neighbor_coords, ring_neighbor

    assert ring_neighbor(0, 4, -1) == 3
    assert ring_neighbor(3, 4, 1) == 0
    assert ring_neighbor(2, 4, 2) == 0
    with pytest.raises(ValueError):
        ring_neighbor(0, 0, 1)
    coords = dma_neighbor_coords(("x", "y"), (1, 3), "y", 1, 4)
    assert coords == (1, 0)
    coords = dma_neighbor_coords(("x", "y"), (0, 2), "x", -1, 2)
    assert coords == (1, 2)
    with pytest.raises(ValueError, match="not in mesh axes"):
        dma_neighbor_coords(("x",), (0,), "z", 1, 2)


def test_dma_kernel_validates_args():
    """Argument validation fails fast on any backend, before any Pallas
    construction. Depth beyond the local extent is NOT an error any more
    (multi-hop landed — `test_dma_kernel_traces_under_shard_map` traces
    it); the only remaining depth bound, T > global extent - 2, lives in
    the step/run drivers."""
    import jax.numpy as jnp

    from repro.kernels.advection.advection import halo_band_exchange_dma

    f = jnp.zeros((4, 8, 16), jnp.float32)
    with pytest.raises(ValueError, match="dim"):
        halo_band_exchange_dma(f, f, f, axis="x", mesh_axes=("x",), n=2,
                               depth=2, dim=2)
    with pytest.raises(ValueError, match="depth"):
        halo_band_exchange_dma(f, f, f, axis="x", mesh_axes=("x",), n=2,
                               depth=0, dim=0)


def test_dma_kernel_traces_under_shard_map():
    """Abstract tracing of the real `make_async_remote_copy` kernel (both
    phases, both slot parities, single- AND multi-hop depths) must succeed
    on any backend — Mosaic lowering is TPU-only, but a trace regression
    would break the compiled path silently until the next TPU run."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.advection.advection import halo_band_exchange_dma
    from repro.launch.mesh import make_stencil_mesh

    mesh = make_stencil_mesh(1, 1)
    spec = P("x", "y", None)
    # depth 10 > L=8 rows (2 hops), depth 14 > L=6 planes (3 hops)
    for dim, depth, block in ((0, 2, 0), (1, 2, 1), (1, 10, 0),
                              (0, 14, 1)):
        def local(u, v, w, dim=dim, depth=depth, block=block):
            bands = halo_band_exchange_dma(
                u, v, w, axis=("x", "y")[dim], mesh_axes=mesh.axis_names,
                n=4, depth=depth, dim=dim, block_index=block,
                collective_id=dim)
            (uh, ul), _, _ = bands
            return uh + ul
        fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3,
                           out_specs=spec, check_vma=False)
        jax.make_jaxpr(fn)(*[jnp.zeros((6, 8, 16), jnp.float32)] * 3)


def test_dma_kernel_traces_with_traced_block_index():
    """The dynamic-parity bugfix: a TRACED block counter (the pipelined
    driver's fori_loop induction variable) must flow through the recv-slot
    selection — Python-level `o[slot]` indexing would raise a
    TracerIntegerConversionError here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.advection.advection import halo_band_exchange_dma
    from repro.launch.mesh import make_stencil_mesh

    mesh = make_stencil_mesh(1, 1)
    spec = P("x", "y", None)

    def local(u, v, w, k):
        bands = halo_band_exchange_dma(
            u, v, w, axis="y", mesh_axes=mesh.axis_names, n=4, depth=10,
            dim=1, block_index=k, collective_id=1)
        (uh, ul), _, _ = bands
        return uh + ul

    fn = jax.shard_map(local, mesh=mesh, in_specs=(spec,) * 3 + (P(),),
                       out_specs=spec, check_vma=False)
    jax.make_jaxpr(fn)(*[jnp.zeros((6, 8, 16), jnp.float32)] * 3,
                       jnp.int32(3))


# --- slow tier: multi-device bitwise equivalence ---------------------------

BITWISE_SWEEP_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.roofline import halo_wire_bytes_model
    from repro.stencil.distributed import (count_exchange_wire_bytes,
                                           make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh

    X, Y, Z = 8, 12, 10
    p = default_params(Z)
    for dtype in (jnp.float32, jnp.bfloat16):
        u, v, w = stratus_fields(X, Y, Z, dtype=dtype)
        for nx, ny in ((2, 2), (1, 4), (4, 1)):
            mesh = make_stencil_mesh(nx, ny)
            sh = NamedSharding(mesh, P("x", "y", None))
            args = [jax.device_put(t, sh) for t in (u, v, w)]
            for T in (1, 2, 3):
                for lk, ov in (("reference", False), ("reference", True),
                               ("fused", True), ("fused", False)):
                    kw = dict(axis="y", x_axis="x", T=T, dt=0.01,
                              local_kernel=lk, overlap=ov)
                    fc = make_distributed_step(mesh, p,
                                               exchange="collective", **kw)
                    fr = make_distributed_step(mesh, p,
                                               exchange="remote_dma", **kw)
                    oc, orr = fc(*args), fr(*args)
                    # BITWISE: both engines assemble the same extended slab
                    diff = max(float(jnp.max(jnp.abs(
                        jnp.asarray(a, jnp.float32)
                        - jnp.asarray(b, jnp.float32))))
                        for a, b in zip(oc, orr))
                    assert diff == 0.0, (dtype, nx, ny, T, lk, ov, diff)
                    got = count_exchange_wire_bytes(fr, u, v, w)
                    model = halo_wire_bytes_model(X, Y, Z, u.dtype.itemsize,
                                                  nx=nx, ny=ny, T=T)
                    assert got == model, (dtype, nx, ny, T, lk, got, model)
                # against the global oracle too (f32 only: bf16 tolerance
                # is the dtype sweep's business in test_distributed_2d)
                if dtype == jnp.float32:
                    fr = make_distributed_step(mesh, p, axis="y",
                                               x_axis="x", T=T, dt=0.01,
                                               local_kernel="fused",
                                               exchange="remote_dma")
                    ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
                    err = max(float(jnp.max(jnp.abs(a - b)))
                              for a, b in zip(fr(*args), ref))
                    assert err < 1e-5, (nx, ny, T, err)
    print("OK")
""")


@pytest.mark.slow
def test_remote_dma_bitwise_equals_collective_sweep():
    _run(BITWISE_SWEEP_CODE)


MULTIHOP_EMULATION_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.roofline import halo_wire_bytes_model
    from repro.stencil.distributed import (count_exchange_wire_bytes,
                                           make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params

    # Yl = 4 per shard: T=6 takes 2 band messages (hops) per side, T=10
    # takes 3 — the emulation's per-hop recv-slab offsets must reproduce
    # the collective's multi-hop concatenation bitwise, and the per-hop
    # messages must still sum to exactly the modelled wire bytes.
    X, Y, Z = 6, 16, 12
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    mesh = jax.make_mesh((4,), ("data",))
    sh = NamedSharding(mesh, P(None, "data", None))
    args = [jax.device_put(t, sh) for t in (u, v, w)]
    for T in (6, 10, 14):
        fc = make_distributed_step(mesh, p, T=T, dt=0.005,
                                   exchange="collective")
        fr = make_distributed_step(mesh, p, T=T, dt=0.005,
                                   exchange="remote_dma")
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(fc(*args), fr(*args)))
        assert diff == 0.0, (T, diff)
        got = count_exchange_wire_bytes(fr, u, v, w)
        model = halo_wire_bytes_model(X, Y, Z, 4, ny=4, T=T)
        assert got == model, (T, got, model)
        ref = reference_global_step(u, v, w, p, T=T, dt=0.005)
        err = max(float(jnp.max(jnp.abs(a - b)))
                  for a, b in zip(fr(*args), ref))
        assert err < 1e-5, (T, err)
    print("OK")
""")


@pytest.mark.slow
def test_remote_dma_emulation_multi_hop():
    _run(MULTIHOP_EMULATION_CODE)
