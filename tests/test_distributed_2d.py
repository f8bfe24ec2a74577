"""2D (x, y) mesh decomposition == single-device oracle: equivalence sweeps
over (nx, ny, T, local_kernel, y_tile, overlap, dtype), the 4-device
corner-exchange regression (the x-then-y two-phase contract), and the
multi-hop depth-T exchange that lifts the old T <= local-extent limit.

Subprocess idiom (`tests/_subproc.run_ok`): meshes come from
`jax.make_mesh` on 4 forced host devices, and the child env
pins JAX_PLATFORMS=cpu so jax never probes libtpu (the old timeout flake).
A cheap single-device wiring test stays in the fast tier.
"""
import textwrap

import pytest

from _subproc import run_ok as _run


SWEEP_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh

    X, Y, Z = 8, 12, 10
    p = default_params(Z)
    # (local_kernel, y_tile, overlap): y_tile=5 does NOT divide any shard's
    # local Y (12, 6 or 3 rows + 2T halo) — the non-divisible tile shapes
    for nx, ny in ((2, 2), (1, 4), (4, 1)):
        mesh = make_stencil_mesh(nx, ny)
        sh = NamedSharding(mesh, P("x", "y", None))
        for T in (1, 2, 3):
            for lk, yt, ov in (("reference", None, False),
                               ("reference", None, True),
                               ("fused", None, True),
                               ("fused", 5, False)):
                u, v, w = stratus_fields(X, Y, Z)
                fn = make_distributed_step(mesh, p, axis="y", x_axis="x",
                                           T=T, dt=0.01, local_kernel=lk,
                                           y_tile=yt, overlap=ov)
                out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
                ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
                err = max(float(jnp.max(jnp.abs(a - b)))
                          for a, b in zip(out, ref))
                assert err < 1e-5, (nx, ny, T, lk, yt, ov, err)
    # dtype sweep: bfloat16 end-to-end (kernel + exchange + oracle all
    # bf16; looser tolerance bounds the accumulated rounding)
    mesh = make_stencil_mesh(2, 2)
    sh = NamedSharding(mesh, P("x", "y", None))
    for lk in ("reference", "fused"):
        u, v, w = stratus_fields(X, Y, Z, dtype=jnp.bfloat16)
        fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=2,
                                   dt=0.01, local_kernel=lk)
        out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref = reference_global_step(u, v, w, p, T=2, dt=0.01)
        err = max(float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                        - jnp.asarray(b, jnp.float32))))
                  for a, b in zip(out, ref))
        assert err < 0.1, (lk, err)
    print("OK")
""")


@pytest.mark.slow
def test_2d_decomposition_matches_oracle_sweep():
    _run(SWEEP_CODE)


CORNER_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.roofline import halo_wire_bytes_model
    from repro.stencil.distributed import (count_exchange_wire_bytes,
                                           make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh

    # 2x2 mesh, T=2: the four cells within T of BOTH interior cuts depend
    # on the diagonal-neighbour shard; they only come out right if the
    # y-phase exchanges the x-EXTENDED slab (corners ride phase 2)
    X, Y, Z, T = 8, 8, 12, 2
    u, v, w = stratus_fields(X, Y, Z, seed=5)
    p = default_params(Z)
    mesh = make_stencil_mesh(2, 2)
    sh = NamedSharding(mesh, P("x", "y", None))
    fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=T, dt=0.01,
                               local_kernel="fused", overlap=True)
    out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
    ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
    cut_x, cut_y = X // 2, Y // 2
    win_x = slice(cut_x - T, cut_x + T)
    win_y = slice(cut_y - T, cut_y + T)
    for a, b in zip(out, ref):
        corner = np.abs(np.asarray(a)[win_x, win_y]
                        - np.asarray(b)[win_x, win_y])
        assert float(corner.max()) < 1e-5, float(corner.max())
    # the corner bytes are priced: counted ppermute operands must include
    # the 2T extra columns of the x-extended phase-2 rows (reordering the
    # phases would shrink the count and break the cells above)
    got = count_exchange_wire_bytes(fn, u, v, w)
    model = halo_wire_bytes_model(X, Y, Z, 4, nx=2, ny=2, T=T)
    assert got == model, (got, model)
    # a phase ordering that exchanged y on the UNextended slab would send
    # exactly 2T*2T*Z fewer elements per field — the corner blocks
    no_corner = 3 * 4 * (2 * T * (Y // 2) * Z + 2 * T * (X // 2) * Z)
    assert got == no_corner + 3 * 4 * 2 * T * 2 * T * Z, (got, no_corner)
    print("OK")
""")


@pytest.mark.slow
def test_corner_exchange_regression_2x2():
    _run(CORNER_CODE)


MULTIHOP_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params

    # Yl = 4 per shard: T=6 needs 2 ppermute hops, T=10 needs 3; T=14 is
    # the global bound (Y-2), T=15 must raise. Both local kernels.
    X, Y, Z = 6, 16, 12
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    mesh = jax.make_mesh((4,), ("data",))
    sh = NamedSharding(mesh, P(None, "data", None))
    # overlap=True composed with multi-hop: the interior/boundary select
    # must hold when the T-deep bands swallow whole shards (T > Yl)
    for T in (6, 10, 14):
        for lk in ("reference", "fused"):
            fn = make_distributed_step(mesh, p, T=T, dt=0.005,
                                       local_kernel=lk, overlap=(T == 10))
            out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
            ref = reference_global_step(u, v, w, p, T=T, dt=0.005)
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(out, ref))
            assert err < 1e-5, (T, lk, err)
    try:
        fn = make_distributed_step(mesh, p, T=15)
        fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        raise SystemExit("T=15 on Y=16 should have raised")
    except ValueError as e:
        assert "exceeds the decomposable global Y" in str(e), e
    # multi-hop along x too: Xl=2 per shard on a (4, 1) mesh, T=3 -> 2 hops
    X2 = 8
    u2, v2, w2 = stratus_fields(X2, Y, Z)
    mesh2 = jax.make_mesh((4, 1), ("x", "y"))
    sh2 = NamedSharding(mesh2, P("x", "y", None))
    fn = make_distributed_step(mesh2, p, axis="y", x_axis="x", T=3, dt=0.01,
                               local_kernel="fused")
    out = fn(*(jax.device_put(t, sh2) for t in (u2, v2, w2)))
    ref = reference_global_step(u2, v2, w2, p, T=3, dt=0.01)
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, ref))
    assert err < 1e-5, err
    print("OK")
""")


@pytest.mark.slow
def test_multi_hop_depth_T_exchange():
    _run(MULTIHOP_CODE)


def test_2d_wiring_single_device():
    """Fast-tier wiring check: a (1, 1) 'mesh' exercises the 2D code path
    (specs, masks, trim) without any exchange; full multi-device coverage
    lives in the slow subprocess sweeps above."""
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
    for lk in ("reference", "fused"):
        fn = make_distributed_step(mesh, p, axis="y", x_axis="x", T=2,
                                   dt=0.01, local_kernel=lk, overlap=True)
        out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref = reference_global_step(u, v, w, p, T=2, dt=0.01)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, ref))
        assert err < 1e-5, (lk, err)
