"""Distributed halo-exchange advection == single-device oracle (4-way mesh),
plus the T-fused distributed step (one depth-T halo exchange per T substeps).
"""
import pytest

pytestmark = pytest.mark.slow  # multi-minute module; -m "slow or not slow"

import textwrap

from _subproc import run_ok

CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import make_distributed_advect, reference_global
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params

    mesh = jax.make_mesh((4,), ("data",))
    for (X, Y, Z) in [(8, 32, 16), (5, 16, 24)]:
        u, v, w = stratus_fields(X, Y, Z)
        p = default_params(Z)
        fn = make_distributed_advect(mesh, p)
        sh = NamedSharding(mesh, P(None, "data", None))
        out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref = reference_global(u, v, w, p)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(out, ref))
        assert err < 1e-5, (X, Y, Z, err)
    # collective-permutes present (halo exchange, not gather)
    txt = jax.jit(fn).lower(*(jax.device_put(t, sh) for t in (u, v, w))
                            ).compile().as_text()
    assert txt.count("collective-permute") >= 6
    print("OK")
""")


def test_halo_exchange_matches_oracle():
    run_ok(CODE, timeout=300)


FUSED_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params

    mesh = jax.make_mesh((4,), ("data",))
    sh_done = False
    for (X, Y, Z) in [(6, 16, 12), (5, 24, 16)]:
        for T in (1, 2, 4):
            u, v, w = stratus_fields(X, Y, Z)
            p = default_params(Z)
            fn = make_distributed_step(mesh, p, T=T, dt=0.01)
            sh = NamedSharding(mesh, P(None, "data", None))
            out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
            ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
            err = max(float(jnp.max(jnp.abs(a - b)))
                      for a, b in zip(out, ref))
            assert err < 1e-5, (X, Y, Z, T, err)
            if T == 4 and not sh_done:
                # ONE depth-T exchange per T substeps: 6 permutes (3 fields
                # x 2 directions), independent of T
                txt = jax.jit(fn).lower(
                    *(jax.device_put(t, sh) for t in (u, v, w))
                    ).compile().as_text()
                n_perm = txt.count("collective-permute-start") or \
                    txt.count("collective-permute(")
                assert n_perm == 6, (T, n_perm)
                sh_done = True
    print("OK")
""")


def test_fused_distributed_step_matches_oracle():
    run_ok(FUSED_CODE, timeout=300)


KERNEL_CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (make_distributed_step,
                                           reference_global_step)
    from repro.stencil.advection import stratus_fields
    from repro.kernels.advection.ref import default_params

    # local_kernel="fused": the per-shard slab streams through the v4
    # Pallas kernel (global-interior mask freezing the wrapped rows),
    # composed with the kernel's in-grid (y_tile, x) tiling.
    mesh = jax.make_mesh((4,), ("data",))
    sh = NamedSharding(mesh, P(None, "data", None))
    for (X, Y, Z) in [(6, 16, 12), (5, 24, 16)]:
        for T in (1, 2, 4):
            for y_tile in (None, 3):
                u, v, w = stratus_fields(X, Y, Z)
                p = default_params(Z)
                fn = make_distributed_step(mesh, p, T=T, dt=0.01,
                                           local_kernel="fused",
                                           y_tile=y_tile)
                out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
                ref = reference_global_step(u, v, w, p, T=T, dt=0.01)
                err = max(float(jnp.max(jnp.abs(a - b)))
                          for a, b in zip(out, ref))
                assert err < 1e-5, (X, Y, Z, T, y_tile, err)
    print("OK")
""")


def test_distributed_step_fused_local_kernel_matches_oracle():
    run_ok(KERNEL_CODE, timeout=300)
