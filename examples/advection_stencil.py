"""The paper's application end-to-end: PW advection with the kernel ladder.

Steps a stratus-cloud test case with each kernel variant, checks they agree,
prints the per-variant modelled HBM traffic (the Fig. 3 ladder) including
the v4 temporal-fusion rung, and runs the distributed halo-exchange version
on a 4-way device mesh (subprocess, so this process keeps the single-device
view).

    PYTHONPATH=src python examples/advection_stencil.py
"""
import subprocess
import sys
import textwrap

import jax.numpy as jnp

from repro.stencil.advection import AdvectionDomain


def main():
    X, Y, Z = 12, 64, 128
    results = {}
    for variant in ("reference", "blocked", "dataflow", "wide", "fused"):
        # fuse_T=1 so every variant advances the same single Euler step;
        # the T=4 traffic win is printed separately below
        dom = AdvectionDomain(X, Y, Z, variant=variant, fuse_T=1, dt=0.1)
        u, v, w = dom.init()
        u2, v2, w2 = dom.step(u, v, w)
        results[variant] = u2
        print(f"{variant:10s}: HBM bytes/step (model) = "
              f"{dom.hbm_bytes_per_step()/1e6:8.2f} MB, "
              f"flops/step = {dom.flops_per_step()/1e6:.1f} MF")
    ref = results["reference"]
    for k, r in results.items():
        err = float(jnp.max(jnp.abs(r - ref)))
        assert err < 1e-4, (k, err)
        print(f"{k:10s} matches reference (max err {err:.2e})")

    print("\n-- temporal fusion (v4): T steps per HBM pass, in-grid tiled --")
    fdom = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=4, dt=0.1,
                           y_tile=32)          # tiling="grid" is the default
    u, v, w = fdom.init()
    out = fdom.advance(u, v, w, 4)   # one fused pass = 4 Euler substeps
    base = AdvectionDomain(X, Y, Z, variant="dataflow", dt=0.1)
    per_pass = fdom.hbm_bytes_per_step()
    per_4_steps = 4 * base.hbm_bytes_per_step()
    host = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=4, dt=0.1,
                           y_tile=32, tiling="host")
    print(f"fused T=4 : {per_pass/1e6:8.2f} MB per 4 steps "
          f"(dataflow would move {per_4_steps/1e6:.2f} MB) -> "
          f"{per_4_steps/per_pass:.1f}x amortisation; "
          f"VMEM register {fdom.vmem_register_bytes()/1e3:.0f} kB")
    print(f"            in-grid tiles serve "
          f"{fdom.vmem_halo_bytes_per_step()/1e3:.0f} kB of halo re-reads "
          f"from VMEM (the host-tiled loop restages "
          f"{(host.hbm_bytes_per_step()-per_pass)/1e3:.0f} kB via HBM)")
    assert jnp.all(jnp.isfinite(out[0]))

    print("\n-- fused Euler update in the v1-v3 kernels (fuse_update) --")
    sdom = AdvectionDomain(X, Y, Z, variant="dataflow", fuse_update=True,
                           dt=0.1, y_tile=32)
    su = sdom.step(u, v, w)
    err = float(jnp.max(jnp.abs(su[0] - base.step(u, v, w)[0])))
    print(f"dataflow fuse_update: advanced fields in-kernel, "
          f"{sdom.hbm_bytes_per_step()/1e6:.2f} MB/step vs "
          f"{base.hbm_bytes_per_step()/1e6:.2f} MB unfused (max err {err:.1e})")

    print("\n-- distributed halo exchange (4-way y-decomposition) --")
    code = textwrap.dedent("""
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, sys
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.stencil.distributed import (make_distributed_advect,
                                               make_distributed_step,
                                               reference_global,
                                               reference_global_step)
        from repro.stencil.advection import stratus_fields
        from repro.kernels.advection.ref import default_params
        mesh = jax.make_mesh((4,), ("data",))
        u, v, w = stratus_fields(8, 32, 16)
        p = default_params(16)
        fn = make_distributed_advect(mesh, p)
        sh = NamedSharding(mesh, P(None, "data", None))
        out = fn(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref = reference_global(u, v, w, p)
        err = max(float(jnp.max(jnp.abs(a-b))) for a, b in zip(out, ref))
        print(f"distributed == global oracle, max err {err:.2e}")
        assert err < 1e-5
        step = make_distributed_step(mesh, p, T=4, dt=0.05)
        out4 = step(*(jax.device_put(t, sh) for t in (u, v, w)))
        ref4 = reference_global_step(u, v, w, p, T=4, dt=0.05)
        err4 = max(float(jnp.max(jnp.abs(a-b))) for a, b in zip(out4, ref4))
        print(f"fused distributed step (T=4, one exchange) max err {err4:.2e}")
        assert err4 < 1e-5
        stepk = make_distributed_step(mesh, p, T=4, dt=0.05,
                                      local_kernel="fused", y_tile=4)
        outk = stepk(*(jax.device_put(t, sh) for t in (u, v, w)))
        errk = max(float(jnp.max(jnp.abs(a-b))) for a, b in zip(outk, ref4))
        print(f"  + v4 Pallas local kernel, in-grid y-tiles: err {errk:.2e}")
        assert errk < 1e-5
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin",
                                       "JAX_PLATFORMS": "cpu"})
    print(r.stdout.strip() or r.stderr[-500:])
    assert r.returncode == 0
    print("advection_stencil OK")


if __name__ == "__main__":
    main()
