"""Data-parallel training with int8 gradient compression + error feedback.

Demonstrates the distributed-optimisation feature for the DCN (pod) axis:
gradients cross the slow link int8-quantised (4x wire-byte cut), the
quantisation error is fed back next step. Runs on a 4-way device mesh in a
subprocess (shard_map over the DP axis — the explicit-collective trainer).

    PYTHONPATH=src python examples/train_compressed_dp.py
"""
import subprocess
import sys
import textwrap


CODE = textwrap.dedent("""
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import pspec
    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.distributed.compression import (compressed_tree_psum,
                                               init_residuals)
    from repro.training import optimizer as O

    cfg = get_smoke_config("qwen3-32b")
    layout = M.make_layout(cfg, tp=1)
    mesh = jax.make_mesh((4,), ("dp",))
    params = pspec.init_params(M.param_specs(cfg, layout), jax.random.PRNGKey(0))
    opt_state = O.init_opt_state(params)
    residuals = init_residuals(params)
    oc = O.OptConfig(peak_lr=3e-3, warmup_steps=2, total_steps=30)

    def local_grads(params, batch):
        loss, _ = M.loss_fn(params, batch, cfg, layout)
        return loss, jax.grad(lambda p: M.loss_fn(p, batch, cfg, layout)[0])(params)

    def dp_step(params, opt_state, residuals, batch, compress):
        def shard_fn(params, batch, residuals):
            loss, grads = local_grads(params, batch)
            if compress:
                grads, residuals = compressed_tree_psum(grads, "dp", residuals)
            else:
                grads = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), grads)
            loss = jax.lax.pmean(loss, "dp")
            return loss, grads, residuals
        pspec_b = jax.tree.map(lambda _: P("dp"), batch)
        loss, grads, residuals = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(), pspec_b, P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )(params, batch, residuals)
        params, opt_state, _ = O.adamw_update(params, grads, opt_state, oc)
        return loss, params, opt_state, residuals

    rng = np.random.default_rng(0)
    B, S = 8, 64
    losses = {True: [], False: []}
    for compress in (False, True):
        p, o, r = params, opt_state, residuals
        step = jax.jit(functools.partial(dp_step, compress=compress))
        for i in range(15):
            toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S + 1)))
            batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
            with mesh:
                loss, p, o, r = step(p, o, r, batch)
            losses[compress].append(float(loss))
    print("fp32 DP:", [f"{l:.3f}" for l in losses[False][::5]])
    print("int8+EF:", [f"{l:.3f}" for l in losses[True][::5]])
    gap = abs(losses[True][-1] - losses[False][-1])
    print(f"final-loss gap fp32 vs int8+error-feedback: {gap:.4f}")
    assert losses[True][-1] < losses[True][0], "compressed training must learn"
    assert gap < 0.35, gap
    print("train_compressed_dp OK (4x DCN wire bytes saved)")
""")


def main():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin",
                                       "JAX_PLATFORMS": "cpu"},
                       timeout=900)
    print(r.stdout.strip() or r.stderr[-2000:])
    assert r.returncode == 0, r.stderr[-2000:]


if __name__ == "__main__":
    main()
