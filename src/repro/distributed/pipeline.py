"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The production mesh's ``pod`` axis can run as a pipeline dimension: layer
stacks are split into S contiguous stages (one per pod), microbatches stream
through, and stage boundaries move activations with `ppermute` — point-to-
point DCN traffic instead of the all-reduce a pure-DP pod axis needs. The
bubble fraction is the usual (S-1)/(T+S-1).

`pipeline_apply` is schedule-exact GPipe: at step t, stage s computes
microbatch (t-s); results equal the sequential layer stack bit-for-bit
(tests/test_pipeline_parallel.py). Works with any per-layer block fn
(the LM blocks in repro.models plug in directly).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stacked_params, xs, block_fn: Callable, mesh: Mesh,
                   axis: str = "pod"):
    """Run a layer stack as a pipeline over `axis`.

    stacked_params: pytree with leading dim L (layers), sharded over `axis`
                    (L % n_stages == 0; each stage owns L/S contiguous layers)
    xs:             (n_micro, micro_batch, ...) microbatched activations
    block_fn:       (layer_params, x) -> x
    Returns (n_micro, micro_batch, ...) outputs, replicated over `axis`.
    """
    n_stage = mesh.shape[axis]
    n_micro = xs.shape[0]

    def local_stack(local_params, x):
        def body(c, p):
            return block_fn(p, c), ()
        y, _ = jax.lax.scan(body, x, local_params)
        return y

    def stage_fn(local_params, xs_local):
        s = jax.lax.axis_index(axis)
        T = n_micro + n_stage - 1
        buf = jnp.zeros_like(xs_local[0])          # incoming activation
        outs = jnp.zeros_like(xs_local)

        def step(t, carry):
            buf, outs = carry
            inject = xs_local[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(s == 0, inject, buf)
            y = local_stack(local_params, x_in)
            # forward the activation to the next stage (ring permute; the
            # wrap-around edge's payload is never consumed)
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stage) for i in range(n_stage)])
            idx = t - (n_stage - 1)
            valid = (s == n_stage - 1) & (idx >= 0) & (idx < n_micro)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.clip(idx, 0, n_micro - 1)].set(y),
                lambda o: o, outs)
            return (y_next, outs)

        _, outs = jax.lax.fori_loop(0, T, step, (buf, outs))
        # broadcast the last stage's collected outputs to every stage
        last = (s == n_stage - 1).astype(outs.dtype)
        return jax.lax.psum(outs * last, axis)

    fn = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P(axis), P()),      # params split by stage; xs replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, xs)


def bubble_fraction(n_stage: int, n_micro: int) -> float:
    return (n_stage - 1) / (n_micro + n_stage - 1)
