"""Serving driver: continuous-batching engine over a (smoke) checkpoint.

    python -m repro.launch.serve --arch qwen3-32b --smoke --requests 8
Optionally --ckpt-dir to serve trained weights (elastic TP relayout applies).

`--stencil` serves forecast jobs instead of tokens: batched multi-domain
advection over the fused kernel (`repro.serving.stencil_engine`), with
`--max-new` bounding each job's fused-step budget and `--fault-plan`
injecting a deterministic fault schedule (`serving.faults.FaultPlan`
spec grammar: ``kind@step[:key=val,...]`` clauses joined by ``;``) whose
recovery counters print as the health surface:

    python -m repro.launch.serve --smoke --stencil --requests 4 \
        --fault-plan "nan_poison@1:slot=1;device_loss@2:reshard_to=1"

`--lose-device-at` is the DEPRECATED single-fault alias — it builds a
one-device-loss plan.

On a TPU the forecast jobs run the compiled fused kernel (interpret mode
only where JAX has no TPU), and compiled programs persist in the cache
`launch.compile_cache` chooses.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import pspec
from repro.configs import get_config, get_smoke_config
from repro.models import model as M
from repro.serving.engine import Request, ServingEngine
from repro.training import checkpoint as CKPT
from repro.training import step as TS


def _run_stencil(args) -> None:
    from repro.kernels.advection.advection import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.stencil_engine import (StencilRequest,
                                              StencilServingEngine)
    from repro.stencil.advection import AdvectionDomain, stratus_fields

    from repro.serving.faults import Fault, FaultPlan

    enable_compile_cache()
    dev = jax.devices()[0]
    mode = "interpret" if resolve_interpret() else "compiled"
    print(f"[serve] device {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}, fused kernel {mode}")
    X, Y, Z, T = (12, 16, 64, 2) if args.smoke else (64, 256, 64, 4)
    dom = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, dt=0.005)
    plan = None
    if args.fault_plan is not None:
        if args.lose_device_at is not None:
            raise SystemExit("--lose-device-at is a deprecated alias for "
                             "--fault-plan; pass only one")
        plan = FaultPlan.parse(args.fault_plan)
    elif args.lose_device_at is not None:
        print("[serve] --lose-device-at is deprecated; use --fault-plan "
              f'"device_loss@{args.lose_device_at}"')
        plan = FaultPlan((Fault("device_loss",
                                at_step=args.lose_device_at),))
    engine = StencilServingEngine(dom, batch_size=args.batch_size,
                                  fault_plan=plan)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(args.requests):
        Xr = int(rng.integers(4, X + 1))
        Yr = int(rng.integers(4, Y + 1))
        u, v, w = stratus_fields(Xr, Yr, Z, seed=i)
        reqs.append(StencilRequest(
            uid=i, u=np.asarray(u), v=np.asarray(v), w=np.asarray(w),
            n_steps=int(rng.integers(1, args.max_new + 1))))
    t0 = time.time()
    done = engine.run(reqs)
    dt_s = time.time() - t0
    steps = sum(len(r.states) for r in done.values() if r.states)
    stats = engine.cache_stats()
    print(f"[serve] {len(done)} forecast domains, {steps} fused steps "
          f"(T={T}) in {dt_s:.1f}s; executable cache "
          f"hits={stats['hits']} misses={stats['misses']} "
          f"evictions={stats['evictions']}")
    print(f"[serve] modelled serving throughput at batch={engine.B}: "
          f"{engine.modelled_throughput():.1f} domains/s")
    h = engine.health()
    print(f"[serve] health: faults={h['faults_injected']} "
          f"retries={h['retries']} quarantines={h['quarantines']} "
          f"rollbacks={h['rollbacks']} degradations={h['degradations']} "
          f"reshards={h['reshards']} exchange={h['exchange']}")
    print(f"[serve] link: {h['bytes_to_device']} B to the device, "
          f"{h['bytes_to_host']} B to the host")
    for t_line in h["transitions"]:
        print(f"  [health] {t_line}")
    for uid in sorted(done)[:4]:
        r = done[uid]
        if r.status == "quarantined":
            print(f"  job {uid}: QUARANTINED ({r.error})")
            continue
        print(f"  job {uid}: extent {r.out[0].shape}, {len(r.states)} "
              f"streamed states, |u|max={float(np.abs(r.out[0]).max()):.3f}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-32b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--stencil", action="store_true",
                    help="serve batched advection-forecast jobs instead of "
                         "tokens")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--fault-plan", default=None,
                    help="(--stencil) deterministic fault schedule, e.g. "
                         "'nan_poison@1:slot=1;device_loss@2:reshard_to=1' "
                         "(serving.faults.FaultPlan.parse grammar)")
    ap.add_argument("--lose-device-at", type=int, default=None,
                    help="(--stencil) DEPRECATED alias for --fault-plan "
                         "'device_loss@K': simulate a device loss after "
                         "this many mega-steps, re-shard to half the slots")
    args = ap.parse_args()

    if args.stencil:
        _run_stencil(args)
        return

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    layout = M.make_layout(cfg, tp=1)
    if args.ckpt_dir:
        like = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype),
                            pspec.abstract_params(TS.state_specs(cfg, layout)))
        state, step = CKPT.restore(args.ckpt_dir, like, cfg=cfg, layout=layout)
        params = jax.tree.map(jax.numpy.asarray, state["params"])
        print(f"[serve] restored step {step} from {args.ckpt_dir}")
    else:
        params = pspec.init_params(M.param_specs(cfg, layout),
                                   jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch_size=args.batch_size,
                           max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        int(rng.integers(4, 24))).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    total = sum(len(v) for v in done.values())
    print(f"[serve] {len(done)} requests, {total} tokens in {dt:.1f}s "
          f"({total/dt:.1f} tok/s aggregate)")
    for uid in sorted(done)[:4]:
        print(f"  req {uid}: {done[uid][:10]}")


if __name__ == "__main__":
    main()
