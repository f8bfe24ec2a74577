"""Traffic kind `ensemble_backlog`: a closed backlog of ensembles.

Each `run()` call hands the engine one whole ensemble at once: `members`
full-slot domains, each the configuration's `n_steps` fused steps. The client waits for the
ensemble, counts it, drops it, and calls again until `seconds` have
passed; the window ends when the last call returns. The members are one
stratus domain from the seed, each perturbed by its own noise of
standard deviation `spread`, made on the device in set-up, where
`warmup_calls` whole ensembles are served first. After the
window, `sample_per_call` members of each call, drawn from the seed, are
compared with the reference.

Traffic keys: `members`, `warmup_calls`, `spread`, `sample_per_call`,
`check_batch`.
"""
from __future__ import annotations

import time

import numpy as np

from bench import fields as F
from bench import harness as H
from bench import serving as S
from bench import work as W


def run(cell, seed: int, seconds: float, traced: bool, t0: float, *,
        devices, control: bool = False) -> H.Outcome:
    cfg, tr = cell.config, cell.traffic
    X, Y, Z = cfg["slot"]
    T, M, steps = cfg["T"], tr["members"], cfg["n_steps"]
    U, V, Wf = (np.asarray(a) for a in F.make_members(
        seed, M, (X, Y, Z), spread=tr["spread"]))
    engine = S.make_engine(cfg)

    def ensemble(call):
        return [S.request(call * M + i, U[i], V[i], Wf[i], steps)
                for i in range(M)]

    # whole ensembles, as the window sends them and dropping their states
    # as it does: the first compiles the mega-step, and the first calls
    # of a process run slower than later ones. Even so, each window's
    # first call takes ~1.7x its second (on a TPU v5e host ~6.7 s, then
    # ~3.9 s) after 1 or 3 warm-up calls alike; why is not known.
    for k in range(tr["warmup_calls"]):
        S.warm_up(engine, ensemble(-1 - k))
    rng = np.random.default_rng(seed)

    m0 = engine.megasteps_executed
    kept, useful, work, attempted, failed, calls = [], 0, 0, 0, 0, 0
    call_s = []
    start = time.perf_counter()
    setup_s = start - t0
    with H.window(cell.name, traced) as cap:
        while True:
            reqs = ensemble(calls)
            t_call = time.perf_counter()
            with H.span("run() call"):
                done = engine.run(reqs)
            call_s.append(time.perf_counter() - t_call)
            keep = set(rng.choice(M, tr["sample_per_call"], replace=False))
            for i, r in enumerate(reqs):
                d = done.get(r.uid)
                attempted += 1
                if d is None or d.status != "done":
                    failed += 1
                    continue
                useful += S.useful_cells(r.u, steps, T)
                work += W.min_hbm_bytes(X, Y, Z)
                if i in keep:
                    kept.append(S.Job(U[i], V[i], Wf[i], steps, d.out))
                r.states = None
            del done, reqs
            calls += 1
            if time.perf_counter() - start >= seconds:
                break
    window_s = time.perf_counter() - start
    megasteps = engine.megasteps_executed - m0
    peak = H.memory_peak_bytes(devices)
    H.log(f"window: {calls} ensembles of {M}, {megasteps} mega-steps in "
          f"{window_s:.3f} s (calls of "
          f"{', '.join(f'{c:.3f}' for c in call_s)} s); "
          f"set-up {setup_s:.3f} s")
    del engine

    t_ref = time.perf_counter()
    err, ctrl = S.check(kept, cfg, max_steps=steps, batch=tr["check_batch"],
                        control=control)
    H.log(f"reference: {len(kept)} jobs in {time.perf_counter() - t_ref:.3f} s")
    return H.Outcome(
        attempted=attempted, failed=failed,
        end_to_end={"setup_s": setup_s, "peak_hbm_gib": peak / 2 ** 30,
                    "served_cell_updates_per_s": useful / window_s / 1e9},
        counters={"megasteps": megasteps, "window_s": window_s,
                  "useful_cells": useful,
                  "work_bytes": work},
        checks=[H.Check("max_rel_err", err, cfg["limit_max_rel_err"]),
                H.Check("jobs_not_done", float(failed), 0.0)],
        devices=devices, memory_peak_bytes=peak,
        trace=H.reduce_trace(cap, devices),
        control=None if ctrl is None else {"max_rel_err": ctrl})
