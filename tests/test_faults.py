"""Tier-1 tests for the fault-injection + recovery subsystem.

Contracts pinned here (the fault_sweep.py gates, at test-sized grids):

  * `FaultPlan` is deterministic and seed-reproducible; `describe()`
    round-trips through `parse()`.
  * The finite-guard pass (`advect_fused(..., guard=True)`) leaves the
    field outputs BITWISE-equal to an unguarded call (detection is a
    separate pallas pass, never fused into the advection loop), flags
    non-finite slots exactly, and its extra HBM bytes are counted from
    the jaxpr == `roofline.guard_bytes_model` EXACTLY.
  * Every fault kind drives injection -> detection -> recovery through
    the serving engine with `health()` counters asserted: a persistent
    NaN poison rolls back once then quarantines its slot while healthy
    slots stay bitwise; a one-shot halo corruption rolls back (memory or
    atomic on-disk snapshot) and resumes bitwise with exactly one
    replayed mega-step; an exchange stall retries with backoff then
    degrades the ladder; ladder exhaustion reshards down; a cache
    eviction records one eviction + one re-trace miss; a device loss
    reshards (down OR up) bitwise.
  * `retry_with_backoff` / `DegradationLadder` /
    `resilient_distributed_run` implement the same discipline at the
    exchange-block layer.
  * `core.dataflow.Pipeline` never silently leaks a hung worker thread.
  * `SlotManager` rejects the fault-path edge misuses (release of a
    dead slot, double occupy, tick of a dead slot).
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st
from repro.core import roofline as R
from repro.core.dataflow import Pipeline, Stage
from repro.kernels.advection.advection import (advect_fused,
                                               advect_fused_batched,
                                               finite_guard)
from repro.kernels.advection.ref import default_params
from repro.serving.faults import (DEFAULT_LADDER, FAULT_KINDS,
                                  DegradationLadder, ExchangeStalled, Fault,
                                  FaultInjector, FaultPlan,
                                  RecoveryExhausted,
                                  resilient_distributed_run,
                                  retry_with_backoff)
from repro.serving.slots import SlotManager
from repro.serving.stencil_engine import (StencilRequest,
                                          StencilServingEngine)
from repro.stencil.advection import AdvectionDomain, stratus_fields
from repro.stencil.distributed import count_guard_bytes

X, Y, Z, T = 8, 10, 16, 2
DT = 0.005
SIZES = [(X, Y, 3), (5, 6, 2), (4, 8, 3)]


def _dom(**kw):
    kw.setdefault("variant", "fused")
    kw.setdefault("fuse_T", T)
    kw.setdefault("dt", DT)
    return AdvectionDomain(X, Y, Z, **kw)


def _req(uid, Xr, Yr, n_steps=1):
    u, v, w = stratus_fields(Xr, Yr, Z, seed=uid)
    return StencilRequest(uid=uid, u=np.asarray(u), v=np.asarray(v),
                          w=np.asarray(w), n_steps=n_steps)


def _reqs():
    return [_req(i, xr, yr, n) for i, (xr, yr, n) in enumerate(SIZES)]


@pytest.fixture(scope="module")
def clean_done():
    return StencilServingEngine(_dom(), batch_size=2).run(_reqs())


def _assert_bitwise(req, ref_req):
    for got, ref in zip(req.out, ref_req.out):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert len(req.states) == len(ref_req.states)
    for st_g, st_r in zip(req.states, ref_req.states):
        for got, ref in zip(st_g, st_r):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# -- the fault plan --------------------------------------------------------

def test_fault_plan_parse_describe_roundtrip():
    spec = ("nan_poison@1:slot=1,field=v,mode=inf;"
            "exchange_stall@2:stalls=6,rung=remote_dma;"
            "device_loss@3:reshard_to=1;"
            "halo_corruption@4:depth=2;cache_evict@5")
    plan = FaultPlan.parse(spec)
    assert len(plan.faults) == 5
    assert plan.at(1)[0].field == "v" and plan.at(1)[0].mode == "inf"
    assert plan.at(2)[0].stalls == 6
    assert plan.at(3)[0].reshard_to == 1
    assert plan.max_step() == 5
    again = FaultPlan.parse(plan.describe())
    assert again.faults == plan.faults


def test_fault_plan_random_is_seed_reproducible():
    a = FaultPlan.random(7, n_steps=5, batch=4)
    b = FaultPlan.random(7, n_steps=5, batch=4)
    assert a.faults == b.faults and a.seed == 7
    assert all(f.kind in ("device_loss", "nan_poison", "halo_corruption",
                          "exchange_stall", "cache_evict")
               for f in a.faults)
    # the plan round-trips so artifacts record exactly what ran
    assert FaultPlan.parse(a.describe()).faults == a.faults


def test_fault_validation():
    with pytest.raises(ValueError, match="kind"):
        Fault("bit_rot", at_step=0)
    with pytest.raises(ValueError, match="at_step"):
        Fault("nan_poison", at_step=-1)
    with pytest.raises(ValueError, match="field"):
        Fault("nan_poison", at_step=0, field="q")
    with pytest.raises(ValueError, match="mode"):
        Fault("nan_poison", at_step=0, mode="zero")
    with pytest.raises(ValueError, match="stalls"):
        Fault("exchange_stall", at_step=0, stalls=0)
    with pytest.raises(ValueError, match="depth"):
        Fault("halo_corruption", at_step=0, depth=0)
    with pytest.raises(ValueError, match="reshard_to"):
        Fault("device_loss", at_step=0, reshard_to=0)
    with pytest.raises(ValueError, match="kind@step"):
        FaultPlan.parse("nan_poison1")
    with pytest.raises(ValueError, match="key=val"):
        FaultPlan.parse("nan_poison@1:slot")


def test_fault_persistence_defaults():
    assert Fault("nan_poison", at_step=0).is_persistent
    assert not Fault("halo_corruption", at_step=0).is_persistent
    assert Fault("halo_corruption", at_step=0, persistent=True).is_persistent
    assert not Fault("nan_poison", at_step=0, persistent=False).is_persistent


# -- the finite-guard pass -------------------------------------------------

def test_guard_pass_is_bitwise_and_detects():
    # (8, 16, 64): a shape where an IN-kernel isfinite probe provably
    # drifts by one ulp — the separate guard pass must not
    Xg, Yg, Zg = 8, 16, 64
    p = default_params(Zg)
    u, v, w = stratus_fields(Xg, Yg, Zg, seed=0)
    ru, rv, rw = advect_fused(u, v, w, p, T=T, dt=DT, interpret=True)
    gu, gv, gw, flags = advect_fused(u, v, w, p, T=T, dt=DT, interpret=True,
                                     guard=True)
    for got, ref in ((gu, ru), (gv, rv), (gw, rw)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert flags.shape == (Xg,) and float(jnp.min(flags)) == 1.0
    # direct pass over poisoned fields: exactly the poisoned slice flags
    up = np.asarray(u).copy()
    up[3, 1, 0] = np.nan
    f = np.asarray(finite_guard(jnp.asarray(up), v, w, interpret=True))
    assert f[3] == 0.0 and np.all(np.delete(f, 3) == 1.0)


def test_guard_pass_batched_isolates_slots():
    p = default_params(Z)
    B = 3
    u, v, w = (jnp.stack([stratus_fields(X, Y, Z, seed=s)[i]
                          for s in range(B)]) for i in range(3))
    up = np.array(u)
    up[1, 1, 1, 0] = np.inf
    ou, ov, ow, gf = advect_fused_batched(jnp.asarray(up), v, w, p, T=T,
                                          dt=DT, interpret=True, guard=True)
    ok = np.asarray(gf).min(axis=1) > 0.0
    assert list(ok) == [True, False, True]
    cu, cv, cw = advect_fused_batched(u, v, w, p, T=T, dt=DT, interpret=True)
    for b in (0, 2):                      # healthy slots stay bitwise
        for got, ref in ((ou, cu), (ov, cv), (ow, cw)):
            np.testing.assert_array_equal(np.asarray(got[b]),
                                          np.asarray(ref[b]))


def test_guard_bytes_counted_equals_model():
    p = default_params(Z)
    for B in (1, 3):
        u, v, w = (jnp.stack([stratus_fields(X, Y, Z, seed=s)[i]
                              for s in range(B)]) for i in range(3))

        def guarded(uu, vv, ww):
            return advect_fused_batched(uu, vv, ww, p, T=T, dt=DT,
                                        interpret=True, guard=True)

        def plain(uu, vv, ww):
            return advect_fused_batched(uu, vv, ww, p, T=T, dt=DT,
                                        interpret=True)

        assert count_guard_bytes(guarded, u, v, w) == \
            R.guard_bytes_model(X, Y, Z, batch=B)
        assert count_guard_bytes(plain, u, v, w) == 0


def test_guard_bytes_model_validation_and_accessors():
    with pytest.raises(ValueError, match="batch"):
        R.guard_bytes_model(X, Y, Z, batch=0)
    with pytest.raises(ValueError, match="extents"):
        R.guard_bytes_model(0, Y, Z)
    assert _dom(batch=3).guard_bytes_per_step() == \
        3 * _dom().guard_bytes_per_step()
    with pytest.raises(ValueError, match="fused"):
        AdvectionDomain(X, Y, Z, variant="baseline").guard_bytes_per_step()
    eng = StencilServingEngine(_dom(), batch_size=2)
    assert eng.guard_bytes_per_step() == R.guard_bytes_model(X, Y, Z,
                                                             batch=2)


# -- engine fault paths: injection -> detection -> recovery ----------------

def test_nan_poison_rolls_back_then_quarantines(clean_done):
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="nan_poison@1:slot=1,field=v")
    done = eng.run(_reqs())
    h = eng.health()
    # first sighting rolls back; the replay re-poisons (persistent) and
    # the suspect site falls through to quarantine
    assert h["rollbacks"] == 1 and h["quarantines"] == 1
    assert h["faults_injected"] == 2          # fired on both crossings
    [quid] = h["quarantined_uids"]
    assert done[quid].status == "quarantined" and done[quid].out is None
    assert "non-finite" in done[quid].error
    for uid in done:
        if uid != quid:
            assert done[uid].status == "done"
            _assert_bitwise(done[uid], clean_done[uid])


def test_halo_corruption_rolls_back_bitwise(clean_done):
    clean_steps = StencilServingEngine(_dom(), batch_size=2)
    clean_steps.run(_reqs())
    eng = StencilServingEngine(
        _dom(), batch_size=2,
        fault_plan="halo_corruption@1:slot=0,mode=inf,depth=2")
    done = eng.run(_reqs())
    h = eng.health()
    assert h["rollbacks"] == 1 and h["quarantines"] == 0
    for uid in done:                          # one-shot: ALL jobs clean
        assert done[uid].status == "done"
        _assert_bitwise(done[uid], clean_done[uid])
    # bounded recovery overhead: snapshot_every=1 -> exactly one replayed
    # mega-step (physical executions; the logical index is rewound)
    assert eng.megasteps_executed == clean_steps.megasteps_executed + 1


def test_disk_snapshot_rollback_bitwise(tmp_path, clean_done):
    eng = StencilServingEngine(
        _dom(), batch_size=2, snapshot_dir=tmp_path,
        fault_plan="halo_corruption@1:slot=1")
    done = eng.run(_reqs())
    h = eng.health()
    assert h["rollbacks"] == 1 and h["snapshots"] >= 1
    for uid in done:
        _assert_bitwise(done[uid], clean_done[uid])


def test_exchange_stall_retries_then_degrades():
    clean = StencilServingEngine(_dom(exchange="remote_dma"), batch_size=2)
    done_c = clean.run(_reqs())
    sleeps = []
    eng = StencilServingEngine(
        _dom(exchange="remote_dma"), batch_size=2,
        fault_plan="exchange_stall@1:stalls=10,rung=remote_dma",
        max_retries=2, backoff_s=0.25, sleeper=sleeps.append)
    done = eng.run(_reqs())
    h = eng.health()
    assert h["retries"] == 2 and h["degradations"] == 1
    assert h["exchange"] == "collective"      # walked the ladder
    assert sleeps == [0.25, 0.5]              # exponential backoff
    assert any("remote_dma -> collective" in t for t in h["transitions"])
    # the re-trace on the fallback transport is a recorded miss
    assert eng.cache_stats()["misses"] == 2
    for uid in done:
        _assert_bitwise(done[uid], done_c[uid])


def test_ladder_exhaustion_reshards_down(clean_done):
    # collective is the LAST rung: a stall there exhausts the ladder and
    # the engine takes the implicit final rung — reshard to half
    eng = StencilServingEngine(
        _dom(), batch_size=2, max_retries=1,
        fault_plan="exchange_stall@1:stalls=10,rung=collective")
    done = eng.run(_reqs())
    h = eng.health()
    assert h["degradations"] == 0 and h["reshards"] == 1
    assert eng.B == 1
    assert any("exhausted" in t for t in h["transitions"])
    for uid in done:
        _assert_bitwise(done[uid], clean_done[uid])


def test_cache_evict_records_eviction_and_retrace():
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="cache_evict@2")
    eng.run(_reqs())
    stats = eng.cache_stats()
    assert stats["evictions"] == 1 and stats["misses"] == 2
    assert eng.health()["cache_evictions"] == 1


def test_device_loss_plan_matches_deprecated_alias(clean_done):
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="device_loss@1:reshard_to=1")
    done = eng.run(_reqs())
    h = eng.health()
    assert h["device_losses"] == 1 and h["reshards"] == 1
    for uid in done:
        _assert_bitwise(done[uid], clean_done[uid])
    alias = StencilServingEngine(_dom(), batch_size=2)
    done_a = alias.run(_reqs(), lose_device_at=1, reshard_to=1)
    ha = alias.health()
    assert (ha["device_losses"], ha["reshards"]) == (1, 1)
    for uid in done_a:
        _assert_bitwise(done_a[uid], done[uid])
    with pytest.raises(ValueError, match="not both"):
        StencilServingEngine(_dom(), batch_size=2).run(
            _reqs(), lose_device_at=1, fault_plan="cache_evict@1")


def test_reshard_up_mid_flight_bitwise(clean_done):
    # devices RETURN: reshard 2 -> 4 slots mid-run, everything bitwise
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="device_loss@1:reshard_to=4")
    done = eng.run(_reqs())
    h = eng.health()
    assert eng.B == 4 and h["reshards"] == 1
    assert eng.cache_stats()["misses"] == 2   # one re-trace at B=4
    for uid in done:
        _assert_bitwise(done[uid], clean_done[uid])


def test_engine_slot_reusable_after_quarantine(clean_done):
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="nan_poison@1:slot=0")
    done = eng.run(_reqs())
    assert eng.health()["quarantines"] == 1
    assert not eng.slots.any_live()
    # the quarantined slot serves fresh work on the next run, clean
    done2 = eng.run([_req(10, X, Y, 2)])
    assert done2[10].status == "done"
    ref = StencilServingEngine(_dom(), batch_size=2).run([_req(10, X, Y, 2)])
    _assert_bitwise(done2[10], ref[10])


def test_health_surface_shape():
    eng = StencilServingEngine(_dom(), batch_size=2,
                               fault_plan="cache_evict@1")
    eng.run(_reqs())
    h = eng.health()
    for key in ("faults_injected", "faults_skipped", "device_losses",
                "quarantines", "rollbacks", "retries", "degradations",
                "reshards", "cache_evictions", "snapshots", "transitions",
                "plan", "exchange", "quarantined_uids", "cache"):
        assert key in h, key
    assert h["plan"] == "cache_evict@1"


# -- retry / ladder / injector units ---------------------------------------

def test_retry_with_backoff_discipline():
    sleeps, tries = [], []

    def flaky():
        tries.append(1)
        if len(tries) < 3:
            raise ExchangeStalled("transient")
        return "ok"

    assert retry_with_backoff(flaky, max_retries=3, backoff_s=0.1,
                              sleeper=sleeps.append) == "ok"
    assert len(tries) == 3 and sleeps == [0.1, 0.2]

    def always():
        raise ExchangeStalled("stuck")

    with pytest.raises(ExchangeStalled):
        retry_with_backoff(always, max_retries=2, backoff_s=0.0)

    def broken():
        raise RuntimeError("not a stall")

    with pytest.raises(RuntimeError, match="not a stall"):
        retry_with_backoff(broken, max_retries=5)
    with pytest.raises(ValueError, match="max_retries"):
        retry_with_backoff(flaky, max_retries=-1)


def test_degradation_ladder():
    lad = DegradationLadder()
    assert lad.rungs == DEFAULT_LADDER and lad.current == "remote_dma"
    assert lad.degrade("stall") == "collective"
    assert lad.transitions == ["remote_dma -> collective (stall)"]
    with pytest.raises(RecoveryExhausted):
        lad.degrade("stall again")
    assert "EXHAUSTED" in lad.transitions[-1]
    with pytest.raises(ValueError, match="start rung"):
        DegradationLadder(start="smoke_signals")
    with pytest.raises(ValueError, match="at least one"):
        DegradationLadder(rungs=())


def test_injector_stall_arming_and_counters():
    inj = FaultInjector(FaultPlan.parse(
        "exchange_stall@0:stalls=2,rung=remote_dma"))
    [(idx, f)] = inj.due(0)
    inj.arm_stall(idx, f)
    inj.mark_fired(idx)
    with pytest.raises(ExchangeStalled):
        inj.poll_stall("remote_dma")
    # degrading PAST the faulted transport clears the armed stall: the
    # fallback does not share the faulted engine's failure
    inj.poll_stall("collective")
    inj.poll_stall("remote_dma")
    with pytest.raises(KeyError, match="unknown health counter"):
        inj.record("optimism")
    assert inj.due(0) == []                   # fired faults are consumed


# -- the distributed-run layer ---------------------------------------------

def test_resilient_distributed_run_degrades_bitwise():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.stencil.distributed import make_distributed_step

    Xd, Yd, Zd = 6, 20, 12
    u, v, w = stratus_fields(Xd, Yd, Zd, seed=3)
    p = default_params(Zd)
    mesh = jax.make_mesh((1,), ("data",))
    sh = NamedSharding(mesh, P(None, "data", None))
    uu, vv, ww = (np.asarray(a) for a in (u, v, w))

    step = make_distributed_step(mesh, p, T=1, dt=DT)
    cu, cv, cw = uu, vv, ww
    for _ in range(3):
        cu, cv, cw = step(*(jnp.asarray(a) for a in (cu, cv, cw)))

    inj = FaultInjector(FaultPlan.parse(
        "exchange_stall@1:stalls=5,rung=remote_dma;"
        "nan_poison@2:persistent=false"))
    (ru, rv, rw), inj = resilient_distributed_run(
        mesh, p, jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
        n_blocks=3, T=1, dt=DT, injector=inj,
        ladder=DegradationLadder(start="remote_dma"), max_retries=1)
    h = inj.health()
    assert h["retries"] == 1 and h["degradations"] == 1
    # nan_poison is injected at the exchange layer now: the guard detects
    # the non-finite rows and the block replays clean from its snapshot
    assert h["faults_skipped"] == 0 and h["faults_injected"] == 2
    assert h["rollbacks"] == 1 and h["snapshots"] >= 1
    for got, ref in ((ru, cu), (rv, cv), (rw, cw)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    # a stall on the LAST rung exhausts the ladder and propagates
    with pytest.raises(RecoveryExhausted):
        resilient_distributed_run(
            mesh, p, jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
            n_blocks=2, T=1, dt=DT, max_retries=0,
            injector=FaultInjector(FaultPlan.parse(
                "exchange_stall@0:stalls=9,rung=collective")),
            ladder=DegradationLadder(start="collective"))


def _one_shard_setup(seed=3):
    from repro.stencil.distributed import make_distributed_step

    Xd, Yd, Zd = 6, 20, 12
    u, v, w = stratus_fields(Xd, Yd, Zd, seed=seed)
    p = default_params(Zd)
    mesh = jax.make_mesh((1,), ("data",))
    uu, vv, ww = (np.asarray(a) for a in (u, v, w))
    step = make_distributed_step(mesh, p, T=1, dt=DT)
    cu, cv, cw = uu, vv, ww
    for _ in range(3):
        cu, cv, cw = step(*(jnp.asarray(a) for a in (cu, cv, cw)))
    return mesh, p, (uu, vv, ww), (cu, cv, cw)


def test_resilient_run_persistent_poison_exhausts_replays():
    mesh, p, (uu, vv, ww), _ = _one_shard_setup()
    inj = FaultInjector(FaultPlan.parse("nan_poison@1"))  # persistent
    with pytest.raises(RecoveryExhausted, match="persists after"):
        resilient_distributed_run(
            mesh, p, jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
            n_blocks=3, T=1, dt=DT, injector=inj, max_replays=2)
    h = inj.health()
    assert h["rollbacks"] == 2 and h["faults_injected"] == 3


def test_resilient_run_all_kinds_on_one_shard_bitwise(tmp_path):
    """Every FAULT_KINDS member is applied (never skipped) at the
    exchange layer, even on a 1-shard mesh where halo_corruption
    degenerates to an edge-row poison; disk-backed snapshots make the
    rollbacks atomic on-disk, and the final fields are bitwise-equal to
    the clean run."""
    mesh, p, (uu, vv, ww), (cu, cv, cw) = _one_shard_setup()
    inj = FaultInjector(FaultPlan.parse(
        "halo_corruption@0;nan_poison@1:persistent=false;"
        "cache_evict@1;device_loss@2:reshard_to=1;"
        "exchange_stall@2:stalls=1,rung=remote_dma"))
    (ru, rv, rw), inj = resilient_distributed_run(
        mesh, p, jnp.asarray(uu), jnp.asarray(vv), jnp.asarray(ww),
        n_blocks=3, T=1, dt=DT, injector=inj,
        ladder=DegradationLadder(start="remote_dma"),
        checkpoint_dir=str(tmp_path), max_retries=2)
    h = inj.health()
    assert h["faults_injected"] == 5 and h["faults_skipped"] == 0
    assert h["rollbacks"] == 2        # halo_corruption + nan_poison
    assert h["cache_evictions"] == 1 and h["reshards"] == 1
    assert h["device_losses"] == 1 and h["retries"] == 1
    for got, ref in ((ru, cu), (rv, cv), (rw, cw)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_retry_with_backoff_cap_and_jitter():
    def make_flaky(n):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] <= n:
                raise ExchangeStalled("transient")
            return "ok"

        return flaky

    sleeps = []
    assert retry_with_backoff(make_flaky(4), max_retries=5, backoff_s=0.1,
                              max_backoff_s=0.25,
                              sleeper=sleeps.append) == "ok"
    assert sleeps == [0.1, 0.2, 0.25, 0.25]     # capped, never unbounded

    seqs = []
    for _ in range(2):
        sleeps = []
        retry_with_backoff(make_flaky(3), max_retries=4, backoff_s=0.1,
                           jitter_seed=7, sleeper=sleeps.append)
        seqs.append(sleeps)
    assert seqs[0] == seqs[1]                   # seeded jitter: determinism
    rng = np.random.default_rng(7)
    expect = [0.1 * 2 ** k * (0.5 + 0.5 * float(rng.random()))
              for k in range(3)]
    assert seqs[0] == expect
    for k, s in enumerate(seqs[0]):             # jitter stays in [1/2, 1]x
        assert 0.05 * 2 ** k <= s <= 0.1 * 2 ** k

    with pytest.raises(ValueError, match="max_backoff_s"):
        retry_with_backoff(make_flaky(0), max_backoff_s=-1.0)


# -- FaultPlan property tests (hypothesis via the _prop shim) ---------------

@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_fault_plan_random_roundtrips_for_any_seed(seed):
    plan = FaultPlan.random(seed, n_steps=7, batch=4, n_faults=5,
                            kinds=FAULT_KINDS)
    assert FaultPlan.parse(plan.describe()).describe() == plan.describe()
    assert all(f.kind in FAULT_KINDS for f in plan.faults)


@settings(max_examples=25, deadline=None)
@given(kind=st.sampled_from(FAULT_KINDS),
       at_step=st.integers(min_value=0, max_value=99),
       slot=st.integers(min_value=0, max_value=7),
       field=st.sampled_from(("u", "v", "w")),
       mode=st.sampled_from(("nan", "inf")),
       depth=st.integers(min_value=1, max_value=4),
       persistent=st.booleans())
def test_fault_describe_parse_roundtrip_all_kinds(kind, at_step, slot,
                                                  field, mode, depth,
                                                  persistent):
    f = Fault(kind=kind, at_step=at_step, slot=slot, field=field,
              mode=mode, depth=depth, persistent=persistent)
    plan = FaultPlan(faults=(f,))
    back = FaultPlan.parse(plan.describe())
    assert back.faults == plan.faults
    assert back.describe() == plan.describe()


@pytest.mark.parametrize("spec,token", [
    ("nan_poison", "nan_poison"),                   # missing @step
    ("nan_poison@soon", "'soon'"),                  # non-integer step
    ("nan_poison@1:slot", "'slot'"),                # option without =
    ("nan_poison@1:turbo=3", "'turbo'"),            # unknown key
    ("nan_poison@1:slot=much", "'much'"),           # bad value
    ("warp_core_breach@1", "warp_core_breach"),     # unknown kind
])
def test_fault_plan_parse_malformed_names_offending_token(spec, token):
    with pytest.raises(ValueError, match="expected|unknown|bad fault") as ei:
        FaultPlan.parse(spec)
    assert token in str(ei.value)


# -- the dataflow leak fix (core/dataflow.py) ------------------------------

def test_pipeline_leak_is_loud_not_silent(caplog):
    """A consumer stage that dies leaves its producer blocked forever on
    the bounded inter-stage queue (depth 1: one parked item fills it).
    The drain must re-raise the stage error AND log the leaked worker —
    never return as if the run were clean."""

    def dies(x):
        raise RuntimeError("consumer died")

    pipe = Pipeline([Stage("produce", lambda x: x, depth=8),
                     Stage("consume", dies, depth=1)], join_timeout=0.2)
    with caplog.at_level(logging.ERROR, logger="repro.core.dataflow"):
        with pytest.raises(RuntimeError, match="consumer died"):
            pipe.run([0, 1, 2])
    assert any("leaked" in rec.message and "produce" in str(rec.args)
               for rec in caplog.records)


def test_pipeline_join_timeout_validation_and_clean_run():
    with pytest.raises(ValueError, match="join_timeout"):
        Pipeline([Stage("a", lambda x: x)], join_timeout=0.0)
    out = Pipeline([Stage("a", lambda x: x + 1),
                    Stage("b", lambda x: x * 2)]).run([1, 2, 3])
    assert out == [4, 6, 8]


# -- SlotManager fault-path edges ------------------------------------------

def test_slot_manager_rejects_fault_path_misuse():
    sm = SlotManager(2)
    with pytest.raises(ValueError, match="not live"):
        sm.release(0)                         # release of a dead slot
    with pytest.raises(ValueError, match="not live"):
        sm.tick(1)
    sm.occupy(0, object(), 2)
    with pytest.raises(ValueError, match="already live"):
        sm.occupy(0, object(), 1)             # double occupy
    sm.release(0)
    with pytest.raises(ValueError, match="not live"):
        sm.release(0)                         # double release
