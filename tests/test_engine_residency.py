"""The serving engine keeps its slot batch on the device.

A tiny interpret-mode engine serves a mix of full-slot and padded jobs.
Checked here: the batch stays a device array and each mega-step donates
the last one; per mega-step, only the primed jobs' fields, the masks and
the coefficients go up, and only the live slots' crops and the guard's
flags come down (`health()`'s byte counters), each job's fields padded
to its extent's bucket; jobs of many extents share the programs of a
few buckets; every streamed state is an ``(Xr, Yr, Z)`` array of its
own; and the snapshot holds the very state objects the jobs were
handed, not copies. Bitwise equality with sequential runs is
`test_stencil_serving.py`'s and `test_faults.py`'s.
"""
import itertools
import math

import jax
import numpy as np
import pytest

from repro.serving import stencil_engine as E
from repro.serving.stencil_engine import StencilRequest, StencilServingEngine
from repro.stencil.advection import AdvectionDomain, stratus_fields

X, Y, Z, T = 8, 10, 16, 2
B = 2
# (extent, n_steps): the 2-step full-slot job keeps slot 0 over both
# mega-steps while the padded jobs take turns in slot 1
JOBS = [((8, 10), 2), ((6, 10), 1), ((5, 7), 1)]
PRIMED = [[0, 1], [2]]               # jobs primed before each mega-step
LIVE = [[0, 1], [0, 2]]              # jobs stepped by each mega-step


def _dom():
    return AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, dt=0.005)


def _bucket(n, cap):
    # the power of two at or above n, at most the slot's own extent
    return min(2 ** math.ceil(math.log2(n)), cap)


def _job_bytes(uid):
    # u, v, w padded to the extent's bucket: what crosses the link
    (Xr, Yr), _ = JOBS[uid]
    return 3 * _bucket(Xr, X) * _bucket(Yr, Y) * Z * 4


def _reqs():
    out = []
    for uid, ((Xr, Yr), n) in enumerate(JOBS):
        u, v, w = (np.asarray(a) for a in stratus_fields(Xr, Yr, Z,
                                                         seed=uid))
        out.append(StencilRequest(uid=uid, u=u, v=v, w=w, n_steps=n))
    return out


@pytest.fixture(scope="module")
def served():
    """The run, with the byte counters and the batch read after each
    mega-step."""
    eng = StencilServingEngine(_dom(), batch_size=B, snapshot_every=1)
    seen, step = [], eng._mega_step

    def recorded():
        before = eng.u
        step()
        h = eng.health()
        seen.append((h["bytes_to_device"], h["bytes_to_host"], before,
                     eng.u))
    eng._mega_step = recorded
    done = eng.run(_reqs())
    return eng, done, seen


def test_batch_stays_on_the_device_and_each_step_donates_the_last(served):
    eng, done, seen = served
    assert sorted(done) == list(range(len(JOBS)))
    assert len(seen) == len(LIVE)
    for f in (eng.u, eng.v, eng.w):
        assert isinstance(f, jax.Array) and f.shape == (B, X, Y, Z)
    # each batch is donated to the next step or slot write
    assert all(before.is_deleted() for _, _, before, _ in seen)
    assert seen[-1][3] is eng.u and not eng.u.is_deleted()


@pytest.mark.parametrize("direction", ["to_device", "to_host"])
def test_bytes_moved_per_mega_step(served, direction):
    eng, _, seen = served
    host = (B * (X + Y) * 4       # masks, then each slot's coefficients
            + B * sum(np.asarray(leaf).nbytes for leaf in _dom().params))
    flags = B * X * 4
    if direction == "to_device":
        want = [host + sum(_job_bytes(j) for j in jobs) for jobs in PRIMED]
        col = 0
    else:
        want = [flags + sum(_job_bytes(j) for j in jobs) for jobs in LIVE]
        col = 1
    totals = [0] + [s[col] for s in seen]
    assert [b - a for a, b in zip(totals, totals[1:])] == want


def test_states_are_arrays_of_their_own(served):
    _, done, _ = served
    every = []
    for uid, ((Xr, Yr), n) in enumerate(JOBS):
        req = done[uid]
        assert len(req.states) == n and req.out is req.states[-1]
        for state in req.states:
            assert len(state) == 3
            for a in state:
                assert isinstance(a, np.ndarray)
                assert a.shape == (Xr, Yr, Z) and a.dtype == np.float32
            every.extend(state)
    for a, b in itertools.combinations(every, 2):
        assert not np.shares_memory(a, b)


def test_snapshot_holds_the_streamed_states_themselves(served):
    eng, done, _ = served
    snap = eng._snap
    assert snap.live                 # the last boundary had a live slot
    for slot, uid, _ in snap.live:
        n = snap.states_len[uid]
        assert n >= 1
        assert snap.fields[slot] is done[uid].states[n - 1]
    # masks and coefficients are copies, and no field is kept besides
    assert snap.arrays["xm"] is not eng.xm
    assert set(snap.arrays) == {"xm", "ym"} | {f"p{i}" for i in range(4)}


def test_mixed_extents_share_the_programs_of_a_few_buckets(monkeypatch):
    """Every extent from 3x3 to the full slot: slot writes and crops see
    only bucket shapes, 2 x 3 of them here, and each job still gets its
    own extent back."""
    seen = {"write": set(), "crop": set()}
    write, crop = E._write_slot, E._crop_slot

    def rec_write(batch, slot, fields):
        seen["write"].add(fields[0].shape)
        return write(batch, slot, fields)

    def rec_crop(batch, slot, extent):
        seen["crop"].add(extent)
        return crop(batch, slot, extent)

    monkeypatch.setattr(E, "_write_slot", rec_write)
    monkeypatch.setattr(E, "_crop_slot", rec_crop)
    extents = list(itertools.product(range(3, X + 1), range(3, Y + 1)))
    rng = np.random.default_rng(0)
    reqs = [StencilRequest(uid=uid, u=u, v=u + 1.0, w=u - 1.0, n_steps=1)
            for uid, (Xr, Yr) in enumerate(extents)
            for u in [rng.standard_normal((Xr, Yr, Z), np.float32)]]
    done = StencilServingEngine(_dom(), batch_size=4,
                                snapshot_every=None).run(reqs)
    buckets = {(bx, by) for bx in (4, 8) for by in (4, 8, 10)}
    assert seen["crop"] == buckets
    assert seen["write"] == {b + (Z,) for b in buckets}
    for uid, (Xr, Yr) in enumerate(extents):
        assert [a.shape for a in done[uid].out] == [(Xr, Yr, Z)] * 3
