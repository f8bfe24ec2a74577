"""The serving engine's host spans, read back from a profiler trace.

A tiny interpret-mode engine serves two waves of jobs with a snapshot
every mega-step under `jax.profiler`; the spans on the host plane are
read with `jax.profiler.ProfileData`. Each span's parent is the
innermost engine span around it on the same thread line.
"""
import collections
import math

import jax
import numpy as np
import pytest

from repro.serving.stencil_engine import StencilRequest, StencilServingEngine
from repro.stencil.advection import AdvectionDomain, stratus_fields

X, Y, Z, T = 8, 10, 16, 2
B = 2
EXTENTS = [(8, 10), (6, 10), (8, 7), (5, 5)]       # two waves of B jobs
MEGASTEPS = len(EXTENTS) // B


def _dom():
    return AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, dt=0.005)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    eng = StencilServingEngine(_dom(), batch_size=B, snapshot_every=1)
    reqs = []
    for uid, (Xr, Yr) in enumerate(EXTENTS):
        u, v, w = (np.asarray(a) for a in stratus_fields(Xr, Yr, Z,
                                                         seed=uid))
        reqs.append(StencilRequest(uid=uid, u=u, v=v, w=w, n_steps=1))
    out = tmp_path_factory.mktemp("engine_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        done = eng.run(reqs)
    finally:
        jax.profiler.stop_trace()
    assert sorted(done) == list(range(len(EXTENTS)))
    assert eng.megasteps_executed == MEGASTEPS
    path = sorted(out.glob("plugins/profile/*/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    found = []
    for plane in data.planes:
        for line in plane.lines:
            found.extend((plane.name, line.name, ev.name, ev.start_ns,
                          ev.start_ns + ev.duration_ns,
                          {k: v for k, v in ev.stats})
                         for ev in line.events
                         if ev.name.startswith("engine."))
    return found


def _parent(span, found):
    plane, line, _, s, e, _ = span
    around = [p for p in found if p is not span and p[:2] == (plane, line)
              and p[3] <= s and e <= p[4]]
    return min(around, key=lambda p: p[4] - p[3])[2] if around else None


def test_each_span_appears_once_per_unit_of_its_work(spans):
    n = collections.Counter(sp[2] for sp in spans)
    assert n == {"engine.run": 1, "engine.snapshot": MEGASTEPS,
                 "engine.megastep": MEGASTEPS, "engine.upload": MEGASTEPS,
                 "engine.device": MEGASTEPS, "engine.download": MEGASTEPS,
                 "engine.prime": len(EXTENTS), "engine.crop": len(EXTENTS)}


def test_spans_nest_under_the_span_that_caused_them(spans):
    want = {"engine.upload": "engine.megastep",
            "engine.device": "engine.megastep",
            "engine.download": "engine.megastep",
            "engine.megastep": "engine.run", "engine.snapshot": "engine.run",
            "engine.prime": "engine.run", "engine.crop": "engine.run",
            "engine.run": None}
    for sp in spans:
        assert _parent(sp, spans) == want[sp[2]], sp[2]
    # a mega-step's phases run in order, each after the last has ended
    for mega in (sp for sp in spans if sp[2] == "engine.megastep"):
        kids = sorted((sp for sp in spans if _parent(sp, spans)
                       == "engine.megastep" and mega[3] <= sp[3]
                       and sp[4] <= mega[4]), key=lambda sp: sp[3])
        assert [k[2] for k in kids] == ["engine.upload", "engine.device",
                                        "engine.download"]
        assert all(a[4] <= b[3] for a, b in zip(kids, kids[1:]))


def _job_bytes(Xr, Yr):
    # the job's u, v, w, padded to the power of two at or above each
    # extent (at most the slot's): what a prime or download moves
    def bucket(n, cap):
        return min(2 ** math.ceil(math.log2(n)), cap)
    return 3 * bucket(Xr, X) * bucket(Yr, Y) * Z * 4


def test_per_job_spans_carry_uid_and_bytes(spans):
    # a prime uploads the job's fields; a crop only files the state the
    # download brought back, and moves nothing
    for name, per_job in (("engine.prime", _job_bytes),
                          ("engine.crop", lambda Xr, Yr: 0)):
        got = sorted((sp[5]["uid"], sp[5]["bytes"]) for sp in spans
                     if sp[2] == name)
        assert got == [(uid, per_job(Xr, Yr))
                       for uid, (Xr, Yr) in enumerate(EXTENTS)], name
    # what the host keeps of the batch, uploaded each mega-step and
    # copied by each snapshot: masks and each slot's coefficients
    host = (B * (X + Y) * 4
            + B * sum(np.asarray(leaf).nbytes for leaf in _dom().params))
    for name in ("engine.upload", "engine.snapshot"):
        assert {sp[5]["bytes"] for sp in spans if sp[2] == name} == {host}
    # what comes back: the guard's flags and each live slot's crop
    down = sorted((sp[3], sp[5]["bytes"]) for sp in spans
                  if sp[2] == "engine.download")
    assert [b for _, b in down] == [
        B * X * 4 + sum(_job_bytes(*e) for e in EXTENTS[k:k + B])
        for k in range(0, len(EXTENTS), B)]
    (run,) = [sp for sp in spans if sp[2] == "engine.run"]
    assert run[5]["requests"] == len(EXTENTS)
    steps = sorted(sp[5]["step"] for sp in spans
                   if sp[2] == "engine.megastep")
    assert steps == list(range(MEGASTEPS))


def test_cache_miss_marks_only_the_first_launch(spans):
    device = sorted((sp for sp in spans if sp[2] == "engine.device"),
                    key=lambda sp: sp[3])
    assert [sp[5]["cache_miss"] for sp in device] == [1] + [0] * (
        MEGASTEPS - 1)
