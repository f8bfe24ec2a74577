"""Checksummed halo exchange + checkpoint/resume for the distributed run.

Fast tier (1-device wiring, runs under `-m "not slow"`):
  * `roofline.integrity_bytes_model` values + validation (hop-count
    dependent, payload-size independent: one uint32 word per band
    message);
  * a verified step on an undecomposed mesh is BITWISE-equal to the
    unchecked step, reports zero mismatch flags, and counts zero
    integrity bytes == the model (on the hand-written AND the
    stencil-spec path — the integrity layer rides `spec=` builds too);
  * the integrity layer's build-time config errors (compiled Mosaic DMA
    has no checksum channel / injection hook);
  * `make_distributed_run(checkpoint_every=, checkpoint_dir=)` +
    `resume_distributed_run`: interrupted-and-resumed == uninterrupted
    BITWISE, and a tampered snapshot (wrong parity, wrong block index)
    is REFUSED with an error naming the inconsistency.

Slow tier (4-device subprocess sweeps, the bench-gate contracts at test
size): counted integrity bytes == model EXACTLY on both ppermute
engines (hand-written advection AND the spec-driven tracer operator, at
`n_fields=spec.n_fields` / `depth=spec.halo(T)`), checksummed clean run
bitwise == unchecked, injected corruption
detected (`HaloCorrupted`), multi-device checkpoint/resume bitwise, the
resilient driver's clean plan == `make_distributed_run` (the
dma_block_index parity regression), and elastic shrink/regrow bitwise.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _subproc import run_ok
from repro.core import roofline as R
from repro.kernels.advection.advection import band_checksum
from repro.kernels.advection.ref import default_params
from repro.launch.mesh import resize_stencil_mesh
from repro.stencil import distributed as D
from repro.stencil.advection import stratus_fields

X, Y, Z, T = 6, 16, 12, 2
DT = 0.005


# --- fast tier: the roofline model ------------------------------------------

def test_integrity_bytes_model_values():
    # ny=4, Yl=4, T=2 -> 1 hop; 2 sides * 3 fields * 1 hop * 4 bytes = 24
    assert R.integrity_bytes_model(X, Y, Z, ny=4, T=2) == 24
    # T=6 over Yl=4 -> ceil(6/4)=2 hops
    assert R.integrity_bytes_model(X, Y, Z, ny=4, T=6) == 48
    # both axes decomposed: hops add
    assert R.integrity_bytes_model(8, 16, Z, nx=2, ny=4, T=2) == \
        2 * 3 * (1 + 1) * R.INTEGRITY_WORD_ITEMSIZE
    # undecomposed mesh: no wire, no checksum words
    assert R.integrity_bytes_model(X, Y, Z) == 0
    # payload-size independent: same T/mesh, bigger Z, same bytes
    assert (R.integrity_bytes_model(X, Y, 4 * Z, ny=4, T=2)
            == R.integrity_bytes_model(X, Y, Z, ny=4, T=2))
    assert R.integrity_bytes_model(X, Y, Z, ny=4, T=2, n_fields=1) == 8


def test_integrity_bytes_model_validation():
    with pytest.raises(ValueError, match="mesh shape"):
        R.integrity_bytes_model(X, Y, Z, ny=0)
    with pytest.raises(ValueError, match="T must be"):
        R.integrity_bytes_model(X, Y, Z, T=0)
    with pytest.raises(ValueError, match="not divisible"):
        R.integrity_bytes_model(X, Y + 1, Z, ny=4)


def test_band_checksum_contract():
    g = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4)
    ck = band_checksum(g)
    assert ck.shape == (1,) and ck.dtype == jnp.uint32
    # order-independent exact sum: permuting rows leaves it unchanged
    assert np.asarray(band_checksum(g[::-1])) == np.asarray(ck)
    # a single flipped bit changes it
    assert np.asarray(band_checksum(g.at[0, 0, 0].add(1.0))) != np.asarray(ck)
    with pytest.raises(TypeError, match="32-bit"):
        band_checksum(g.astype(jnp.float16))


# --- fast tier: 1-device wiring ---------------------------------------------

def _setup():
    u, v, w = stratus_fields(X, Y, Z, seed=0)
    return jax.make_mesh((1,), ("data",)), default_params(Z), (u, v, w)


def test_verified_step_one_device_bitwise_and_priced():
    mesh, p, (u, v, w) = _setup()
    kw = dict(axis="data", x_axis=None, T=T, dt=DT)
    for ex in ("collective", "remote_dma"):
        step0 = D.make_distributed_step(mesh, p, exchange=ex, **kw)
        stepv = D.make_distributed_step(mesh, p, exchange=ex,
                                        verify_integrity=True, **kw)
        o0 = step0(u, v, w)
        *ov, flags = stepv(u, v, w)
        for a, b in zip(o0, ov):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        D.check_integrity(flags)                      # clean: no raise
        assert int(np.sum(np.asarray(flags))) == 0
        # undecomposed: zero checksum words, and counted == model == 0
        assert D.count_integrity_bytes(stepv, u, v, w) == 0
        assert R.integrity_bytes_model(X, Y, Z, nx=1, ny=1, T=T) == 0


def test_check_integrity_raises_on_nonzero_flags():
    flags = np.zeros((4,), np.uint32)
    D.check_integrity(flags)
    flags[2] = 1
    with pytest.raises(D.HaloCorrupted, match="checksum"):
        D.check_integrity(flags)


def test_integrity_config_build_time_errors():
    mesh, p, _ = _setup()
    kw = dict(axis="data", x_axis=None, T=T, dt=DT, exchange="remote_dma",
              interpret=False)
    with pytest.raises(RuntimeError, match="checksum"):
        D.make_distributed_step(mesh, p, verify_integrity=True, **kw)
    with pytest.raises(RuntimeError, match="injection"):
        D.make_distributed_step(mesh, p, corrupt_halo=(0, 1, float("nan")),
                                **kw)
    with pytest.raises(ValueError, match="field index"):
        D.make_distributed_step(mesh, p, axis="data", x_axis=None, T=T,
                                dt=DT, corrupt_halo=(7, 1, float("nan")))
    with pytest.raises(ValueError, match="depth"):
        D.make_distributed_step(mesh, p, axis="data", x_axis=None, T=T,
                                dt=DT, corrupt_halo=(0, 0, float("nan")))


def test_spec_verified_step_one_device_bitwise_and_priced():
    # the integrity layer rides the SPEC path too: n_fields slabs, one
    # extra uint32 flag output, fields bitwise-identical to unchecked
    from repro.stencil.spec import tracer_advection_spec
    mesh, p, _ = _setup()
    spec = tracer_advection_spec()
    fields = stratus_fields(X, Y, Z, seed=1)
    fields = tuple(fields) + tuple(
        f * 0.5 for f in fields[:spec.n_fields - 3])
    kw = dict(axis="data", x_axis=None, T=T, dt=DT, spec=spec,
              spec_params=p)
    step0 = D.make_distributed_step(mesh, p, **kw)
    stepv = D.make_distributed_step(mesh, p, verify_integrity=True, **kw)
    o0 = step0(*fields)
    *ov, flags = stepv(*fields)
    assert len(ov) == spec.n_fields
    for a, b in zip(o0, ov):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    D.check_integrity(flags)
    assert int(np.sum(np.asarray(flags))) == 0
    # undecomposed: zero words counted == modelled at the spec's field
    # count and halo depth
    assert D.count_integrity_bytes(stepv, *fields) == 0
    assert R.integrity_bytes_model(X, Y, Z, nx=1, ny=1, T=T,
                                   n_fields=spec.n_fields,
                                   depth=spec.halo(T)) == 0
    # the spec path validates corrupt_halo against spec.n_fields
    with pytest.raises(ValueError, match="field index"):
        D.make_distributed_step(
            mesh, p, corrupt_halo=(spec.n_fields, 1, float("nan")), **kw)


def test_resize_stencil_mesh_validates():
    with pytest.raises(ValueError, match="mesh shape"):
        resize_stencil_mesh(1, 0)
    with pytest.raises(ValueError, match="devices"):
        resize_stencil_mesh(64, 64)
    m = resize_stencil_mesh(1, 1, y_axis="data")
    assert m.shape["data"] == 1


# --- fast tier: checkpoint / resume ----------------------------------------

def test_checkpoint_kwargs_come_together():
    mesh, p, _ = _setup()
    for kw in (dict(checkpoint_every=2), dict(checkpoint_dir="/tmp/x")):
        with pytest.raises(ValueError, match="together"):
            D.make_distributed_run(mesh, p, n_blocks=2, axis="data",
                                   x_axis=None, T=T, dt=DT, **kw)


def test_checkpointed_run_and_resume_bitwise(tmp_path):
    mesh, p, (u, v, w) = _setup()
    kw = dict(axis="data", x_axis=None, T=T, dt=DT, exchange="remote_dma")
    full = D.make_distributed_run(mesh, p, n_blocks=5, **kw)(u, v, w)

    ck = tmp_path / "ck"
    out = D.make_distributed_run(mesh, p, n_blocks=5, checkpoint_every=2,
                                 checkpoint_dir=str(ck), **kw)(u, v, w)
    for a, b in zip(full, out):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # interrupted at block 3 (separate dir), resumed to 5: bitwise == full
    part = tmp_path / "part"
    D.make_distributed_run(mesh, p, n_blocks=3, checkpoint_every=2,
                           checkpoint_dir=str(part), **kw)(u, v, w)
    res = D.resume_distributed_run(mesh, p, u, v, w, n_blocks=5,
                                   checkpoint_dir=str(part),
                                   checkpoint_every=2, **kw)
    for a, b in zip(full, res):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the resume wrote its own checkpoints: resuming again is a no-op
    # that returns the finished block-5 fields
    done = D.resume_distributed_run(mesh, p, u, v, w, n_blocks=5,
                                    checkpoint_dir=str(part), **kw)
    for a, b in zip(full, done):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpointed_run_with_verify_carries_flags(tmp_path):
    mesh, p, (u, v, w) = _setup()
    kw = dict(axis="data", x_axis=None, T=T, dt=DT, exchange="collective",
              verify_integrity=True)
    *full, ffl = D.make_distributed_run(mesh, p, n_blocks=4, **kw)(u, v, w)
    D.make_distributed_run(mesh, p, n_blocks=2, checkpoint_every=1,
                           checkpoint_dir=str(tmp_path), **kw)(u, v, w)
    *res, rfl = D.resume_distributed_run(mesh, p, u, v, w, n_blocks=4,
                                         checkpoint_dir=str(tmp_path), **kw)
    for a, b in zip(full, res):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.sum(np.asarray(rfl))) == 0


def test_resume_refuses_tampered_snapshots(tmp_path):
    from repro.training import checkpoint as CKPT

    mesh, p, (u, v, w) = _setup()
    kw = dict(axis="data", x_axis=None, T=T, dt=DT)
    uu, vv, ww = (np.asarray(a) for a in (u, v, w))

    # parity that contradicts the stored block index
    bad = {"u": uu, "v": vv, "w": ww, "block": np.int64(1),
           "parity": np.int64(0)}
    d1 = tmp_path / "parity"
    CKPT.save(d1, bad, 1)
    with pytest.raises(ValueError, match="parity"):
        D.resume_distributed_run(mesh, p, u, v, w, n_blocks=4,
                                 checkpoint_dir=str(d1), **kw)

    # step directory number that contradicts the stored block index
    bad = {"u": uu, "v": vv, "w": ww, "block": np.int64(1),
           "parity": np.int64(1)}
    d2 = tmp_path / "step"
    CKPT.save(d2, bad, 2)
    with pytest.raises(ValueError, match="block index"):
        D.resume_distributed_run(mesh, p, u, v, w, n_blocks=4,
                                 checkpoint_dir=str(d2), **kw)

    with pytest.raises(FileNotFoundError, match="no checkpoint"):
        D.resume_distributed_run(mesh, p, u, v, w, n_blocks=4,
                                 checkpoint_dir=str(tmp_path / "void"), **kw)


# --- slow tier: 4-device subprocess sweeps ----------------------------------

_PRELUDE = textwrap.dedent("""
    import os, tempfile
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_stencil_mesh
    from repro.kernels.advection.ref import default_params
    from repro.stencil.advection import stratus_fields
    from repro.stencil import distributed as D
    from repro.core import roofline as RL

    X, Y, Z, T, DT = 6, 16, 12, 2, 0.005
    u, v, w = stratus_fields(X, Y, Z)
    p = default_params(Z)
    mesh = make_stencil_mesh(1, 4)
    kw = dict(axis="y", x_axis=None, T=T, dt=DT)

    def bw(a, b):
        assert all(np.array_equal(np.asarray(x), np.asarray(y))
                   for x, y in zip(a, b))
""")

INTEGRITY_CODE = _PRELUDE + textwrap.dedent("""
    for ex in ("collective", "remote_dma"):
        step0 = D.make_distributed_step(mesh, p, exchange=ex, **kw)
        stepv = D.make_distributed_step(mesh, p, exchange=ex,
                                        verify_integrity=True, **kw)
        o0 = step0(u, v, w)
        *ov, fl = stepv(u, v, w)
        bw(o0, ov)                                  # checksums change nothing
        assert int(np.sum(np.asarray(fl))) == 0, ex
        counted = D.count_integrity_bytes(stepv, u, v, w)
        model = RL.integrity_bytes_model(X, Y, Z, nx=1, ny=4, T=T)
        assert counted == model == 24, (ex, counted, model)
        # the FIELD wire bytes are verify-invariant; unchecked = 0 words
        assert (D.count_exchange_wire_bytes(step0, u, v, w)
                == D.count_exchange_wire_bytes(stepv, u, v, w)), ex
        assert D.count_integrity_bytes(step0, u, v, w) == 0, ex
        # injected wire damage trips the receiver-side checksum
        stepc = D.make_distributed_step(mesh, p, exchange=ex,
                                        verify_integrity=True,
                                        corrupt_halo=(0, 1, float("nan")),
                                        **kw)
        *oc, flc = stepc(u, v, w)
        assert int(np.sum(np.asarray(flc))) > 0, ex
        try:
            D.check_integrity(flc)
            raise SystemExit("corruption not raised")
        except D.HaloCorrupted:
            pass
    # multi-hop: T=6 over Yl=4 -> 2 hops -> 2x the words
    stepm = D.make_distributed_step(mesh, p, axis="y", x_axis=None, T=6,
                                    dt=DT, verify_integrity=True)
    assert (D.count_integrity_bytes(stepm, u, v, w)
            == RL.integrity_bytes_model(X, Y, Z, nx=1, ny=4, T=6) == 48)
    print("OK")
""")

CKPT_CODE = _PRELUDE + textwrap.dedent("""
    full = D.make_distributed_run(mesh, p, n_blocks=5,
                                  exchange="remote_dma", **kw)(u, v, w)
    with tempfile.TemporaryDirectory() as d:
        out = D.make_distributed_run(mesh, p, n_blocks=5, checkpoint_every=2,
                                     checkpoint_dir=d, exchange="remote_dma",
                                     **kw)(u, v, w)
        bw(full, out)
    with tempfile.TemporaryDirectory() as d:
        D.make_distributed_run(mesh, p, n_blocks=3, checkpoint_every=2,
                               checkpoint_dir=d, exchange="remote_dma",
                               **kw)(u, v, w)
        res = D.resume_distributed_run(mesh, p, u, v, w, n_blocks=5,
                                       checkpoint_dir=d, checkpoint_every=2,
                                       exchange="remote_dma", **kw)
        bw(full, res)
    print("OK")
""")

RESILIENT_CODE = _PRELUDE + textwrap.dedent("""
    from repro.serving import faults as F

    rkw = dict(n_blocks=4, T=T, dt=DT, axis="y", x_axis=None)
    clean = D.make_distributed_run(mesh, p, exchange="remote_dma",
                                   **rkw)(u, v, w)
    # the dma_block_index parity regression: clean plan == the pipelined run
    out, inj = F.resilient_distributed_run(mesh, p, u, v, w, **rkw)
    bw(clean, out)
    assert inj.health()["rollbacks"] == 0

    # injected halo corruption: detected by the band checksums, one
    # bounded replay from the last snapshot, final fields bitwise
    plan = F.FaultPlan.parse("halo_corruption@2:field=v")
    out, inj = F.resilient_distributed_run(mesh, p, u, v, w,
                                           injector=F.FaultInjector(plan),
                                           **rkw)
    h = inj.health()
    bw(clean, out)
    assert h["rollbacks"] == 1 and h["faults_skipped"] == 0
    assert any("checksum" in t for t in h["transitions"])

    # elastic: lose devices (4->2), regrow (2->4); fused kernel with a
    # fixed y_tile keeps per-tile arithmetic shard-shape independent,
    # so the whole trajectory is bitwise vs the never-interrupted run
    fkw = dict(n_blocks=4, T=T, dt=DT, axis="y", x_axis=None,
               local_kernel="fused", y_tile=2)
    cleanf = D.make_distributed_run(mesh, p, exchange="remote_dma",
                                    **fkw)(u, v, w)
    plan = F.FaultPlan.parse(
        "device_loss@1:reshard_to=2;device_loss@3:reshard_to=4")
    out, inj = F.resilient_distributed_run(mesh, p, u, v, w,
                                           injector=F.FaultInjector(plan),
                                           **fkw)
    h = inj.health()
    bw(cleanf, out)
    assert h["device_losses"] == 2 and h["reshards"] == 2
    print("OK")
""")


SPEC_INTEGRITY_CODE = _PRELUDE + textwrap.dedent("""
    from repro.stencil.spec import tracer_advection_spec

    spec = tracer_advection_spec()
    mesh2 = make_stencil_mesh(2, 2)
    GX, GY = 8, 8
    key = jax.random.PRNGKey(3)
    fields = tuple(jax.random.normal(jax.random.fold_in(key, i),
                                     (GX, GY, Z), jnp.float32) * 0.01
                   for i in range(spec.n_fields))
    skw = dict(axis="y", x_axis="x", T=1, dt=DT, spec=spec, spec_params=p)
    model = RL.integrity_bytes_model(GX, GY, Z, nx=2, ny=2, T=1,
                                     n_fields=spec.n_fields,
                                     depth=spec.halo(1))
    for ex in ("collective", "remote_dma"):
        step0 = D.make_distributed_step(mesh2, p, exchange=ex, **skw)
        stepv = D.make_distributed_step(mesh2, p, exchange=ex,
                                        verify_integrity=True, **skw)
        o0 = step0(*fields)
        *ov, fl = stepv(*fields)
        bw(o0, ov)                       # checksums change nothing
        assert int(np.sum(np.asarray(fl))) == 0, ex
        counted = D.count_integrity_bytes(stepv, *fields)
        assert counted == model > 0, (ex, counted, model)
        # field wire bytes are verify-invariant at the spec's depth
        assert (D.count_exchange_wire_bytes(step0, *fields)
                == D.count_exchange_wire_bytes(stepv, *fields)), ex
        # injected wire damage on the LAST (tracer) field is caught
        stepc = D.make_distributed_step(
            mesh2, p, exchange=ex, verify_integrity=True,
            corrupt_halo=(spec.n_fields - 1, 1, float("nan")), **skw)
        *oc, flc = stepc(*fields)
        assert int(np.sum(np.asarray(flc))) > 0, ex
        try:
            D.check_integrity(flc)
            raise SystemExit("spec corruption not raised")
        except D.HaloCorrupted:
            pass
    # the verified RUN accumulates flags across blocks and stays bitwise
    run0 = D.make_distributed_run(mesh2, p, n_blocks=3, **skw)
    runv = D.make_distributed_run(mesh2, p, n_blocks=3,
                                  verify_integrity=True, **skw)
    o0 = run0(*fields)
    *ov, fl = runv(*fields)
    bw(o0, ov)
    assert int(np.sum(np.asarray(fl))) == 0
    # ONE traced block: the run's per-block words == the step's
    assert D.count_integrity_bytes(runv, *fields) == model
    print("OK")
""")


@pytest.mark.slow
def test_integrity_counted_equals_model_multidevice():
    run_ok(INTEGRITY_CODE, timeout=600)


@pytest.mark.slow
def test_spec_integrity_counted_equals_model_multidevice():
    run_ok(SPEC_INTEGRITY_CODE, timeout=600)


@pytest.mark.slow
def test_checkpoint_resume_multidevice_bitwise():
    run_ok(CKPT_CODE, timeout=600)


@pytest.mark.slow
def test_resilient_run_parity_corruption_elastic_multidevice():
    run_ok(RESILIENT_CODE, timeout=600)
