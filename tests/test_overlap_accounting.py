"""Overlap accounting: `RooflineTerms` exposed-vs-hidden collective
seconds, the engine overlap-efficiency model, and the remote-DMA schedule's
wire bytes — all pinned to `halo_wire_bytes_model` across (nx, ny, T) —
plus the clear-error contract for `exchange="remote_dma"` on non-TPU
backends in compiled mode.
"""
import pytest

from repro.core import roofline as R
from repro.core.roofline import (RooflineTerms, halo_wire_bytes_model,
                                 interior_compute_fraction,
                                 overlap_efficiency_model,
                                 pipeline_efficiency_model)
from repro.stencil.advection import AdvectionDomain
from repro.stencil.distributed import remote_dma_schedule_wire_bytes


def _terms(wire_bytes, eff, flops=1e12, hbm=1e9):
    return RooflineTerms(flops_per_dev=flops, hbm_bytes_per_dev=hbm,
                         ici_wire_bytes=wire_bytes, dcn_wire_bytes=0.0,
                         n_chips=4, overlap_efficiency=eff)


# --- RooflineTerms hidden/exposed split ------------------------------------

@pytest.mark.parametrize("eff", [0.0, 0.25, 0.5, 1.0])
def test_hidden_plus_exposed_is_collective(eff):
    t = _terms(3e9, eff)
    assert t.collective_hidden_s + t.collective_exposed_s == \
        pytest.approx(t.collective_s)
    assert t.collective_hidden_s >= 0.0
    assert t.collective_exposed_s >= 0.0


def test_zero_efficiency_exposes_everything():
    t = _terms(3e9, 0.0)
    assert t.collective_hidden_s == 0.0
    assert t.collective_exposed_s == pytest.approx(t.collective_s)
    assert t.overlapped_step_time_s == pytest.approx(
        max(t.compute_s, t.memory_s) + t.collective_s)


def test_hidden_bounded_by_onchip_work():
    """A huge exchange over tiny compute cannot hide more than the on-chip
    time, even at efficiency 1."""
    t = _terms(1e12, 1.0, flops=1e9, hbm=1e6)
    onchip = max(t.compute_s, t.memory_s)
    assert t.collective_hidden_s == pytest.approx(onchip)
    assert t.collective_exposed_s == pytest.approx(t.collective_s - onchip)


def test_exposed_monotone_decreasing_in_efficiency():
    exposed = [_terms(3e9, e).collective_exposed_s
               for e in (0.0, 0.3, 0.6, 1.0)]
    assert exposed == sorted(exposed, reverse=True)
    assert exposed[0] > exposed[-1]


def test_overlapped_step_time_between_bounds():
    t = _terms(3e9, 0.5)
    assert t.step_time_s <= t.overlapped_step_time_s <= t.no_overlap_s


def test_overlap_efficiency_validation():
    with pytest.raises(ValueError, match="overlap_efficiency"):
        _terms(1e9, 1.5)
    with pytest.raises(ValueError, match="overlap_efficiency"):
        _terms(1e9, -0.1)


def test_overlapped_bound_ranks_exposed_seconds():
    """`bound` ranks the raw collective_s; `overlapped_bound` ranks what
    is actually left on the critical path — a well-hidden exchange must
    stop reporting 'collective'-bound."""
    # wire time dominates raw (collective_s = 1.5x memory_s > compute_s),
    # but 95% of the hideable part is hidden -> exposed falls below the
    # memory term (hidden = 0.95 * memory_s, exposed = 0.55 * memory_s)
    t = _terms(1e12 * R.ICI_BW / R.HBM_BW * 1.5, 0.95,
               flops=1e6, hbm=1e12)
    assert t.bound == "collective"
    assert t.collective_exposed_s < t.memory_s
    assert t.overlapped_bound == "memory"
    # nothing hidden: the two rankings agree
    t0 = _terms(3e9, 0.0, flops=1e6, hbm=1e3)
    assert t0.overlapped_bound == t0.bound == "collective"
    d = t.as_dict()
    assert d["overlapped_bound"] == "memory"
    assert d["bound"] == "collective"


# --- pipelined multi-block efficiency model ---------------------------------

def test_pipeline_efficiency_collective_is_k_independent():
    frac = 0.8
    for K in (1, 2, 16):
        assert pipeline_efficiency_model(
            n_blocks=K, overlap=True, exchange="collective",
            interior_fraction=frac) == pytest.approx(
                frac * R.XLA_OVERLAP_DISCOUNT)


def test_pipeline_efficiency_remote_dma_fill_penalty():
    """K=1 hides nothing (the kernel serialises its own waits); K blocks
    pay exactly one fill block; the steady state approaches the
    single-block interior-fraction figure from below."""
    frac = 0.8
    assert pipeline_efficiency_model(
        n_blocks=1, overlap=True, exchange="remote_dma",
        interior_fraction=frac) == 0.0
    effs = [pipeline_efficiency_model(
        n_blocks=K, overlap=True, exchange="remote_dma",
        interior_fraction=frac) for K in (2, 4, 16, 1024)]
    assert effs == sorted(effs)
    assert effs[0] == pytest.approx(frac / 2)
    assert effs[-1] < frac
    assert effs[-1] == pytest.approx(frac, rel=1e-2)


def test_pipeline_efficiency_validation_and_no_overlap():
    with pytest.raises(ValueError, match="n_blocks"):
        pipeline_efficiency_model(n_blocks=0, overlap=True)
    with pytest.raises(ValueError, match="exchange engine"):
        pipeline_efficiency_model(n_blocks=2, overlap=True,
                                  exchange="carrier_pigeon")
    for ex in ("collective", "remote_dma"):
        assert pipeline_efficiency_model(n_blocks=8, overlap=False,
                                         exchange=ex,
                                         interior_fraction=0.9) == 0.0


# --- engine efficiency model -----------------------------------------------

def test_efficiency_model_no_overlap_is_zero():
    for ex in ("collective", "remote_dma"):
        assert overlap_efficiency_model(overlap=False, exchange=ex,
                                        interior_fraction=0.9) == 0.0


def test_efficiency_model_remote_dma_beats_collective():
    frac = 0.8
    coll = overlap_efficiency_model(overlap=True, exchange="collective",
                                    interior_fraction=frac)
    dma = overlap_efficiency_model(overlap=True, exchange="remote_dma",
                                   interior_fraction=frac)
    assert dma == pytest.approx(frac)
    assert coll == pytest.approx(frac * R.XLA_OVERLAP_DISCOUNT)
    assert dma > coll


def test_efficiency_model_validation():
    with pytest.raises(ValueError, match="exchange engine"):
        overlap_efficiency_model(overlap=True, exchange="carrier_pigeon")
    with pytest.raises(ValueError, match="interior_fraction"):
        overlap_efficiency_model(overlap=True, interior_fraction=1.2)


@pytest.mark.parametrize("Xl,Yl,T,nx,ny,expect", [
    (256, 64, 8, 16, 16, (240 / 256) * (48 / 64)),
    (256, 64, 8, 1, 16, 48 / 64),       # undecomposed x: no x band
    (256, 64, 8, 16, 1, 240 / 256),
    (8, 8, 4, 2, 2, 0.0),               # bands swallow the shard
    (100, 100, 1, 1, 1, 1.0),
])
def test_interior_compute_fraction(Xl, Yl, T, nx, ny, expect):
    assert interior_compute_fraction(Xl, Yl, T, nx=nx, ny=ny) == \
        pytest.approx(expect)


def test_interior_compute_fraction_validation():
    with pytest.raises(ValueError):
        interior_compute_fraction(0, 8, 1)
    with pytest.raises(ValueError):
        interior_compute_fraction(8, 8, 0)


# --- consistency with the wire model across (nx, ny, T) --------------------

SWEEP = [(nx, ny, T) for nx, ny in ((1, 4), (4, 1), (2, 2), (4, 4), (16, 16))
         for T in (1, 4, 8)]


@pytest.mark.parametrize("nx,ny,T", SWEEP)
def test_exposed_seconds_consistent_with_wire_model(nx, ny, T):
    """The split prices exactly the modelled wire bytes: exposed + hidden
    reconstruct wire/bw, and overlap strictly cuts the exposed time vs the
    overlap=False baseline whenever there is an exchange to hide."""
    X, Y, Z = 4096, 1024, 64
    wire = halo_wire_bytes_model(X, Y, Z, 4, nx=nx, ny=ny, T=T)
    base = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T,
                           mesh_nx=nx, mesh_ny=ny)
    assert base.roofline_terms().ici_wire_bytes == wire
    t_off = base.roofline_terms()
    assert t_off.collective_exposed_s == pytest.approx(wire / t_off.ici_bw)
    for ex in ("collective", "remote_dma"):
        t_on = AdvectionDomain(
            X, Y, Z, variant="fused", fuse_T=T, mesh_nx=nx, mesh_ny=ny,
            exchange=ex, overlap=True).roofline_terms()
        assert (t_on.collective_hidden_s + t_on.collective_exposed_s
                == pytest.approx(t_on.collective_s))
        assert t_on.collective_exposed_s < t_off.collective_exposed_s
    dma = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, mesh_nx=nx,
                          mesh_ny=ny, exchange="remote_dma",
                          overlap=True).roofline_terms()
    coll = AdvectionDomain(X, Y, Z, variant="fused", fuse_T=T, mesh_nx=nx,
                           mesh_ny=ny, exchange="collective",
                           overlap=True).roofline_terms()
    assert dma.collective_exposed_s < coll.collective_exposed_s


@pytest.mark.parametrize("nx,ny,T", SWEEP + [(4, 4, 40), (2, 8, 70)])
def test_dma_schedule_bytes_match_model_exactly(nx, ny, T):
    """The remote-DMA engine's per-hop band messages (summed independently
    of the closed form, multi-hop included) put EXACTLY the modelled bytes
    on the wire — the schedule and the pricing can never drift apart."""
    X, Y, Z = 256, 128, 64
    sched = remote_dma_schedule_wire_bytes(X // nx, Y // ny, Z, 4,
                                           nx=nx, ny=ny, T=T)
    model = halo_wire_bytes_model(X, Y, Z, 4, nx=nx, ny=ny, T=T)
    assert sched == model


# --- AdvectionDomain plumbing ----------------------------------------------

def test_domain_overlap_efficiency_values():
    kw = dict(variant="fused", fuse_T=8, mesh_nx=16, mesh_ny=16)
    dom = AdvectionDomain(4096, 1024, 64, **kw)
    assert dom.overlap_efficiency() == 0.0          # overlap=False default
    frac = interior_compute_fraction(256, 64, 8, nx=16, ny=16)
    on = AdvectionDomain(4096, 1024, 64, overlap=True, **kw)
    assert on.overlap_efficiency() == pytest.approx(
        frac * R.XLA_OVERLAP_DISCOUNT)
    dma = AdvectionDomain(4096, 1024, 64, overlap=True,
                          exchange="remote_dma", **kw)
    assert dma.overlap_efficiency() == pytest.approx(frac)
    single = AdvectionDomain(64, 64, 64, variant="fused", overlap=True)
    assert single.overlap_efficiency() == 0.0       # nothing to exchange


def test_domain_rejects_unknown_exchange():
    with pytest.raises(ValueError, match="exchange"):
        AdvectionDomain(16, 16, 16, exchange="smoke_signals")


def test_domain_pipeline_efficiency_plumbing():
    """n_blocks threads the pipelined model into roofline_terms (n_blocks
    > 1), while n_blocks=1 keeps the single-block figure — BENCH_overlap
    back-compat."""
    kw = dict(variant="fused", fuse_T=8, mesh_nx=16, mesh_ny=16,
              overlap=True, exchange="remote_dma")
    frac = interior_compute_fraction(256, 64, 8, nx=16, ny=16)
    one = AdvectionDomain(4096, 1024, 64, **kw)
    assert one.pipeline_efficiency() == 0.0
    assert one.roofline_terms().overlap_efficiency == pytest.approx(frac)
    k8 = AdvectionDomain(4096, 1024, 64, n_blocks=8, **kw)
    assert k8.pipeline_efficiency() == pytest.approx(frac * 7 / 8)
    assert k8.roofline_terms().overlap_efficiency == pytest.approx(
        frac * 7 / 8)
    coll = AdvectionDomain(4096, 1024, 64, variant="fused", fuse_T=8,
                           mesh_nx=16, mesh_ny=16, overlap=True,
                           n_blocks=8)
    assert coll.pipeline_efficiency() == pytest.approx(
        frac * R.XLA_OVERLAP_DISCOUNT)
    single = AdvectionDomain(64, 64, 64, variant="fused", overlap=True,
                             n_blocks=8)
    assert single.pipeline_efficiency() == 0.0  # nothing to exchange
    with pytest.raises(ValueError, match="n_blocks"):
        AdvectionDomain(16, 16, 16, n_blocks=0)


# --- compiled-mode backend gate --------------------------------------------

def test_remote_dma_compiled_requires_tpu():
    """On a CPU mesh, building the compiled remote-DMA step must fail
    loudly at build time — not at first call — and say why."""
    import jax
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil.distributed import make_distributed_step

    if jax.default_backend() == "tpu":
        pytest.skip("this asserts the NON-TPU error path")
    mesh = make_stencil_mesh(1, 1)
    with pytest.raises(RuntimeError, match="needs TPU devices"):
        make_distributed_step(mesh, default_params(8), axis="y", x_axis="x",
                              T=2, exchange="remote_dma", interpret=False)


def test_unknown_exchange_engine_rejected():
    from repro.kernels.advection.ref import default_params
    from repro.launch.mesh import make_stencil_mesh
    from repro.stencil.distributed import make_distributed_step

    with pytest.raises(ValueError, match="exchange"):
        make_distributed_step(make_stencil_mesh(1, 1), default_params(8),
                              exchange="telepathy")
