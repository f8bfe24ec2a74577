"""The readers of the exchange's remote-DMA kernels, on a synthetic
two-chip trace, and the exchange's least bytes."""
import pytest

from _benchcells import ROOT  # noqa: F401
from bench import exchange_work as EW
from bench import harness as H
from bench import trace as TR
from bench.metrics import _exchange


def op(name, start, dur, chip):
    text = (f'%{name} = (f32[2,4,8,128]{{3,2,1,0}}) custom-call(s32[1] %p), '
            f'custom_call_target="tpu_custom_call"')
    return TR.Event(f"/device:TPU:{chip}", TR.OPS_LINE, text, start, dur)


def trace():
    """A window [1000, 5000) with DMA kernels of both phases on two
    chips, one under the unsuffixed name earlier programs use, and some
    that begin before the window or end after it."""
    return [
        TR.Event(TR.HOST_PLANE, "python", TR.WINDOW, 1000, 4000),
        op("halo_band_exchange_dma_x.10", 900, 200, 0),    # 100 inside
        op("halo_band_exchange_dma_y.11", 2000, 300, 0),
        op("halo_band_exchange_dma.10", 3000, 100, 0),
        op("advect_fused.3", 2300, 500, 0),                # not a DMA
        op("halo_band_exchange_dma_x.10", 1200, 150, 1),
        op("halo_band_exchange_dma_y.11", 4900, 400, 1),   # 100 inside
        op("advect_fused.3", 1400, 500, 1),
    ]


# seconds of DMA kernel time in the window, per chip
CHIP0, CHIP1 = 500e-9, 250e-9
SMALL = {"X": 32, "Y": 32, "Z": 8, "mesh": [2, 1], "T": 4}


class _Cell:
    name = "exchange-test-cell"
    config = SMALL


def _ctx(events, blocks, device_kind="TPU v5 lite", chips=(0, 1)):
    summary = None if events is None else TR.reduce(events, list(chips))
    return H.Context(cell=_Cell(), trace=summary,
                     counters={"blocks": blocks}, device_kind=device_kind,
                     n_chips=len(chips))


@pytest.fixture
def capture(tmp_path, monkeypatch):
    """The cell's capture on disk, read back as `events`."""
    monkeypatch.setattr(H, "OUT_DIR", tmp_path)
    where = tmp_path / "trace" / _Cell.name / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    (where / "h.xplane.pb").write_bytes(b"")
    _exchange._load.cache_clear()

    def use(events):
        monkeypatch.setattr(TR, "load_events", lambda path: events)
    use(trace())
    return use


def test_dma_time_is_clipped_to_the_window_and_summed_per_chip():
    got = _exchange.dma_seconds(trace(), (0, 1))
    assert got[0] == pytest.approx(CHIP0)
    assert got[1] == pytest.approx(CHIP1)


def test_exchange_dma_ms_is_the_mean_chips_time_a_block(capture):
    read = H.metric_reader("exchange_dma_ms")
    want = (CHIP0 + CHIP1) / 2 / 3 * 1e3
    assert read(_ctx(trace(), 3)) == pytest.approx(want)


def test_exchange_dma_roofline_is_least_ici_time_over_summed_dma_time(
        capture):
    read = H.metric_reader("exchange_dma_roofline")
    # per block: both directions across the one internal face of 32 rows
    # x 8 levels, depth 4, three float32 fields
    per_block = 2 * 4 * 32 * 8 * 3 * 4
    assert EW.least_exchange_bytes(**SMALL) == per_block
    want = 100.0 * (3 * per_block / 2e11) / (CHIP0 + CHIP1)
    assert read(_ctx(trace(), 3)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["exchange_dma_ms", "exchange_dma_roofline"])
def test_no_reading_untraced_without_blocks_or_without_dma(capture, name):
    read = H.metric_reader(name)
    assert read(_ctx(None, 3)) is None                       # untraced
    assert read(_ctx(trace(), 0)) is None                    # no block
    # the 1x1 cell: one chip, the fused kernel and no exchange
    one_chip = [e for e in trace() if "dma" not in e.name
                and e.plane != "/device:TPU:1"]
    capture(one_chip)
    _exchange._load.cache_clear()
    assert read(_ctx(one_chip, 3, chips=(0,))) is None


@pytest.mark.parametrize("name", ["exchange_dma_ms", "exchange_dma_roofline"])
def test_no_reading_without_a_capture(tmp_path, monkeypatch, name):
    monkeypatch.setattr(H, "OUT_DIR", tmp_path)
    assert H.metric_reader(name)(_ctx(trace(), 3)) is None


def test_least_exchange_bytes_of_the_cells():
    cfg = H.load_cell("integ-537m-2x2-dma").config
    got = EW.least_exchange_bytes(cfg["X"], cfg["Y"], cfg["Z"], cfg["mesh"],
                                  cfg["T"])
    assert got == 37_748_736                      # 37.7 MB a block
    one = H.load_cell("integ-268m-1chip").config
    assert EW.least_exchange_bytes(one["X"], one["Y"], one["Z"],
                                   one["mesh"], one["T"]) == 0


def test_an_unknown_device_kind_is_an_error(capture):
    with pytest.raises(KeyError, match="no ICI peak"):
        EW.least_ici_seconds(1, "TPU v99")
    with pytest.raises(KeyError, match="no ICI peak"):
        H.metric_reader("exchange_dma_roofline")(
            _ctx(trace(), 3, device_kind="TPU v99"))
    assert EW.ici_peaks("TPU v5 lite")["ici_bytes_per_s"] == 2e11
