"""The readers of the serving engine's spans, on a synthetic trace: the
benchmark's window span and the engine's spans on the host plane, and
one chip's ops."""
import pytest

from _benchcells import ROOT  # noqa: F401
from bench import harness as H
from bench import trace as TR
from bench.metrics import _spans

KERNEL = ('%vmap_advect_fused_.1 = (f32[13,16,1024,64]{3,2,1,0}) '
          'custom-call(f32[13,16,1024,64]{3,2,1,0} %p), '
          'custom_call_target="tpu_custom_call"')


def host(name, start, dur):
    return TR.Event(TR.HOST_PLANE, "python", name, start, dur)


def trace():
    """A window [1000, 3000) holding two mega-steps, with spans that
    begin before it and end after it."""
    return [
        host(TR.WINDOW, 1000, 2000), host("run() call", 1000, 2000),
        host("engine.run", 500, 2600),
        host("engine.snapshot", 800, 300),         # 100 of it in the window
        host("engine.prime", 1200, 100),
        host("engine.megastep", 1300, 600),
        host("engine.upload", 1300, 200), host("engine.device", 1500, 100),
        host("engine.download", 1600, 300),
        host("engine.crop", 1900, 100),
        host("engine.snapshot", 2000, 100),
        host("engine.megastep", 2100, 600),
        host("engine.upload", 2100, 200), host("engine.device", 2300, 100),
        host("engine.download", 2400, 300),
        host("engine.crop", 2700, 500),            # 300 of it in the window
        TR.Event("/device:TPU:0", TR.OPS_LINE, KERNEL, 1500, 100),
        TR.Event("/device:TPU:0", TR.OPS_LINE, KERNEL, 2300, 100),
    ]


def test_spans_are_clipped_to_the_window_and_summed_per_megastep():
    spans = _spans.in_window(trace())
    assert set(spans) == set(_spans.LEAVES)
    assert "engine.megastep" not in spans and "engine.run" not in spans
    assert _spans.phase_ms(spans, "engine.snapshot", 2) == pytest.approx(
        (100 + 100) / 2 * 1e-6)
    assert _spans.phase_ms(spans, "engine.crop", 2) == pytest.approx(
        (100 + 300) / 2 * 1e-6)
    assert _spans.phase_ms(spans, "engine.upload", 4) == pytest.approx(
        400 / 4 * 1e-6)
    # the leaves fill the window but for [1100, 1200)
    per_step = sum(_spans.phase_ms(spans, n, 2) for n in _spans.LEAVES)
    assert per_step == pytest.approx((2000 - 100) / 2 * 1e-6)


def test_no_megasteps_or_no_spans_give_no_reading_not_zero():
    spans = _spans.in_window(trace())
    assert _spans.phase_ms(spans, "engine.upload", 0) is None
    assert _spans.phase_ms({}, "engine.upload", 2) is None
    assert _spans.phase_ms(None, "engine.upload", 2) is None
    # a program without the engine's spans: the benchmark's spans only
    bare = [e for e in trace() if not e.name.startswith("engine.")]
    assert _spans.in_window(bare) == {}
    assert _spans.idle_outside(_spans.in_window(bare), [(0, 10)]) is None
    spans = _spans.in_window([e for e in trace()
                              if e.name != "engine.prime"])
    assert _spans.phase_ms(spans, "engine.prime", 2) is None


def test_idle_outside_the_spans_is_the_share_no_leaf_covers():
    s = TR.reduce(trace(), [0])
    idle = s.devices[0].idle
    assert idle == [(1000, 1500), (1600, 2300), (2400, 3000)]
    spans = _spans.in_window(trace())
    # only [1100, 1200), between the snapshot and the prime, is uncovered
    assert _spans.idle_outside(spans, idle) == pytest.approx(
        100.0 * 100 / 1800)
    assert _spans.idle_outside(spans, []) is None


class _Cell:
    name = "span-test-cell"


class _Ctx:
    def __init__(self, summary, megasteps):
        self.cell, self.trace = _Cell(), summary
        self.counters = {"megasteps": megasteps}


def test_readers_find_nothing_without_a_capture(tmp_path, monkeypatch):
    monkeypatch.setattr(H, "OUT_DIR", tmp_path)
    s = TR.reduce(trace(), [0])
    for name in ("engine_upload_ms", "engine_download_ms",
                 "engine_snapshot_ms", "engine_prime_ms", "engine_crop_ms",
                 "engine_device_ms", "idle_outside_engine_spans"):
        read = H.metric_reader(name)
        assert read(_Ctx(s, 2)) is None, name
        assert read(_Ctx(None, 2)) is None, name


def test_readers_read_the_cells_capture(tmp_path, monkeypatch):
    monkeypatch.setattr(H, "OUT_DIR", tmp_path)
    xplane = tmp_path / "trace" / _Cell.name / "plugins" / "profile" / "1"
    xplane.mkdir(parents=True)
    (xplane / "h.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(TR, "load_events", lambda path: trace())
    s = TR.reduce(trace(), [0])
    ctx = _Ctx(s, 2)
    assert H.metric_reader("engine_crop_ms")(ctx) == pytest.approx(2e-4)
    assert H.metric_reader("engine_device_ms")(ctx) == pytest.approx(1e-4)
    assert H.metric_reader("idle_outside_engine_spans")(ctx) == \
        pytest.approx(100.0 * 100 / 1800)
    assert H.metric_reader("engine_prime_ms")(_Ctx(s, 0)) is None
