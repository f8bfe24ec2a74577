"""The trace reducer, on a small synthetic trace shaped as a v5e trace:
one plane per chip whose `XLA Ops` events are named by their HLO text,
and the benchmark's host spans on the host plane."""
import pytest

from _benchcells import ROOT  # noqa: F401
from bench import trace as TR
from bench.metrics import _shares

KERNEL = ('%body.3 = (f32[4096,1024,64]{2,1,0:T(8,128)}) custom-call('
          'f32[4096,1024,64]{2,1,0:T(8,128)} %p), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')
COPY = ('%copy.26 = f32[4096,1024,64]{2,1,0:T(8,128)} copy('
        'f32[4096,1024,64]{2,1,0:T(8,128)} %collective-permute-done.1)')
PERMUTE = ('%collective-permute-done.1 = f32[4,512,64]{2,1,0} '
           'collective-permute-done((f32[4,512,64]{2,1,0}, '
           'f32[4,512,64]{2,1,0}) %collective-permute-start.1)')
FUSION = ('%fusion.7 = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %a), kind=kLoop, '
          'calls=%fused_computation.7')
WHILE = ('%while = (s32[], f32[4096,1024,64]{2,1,0}) while((s32[], '
         'f32[4096,1024,64]{2,1,0}) %tuple), condition=%cond, body=%body')


def dev(d, name, start, dur):
    return TR.Event(f"/device:TPU:{d}", TR.OPS_LINE, name, start, dur)


def host(name, start, dur):
    return TR.Event(TR.HOST_PLANE, "python", name, start, dur)


def trace():
    return [
        host(TR.WINDOW, 0, 1000), host("submit", 0, 100),
        host("wait", 100, 900),
        dev(0, WHILE, 100, 600), dev(0, KERNEL, 100, 300),
        dev(0, COPY, 400, 100), dev(0, PERMUTE, 600, 100),
        dev(0, FUSION, 650, 30),
        dev(1, KERNEL, 200, 500),
        dev(0, KERNEL, 1500, 100),            # after the window: left out
        TR.Event("/device:TPU:0", "XLA Modules", "jit_run(1)", 100, 600),
    ]


def test_opcodes_from_hlo_text():
    assert TR.opcode(KERNEL) == "custom-call"
    assert TR.opcode(COPY) == "copy"              # an operand's name is not it
    assert TR.opcode(PERMUTE) == "collective-permute-done"
    assert TR.opcode(WHILE) == "while"
    assert TR.opcode("jit_run(1)") == ""
    assert TR.op_name(KERNEL) == "body.3"
    assert TR.label(dev(0, KERNEL, 0, 1)) == "body.3 (tpu_custom_call)"


def test_kernels_and_collectives_are_told_apart():
    assert TR.is_kernel(dev(0, KERNEL, 0, 1))
    assert not TR.is_kernel(dev(0, FUSION, 0, 1))
    assert TR.is_collective(dev(0, PERMUTE, 0, 1))
    assert not TR.is_collective(dev(0, COPY, 0, 1))


def test_busy_idle_kernel_and_exposed_collective_time():
    s = TR.reduce(trace(), [0, 1])
    assert s.window_s == pytest.approx(1000e-9)
    d0, d1 = s.devices[0], s.devices[1]
    # the union of [100, 500) and [600, 700): the loop's own event, which
    # spans the gap between its body's ops, does not count
    assert d0.busy_s == pytest.approx(500e-9)
    assert d1.busy_s == pytest.approx(500e-9)
    assert s.busy_s == pytest.approx(500e-9)      # averaged over the chips
    assert s.idle_share == pytest.approx(0.5)
    assert d0.kernel_s == pytest.approx(300e-9)
    assert s.kernel_s == pytest.approx(800e-9)    # summed over the chips
    assert d0.collective_s == pytest.approx(100e-9)
    # the permute runs alone in [600, 650) and [680, 700)
    assert d0.exposed_collective_s == pytest.approx(70e-9)
    assert d0.idle == [(0, 100), (500, 600), (700, 1000)]


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = TR.reduce(trace(), [0])
    assert s.idle_by_span == [("wait", pytest.approx(400e-9)),
                              ("submit", pytest.approx(100e-9))]


def test_top_ops_leave_out_control_flow_and_name_the_kernel():
    s = TR.reduce(trace(), [0])
    names = [n for n, _ in s.top_ops]
    assert names[0] == "body.3 (tpu_custom_call)"
    assert not any("(while)" in n for n in names)
    out = TR.breakdown(s)
    assert set(out) == {"device_ops", "idle_gaps"}
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10


def test_a_trace_without_the_window_or_the_chip_is_refused():
    with pytest.raises(ValueError, match="window"):
        TR.reduce([e for e in trace() if e.name != TR.WINDOW], [0])
    with pytest.raises(ValueError, match="TPU:3"):
        TR.reduce(trace(), [3])


class _Ctx:
    def __init__(self, summary, counters):
        self.trace, self.counters = summary, counters
        self.device_kind = "TPU v5 lite"


def test_roofline_share_reads_the_work_over_kernel_time():
    s = TR.reduce(trace(), [0])
    # 819 bytes take 1 ns at the v5e's 819 GB/s; the kernels took 300 ns
    share = _shares.roofline_share(_Ctx(s, {"work_bytes": 819 * 30}))
    assert share == pytest.approx(10.0)
    assert _shares.idle_share(_Ctx(s, {})) == pytest.approx(50.0)


def test_nothing_to_read_gives_no_metric_not_zero():
    s = TR.reduce([e for e in trace() if not TR.is_kernel(e)], [0])
    assert _shares.roofline_share(_Ctx(s, {"work_bytes": 10})) is None
    assert _shares.roofline_share(_Ctx(None, {"work_bytes": 10})) is None
