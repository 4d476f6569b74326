"""The reduction from a trace to busy time, kernel time and idle gaps: on
hand-made planes, and on a small trace recorded on the chip."""

import os

import pytest

from conftest import BENCH, load

tr = load("trace_reduce.py")
RECORDED = os.path.join(BENCH, "tests", "data", "node1_rest.xplane.pb")


def planes(ops, marks=(), modules=()):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            (tr.MARK, float(t), 10.0) for t in marks]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": list(ops)},
            {"name": tr.MODULES_LINE, "events": list(modules)}]},
    ]


def test_union_counts_nested_and_overlapping_once():
    assert tr.union_length([(0, 10), (2, 5), (8, 12), (20, 21)]) == 13
    assert tr.union_length([]) == 0


def test_busy_kernel_time_and_gaps_attributed_to_the_host():
    # window 0..1000 ns; two flushes; ops (name, start, duration)
    ops = [("sort", 100.0, 50.0), ("fusion", 120.0, 10.0),   # nested
           ("sort", 600.0, 40.0)]
    host = [("flush: snapshot", 50.0, 100.0), ("ingest", 300.0, 500.0)]
    red = tr.reduce(planes(ops), (0.0, 1000.0), host,
                    [(90.0, 200.0), (590.0, 700.0)])
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(90e-9)
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["kernel_ms_per_flush"] == pytest.approx([50e-6, 40e-6])
    assert red["device_ops"][0] == ["sort", pytest.approx(90e-9)]
    gaps = dict(red["idle_gaps"])
    assert gaps["flush: snapshot"] == pytest.approx(50e-9)
    assert gaps["ingest"] == pytest.approx(200e-9)
    # what no host span covers: 0-50, 150-300, 500-600, 640-1000
    assert gaps["waiting for the tick"] == pytest.approx(660e-9)
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(1e-6)


def test_clock_offset_from_the_marks():
    pl = planes([], marks=(1000.0, 5000.0))
    assert tr.clock_offset_ns(pl, [10_001_000, 10_005_000]) == 10_000_000
    with pytest.raises(ValueError):
        tr.clock_offset_ns(pl, [1])


def test_no_device_plane_is_no_busy_time():
    red = tr.reduce([{"name": "/host:CPU", "lines": []}], (0.0, 1e9), [], [])
    assert red["devices"] == 0 and red["busy_s"] == 0.0


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_from_the_chip():
    pl = tr.load(RECORDED)
    ops = tr.device_ops(pl)
    assert list(ops) == [0] and len(ops[0]) > 0
    w0 = min(s for _n, s, _e in ops[0]) - 1e6
    w1 = max(e for _n, _s, e in ops[0]) + 1e6
    red = tr.reduce(pl, (w0, w1), [], [(w0, w1)])
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["kernel_ms_per_flush"][0] == pytest.approx(
        red["busy_s"] * 1e3)
    assert red["programs"], "the device plane has a programs line"


def test_kernel_bytes_from_the_hlo_text():
    kb = load("kernel_bytes.py")
    text = ('%uniform_eval.1 = f32[4,32768]{1,0:T(4,128)} custom-call('
            'f32[32768,8]{1,0:T(8,128)S(1)} %copy, s32[1,32768]{1,0} %b.3, '
            'f32[1,4]{1,0} %b.4), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[32768,8]{1,0}}')
    assert kb.is_kernel(text)
    # result + the three operands, nothing from the attributes
    assert kb.op_bytes(text) == 4 * (4 * 32768 + 32768 * 8 + 32768 + 4)
    assert kb.short_name(text) == "uniform_eval.1 (custom-call)"
    assert not kb.is_kernel("%copy = f32[8,8]{1,0} copy(f32[8,8]{0,1} %x)")
    assert kb.op_bytes("%c = bf16[2,3]{1,0} convert(f32[2,3]{1,0} %x)") == 36
    # 1 MB in 10 us at 819 GB/s: 12.2 %
    assert kb.hbm_share_percent(1e6, 10e-6, 819e9) == pytest.approx(12.21, 1e-3)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_trace_has_the_flush_kernel():
    kb = load("kernel_bytes.py")
    ops = tr.device_ops(tr.load(RECORDED))[0]
    kernels = [n for n, _s, _e in ops if kb.is_kernel(n)]
    assert kernels and all(kb.op_bytes(n) > 0 for n in kernels)
