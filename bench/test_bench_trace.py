"""The reduction from a profiler trace to device busy time, per-executable
time, top operations and named idle gaps."""
from __future__ import annotations

import pytest

from bench import trace
from bench.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _events():
    ms = 1e6
    return [
        Event(HOST, "python", "bench.window", 0 * ms, 100 * ms),
        Event(HOST, "python", "bench.call", 1 * ms, 98 * ms),
        Event(HOST, "python", "bench.consumer", 60 * ms, 8 * ms),
        # before the window: not counted
        Event(DEV, "XLA Ops", "sort.1", -20 * ms, 10 * ms),
        Event(DEV, "XLA Modules", "jit_summa3d_fused_step(1)", -20 * ms,
              10 * ms),
        # inside: ops of two overlapping kinds, one module run wholly in
        Event(DEV, "XLA Ops", "sort.1", 10 * ms, 30 * ms),
        Event(DEV, "XLA Ops", "gather.2", 35 * ms, 20 * ms),  # overlaps
        Event(DEV, "XLA Ops", "copy.3", 70 * ms, 20 * ms),
        Event(DEV, "XLA Modules", "jit_summa3d_fused_step(1)", 10 * ms,
              45 * ms),
        # runs past the window's end: clipped, its module not counted
        Event(DEV, "XLA Ops", "sort.1", 95 * ms, 10 * ms),
        Event(DEV, "XLA Modules", "jit_summa3d_fused_step(1)", 70 * ms,
              35 * ms),
    ]


def test_busy_union_and_idle_share():
    s = trace.summarize(_events())
    assert s.window_ns == pytest.approx(100e6)
    # busy: [10, 55] + [70, 90] + [95, 100] = 45 + 20 + 5 ms
    assert s.busy_ns == pytest.approx(70e6)
    assert s.idle_frac == pytest.approx(0.30)


def test_executable_runs_inside_the_window_only():
    s = trace.summarize(_events())
    assert s.module_durations("summa3d_fused_step") == [pytest.approx(0.045)]
    assert s.module_durations("no_such_step") == []


def test_top_ops_and_idle_gaps_named_by_host_span():
    s = trace.summarize(_events())
    assert [n for n, _ in s.top_ops] == ["sort.1", "gather.2", "copy.3"]
    assert s.top_ops[0][1] == pytest.approx(0.035)  # 30 ms + 5 clipped
    # gaps: [0,10] call, [55,70] consumer at its middle 62.5, [90,95] call
    assert s.idle_gaps[0] == ("bench.consumer", pytest.approx(0.015))
    assert s.idle_gaps[1] == ("bench.call", pytest.approx(0.010))
    assert s.idle_gaps[2] == ("bench.call", pytest.approx(0.005))


def test_busy_is_averaged_over_devices():
    ev = _events() + [
        Event("/device:TPU:1", "XLA Ops", "sort.1", 0.0, 100e6)]
    s = trace.summarize(ev)
    assert s.devices == 2
    assert s.busy_ns == pytest.approx((70e6 + 100e6) / 2)


def test_no_window_or_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        trace.summarize([e for e in _events() if e.name != "bench.window"])
    with pytest.raises(ValueError):
        trace.summarize([e for e in _events() if e.plane == HOST])


CHIP_TRACE = "bench/testdata/protein-2e16.expand.xplane.pb.gz"


def test_trace_recorded_on_the_chip():
    """A traced window of protein-2e16.expand on one TPU v5e: one whole
    multiply (b = 1), the device busy but for the plan before the step and
    the copy of the batch to the host after it."""
    from bench.conftest import ROOT

    s = trace.summarize(trace.load_xplane(str(ROOT / CHIP_TRACE)))
    assert s.devices == 1
    assert s.window_ns == pytest.approx(13660604436.0)
    assert s.busy_ns == pytest.approx(13395485806.0)
    assert s.idle_frac == pytest.approx(0.0194075, abs=1e-6)
    assert s.module_durations("summa3d_fused_step") == [
        pytest.approx(13.306939892)]
    assert s.top_ops[0] == ("%fusion.4 s32[87654656]",
                            pytest.approx(1.98943442))
    assert s.idle_gaps[0] == ("bench.consumer", pytest.approx(0.196858302))
    assert s.idle_gaps[1] == ("bench.call", pytest.approx(0.068257252))


def test_short_op_names():
    assert trace.short_op_name(
        "%fusion.4 = s32[87654656]{0:T(1024)} fusion(s32[2] %a), kind=kCustom"
    ) == "%fusion.4 s32[87654656]"
    assert trace.short_op_name("copy-start") == "copy-start"
