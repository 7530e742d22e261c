"""The split of a traced window by the program's own names: the wire reader
of each device op's ``tf_op``, phase seconds per run of the fused step, the
program's spans and idle gaps named by them."""
from __future__ import annotations

import pytest

from bench import phases, xspace
from bench.conftest import ROOT
from bench.phases import Op, Span, Trace
from bench.trace import Event

DEV = "/device:TPU:0"
MS = 1e6
OLD_TRACE = str(ROOT / "bench/testdata/protein-2e16.expand.xplane.pb.gz")


def test_scope_is_the_innermost_spgemm_segment():
    assert phases.scope_of(
        "jit(summa3d_fused_step)/spgemm.esc.compress/jit(lexsort)/sort:"
    ) == "spgemm.esc.compress"
    assert phases.scope_of(
        "jit(f)/spgemm.merge/spgemm.esc.compress/gather:") == \
        "spgemm.esc.compress"
    assert phases.scope_of("jit(f)/spgemm.gather:") == "spgemm.gather"
    assert phases.scope_of("jit(summa3d_fused_step)/gather:") == ""
    assert phases.scope_of("") == ""


def _trace(scoped=True, retries=0):
    """A window of 100 ms: the plan (symbolic 10 ms on the device, host
    plan 8 ms with the device idle), one 60 ms fused-step run, a copy to
    the host, and a second step run cut by the window's end."""
    def sc(scope):  # the tf_op prefix of an op under ``scope``
        return "jit(summa3d_fused_step)/" + (scope + "/" if scoped else "")

    spans = [
        Span("bench.window", 0, 100 * MS),
        Span("bench.call", 1 * MS, 99 * MS),
        Span("bench.consumer", 85 * MS, 10 * MS),
    ]
    if scoped:
        spans += [
            Span("spgemm.call", 2 * MS, 98 * MS),
            Span("spgemm.plan", 3 * MS, 20 * MS),
            Span("spgemm.symbolic", 3 * MS, 11 * MS),
            Span("spgemm.plan_host", 14 * MS, 8 * MS),
            Span("spgemm.dispatch", 23 * MS, 1 * MS, (("batch", 0),)),
            Span("spgemm.wait", 24 * MS, 60 * MS, (("batch", 0),)),
            Span("spgemm.consume", 84 * MS, 12 * MS, (("batch", 0),)),
        ] + [Span("spgemm.retry", 23 * MS, 0.1 * MS,
                  (("batch", 0), ("kind", "mul")))] * retries
        # a second call's planner spans: not the first call's
        spans += [Span("spgemm.call", 99 * MS, 5 * MS),
                  Span("spgemm.symbolic", 99.5 * MS, 3 * MS)]
    ops = [
        # the symbolic pass, another executable
        Op(DEV, 3 * MS, 10 * MS, "jit(_symbolic3d_jit)/scatter-add:"),
        # the step run [24, 84]: a loop with an op nested in it
        Op(DEV, 24 * MS, 10 * MS, sc("spgemm.select") + "gather:"),
        Op(DEV, 34 * MS, 30 * MS, sc("spgemm.esc.expand") + "gather:"),
        Op(DEV, 64 * MS, 18 * MS, ""),  # a loop the compiler made
        Op(DEV, 66 * MS, 10 * MS, sc("spgemm.esc.compress") + "sort:"),
        Op(DEV, 82 * MS, 2 * MS, sc("spgemm.merge") + "slice:"),
        Op(DEV, 97 * MS, 10 * MS,
           sc("spgemm.esc.expand") + "gather:"),  # cut off
    ]
    modules = [
        Event(DEV, "XLA Modules", "jit__symbolic3d_jit(1)", 3 * MS, 10 * MS),
        Event(DEV, "XLA Modules", "jit_summa3d_fused_step(2)", 24 * MS,
              60 * MS),
        Event(DEV, "XLA Modules", "jit_summa3d_fused_step(2)", 97 * MS,
              10 * MS),
    ]
    return Trace(spans, ops, modules)


def test_phase_seconds_of_each_run_exclude_nested_ops():
    s = phases.summarize(_trace())
    assert s.counted_batches() == 1
    (run,) = s.step_runs  # the cut run is not in the window
    assert run == {
        "spgemm.select": pytest.approx(0.010),
        "spgemm.esc.expand": pytest.approx(0.030),
        "": pytest.approx(0.008),  # 18 ms less the 10 nested in it
        "spgemm.esc.compress": pytest.approx(0.010),
        "spgemm.merge": pytest.approx(0.002),
    }
    assert s.step_phases() == [
        ["spgemm.esc.expand", pytest.approx(0.030)],
        ["spgemm.select", pytest.approx(0.010)],
        ["spgemm.esc.compress", pytest.approx(0.010)],
        [phases.UNSCOPED, pytest.approx(0.008)],
        ["spgemm.merge", pytest.approx(0.002)],
    ]


def test_metrics_read_phases_and_the_first_calls_planner_spans():
    m = phases.summarize(_trace(retries=2)).metrics()
    assert set(m) == set(phases.METRICS)
    assert m["select_device_s"] == pytest.approx(0.010)
    assert m["sort_a_device_s"] == 0.0  # scoped step, no such op
    assert m["expand_device_s"] == pytest.approx(0.030)
    assert m["compress_device_s"] == pytest.approx(0.010)
    assert m["merge_device_s"] == pytest.approx(0.002)
    assert m["symbolic_s"] == pytest.approx(0.011)
    assert m["plan_host_s"] == pytest.approx(0.008)
    assert m["overflow_retries"] == 2
    assert phases.summarize(_trace()).metrics()["overflow_retries"] == 0


def test_a_program_without_scopes_or_spans_gives_no_metric():
    s = phases.summarize(_trace(scoped=False))
    assert s.metrics() == dict.fromkeys(phases.METRICS)
    assert s.step_phases() == [[phases.UNSCOPED, pytest.approx(0.060)]]


def test_idle_gaps_are_named_by_the_innermost_program_span():
    s = phases.summarize(_trace())
    # idle [13, 24]: host plan at its middle 18.5; [0, 3]: call, before the
    # plan; [84, 97]: the consumer's copy
    assert s.idle_gaps[0] == ("bench.consumer", pytest.approx(0.013))
    assert s.idle_gaps[1] == ("spgemm.plan_host", pytest.approx(0.011))
    assert s.idle_gaps[2] == ("bench.call", pytest.approx(0.003))
    unscoped = phases.summarize(_trace(scoped=False))
    assert unscoped.idle_gaps[1] == ("bench.call", pytest.approx(0.011))


def test_batches_bound_the_runs_read():
    s = phases.summarize(_trace())
    assert s.step_phases(0) == []
    assert s.metrics(0)["expand_device_s"] is None


def test_no_window_or_no_device_work_is_an_error():
    tr = _trace()
    with pytest.raises(ValueError):
        phases.summarize(Trace([x for x in tr.spans
                                if x.name != "bench.window"],
                               tr.ops, tr.modules))
    with pytest.raises(ValueError):
        phases.summarize(Trace(tr.spans, [], tr.modules))


def test_wire_reader_recovers_tf_op_of_the_recorded_chip_trace():
    """The parent program's trace (protein-2e16.expand, no scopes): 9.55 s
    of its 13.31 s step is ``jit(summa3d_fused_step)/gather:``."""
    ops = xspace.tf_ops(xspace.read_bytes(OLD_TRACE))
    assert set(ops) == {"/device:TPU:0", "/device:CUSTOM:Megascale Trace"}
    names = ops["/device:TPU:0"]
    assert len(names) == 106
    tr = phases.load(OLD_TRACE)
    (run,) = [m for m in tr.modules if "summa3d_fused_step" in m.name]
    by_tf_op = {}
    for op in tr.ops:
        if run.start <= op.start and op.start + op.dur <= run.end:
            by_tf_op[op.tf_op] = by_tf_op.get(op.tf_op, 0) + op.dur
    assert by_tf_op["jit(summa3d_fused_step)/gather:"] / 1e9 == \
        pytest.approx(9.5500, abs=1e-4)
    assert by_tf_op["jit(summa3d_fused_step)/scatter-min:"] / 1e9 == \
        pytest.approx(2.0863, abs=1e-4)


def test_the_parent_programs_trace_is_all_unscoped():
    s = phases.summarize(phases.load(OLD_TRACE))
    assert s.counted_batches() == 1
    assert s.step_phases() == [[phases.UNSCOPED, pytest.approx(13.306936,
                                                               abs=1e-6)]]
    assert s.metrics() == dict.fromkeys(phases.METRICS)
    assert s.idle_gaps[0] == ("bench.consumer", pytest.approx(0.196858302))


NEW_TRACE = str(ROOT / "bench/testdata/protein-2e18.square-sync.xplane.pb.gz")


@pytest.fixture(scope="module")
def protein_window():
    """A traced window of protein-2e18.square-sync on one TPU v5e, recorded
    with the program's scopes and spans: the plan and one batch (b = 16)."""
    return phases.summarize(phases.load(NEW_TRACE))


def test_recorded_window_splits_the_step_by_phase(protein_window):
    s = protein_window
    assert s.counted_batches() == 1
    (run,) = s.step_runs
    step_s = sum(run.values())
    assert step_s == pytest.approx(50.700525, abs=1e-6)
    assert s.step_phases() == [
        ["spgemm.esc.expand", pytest.approx(25.975763, abs=1e-6)],
        ["spgemm.esc.compress", pytest.approx(21.990479, abs=1e-6)],
        ["spgemm.esc.sort_a", pytest.approx(1.848495, abs=1e-6)],
        ["spgemm.select", pytest.approx(0.667613, abs=1e-6)],
        [phases.UNSCOPED, pytest.approx(0.216787, abs=1e-6)],
        ["spgemm.gather", pytest.approx(0.001387, abs=1e-6)],
    ]
    assert run[""] / step_s < 0.01


def test_recorded_window_gives_the_eight_numbers(protein_window):
    assert protein_window.metrics() == {
        "select_device_s": pytest.approx(0.669000, abs=1e-6),
        "sort_a_device_s": pytest.approx(1.848495, abs=1e-6),
        "expand_device_s": pytest.approx(25.975763, abs=1e-6),
        "compress_device_s": pytest.approx(21.990479, abs=1e-6),
        "merge_device_s": 0.0,  # one layer: Merge-Fiber has one piece
        "symbolic_s": pytest.approx(1.283959, abs=1e-6),
        "plan_host_s": pytest.approx(0.001634, abs=1e-6),
        "overflow_retries": 0,
    }


def test_recorded_window_names_its_gaps_by_the_programs_spans(
        protein_window):
    gaps = protein_window.idle_gaps
    assert gaps[0] == ("bench.consumer", pytest.approx(0.778219, abs=1e-6))
    assert gaps[1] == ("spgemm.symbolic", pytest.approx(0.005663, abs=1e-6))
    assert all(name.startswith("spgemm.") for name, _ in gaps[1:])
    spans = protein_window.spans
    (call,) = [x for x in spans if x.name == "spgemm.call"]
    (dispatch,) = [x for x in spans if x.name == "spgemm.dispatch"]
    assert dispatch.args == (("batch", 0),)
    assert all(call.start <= x.start and x.end <= call.end for x in spans
               if x.name.startswith("spgemm."))
