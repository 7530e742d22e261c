"""The window and its stop logic, and whole runs of a tiny cell on the CPU:
sound runs come out correct; the control and broken programs do not."""
from __future__ import annotations

import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import control, run
from bench.conftest import TEST_PEAKS
from bench.window import StopWindow, Window

# fits the tiny protein cell's inputs and forces b > 1 (the chip's budget
# cannot be read on the CPU)
TINY_BUDGET = 20_000_000
# batches the runs below count: two, so that a step returning an earlier
# batch shows (a chip cell's window holds one memory-sized batch, PERF.md
# section 2)
WINDOW_BATCHES = 2


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fake_copy(c_batch):
    z = np.zeros((1, 1, 1, 1), np.int32)
    return z, z, z.astype(np.float32), np.zeros((1, 1, 1), np.int32), 1


def fake_multiply(clock, batches, step_s, calls):
    """A system whose calls each deliver ``batches`` batches ``step_s``
    apart on the fake clock."""
    def multiply(consumer):
        calls.append(clock.t)
        for bi in range(batches):
            clock.t += step_s
            consumer(bi, None, np.array([[[bi]]]))
    return multiply


@pytest.mark.parametrize("seconds,batches,step,want_calls,want_batches", [
    (10.0, 8, 4.0, 1, 3),    # stops inside the first call at 12 s
    (10.0, 8, 40.0, 1, 1),   # one long batch ends the window
    (10.0, 1, 3.0, 4, 4),    # one-batch calls back to back: 3, 6, 9, 12 s
    (12.0, 2, 3.0, 2, 4),    # the arrival at exactly 12 s ends it
])
def test_window_stops_at_first_arrival_past_its_length(
        seconds, batches, step, want_calls, want_batches):
    clock, calls = FakeClock(), []
    win = Window(seconds, clock=clock, copy=fake_copy)
    win.run(fake_multiply(clock, batches, step, calls))
    assert win.calls == want_calls == len(calls)
    assert len(win.batches) == want_batches
    assert win.elapsed == pytest.approx(want_batches * step)
    assert win.elapsed >= seconds
    assert win.elapsed - step < seconds  # the arrival before was inside
    assert [b.call for b in win.batches] == [
        i // batches for i in range(want_batches)]
    assert win.arrivals()[-1] == pytest.approx(win.elapsed)


def test_consumer_raises_stop_only_at_the_end():
    clock = FakeClock()
    win = Window(5.0, clock=clock, copy=fake_copy)
    win.start, win.calls = clock(), 1
    clock.t += 4.0
    assert win.consumer(0, None, np.array([[[0]]])) == 0
    clock.t += 1.0
    with pytest.raises(StopWindow):
        win.consumer(1, None, np.array([[[1]]]))
    assert win.end == clock.t


def test_products_per_s_reads_the_counted_batches(bench_spec):
    reader = bench_spec.cell("protein-2e18.square-sync").end_to_end[0].reader
    counts = SimpleNamespace(products=lambda cols: 1000 * len(cols))
    win = SimpleNamespace(
        batches=[SimpleNamespace(columns=np.arange(5)),
                 SimpleNamespace(columns=np.arange(3))], elapsed=4.0)
    assert reader.read(SimpleNamespace(window=win, counts=counts)) == 2000.0


class TickingClock:
    """Moves ``step`` seconds at every reading: the window's start, then
    each arrival."""

    def __init__(self, step):
        self.t, self.step = 100.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def _run(cell, seed=11, arrivals=WINDOW_BATCHES, step=1.0, **kw):
    """A whole untraced run on the CPU whose clock moves ``step`` seconds
    per batch, so that the window counts ``arrivals`` batches."""
    return run.run_cell(cell, seed, arrivals * step - step / 2, False,
                        jax.devices(), TEST_PEAKS,
                        t_process=time.perf_counter(), budget=TINY_BUDGET,
                        clock=TickingClock(step), **kw)


def test_sound_run_is_correct(tiny_protein):
    result, ctx = _run(tiny_protein)
    assert result["correct"] is True
    assert result["attempted"] == 2 and result["failed"] == 0
    assert set(result["metrics"]) == {"products_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    checks = result["checks"]
    assert checks["pattern_diff"]["value"] == 0
    assert checks["max_rel_err"]["value"] < 1e-6
    assert ctx.window.calls == 1  # b > 1: two batches of one call
    assert [b.index for b in ctx.window.batches] == [0, 1]


def test_sound_run_on_the_skewed_graph_is_correct(tiny_rmat):
    result, _ = _run(tiny_rmat, seed=2**31 + 5)
    assert result["correct"] is True and result["attempted"] == 2


def test_control_in_bf16_is_not_correct(tiny_protein):
    result, _ = _run(tiny_protein, make_multiply=control.make_control)
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["pattern_diff"]["value"] == 0
    assert checks["max_rel_err"]["value"] > 10 * checks["max_rel_err"][
        "limit"]


def _broken(monkeypatch, fault):
    """Break the program's fused batch step underneath the driver."""
    import repro.core.batched as batched

    # restored after the test
    monkeypatch.setattr(batched, "_fused_jit", batched._fused_jit)
    control.plant(fault)


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered",
                                   "step_returns_old_state"])
def test_broken_program_is_not_correct(tiny_protein, monkeypatch, fault):
    _broken(monkeypatch, fault)
    result, _ = _run(tiny_protein)
    assert result["correct"] is False
    assert result["failed"] >= 1


def test_entry_outside_its_tile_is_not_correct(tiny_protein, monkeypatch):
    """A column index past the batch's tile is a wrong answer, not a crash."""
    _broken(monkeypatch, "entry_outside_tile")
    result, _ = _run(tiny_protein)
    assert result["correct"] is False
    assert result["checks"]["pattern_diff"]["value"] >= 1
