"""The control of the check: the reference put in the program's place,
computed one precision below what the configuration states.

The configurations state float32 values; the control rounds the operands'
values to bfloat16 (the step that would tempt a later change: half the
bytes per value) and multiplies them with float32 sums, column batch by
column batch as the program's plan lays them out. Run through the harness in
place of the program, its batches must come out not correct: the largest
relative error it reads is the upper end of the ``max_rel_err`` limit.

``FAULTS`` are the program broken underneath the driver, one way each: its
fused batch step leaving out half of a batch's entries, altering a value
where it is produced, returning its first batch again (a step that returns
its state unchanged), or placing an entry outside its tile. Each has to
come out not correct too.

    python3 -m bench.control --workload <cell> --seeds 1,2,3 [--fault <name>]

runs the harness with the control (or the program with ``--fault``) in the
program's place (set-up, window and check), on the chip, at the cell's own
size, and prints one line per seed with the numbers compared.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from types import SimpleNamespace

import numpy as np
import scipy.sparse as sps


def bf16_round(x: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def make_control(A, B, grid, budget, spec, left, right):
    """``multiply(consumer, exec_spec=None)`` computing each batch of the
    program's plan in bfloat16 values with float32 sums, on the host."""
    from repro.core.batched import batch_column_map, plan_batches
    from repro.core.specs import PlanSpec

    nb = plan_batches(A, B, grid, budget, spec=spec or PlanSpec()).num_batches
    a = sps.csr_matrix(left.to_scipy(np.float32))
    a.data = bf16_round(a.data)
    b = sps.csc_matrix(right.to_scipy(np.float32))
    b.data = bf16_round(b.data)
    n = right.n

    def multiply(consumer, exec_spec=None):
        for bi in range(nb):
            col_map = batch_column_map(n, grid, nb, bi)
            cols = np.asarray(col_map).ravel()
            c = (a @ b[:, cols]).tocoo()
            nnz = c.nnz
            batch = SimpleNamespace(
                rows=c.row.astype(np.int32).reshape(1, 1, 1, nnz),
                cols=c.col.astype(np.int32).reshape(1, 1, 1, nnz),
                vals=c.data.astype(np.float32).reshape(1, 1, 1, nnz),
                nnz=np.array([[[nnz]]], np.int32),
                tile_shape=(left.n, len(cols)),
            )
            consumer(bi, batch, np.asarray(col_map).reshape(1, 1, -1))

    return multiply


def _drop_half(c, state):
    return c.__class__(**{**c.__dict__, "nnz": c.nnz // 2})


def _alter_one(c, state):
    return c.__class__(**{**c.__dict__, "vals": c.vals.at[..., 0].mul(1.5)})


def _stale(c, state):
    return state.setdefault("first", c)


def _wild_column(c, state):
    return c.__class__(**{**c.__dict__, "cols": c.cols.at[..., 0].set(10**6)})


FAULTS = {
    "half_left_out": _drop_half,
    "answer_altered": _alter_one,
    "step_returns_old_state": _stale,
    "entry_outside_tile": _wild_column,
}


def plant(fault: str):
    """Break the program's fused batch step underneath the driver with
    ``FAULTS[fault]``; returns a function that undoes it."""
    import repro.core.batched as batched

    orig, corrupt, state = batched._fused_jit, FAULTS[fault], {}

    def fused(*args, **kwargs):
        c, ovf = orig(*args, **kwargs)
        return corrupt(c, state), ovf

    batched._fused_jit = fused

    def undo():
        batched._fused_jit = orig

    return undo


def main(argv=None) -> int:
    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None,
                    help="the window (default: BENCHMARK.json's run_seconds)")
    ap.add_argument("--fault", choices=sorted(FAULTS), default=None,
                    help="run the program with this fault instead of the "
                         "bf16 control")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("bench.control: no TPU", file=sys.stderr)
        return 2
    bench = run.Benchmark(run.ROOT)
    cell = bench.cell(args.workload)
    seconds = args.seconds or bench.spec["run_seconds"]
    peaks = run.peaks_for(devices[0].device_kind)
    if args.fault:
        plant(args.fault)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, _ = run.run_cell(
            cell, seed, seconds, False, devices, peaks,
            t_process=time.perf_counter(),
            make_multiply=None if args.fault else make_control)
        print(json.dumps({"control": args.fault or "bf16",
                          "workload": cell.name,
                          "seed": seed, "correct": result["correct"],
                          "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
