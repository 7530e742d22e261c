"""End-to-end batched-SUMMA3D driver benchmark (paper Fig. 4/5 regime).

Measures the pipelined scheduler against the serial one on a multi-batch
R-MAT workload — the paper's claim that streaming numeric batches through the
communicators without the host in the loop is what keeps the per-batch
pipeline busy (§IV-A, Alg. 4):

  * serial: one fused step per batch, host-syncs the overflow flags before
    dispatching the next batch (the pre-pipelining schedule).
  * pipelined: batches i+1..i+lookahead dispatched before batch i's flags
    are read; consumer host work overlaps device compute.
  * ESC vs hash-accumulator local multiply on the same plan.

The suite also emits the hash path's MEMORY claim as a plan row: at a fixed
``per_process_memory`` (the probe budget that forces the ESC plan to batch),
the hash memory model — table slots over the merged output instead of the
full expansion — plans strictly fewer batches.

CPU wall times are NOT TPU predictions; the reproduced claim is the shape of
the comparison (host-sync per batch vs windowed async dispatch).
``run_summa3d_suite`` emits JSON rows for BENCH_summa3d.json: per-batch
wall-ms, end-to-end wall-ms per driver, and an acceptance summary row.
"""
import time

import numpy as np

from repro.core import gen
from repro.core.batched import (
    batched_summa3d,
    plan_batches,
    probe_memory_budget,
)
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.specs import ExecSpec, PlanSpec

from .common import emit


def _consumer_factory(n, grid):
    """HipMCL-style consumer: pull the batch to host and store it into the
    global output structure (the prune/store step of §V-C) — real host work
    that the pipelined schedule overlaps with device compute while the next
    batch's fused step is already in flight."""
    acc = np.zeros((n, n), np.float32)
    state = dict(nnz=0, t_last=0.0, batch_ms=[], acc=acc)
    pr, pc, l = grid.pr, grid.pc, grid.l

    def consumer(bi, c_batch, col_map):
        rows = np.asarray(c_batch.rows)
        cols = np.asarray(c_batch.cols)
        vals = np.asarray(c_batch.vals)
        nnzs = np.asarray(c_batch.nnz)
        tm = c_batch.tile_shape[0]
        for i in range(pr):
            for j in range(pc):
                for k in range(l):
                    cnt = int(nnzs[i, j, k])
                    gr = i * tm + rows[i, j, k, :cnt]
                    gc = col_map[j, k][cols[i, j, k, :cnt]]
                    np.add.at(acc, (gr, gc), vals[i, j, k, :cnt])
        state["nnz"] += int(nnzs.sum())
        now = time.perf_counter()
        state["batch_ms"].append((now - state["t_last"]) * 1e3)
        state["t_last"] = now
        return int(nnzs.sum())

    return state, consumer


def _run_once(A, B, grid, nb, pipelined, local_path="auto"):
    """One timed end-to-end driver run; returns (wall_ms, batch_ms, result)."""
    n = A.shape[0]
    state, consumer = _consumer_factory(n, grid)
    t0 = time.perf_counter()
    state["t_last"] = t0
    res = batched_summa3d(
        A, B, grid, per_process_memory=1 << 30, consumer=consumer,
        path="sparse",
        spec=PlanSpec(local_path=local_path, force_num_batches=nb),
        exec_spec=ExecSpec(pipelined=pipelined),
    )
    dt = (time.perf_counter() - t0) * 1e3
    return dt, state["batch_ms"], res


def _time_drivers(A, B, grid, nb, configs, iters=5):
    """Per-config wall-ms over ``iters`` INTERLEAVED rounds (variant A, B,
    ... then again): adjacent runs share machine conditions, so per-round
    ratios cancel noise drift that best-of-N over separate blocks cannot.
    Round 0 warms the jit cache and is discarded. Returns (per-config list of
    round times, serial per-batch ms from the fastest serial round, results).
    """
    times = {name: [] for name in configs}
    batch_ms = {name: None for name in configs}
    results = {}
    for it in range(iters + 1):
        for name, (pipelined, local_path) in configs.items():
            dt, bms, res = _run_once(A, B, grid, nb, pipelined, local_path)
            results[name] = res
            if it == 0:
                continue
            if not times[name] or dt < min(times[name]):
                batch_ms[name] = bms
            times[name].append(dt)
    return times, batch_ms, results


def run_summa3d_suite(scale=8, edge_factor=8, nb=32, iters=5) -> list:
    """The ``--suite summa3d`` entry: returns JSON-ready rows."""
    grid = make_grid(2, 2, 2)
    n = 1 << scale
    a = gen.rmat(scale=scale, edge_factor=edge_factor, seed=3)
    b = gen.rmat(scale=scale, edge_factor=edge_factor, seed=4)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    rows = []

    # --- the hash path's memory claim: at the SAME fixed per-process budget
    # (probed so the ESC plan must batch), the hash plan needs fewer
    # batches. Measured on the compressing regime the hash table targets —
    # A·Aᵀ of a denser R-MAT (2× edge factor), the overlap/MCL-like shape
    # where flops ≫ nnz(C).
    ah = gen.rmat(scale=scale, edge_factor=2 * edge_factor, seed=3)
    Ah = scatter_to_grid(ah, grid, "A")
    Bh = scatter_to_grid(ah.transpose().sort_rowmajor(), grid, "B")
    ppm = probe_memory_budget(Ah, Bh, grid)
    p_esc = plan_batches(Ah, Bh, grid, per_process_memory=ppm,
                         spec=PlanSpec(local_path="esc"))
    p_hash = plan_batches(Ah, Bh, grid, per_process_memory=ppm,
                          spec=PlanSpec(local_path="hash"))
    rows.append(dict(
        op="plan", variant="fixed_mem_batches", wall_ms=0.0, n=n,
        edge_factor=2 * edge_factor,
        per_process_memory=ppm,
        num_batches_esc=p_esc.num_batches,
        num_batches_hash=p_hash.num_batches,
        compression_factor=p_hash.compression_est,
        hash_table_cap=(p_hash.hash_caps.table_cap
                        if p_hash.hash_caps else 0),
    ))
    emit("fig4/summa3d_fixed_mem_batches", 0.0,
         f"b_esc={p_esc.num_batches} b_hash={p_hash.num_batches} "
         f"cf={p_hash.compression_est:.2f}")

    configs = {
        "serial": (False, "auto"),
        "pipelined": (True, "auto"),
        "pipelined_esc": (True, "esc"),
        "pipelined_hash": (True, "hash"),
    }
    times, batch_ms, results = _time_drivers(A, B, grid, nb, configs,
                                             iters=iters)
    for bi, ms in enumerate(batch_ms["serial"]):
        rows.append(dict(op="driver_batch", variant=f"serial_batch{bi}",
                         wall_ms=ms))
    for variant, ts in times.items():
        ms = float(np.median(ts))
        rows.append(dict(op="driver_e2e", variant=variant, wall_ms=ms,
                         wall_ms_min=min(ts), num_batches=nb))
        emit(f"fig4/summa3d_{variant}", ms * 1e3, f"b={nb}")
    res = results["pipelined"]

    # per-round ratio median: serial and pipelined runs of the same round are
    # adjacent in time, so shared machine noise cancels
    speedup = float(np.median(
        [s / max(p, 1e-9)
         for s, p in zip(times["serial"], times["pipelined"])]
    ))
    rows.append(dict(
        op="summary", variant="acceptance", wall_ms=0.0,
        speedup_pipelined_vs_serial=speedup,
        local_path_used=res.local_path,
        num_batches_esc=p_esc.num_batches,
        num_batches_hash=p_hash.num_batches,
        hash_batches_fewer=bool(p_hash.num_batches < p_esc.num_batches),
    ))
    emit("fig4/summa3d_speedup", 0.0, f"{speedup:.2f}x pipelined vs serial")
    return rows


def run() -> None:
    run_summa3d_suite(iters=2)
