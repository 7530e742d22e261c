#!/usr/bin/env python3
"""Chip smoke test: batched SUMMA3D computes the HipMCL expansion A·A of a
protein-similarity network on a TPU and checks it against scipy.

Run from the repository root:

    python chip_smoke.py             # one chip (the default)
    python chip_smoke.py --chips 4   # the four-chip grids, and nothing else

One chip:
  1. A·A at n = 2^20 proteins (families of 64, ~32 nonzeros per column),
     column-normalized as HipMCL does, on a 1x1x1 grid through
     ``batched_summa3d`` with ``local_path="auto"``. The budget is the
     device's own ``bytes_limit`` less what is resident after the scatter,
     so the chip's HBM decides the batch count. One cold run: it takes
     ~420 s on a v5e, and a warm rerun would not fit the time limit.
  2. The ESC and the hash local multiply forced at n = 2^16, and three
     dense-path ``mcl_iterate`` iterations at n = 2^14 (the device-resident
     loop and the ``col_prune`` Pallas kernel).
Four chips: the n = 2^20 A·A on the 2x2x1 grid and on the layered 1x1x4
grid (``--log2n`` sets a smaller n for this phase; the cut is printed).

Every batch of every multiply is compared with scipy's float64 A@A: the
structure exactly, the values within ``RTOL``. MCL is compared with a
float64 host loop of the same iterations. Each phase prints n, nnz, the
grid, the memory limit, the planned b, the local path and sort engine, cold
and (time allowing) warm wall seconds, the steady per-batch seconds, the
compiles in the warm run and peak device bytes.
The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import scipy.sparse as sps  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402

FAMILY = 64  # proteins per planted family (blocks = n // FAMILY)
# in-family edge probability: ~32 nonzeros per column after symmetrization,
# deduplication, background edges and self loops (31.8 at n = 2^16, seed 0)
INTRA_P = 0.32
N_MAIN = 1 << 20
N_SMALL = 1 << 16
# The dense MCL path costs O(nnz(A) · n) per iteration (each batch gathers a
# wb-wide row of B per A entry), so it runs at 2^14, not 2^16, to fit the
# script's time limit.
N_MCL = 1 << 14
# Warm reruns are made only while this much of the 1200 s limit is left
# after them (the n = 2^20 cold run alone takes ~420 s on one v5e).
DEADLINE_S = 1050
# f32 rounding bound for a sum of at most ~64 positive f32 products,
# (terms + 1) · 2^-24 ≈ 4e-6, with headroom
RTOL = 1e-5
MCL_ITERS = 3


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data and references
# ---------------------------------------------------------------------------
def make_problem(n: int, seed: int):
    """The column-normalized protein-similarity input, as a host SparseCOO
    (numpy arrays) and as a float64 scipy CSR of the same float32 values."""
    from repro.core import gen
    from repro.core.sparse import SparseCOO
    from repro.sparse_apps.mcl import _col_normalize_np

    raw = gen.protein_similarity_like(
        n, blocks=n // FAMILY, intra_p=INTRA_P, seed=seed
    )
    nnz = int(raw.nnz)
    rows = np.asarray(raw.rows[:nnz])
    cols = np.asarray(raw.cols[:nnz])
    vals = _col_normalize_np(
        rows, cols, np.asarray(raw.vals[:nnz]).astype(np.float64), n
    ).astype(np.float32)
    del raw
    a = SparseCOO(rows, cols, vals, np.int32(nnz), (n, n))
    a64 = sps.csr_matrix((vals.astype(np.float64), (rows, cols)), shape=(n, n))
    return a, a64


def reference_product(a64) -> sps.csc_matrix:
    c = (a64 @ a64).tocsc()
    c.sort_indices()
    return c


class BatchChecker:
    """Consumer for ``batched_summa3d``: compares each batch with the
    matching columns of the reference, then drops the device batch."""

    def __init__(self, ref: sps.csc_matrix):
        self.ref = ref
        self.nnz = 0
        self.max_rel_err = 0.0
        self.cols_seen = np.zeros(ref.shape[1], bool)
        self.times = []  # perf_counter when each batch reached the host

    def __call__(self, bi, c_batch, col_map):
        from repro.sparse_apps.mcl import _sparse_batch_to_global

        rows, cols, vals = _sparse_batch_to_global(c_batch, col_map)
        self.times.append(time.perf_counter())
        gcols = np.unique(col_map)
        if self.cols_seen[gcols].any():
            raise AssertionError(f"batch {bi}: columns already seen")
        self.cols_seen[gcols] = True
        local = np.full(self.ref.shape[1], -1, np.int64)
        local[gcols] = np.arange(len(gcols))
        # COO -> CSC is a counting sort; it would merge duplicate
        # coordinates, so their absence is checked through the count
        got = sps.coo_matrix(
            (vals.astype(np.float64), (rows, local[cols])),
            shape=(self.ref.shape[0], len(gcols)),
        ).tocsc()
        got.sort_indices()
        sub = self.ref[:, gcols]
        sub.sort_indices()
        if not (got.nnz == len(rows)
                and np.array_equal(got.indptr, sub.indptr)
                and np.array_equal(got.indices, sub.indices)):
            raise AssertionError(
                f"batch {bi}: structure differs from the reference "
                f"({len(rows)} entries vs {sub.nnz})"
            )
        err = np.abs(got.data - sub.data) / np.abs(sub.data)
        worst = float(err.max()) if err.size else 0.0
        if worst > RTOL:
            raise AssertionError(
                f"batch {bi}: max relative error {worst:.3e} > {RTOL:g}"
            )
        self.nnz += len(rows)
        self.max_rel_err = max(self.max_rel_err, worst)
        return len(rows)

    def finish(self) -> None:
        if not self.cols_seen.all() or self.nnz != self.ref.nnz:
            raise AssertionError(
                f"batches covered {int(self.cols_seen.sum())} of "
                f"{self.ref.shape[1]} columns and {self.nnz} of "
                f"{self.ref.nnz} nonzeros"
            )


def mcl_reference(a64, iters: int, inflation: float, thresh: float, k: int):
    """Float64 host loop of the MCL iteration ``mcl_iterate`` runs: expand,
    inflate, column-normalize, threshold + per-column top-k, renormalize."""
    from repro.sparse_apps.mcl import _col_normalize_np, _prune_topk_np

    n = a64.shape[0]
    cur = a64.tocsr()
    history = []
    for _ in range(iters):
        c = (cur @ cur).tocoo()
        rows, cols = c.row.astype(np.int64), c.col.astype(np.int64)
        vals = _col_normalize_np(rows, cols, c.data ** inflation, n)
        rows, cols, vals = _prune_topk_np(rows, cols, vals, n, thresh, k)
        vals = _col_normalize_np(rows, cols, vals, n)
        cur = sps.csr_matrix((vals, (rows, cols)), shape=(n, n))
        history.append(int(cur.nnz))
    return cur, history


# ---------------------------------------------------------------------------
# device bookkeeping
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts backend compiles (JAX's compile-duration monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1


def device_memory(grid):
    """``memory_stats()`` of each device of ``grid``, or None where the
    backend keeps none (the CPU)."""
    stats = [d.memory_stats() for d in grid.mesh.devices.flat]
    return None if any(st is None for st in stats) else stats


def budget_from_device(grid) -> int:
    """Per-process budget: the smallest ``bytes_limit`` over the grid's
    devices less the most bytes any of them already holds."""
    stats = device_memory(grid)
    if stats is None:
        raise RuntimeError("the devices report no memory stats")
    return (min(st["bytes_limit"] for st in stats)
            - max(st["bytes_in_use"] for st in stats))


def compress_engine(plan, tile_rows: int, batch_width: int) -> str:
    """The packed-key engine the local multiply's compress uses."""
    from repro.core import sortkeys

    if plan.local_path == "hash":
        return "packed (hash-table sort)"
    return sortkeys.choose_engine(tile_rows, batch_width, plan.caps.flops_cap)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def run_multiply(label, a, ref, grid, local_path, counter, budget=None,
                 warm=True, deadline=None):
    """A·A through ``batched_summa3d`` on ``grid``: a cold run that checks
    every batch against ``ref``, then (``warm``) a run with the same plan —
    skipped when it would end past ``deadline`` (a ``perf_counter`` time).

    ``budget`` (per-process bytes) defaults to the devices' ``bytes_limit``
    less their bytes in use once the operands are scattered."""
    import jax

    from repro.core.batched import batched_summa3d
    from repro.core.distsparse import scatter_to_grid
    from repro.core.specs import PlanSpec

    t0 = time.perf_counter()
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(a, grid, "B")
    jax.block_until_ready((A, B))
    scatter_s = time.perf_counter() - t0
    if budget is None:
        stats = device_memory(grid)
        budget = budget_from_device(grid)
        log(f"[{label}] bytes_limit="
            f"{[st['bytes_limit'] for st in stats]} resident_after_scatter="
            f"{[st['bytes_in_use'] for st in stats]} "
            f"per_process_memory={budget}")
    spec = PlanSpec(local_path=local_path)

    checker = BatchChecker(ref)
    t0 = time.perf_counter()
    res = batched_summa3d(A, B, grid, budget, consumer=checker, spec=spec)
    cold_s = time.perf_counter() - t0
    checker.finish()
    # the pipelined driver keeps the device busy while the host checks, so
    # the spacing of later batches is the steady per-batch time
    first_batch_s = checker.times[0] - t0
    steady_batch_s = (float(np.median(np.diff(checker.times)))
                      if len(checker.times) > 1 else None)

    def settle(bi, c_batch, col_map):
        jax.block_until_ready(c_batch)
        return c_batch.nnz

    warm_s = warm_compiles = None
    warm_skipped = None
    if warm and deadline is not None and deadline - time.perf_counter() < cold_s:
        warm = False
        warm_skipped = "time limit"
    if warm:
        before = counter.count
        t0 = time.perf_counter()
        again = batched_summa3d(A, B, grid, budget, consumer=settle, spec=spec)
        warm_nnz = sum(int(np.asarray(x).sum()) for x in again.consumed)
        warm_s = time.perf_counter() - t0
        warm_compiles = counter.count - before
        if (warm_nnz != ref.nnz
                or again.plan.num_batches != res.plan.num_batches):
            raise AssertionError(
                f"[{label}] warm run: {warm_nnz} nonzeros in "
                f"{again.plan.num_batches} batches, cold: {ref.nnz} in "
                f"{res.plan.num_batches}"
            )
    plan = res.plan
    tm = A.tile_shape[0]
    wb = B.tile_shape[1] // plan.num_batches
    out = {
        "phase": label, "n": a.shape[0], "nnz_a": int(a.nnz),
        "nnz_c": int(ref.nnz), "grid": "x".join(map(str, grid_shape(grid))),
        "per_process_memory": int(budget), "b": plan.num_batches,
        "local_path": res.local_path, "path_reason": plan.path_reason,
        "sort_engine": compress_engine(plan, tm, wb),
        "flops_cap": plan.caps.flops_cap, "retries": res.num_retries,
        "scatter_s": scatter_s, "cold_s": cold_s,
        "first_batch_s": first_batch_s, "steady_batch_s": steady_batch_s,
        "warm_s": warm_s, "warm_skipped": warm_skipped,
        "warm_compiles": warm_compiles, "max_rel_err": checker.max_rel_err,
        "tile_devices": len(A.rows.sharding.device_set),
    }
    stats = device_memory(grid)
    if stats is not None:
        out["bytes_in_use"] = [st["bytes_in_use"] for st in stats]
        out["peak_bytes_in_use"] = [st["peak_bytes_in_use"] for st in stats]
    log(json.dumps(out))
    if local_path != "auto" and res.local_path != local_path:
        raise AssertionError(
            f"[{label}] asked for {local_path}, ran {res.local_path}"
        )
    return out


def run_mcl(label, a, a64, grid, counter, budget=None, deadline=None):
    """``MCL_ITERS`` dense-path MCL iterations on ``grid`` (cold, then warm
    unless that would end past ``deadline``), checked against the float64
    host loop. ``budget`` defaults to the devices' free memory."""
    from repro.sparse_apps.mcl import MCLConfig, mcl_iterate

    n = a.shape[0]
    if budget is None:
        budget = budget_from_device(grid)
    cfg = MCLConfig(
        path="dense", max_iters=MCL_ITERS, converge_tol=0.0,
        per_process_memory=budget,
    )
    t0 = time.perf_counter()
    final, hist = mcl_iterate(a, grid, cfg)
    cold_s = time.perf_counter() - t0
    warm_s = warm_compiles = final2 = None
    if deadline is None or deadline - time.perf_counter() >= cold_s:
        before = counter.count
        t0 = time.perf_counter()
        final2, _ = mcl_iterate(a, grid, cfg)
        warm_s = time.perf_counter() - t0
        warm_compiles = counter.count - before

    ref, ref_hist = mcl_reference(
        a64, MCL_ITERS, cfg.inflation, cfg.prune_threshold, cfg.max_per_col
    )
    got_hist = [h["nnz"] for h in hist]
    nnz = int(final.nnz)
    got_keys = (np.asarray(final.rows[:nnz]).astype(np.int64) * n
                + np.asarray(final.cols[:nnz]))
    got_vals = np.asarray(final.vals[:nnz]).astype(np.float64)
    ref = ref.tocoo()
    ref_keys = ref.row.astype(np.int64) * n + ref.col
    common, ig, ir = np.intersect1d(got_keys, ref_keys, return_indices=True)
    # entries kept on one side only: f32 and f64 can order two values that
    # tie at a column's top-k boundary (or sit on the threshold) differently
    pattern_diff = len(got_keys) + len(ref_keys) - 2 * len(common)
    rel = np.abs(got_vals[ig] - ref.data[ir]) / ref.data[ir]
    max_rel = float(rel.max()) if rel.size else 0.0
    out = {
        "phase": label, "n": n, "grid": "x".join(map(str, grid_shape(grid))),
        "iters": len(hist), "b": hist[0]["batches"],
        "nnz_per_iter": got_hist, "ref_nnz_per_iter": ref_hist,
        "pattern_diff": pattern_diff, "max_rel_err": max_rel,
        "cold_s": cold_s, "warm_s": warm_s, "warm_compiles": warm_compiles,
        "same_as_warm": final2 is None or bool(
            int(final2.nnz) == nnz
            and np.array_equal(got_vals, np.asarray(final2.vals[:nnz]))
        ),
    }
    stats = device_memory(grid)
    if stats is not None:
        out["peak_bytes_in_use"] = [st["peak_bytes_in_use"] for st in stats]
    log(json.dumps(out))
    # such a swap costs two entries and moves the column sums by the f32
    # rounding of the swapped values, so allow a few per 100k entries and
    # values within a tolerance covering three iterations of f32 rounding
    if len(hist) != MCL_ITERS or pattern_diff > 16 + ref.nnz // 100_000:
        raise AssertionError(f"[{label}] MCL differs from the reference: {out}")
    if max_rel > 1e-3 or not out["same_as_warm"]:
        raise AssertionError(f"[{label}] MCL values differ: {out}")
    return out


def grid_shape(grid):
    return (grid.pr, grid.pc, grid.l)


def one_chip(seed, devices, counter, n_main=N_MAIN, n_small=N_SMALL,
             n_mcl=N_MCL, budget=None, deadline=None):
    """The default phase, all on ``devices[0]``: ``n_main`` A·A with the
    auto path (one cold run: a second would not fit the time limit), the
    forced ESC and hash paths at ``n_small`` and MCL at ``n_mcl``. Warm
    reruns are skipped when they would end past ``deadline``."""
    from repro.core.grid import make_grid

    grid = make_grid(1, 1, 1, devices=devices[:1])
    t0 = time.perf_counter()
    a, a64 = make_problem(n_main, seed)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = reference_product(a64)
    log(f"[main] n={n_main} nnz={int(a.nnz)} nnz(A·A)={ref.nnz} "
        f"generate_s={gen_s:.1f} "
        f"scipy_reference_s={time.perf_counter() - t0:.1f}; one cold run "
        f"(a warm rerun would not fit the time limit)")
    outs = [run_multiply("main", a, ref, grid, "auto", counter, budget,
                         warm=False)]
    del a, a64, ref

    a, a64 = make_problem(n_small, seed)
    ref = reference_product(a64)
    log(f"[small] n={n_small} nnz={int(a.nnz)} nnz(A·A)={ref.nnz}")
    outs.append(run_multiply("forced_esc", a, ref, grid, "esc", counter,
                             budget, deadline=deadline))
    am, am64 = make_problem(n_mcl, seed)
    log(f"[mcl] n={n_mcl} nnz={int(am.nnz)} (dense MCL costs O(nnz·n) per "
        f"iteration: run at 2^14, not 2^16, to fit the time limit)")
    outs.append(run_mcl("mcl", am, am64, grid, counter, budget, deadline))
    outs.append(run_multiply("forced_hash", a, ref, grid, "hash", counter,
                             budget, deadline=deadline))
    return outs


def four_chips(seed, devices, counter, n=N_MAIN, budget=None):
    """The ``--chips 4`` phase: ``n`` A·A on the 2x2x1 and the layered
    1x1x4 grid over ``devices[:4]``, each checked against scipy."""
    from repro.core.grid import make_grid

    a, a64 = make_problem(n, seed)
    ref = reference_product(a64)
    log(f"[four] n={n} nnz={int(a.nnz)} nnz(A·A)={ref.nnz}")
    outs = []
    for shape in ((2, 2, 1), (1, 1, 4)):
        grid = make_grid(*shape, devices=devices[:4])
        # cold runs only: four chips are charged four times over
        out = run_multiply("grid_" + "x".join(map(str, shape)), a, ref, grid,
                           "auto", counter, budget, warm=False)
        if out["tile_devices"] != 4 or min(out.get("bytes_in_use", [1])) == 0:
            raise AssertionError(f"tiles are not on all four devices: {out}")
        outs.append(out)
    return outs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log2n", type=int, default=N_MAIN.bit_length() - 1,
                    help="log2 of the proteins in the --chips 4 phase "
                         "(default 20)")
    args = ap.parse_args(argv)

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    counter = CompileCounter()
    t0 = time.perf_counter()
    if args.chips == 4:
        n = 1 << args.log2n
        if n < N_MAIN:
            log(f"[four] size cut: n = 2^{args.log2n} instead of 2^20 "
                f"(--log2n {args.log2n})")
        four_chips(args.seed, devices, counter, n=n)
    else:
        one_chip(args.seed, devices, counter, deadline=t0 + DEADLINE_S)
    log(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": args.chips,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
