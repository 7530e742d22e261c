"""HipMCL-style Markov clustering on BatchedSUMMA3D (paper §V-C, Fig. 3).

Each MCL iteration: expansion (A ← A·A, the SpGEMM), inflation (entrywise
power + column normalization), then pruning (threshold + per-column top-k).
The batched multiply lets the expansion run even when nnz(A²) exceeds
memory: each column batch is pruned IMMEDIATELY after it is produced and
only the pruned entries survive — exactly the paper's integration.

``mcl_iterate`` is the DEVICE-RESIDENT pipeline: the per-batch
inflate+normalize+prune runs as a ``batched_summa3d`` postprocess hook (one
jitted SPMD step per batch — column sums/maxima are ``psum``/``pmax``
reductions over the grid, top-k is a distributed threshold bisection on the
sparse path and the ``kernels.col_prune`` Pallas bisection on the dense
path), the pruned batches are reassembled into the next iteration's A/B
operands ON the grid (``summa3d.reassemble_operands`` — a layer all-to-all,
no ``gather_to_global``/``scatter_to_grid`` inside the loop), and chaos is a
distributed per-column max/sumsq reduction read back as one scalar per
batch. The pruned-output capacities feed back into ``plan_batches`` via
``reserved_bytes`` so ``MCLConfig.per_process_memory`` bounds operands +
unmerged batch + kept pruned output together.

``mcl_iterate_host`` is the kept host-loop reference (gathers every batch,
prunes in numpy, re-scatters each iteration) — the parity baseline for tests
and the host-transfer comparison in ``benchmarks.bench_mcl``.

Usage (device-resident loop)::

    from repro.core.grid import make_grid
    from repro.sparse_apps.mcl import MCLConfig, mcl_iterate, clusters_from_matrix

    grid = make_grid(2, 2, 2)            # 8 devices: 2x2 layers x 2
    a = ...  # column-stochastic SparseCOO adjacency (n x n)
    final, history = mcl_iterate(a, grid, MCLConfig(
        inflation=2.0, max_per_col=64, per_process_memory=1 << 26))
    labels = clusters_from_matrix(final.rows[:final.nnz],
                                  final.cols[:final.nnz], a.shape[0])

``history[i]["host_bytes"]`` records the host<->device traffic of iteration
i — a few stat scalars on the device-resident path vs. the full matrix every
batch on the host reference.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from ..core import distsparse
from ..core.batched import RunReport, batched_summa3d
from ..core.distsparse import DistSparse, dist_spec, local_col_reduce
from ..core.grid import COL_AX, LAYER_AX, ROW_AX, Grid
from ..core.sparse import SparseCOO, from_dense_overflow, from_numpy_coo
from ..core.specs import ExecSpec, PlanFloors, PlanSpec
from ..core.summa3d import (
    _pmax_grid,
    _psum_grid,
    _squeeze_tile,
    reassemble_operands,
)
from ..core.symbolic import rup8 as _rup8
from ..kernels.col_prune import THRESH_ITERS, col_topk_bounds_pallas


@dataclasses.dataclass
class MCLConfig:
    inflation: float = 2.0
    prune_threshold: float = 1e-4
    max_per_col: int = 64  # top-k per column (HipMCL "recovery/selection")
    max_iters: int = 20
    converge_tol: float = 1e-3
    per_process_memory: int = 1 << 26
    path: str = "sparse"
    force_num_batches: Optional[int] = None  # None: symbolic-step planning
    lookahead: int = 2  # pipelined driver window
    r_bytes: int = 12  # bytes per stored nonzero (COO: i32+i32+f32)
    # local-multiply dispatch: "auto" | "esc" | "hash"
    local_path: str = "auto"


# ---------------------------------------------------------------------------
# Host<->device transfer accounting (benchmark instrumentation)
# ---------------------------------------------------------------------------
_TRANSFER_BYTES = [0]


def reset_transfer_bytes() -> None:
    _TRANSFER_BYTES[0] = 0


def transfer_bytes() -> int:
    """Host<->device bytes moved by MCL code since the last reset."""
    return _TRANSFER_BYTES[0]


def _to_host(x) -> np.ndarray:
    """Device -> host read with byte accounting."""
    a = np.asarray(x)
    _TRANSFER_BYTES[0] += a.nbytes
    return a


def _dist_bytes(d: DistSparse) -> int:
    return d.rows.nbytes + d.cols.nbytes + d.vals.nbytes + d.nnz.nbytes


def _scatter(a: SparseCOO, grid: Grid, kind: str) -> DistSparse:
    """Host -> device scatter with byte accounting (module indirection so
    tests can count/forbid scatter calls inside the iteration loop)."""
    d = distsparse.scatter_to_grid(a, grid, kind)
    _TRANSFER_BYTES[0] += _dist_bytes(d)
    return d


# ---------------------------------------------------------------------------
# Host reference pruning math (kept: parity oracle for the device pipeline)
# ---------------------------------------------------------------------------
def _col_normalize_np(rows, cols, vals, n):
    sums = np.zeros(n, vals.dtype)
    np.add.at(sums, cols, vals)
    sums[sums == 0] = 1.0
    return vals / sums[cols]


def _prune_topk_np(rows, cols, vals, n, thresh, k):
    keep = vals >= thresh
    rows, cols, vals = rows[keep], cols[keep], vals[keep]
    # per-column top-k
    order = np.lexsort((-vals, cols))
    rows, cols, vals = rows[order], cols[order], vals[order]
    # rank within column
    first = np.ones(len(cols), bool)
    first[1:] = cols[1:] != cols[:-1]
    idx_of_first = np.maximum.accumulate(np.where(first, np.arange(len(cols)), 0))
    rank = np.arange(len(cols)) - idx_of_first
    keep = rank < k
    return rows[keep], cols[keep], vals[keep]


def _record_iter(history, it, nnz, chaos, res, t0, t0_bytes, verbose):
    """Shared per-iteration epilogue: one history row schema for all three
    loop variants (device sparse / device dense / host reference) so the
    bench and parity consumers can zip them together. The robustness fields
    (retries/replans, from the driver's `RunReport`) ride along so the
    resilient loop's trajectory log carries the degradation story too."""
    history.append({
        "iter": it, "nnz": nnz, "chaos": chaos,
        "batches": res.plan.num_batches, "flops": res.plan.total_flops,
        "retries": res.num_retries, "replans": res.report.replans,
        "host_bytes": transfer_bytes() - t0_bytes,
        "wall_ms": (time.perf_counter() - t0) * 1e3,
    })
    if verbose:
        print(f"[mcl] iter={it} nnz={nnz} chaos={chaos:.5f} "
              f"b={res.plan.num_batches}")


# ---------------------------------------------------------------------------
# Device-side per-batch postprocess (the fused §V-C consumption step)
# ---------------------------------------------------------------------------
@partial(
    jax.jit,
    static_argnames=("grid", "inflation", "thresh", "k", "new_cap"),
)
def _mcl_prune_sparse(
    c: DistSparse, grid: Grid, inflation: float, thresh: float, k: int,
    new_cap: int,
):
    """Inflate + column-normalize + prune one sparse C batch ON the grid.

    One SPMD step per batch (dispatched by the driver's postprocess hook, so
    it overlaps later batches under the pipelined schedule):

      1. inflation: entrywise power (local).
      2. column normalization: column sums are a segment-sum + ``psum`` over
         the grid row axis (a batch column lives in the pr tiles of one
         (grid column, layer) pair — ``distsparse.local_col_reduce``).
      3. top-k: distributed threshold bisection (the sparse masked-select
         realization of ``kernels.col_prune``) — per-column counts are
         ``psum``-reduced each step, so the k-th value is found across all
         row blocks without moving entries; combined with the absolute
         ``thresh`` cut, then one ``compact`` to the pruned capacity.
      4. renormalize survivors; chaos (max per-column max - sumsq) and the
         kept-entry count come back as replicated device scalars.

    Returns ``(pruned DistSparse, stats)`` with stats device-resident:
    ``{"chaos": f32[], "nnz": i32[], "overflow": i32[]}``.
    """
    tm, tn = c.tile_shape

    def step(c_t: DistSparse):
        t = _squeeze_tile(c_t)
        valid = t.valid_mask()
        v = jnp.where(valid, t.vals.astype(jnp.float32), 0.0)
        v = v ** inflation
        # column normalization over the grid row group
        colsum = local_col_reduce(v, t.cols, valid, tn, "sum", (ROW_AX,))
        inv = 1.0 / jnp.where(colsum > 0, colsum, 1.0)
        inv_pad = jnp.concatenate([inv, jnp.ones((1,), jnp.float32)])
        segids = jnp.where(valid, t.cols, tn)
        v = v * inv_pad[segids]
        # distributed per-column top-k threshold (bisection on value)
        colmax = local_col_reduce(v, t.cols, valid, tn, "max", (ROW_AX,))
        hi = colmax + 1e-6
        lo = jnp.zeros_like(hi)

        def body(_, lohi):
            lo_, hi_ = lohi
            mid = 0.5 * (lo_ + hi_)
            mid_pad = jnp.concatenate([mid, jnp.zeros((1,), jnp.float32)])
            over = valid & (v >= mid_pad[segids])
            cnt = local_col_reduce(
                over.astype(jnp.float32), t.cols, valid, tn, "sum", (ROW_AX,)
            )
            take_hi = cnt > k
            return (
                jnp.where(take_hi, mid, lo_),
                jnp.where(take_hi, hi_, mid),
            )

        lo_f, tcol = lax.fori_loop(0, THRESH_ITERS, body, (lo, hi))
        tcol_pad = jnp.concatenate([tcol, jnp.full((1,), jnp.inf, jnp.float32)])
        lo_pad = jnp.concatenate([lo_f, jnp.full((1,), jnp.inf, jnp.float32)])
        # k-boundary ties: a value repeated across the k-th position sits in
        # the final bracket [lo, tcol) — "v >= tcol" alone would drop the
        # WHOLE tied group (annihilating uniform columns, where every entry
        # ties). HipMCL keeps exactly k: take all strictly-greater entries,
        # then fill the remaining slots from the tie band by rank — local
        # rank within the tile plus an all-gathered per-row-block offset, so
        # the quota is allocated consistently across the grid row group.
        greater = valid & (v >= thresh) & (v >= tcol_pad[segids])
        cnt_hi = local_col_reduce(
            greater.astype(jnp.float32), t.cols, valid, tn, "sum", (ROW_AX,)
        ).astype(jnp.int32)
        slots = jnp.maximum(k - cnt_hi, 0)  # (tn,) free slots per column
        tied = (
            valid & (v >= thresh) & (v >= lo_pad[segids])
            & (v < tcol_pad[segids])
        )
        # within-column rank of the tied entries (slot order), O(cap) memory:
        # one stable two-key sort groups tied entries by column, the rank is
        # the position within the column run, scattered back to entry slots —
        # no (cap, tn) scratch in the memory-constrained hot path.
        cap = v.shape[0]
        idx = jnp.arange(cap, dtype=jnp.int32)
        sort_seg = jnp.where(tied, segids, tn)  # non-tied group last
        seg_sorted, perm = lax.sort((sort_seg, idx), num_keys=2)
        pos = jnp.arange(cap, dtype=jnp.int32)
        is_first = jnp.concatenate([
            jnp.ones((1,), bool), seg_sorted[1:] != seg_sorted[:-1]
        ])
        run_start = lax.cummax(jnp.where(is_first, pos, 0))
        rank = jnp.zeros((cap,), jnp.int32).at[perm].set(pos - run_start)
        tied_cnt = jax.ops.segment_sum(
            tied.astype(jnp.int32), segids, num_segments=tn + 1
        )[:tn]
        all_cnt = lax.all_gather(tied_cnt, ROW_AX)  # (pr, tn)
        i_own = lax.axis_index(ROW_AX)
        offset = jnp.sum(
            jnp.where(
                jnp.arange(all_cnt.shape[0], dtype=jnp.int32)[:, None] < i_own,
                all_cnt, 0,
            ),
            axis=0,
        )
        quota = jnp.clip(slots - offset, 0, None)
        quota_pad = jnp.concatenate([quota, jnp.zeros((1,), jnp.int32)])
        keep = greater | (tied & (rank < quota_pad[segids]))
        # renormalize the survivors
        vk = jnp.where(keep, v, 0.0)
        colsum2 = local_col_reduce(vk, t.cols, valid, tn, "sum", (ROW_AX,))
        inv2 = 1.0 / jnp.where(colsum2 > 0, colsum2, 1.0)
        inv2_pad = jnp.concatenate([inv2, jnp.ones((1,), jnp.float32)])
        v2 = vk * inv2_pad[segids]
        # chaos = max over columns of (col max - col sum of squares)
        colmax2 = local_col_reduce(v2, t.cols, keep, tn, "max", (ROW_AX,))
        colsq2 = local_col_reduce(v2 * v2, t.cols, keep, tn, "sum", (ROW_AX,))
        chaos = _pmax_grid(jnp.max(colmax2 - colsq2))
        nnz = _psum_grid(jnp.sum(keep.astype(jnp.int32)))
        pruned, ovf = SparseCOO(t.rows, t.cols, v2, t.nnz, (tm, tn)).compact(
            keep, new_cap
        )
        return (
            pruned.rows[None, None, None],
            pruned.cols[None, None, None],
            pruned.vals[None, None, None],
            pruned.nnz[None, None, None],
            chaos,
            nnz,
            _pmax_grid(ovf),
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(step, mesh=grid.mesh, in_specs=(dist_spec(c, spec3),),
                   out_specs=(spec3,) * 4 + (spec0,) * 3, check_vma=False)
    rows, cols, vals, nnz, chaos, total, ovf = fn(c)
    pruned = DistSparse(rows=rows, cols=cols, vals=vals, nnz=nnz,
                        shape=c.shape, tile_shape=c.tile_shape,
                        grid_shape=c.grid_shape, kind=c.kind)
    return pruned, {"chaos": chaos, "nnz": total, "overflow": ovf}


@partial(jax.jit, static_argnames=("grid", "inflation", "thresh", "k"))
def _mcl_prune_dense(c_tiles, grid: Grid, inflation: float, thresh: float, k: int):
    """Dense-path batch postprocess: inflate + normalize + top-k prune the
    stacked (pr, pc, l, tm, wbl) C tiles on-device. The per-column top-k
    threshold comes from the ``kernels.col_prune`` Pallas bisection on the
    row-gathered column block (the batch column is split across the pr row
    tiles, so the kernel sees the full column). Returns (pruned tiles, stats).
    """
    def step(x):
        t = x.reshape(x.shape[-2:]).astype(jnp.float32)  # (tm, wbl)
        tm = t.shape[0]
        t = t ** inflation
        colsum = lax.psum(jnp.sum(t, axis=0), ROW_AX)
        t = t / jnp.where(colsum > 0, colsum, 1.0)[None, :]
        full = lax.all_gather(t, ROW_AX).reshape(-1, t.shape[1])
        lo, thr = col_topk_bounds_pallas(full, k)
        # keep all strictly-greater entries, then fill the remaining top-k
        # slots from the [lo, thr) tie band by rank (a value repeated across
        # the k boundary would otherwise be pruned entirely); the full
        # column is gathered here, so the rank fill is local.
        greater = (full >= thr[None, :]) & (full >= thresh)
        tied = (full >= lo[None, :]) & (full < thr[None, :]) & (full >= thresh)
        slots = (k - jnp.sum(greater.astype(jnp.int32), axis=0))
        rank = jnp.cumsum(tied.astype(jnp.int32), axis=0) - tied
        keep_full = greater | (tied & (rank < slots[None, :]))
        i_own = lax.axis_index(ROW_AX)
        keep = lax.dynamic_slice_in_dim(keep_full, i_own * tm, tm, axis=0)
        t = jnp.where(keep, t, 0.0)
        colsum2 = lax.psum(jnp.sum(t, axis=0), ROW_AX)
        t = t / jnp.where(colsum2 > 0, colsum2, 1.0)[None, :]
        colmax = lax.pmax(jnp.max(t, axis=0), ROW_AX)
        colsq = lax.psum(jnp.sum(t * t, axis=0), ROW_AX)
        chaos = _pmax_grid(jnp.max(colmax - colsq))
        nnz = _psum_grid(jnp.sum((t > 0).astype(jnp.int32)))
        return t[None, None, None], chaos, nnz

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(step, mesh=grid.mesh, in_specs=(spec3,),
                   out_specs=(spec3, spec0, spec0), check_vma=False)
    tiles, chaos, nnz = fn(c_tiles)
    return tiles, {"chaos": chaos, "nnz": nnz, "overflow": jnp.int32(0)}


@partial(jax.jit, static_argnames=("grid", "shape", "cap"))
def _dense_to_sparse_batch(tiles, grid: Grid, shape, cap: int):
    """Sparsify one pruned dense batch ON the grid: per-tile
    ``from_dense_overflow`` over the stacked (pr, pc, l, tm, wbl) tiles,
    producing the sparse C-batch layout ``summa3d.reassemble_operands``
    consumes. Returns ``(DistSparse kind "C", pmax-reduced overflow)`` —
    overflow is provably 0 when ``cap >= min(k, tm) * wbl`` (the post-prune
    per-tile hard bound)."""

    def step(x):
        t = x.reshape(x.shape[-2:])
        s, ovf = from_dense_overflow(t, cap)
        return (
            s.rows[None, None, None], s.cols[None, None, None],
            s.vals[None, None, None], s.nnz[None, None, None],
            _pmax_grid(ovf),
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(step, mesh=grid.mesh, in_specs=(spec3,),
                   out_specs=(spec3,) * 4 + (spec0,), check_vma=False)
    rows, cols, vals, nnz, ovf = fn(tiles)
    tm, wbl = tiles.shape[-2:]
    return DistSparse(rows=rows, cols=cols, vals=vals, nnz=nnz,
                      shape=shape, tile_shape=(tm, wbl),
                      grid_shape=(grid.pr, grid.pc, grid.l), kind="C"), ovf


def _extract_dense_batch(tiles: np.ndarray, col_map: np.ndarray):
    """Vectorized host extraction of one dense batch: one ``np.nonzero``
    over the stacked tiles instead of a pr×pc×l Python tile loop."""
    pr, pc, l, tm, wbl = tiles.shape
    i, j, kk, r, c = np.nonzero(tiles)
    return i * tm + r, col_map[j, kk, c], tiles[i, j, kk, r, c]


# ---------------------------------------------------------------------------
# Device-resident MCL loop (explicit-state form: one step = one iteration)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MCLLoopState:
    """Everything one sparse MCL iteration carries to the next — the
    device-resident iterate (A/B operands) PLUS the plan signature: ONE
    ``PlanFloors`` (pow2/floor caps, hash caps, batch-count floor — it
    replaced parallel floor attributes) and the pinned local-path decision.
    The resilient loop checkpoints
    exactly this: the arrays via the content-hashed store, the signature as
    manifest meta — so a restored run replans to the IDENTICAL fused-step
    static signature and hits the jit cache (zero extra retraces after a
    resume)."""

    A: DistSparse
    B: DistSparse
    it: int
    chaos: float
    history: List[dict]
    report: RunReport
    floors: PlanFloors = dataclasses.field(default_factory=PlanFloors)
    lp_arg: object = "auto"


def _mcl_caps(n: int, grid: Grid, cfg: MCLConfig) -> Tuple[int, int, int]:
    """Post-prune operand capacities (<= min(k, rows-in-tile) per column)
    and the reserved-bytes charge they place on the multiply budget."""
    tm = n // grid.pr
    w = n // grid.pc
    wl = w // grid.l
    k = cfg.max_per_col
    cap_a = _rup8(max(8, min(k, tm) * wl))
    cap_b = _rup8(max(8, min(k, wl) * w))
    return cap_a, cap_b, cfg.r_bytes * (cap_a + cap_b)


def _mcl_cold_state(a: SparseCOO, grid: Grid, cfg: MCLConfig) -> MCLLoopState:
    """Iteration-0 state: input scattered once, plan signature unpinned."""
    return MCLLoopState(
        A=_scatter(a, grid, "A"), B=_scatter(a, grid, "B"),
        it=0, chaos=float("inf"), history=[], report=RunReport(),
        lp_arg=cfg.local_path,
    )


def _mcl_sparse_step(
    state: MCLLoopState, grid: Grid, cfg: MCLConfig, verbose: bool = False,
    injector=None, slack: Optional[float] = None,
) -> Tuple[MCLLoopState, RunReport, bool]:
    """ONE device-resident MCL iteration on explicit state.

    Returns ``(state', per-iteration RunReport, converged)``. The plan
    signature floors are pinned after the first iteration exactly as before
    (pow2-quantized + monotone capacities → one fused-step executable, see
    tests/test_mcl_pipeline.py). ``injector`` (resilient runs) hooks the
    consumer — straggler sleeps and mid-iteration preemption fire at batch
    granularity, inside the pipelined lookahead window; ``slack`` overrides
    the planner's capacity slack (overflow-storm injection).
    """
    n = state.A.shape[0]
    tm = n // grid.pr
    k = cfg.max_per_col
    cap_a, cap_b, reserved = _mcl_caps(n, grid, cfg)
    it = state.it
    t0_bytes = transfer_bytes()
    t0 = time.perf_counter()
    batches: List[DistSparse] = []
    stats: List[dict] = []

    def postprocess(bi, c_batch):
        tn = c_batch.tile_shape[1]
        new_cap = _rup8(max(8, min(min(k, tm) * tn, c_batch.cap)))
        return _mcl_prune_sparse(
            c_batch, grid=grid, inflation=cfg.inflation,
            thresh=cfg.prune_threshold, k=k, new_cap=new_cap,
        )

    def consumer(bi, payload, col_map):
        if injector is not None:
            injector.maybe_straggle_batch(it, bi)
            injector.maybe_preempt(it, batch=bi)
        pruned, st = payload
        batches.append(pruned)
        stats.append(st)
        return None

    res = batched_summa3d(
        state.A, state.B, grid,
        per_process_memory=cfg.per_process_memory,
        consumer=consumer, path="sparse",
        postprocess=postprocess,
        spec=PlanSpec(
            local_path=state.lp_arg, r_bytes=cfg.r_bytes,
            reserved_bytes=reserved,
            force_num_batches=cfg.force_num_batches,
            **({"slack": slack} if slack is not None else {}),
        ),
        floors=state.floors.replace(caps_pow2=True),
        exec_spec=ExecSpec(lookahead=cfg.lookahead),
    )
    # pin iteration 1's decisions + used capacities (monotone fold) so every
    # later iteration replans onto the same fused-step static signature
    state.floors = state.floors.merged(res.floors())
    state.lp_arg = res.local_path
    state.A, state.B, ovf = reassemble_operands(
        tuple(batches), grid, cap_a, cap_b
    )
    # ONE host sync per iteration, scalars only (convergence check)
    chaos = max(float(_to_host(st["chaos"])) for st in stats)
    nnz = sum(int(_to_host(st["nnz"])) for st in stats)
    overflow = int(_to_host(ovf)) + sum(
        int(_to_host(st["overflow"])) for st in stats
    )
    assert overflow == 0, f"iter {it}: pruned-capacity overflow {overflow}"
    _record_iter(state.history, it, nnz, chaos, res, t0, t0_bytes, verbose)
    state.chaos = chaos
    state.it = it + 1
    state.report = state.report.merged(res.report)
    return state, res.report, chaos < cfg.converge_tol


def mcl_iterate(
    a: SparseCOO, grid: Grid, cfg: MCLConfig, verbose: bool = False
) -> Tuple[SparseCOO, List[dict]]:
    """Run MCL until convergence; returns (final matrix, per-iter stats).

    Device-resident: the input is scattered ONCE, every iteration's
    expansion+inflation+normalization+pruning runs on the grid, the pruned
    batches become the next A/B operands via an on-grid reassembly, and only
    per-batch stat scalars (chaos, nnz) cross to the host until the final
    matrix is gathered after convergence. ``cfg.path="dense"`` runs the
    dense-accumulator expansion with the Pallas ``col_prune`` postprocess,
    sparsified on-device and reassembled on-grid exactly like the sparse
    path (scatter once, gather once).

    For long runs, `mcl_iterate_resilient` wraps the same per-iteration step
    in the checkpoint/resume harness (`runtime.resilient.run_iterated`).
    """
    if cfg.path == "dense":
        return _mcl_iterate_dense(a, grid, cfg, verbose)
    state = _mcl_cold_state(a, grid, cfg)
    while state.it < cfg.max_iters:
        state, _, done = _mcl_sparse_step(state, grid, cfg, verbose)
        if done:
            break
    final = distsparse.gather_to_global(state.A)
    _TRANSFER_BYTES[0] += _dist_bytes(state.A)
    return final, state.history


# ---------------------------------------------------------------------------
# Checkpoint codec + resilient loop (durability harness)
# ---------------------------------------------------------------------------
def _dist_to_arrays(d: DistSparse, prefix: str, arrays: dict) -> None:
    arrays[f"{prefix}_rows"] = np.asarray(d.rows)
    arrays[f"{prefix}_cols"] = np.asarray(d.cols)
    arrays[f"{prefix}_vals"] = np.asarray(d.vals)
    arrays[f"{prefix}_nnz"] = np.asarray(d.nnz)


def _dist_from_arrays(
    arrays: dict, prefix: str, grid: Grid, shape, tile_shape, kind: str
) -> DistSparse:
    """Re-device_put checkpointed tiles with the CURRENT grid's shardings
    (elastic restore: the saved mesh layout is irrelevant)."""
    shard = grid.tile_sharding()
    nnz_shard = jax.sharding.NamedSharding(
        grid.mesh, jax.sharding.PartitionSpec(*grid.axis_names)
    )
    return DistSparse(
        rows=jax.device_put(arrays[f"{prefix}_rows"], shard),
        cols=jax.device_put(arrays[f"{prefix}_cols"], shard),
        vals=jax.device_put(arrays[f"{prefix}_vals"], shard),
        nnz=jax.device_put(arrays[f"{prefix}_nnz"], nnz_shard),
        shape=tuple(shape), tile_shape=tuple(tile_shape),
        grid_shape=(grid.pr, grid.pc, grid.l), kind=kind,
    )


def _plan_sig_encode(state: MCLLoopState) -> dict:
    """JSON-safe plan signature: everything `plan_batches` needs to rebuild
    the identical fused-step static signature after a restore — the floors
    round-trip through ``PlanFloors.to_meta`` plus the pinned local-path
    decision."""
    return {
        "floors": state.floors.to_meta(),
        "local_path": state.lp_arg,
    }


def _plan_sig_decode(state: MCLLoopState, sig: dict) -> None:
    state.floors = PlanFloors.from_meta(sig["floors"])
    state.lp_arg = sig["local_path"]


def mcl_iterate_resilient(
    a: SparseCOO, grid: Grid, cfg: MCLConfig, rc: "ResilientConfig",
    injector=None, verbose: bool = False,
) -> Tuple[SparseCOO, List[dict], RunReport]:
    """`mcl_iterate` under the durability harness: checkpoint every
    ``rc.ckpt_every`` iterations (device iterate + plan signature), resume
    from ``store.latest_step(rc.ckpt_dir)`` after a preemption (or on
    launch, unless ``rc.resume=False``), refuse corrupt checkpoints, and
    report retries/replans/stalls/stragglers in the returned `RunReport`.

    The encode/decode round-trip is bitwise (i32/f32 host copies) and the
    plan signature restores the exact floors, so a resumed run's trajectory
    — chaos/nnz history AND the final matrix — is identical to the
    uninterrupted run's, with zero extra fused-step retraces (the restored
    operands replan to the same static signature; see tests).
    """
    from ..runtime.resilient import run_iterated

    assert cfg.path == "sparse", "resilient MCL requires the sparse path"
    n = a.shape[0]
    tile_a = (n // grid.pr, n // grid.pc // grid.l)
    tile_b = (n // grid.pr // grid.l, n // grid.pc)

    def encode(state: MCLLoopState):
        arrays: dict = {}
        _dist_to_arrays(state.A, "A", arrays)
        _dist_to_arrays(state.B, "B", arrays)
        meta = {
            "workload": "mcl",
            "it": state.it,
            "chaos": state.chaos,
            "history": state.history,
            "report": state.report.to_dict(),
            "plan_sig": _plan_sig_encode(state),
        }
        return arrays, meta

    def decode(arrays: dict, meta: dict) -> MCLLoopState:
        state = MCLLoopState(
            A=_dist_from_arrays(arrays, "A", grid, (n, n), tile_a, "A"),
            B=_dist_from_arrays(arrays, "B", grid, (n, n), tile_b, "B"),
            it=int(meta["it"]), chaos=float(meta["chaos"]),
            history=list(meta["history"]),
            report=RunReport.from_dict(meta["report"]),
        )
        _plan_sig_decode(state, meta["plan_sig"])
        return state

    def step_fn(state: MCLLoopState, it: int, inj):
        return _mcl_sparse_step(
            state, grid, cfg, verbose, injector=inj,
            slack=inj.capacity_slack(it),
        )

    result = run_iterated(
        rc=rc, max_iters=cfg.max_iters,
        cold_start=lambda: _mcl_cold_state(a, grid, cfg),
        step_fn=step_fn, encode=encode, decode=decode,
        injector=injector, verbose=verbose,
    )
    state = result.state
    final = distsparse.gather_to_global(state.A)
    _TRANSFER_BYTES[0] += _dist_bytes(state.A)
    return final, state.history, state.report.merged(dataclasses.replace(
        result.report, retries=0, sel_retries=0, replans=0, ladder_blocked=0,
        degraded_batches=(),
    ))


def _mcl_iterate_dense(
    a: SparseCOO, grid: Grid, cfg: MCLConfig, verbose: bool = False
) -> Tuple[SparseCOO, List[dict]]:
    """Dense-path loop, device-resident like the sparse path: the input is
    scattered ONCE, each batch is pruned by the Pallas ``col_prune``
    postprocess, sparsified on-device (``from_dense_overflow`` per tile),
    and the sparse batches feed ``summa3d.reassemble_operands`` — no
    ``gather_to_global``/``scatter_to_grid`` inside the iteration loop. The
    final matrix is gathered once after convergence."""
    n = a.shape[0]
    tm = n // grid.pr
    k = cfg.max_per_col
    cap_a, cap_b, reserved = _mcl_caps(n, grid, cfg)
    A = _scatter(a, grid, "A")
    B = _scatter(a, grid, "B")
    history: List[dict] = []
    floors = PlanFloors(caps_pow2=True)
    for it in range(cfg.max_iters):
        t0_bytes = transfer_bytes()
        t0 = time.perf_counter()
        batches: List[DistSparse] = []
        stats: List[dict] = []

        def postprocess(bi, c_tiles):
            tiles, st = _mcl_prune_dense(
                c_tiles, grid=grid, inflation=cfg.inflation,
                thresh=cfg.prune_threshold, k=k,
            )
            wbl = tiles.shape[-1]
            cap = _rup8(max(8, min(k, tm) * wbl))
            sparse, conv_ovf = _dense_to_sparse_batch(
                tiles, grid, (n, n), cap
            )
            return sparse, dict(st, overflow=st["overflow"] + conv_ovf)

        def consumer(bi, payload, col_map):
            sparse, st = payload
            batches.append(sparse)
            stats.append(st)
            return None

        res = batched_summa3d(
            A, B, grid,
            per_process_memory=cfg.per_process_memory,
            consumer=consumer, path="dense", postprocess=postprocess,
            spec=PlanSpec(
                r_bytes=cfg.r_bytes, reserved_bytes=reserved,
                force_num_batches=cfg.force_num_batches,
            ),
            floors=floors,
            exec_spec=ExecSpec(lookahead=cfg.lookahead),
        )
        floors = floors.merged(res.floors())
        A, B, ovf = reassemble_operands(tuple(batches), grid, cap_a, cap_b)
        # ONE host sync per iteration, scalars only (convergence check)
        chaos = max(float(_to_host(st["chaos"])) for st in stats)
        nnz = sum(int(_to_host(st["nnz"])) for st in stats)
        overflow = int(_to_host(ovf)) + sum(
            int(_to_host(st["overflow"])) for st in stats
        )
        assert overflow == 0, f"iter {it}: dense-path overflow {overflow}"
        _record_iter(history, it, nnz, chaos, res, t0, t0_bytes, verbose)
        if chaos < cfg.converge_tol:
            break
    final = distsparse.gather_to_global(A)
    _TRANSFER_BYTES[0] += _dist_bytes(A)
    return final, history


# ---------------------------------------------------------------------------
# Host-loop reference (the kept pre-device implementation)
# ---------------------------------------------------------------------------
def mcl_iterate_host(
    a: SparseCOO, grid: Grid, cfg: MCLConfig, verbose: bool = False
) -> Tuple[SparseCOO, List[dict]]:
    """Host-loop MCL reference: every batch is pulled to numpy, inflation /
    normalization / pruning / chaos all run on the host, and the iterate
    round-trips host<->device each iteration. Kept as the parity oracle and
    the host-transfer baseline for ``benchmarks.bench_mcl``."""
    n = a.shape[0]
    cur = a
    history: List[dict] = []
    for it in range(cfg.max_iters):
        t0_bytes = transfer_bytes()
        t0 = time.perf_counter()
        A = _scatter(cur, grid, "A")
        B = _scatter(cur, grid, "B")
        pieces = []

        def consumer(bi, c_batch, col_map):
            # pull THIS batch to host, prune there, then discard the product
            if cfg.path == "dense":
                pieces.append(_extract_dense_batch(_to_host(c_batch), col_map))
            else:
                pieces.append(_sparse_batch_to_global(c_batch, col_map))
            return None

        res = batched_summa3d(
            A, B, grid,
            per_process_memory=cfg.per_process_memory,
            consumer=consumer, path=cfg.path,
            force_num_batches=cfg.force_num_batches,
        )
        rows = np.concatenate([p[0] for p in pieces])
        cols = np.concatenate([p[1] for p in pieces])
        vals = np.concatenate([p[2] for p in pieces]).astype(np.float64)
        # inflation
        vals = vals ** cfg.inflation
        vals = _col_normalize_np(rows, cols, vals, n)
        rows, cols, vals = _prune_topk_np(
            rows, cols, vals, n, cfg.prune_threshold, cfg.max_per_col
        )
        vals = _col_normalize_np(rows, cols, vals, n).astype(np.float32)
        new = from_numpy_coo(rows, cols, vals, (n, n), cap=max(len(rows), 8))

        # convergence: chaos ~ max col max - col sumsq
        colmax = np.zeros(n, np.float32)
        np.maximum.at(colmax, cols, vals)
        colsq = np.zeros(n, np.float32)
        np.add.at(colsq, cols, vals ** 2)
        chaos = float((colmax - colsq).max())
        _record_iter(history, it, int(len(rows)), chaos, res, t0, t0_bytes,
                     verbose)
        cur = new
        if chaos < cfg.converge_tol:
            break
    return cur, history


def _sparse_batch_to_global(c: DistSparse, col_map: np.ndarray):
    """Host-side reassembly of one sparse C batch into global coordinates
    (vectorized over the tile grid)."""
    pr, pc, l = c.grid_shape
    tm, wbl = c.tile_shape
    R = _to_host(c.rows)
    C = _to_host(c.cols)
    V = _to_host(c.vals)
    N = _to_host(c.nnz)
    cap = R.shape[-1]
    valid = np.arange(cap)[None, None, None, :] < N[..., None]
    i, j, kk, s = np.nonzero(valid)
    return (
        i * tm + R[i, j, kk, s],
        col_map[j, kk, C[i, j, kk, s]],
        V[i, j, kk, s],
    )


def clusters_from_matrix(rows, cols, n: int) -> np.ndarray:
    """Connected components of the converged MCL matrix = cluster labels."""
    parent = np.arange(n)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for r, c in zip(rows, cols):
        pr_, pc_ = find(r), find(c)
        if pr_ != pc_:
            parent[pr_] = pc_
    return np.array([find(i) for i in range(n)])
