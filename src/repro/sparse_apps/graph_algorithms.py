"""SpGEMM applications from paper §V-B: triangle counting and AA^T overlap.

Triangle counting (app (b)): count(G) = Σ (L·U) ⊙ L with L/U the strict
lower/upper parts of the adjacency matrix — a *masked* SpGEMM. The mask is
scattered once as a C-layout operand and applied INSIDE the batched multiply
(``batched_summa3d(mask=...)``): the symbolic step budgets only surviving
entries (smaller capacities, fewer batches) and the local multiply filters
partial products against the mask's packed keys before its compress, so
non-triangle products never occupy output capacity, never ride the fiber
all-to-all, and never reach the host. Per-batch sums come back as device
scalars (like MCL's chaos/nnz) — the host sees one number per batch.

Overlap detection (app (c), BELLA/PASTIS): C = A·Aᵀ over plus-times where A
is the (sequences × k-mers) indicator matrix; C[i,j] = shared k-mers between
sequences i and j. The BELLA filter (i < j, shared ≥ min_shared) runs as a
device-side postprocess on each batch — a jitted on-grid compact, one
executable for all batches — so only surviving pairs are ever transferred;
an optional ``candidates`` mask (known candidate pairs, the PASTIS regime)
additionally gates the multiply itself through the masked path.

``triangle_count_host`` / ``overlap_pairs_host`` keep the original
pull-every-batch, filter-in-Python implementations as parity oracles; their
per-entry filters are routed through ``_host_mask_filter`` /
``_host_pair_filter`` so tests can count (and forbid) host-side filtering on
the device paths.
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
from ..core import semiring as sr
from ..core.batched import RunReport, batched_summa3d
from ..core.distsparse import DistSparse, dist_spec, scatter_to_grid
from ..core.grid import COL_AX, LAYER_AX, ROW_AX, Grid
from ..core.sparse import SparseCOO, from_numpy_coo
from ..core.specs import ExecSpec, PlanFloors, PlanSpec
from ..core.summa3d import (
    _pmax_grid,
    _psum_grid,
    _squeeze_tile,
    reassemble_operands,
)
from ..core.symbolic import rup8, rup_pow2
from . import mcl as _mcl
from .mcl import _sparse_batch_to_global, _to_host


def _charge_mask_planning_transfer(mask: DistSparse) -> None:
    """Masked planning counts the mask's per-tile column structure ON the
    grid (inside ``batched._symbolic3d_jit``); only the (pr, pc, l, w_l) i32
    count array crosses to the host. Charge those bytes against the transfer
    accounting so the device-vs-host comparisons stay honest."""
    pr, pc, l = mask.grid_shape
    wl = mask.tile_shape[1]
    _mcl._TRANSFER_BYTES[0] += pr * pc * l * wl * 4


def _strict_parts(a: SparseCOO) -> Tuple[SparseCOO, SparseCOO]:
    """Strict lower (L) and upper (U) triangular parts as unit-weight COO."""
    n = a.shape[0]
    nnz = int(a.nnz)
    rows = np.asarray(a.rows[:nnz])
    cols = np.asarray(a.cols[:nnz])
    lo = rows > cols
    hi = rows < cols
    L = from_numpy_coo(rows[lo], cols[lo], np.ones(lo.sum(), np.float32),
                       (n, n), cap=max(int(lo.sum()), 8))
    U = from_numpy_coo(rows[hi], cols[hi], np.ones(hi.sum(), np.float32),
                       (n, n), cap=max(int(hi.sum()), 8))
    return L, U


# ---------------------------------------------------------------------------
# Device-side per-batch reductions / filters (the §V-B consumption hooks)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("grid",))
def _batch_value_sum(c: DistSparse, grid: Grid):
    """Σ of one batch's values as a replicated DEVICE scalar (one f32 per
    batch crosses to the host — the masked triangle count's only traffic)."""

    def step(c_t: DistSparse):
        t = _squeeze_tile(c_t)
        return _psum_grid(jnp.sum(jnp.where(t.valid_mask(), t.vals, 0.0)))

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    fn = shard_map(step, mesh=grid.mesh, in_specs=(dist_spec(c, spec3),),
                   out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    return fn(c)


@partial(jax.jit, static_argnames=("grid", "num_batches", "min_shared"))
def _overlap_filter(
    c: DistSparse, batch, grid: Grid, num_batches: int, min_shared: int
):
    """BELLA pair filter ON the grid: keep entries with global row < global
    col and value ≥ ``min_shared``, compacted in place. ``batch`` stays a
    traced scalar (one executable for every batch). Returns the filtered
    batch plus replicated device scalars (surviving count, compact overflow).
    """
    tm, wbl = c.tile_shape
    n_total = c.shape[1] * num_batches
    w = n_total // grid.pc

    def step(c_t: DistSparse, batch_):
        t = _squeeze_tile(c_t)
        i = lax.axis_index(ROW_AX)
        j = lax.axis_index(COL_AX)
        k = lax.axis_index(LAYER_AX)
        g_row = i * tm + t.rows
        g_col = j * w + (k * num_batches + batch_) * wbl + t.cols
        keep = t.valid_mask() & (t.vals >= min_shared) & (g_row < g_col)
        kept, ovf = t.compact(keep, t.cap)
        local = jnp.sum(keep.astype(jnp.int32))
        return (
            kept.rows[None, None, None],
            kept.cols[None, None, None],
            kept.vals[None, None, None],
            kept.nnz[None, None, None],
            _psum_grid(local),
            _pmax_grid(local),
            _pmax_grid(ovf),
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(step, mesh=grid.mesh,
                   in_specs=(dist_spec(c, spec3), spec0),
                   out_specs=(spec3,) * 4 + (spec0,) * 3, check_vma=False)
    rows, cols, vals, nnz, cnt, maxc, ovf = fn(c, jnp.int32(batch))
    filtered = DistSparse(rows=rows, cols=cols, vals=vals, nnz=nnz,
                          shape=c.shape, tile_shape=c.tile_shape,
                          grid_shape=c.grid_shape, kind=c.kind)
    return filtered, cnt, maxc, ovf


def _shrink_batch(d: DistSparse, max_tile_nnz: int) -> DistSparse:
    """Slice a front-compacted batch down to its survivor capacity before the
    device→host pull. ``compact`` front-packs every tile, so dropping the
    tail beyond the max per-tile survivor count is lossless while the pull
    shrinks from O(plan cap) to O(survivors). Pow2-quantized so repeated
    batches reuse the same slice executables."""
    cap = d.rows.shape[-1]
    new_cap = min(cap, rup_pow2(max(int(max_tile_nnz), 8)))
    if new_cap >= cap:
        return d
    return dataclasses.replace(
        d,
        rows=d.rows[..., :new_cap],
        cols=d.cols[..., :new_cap],
        vals=d.vals[..., :new_cap],
    )


# ---------------------------------------------------------------------------
# Triangle counting — masked SpGEMM, device-resident
# ---------------------------------------------------------------------------
def triangle_count(a: SparseCOO, grid: Grid,
                   per_process_memory: int = 1 << 26) -> int:
    """Σ_{(i,j) ∈ A, i>j} (L·U)[i,j] via the masked batched multiply.

    The A-mask (element-wise ⊙) is the strict lower part L, scattered as a
    C-layout operand and applied on-grid inside every batch's fused step;
    each batch contributes ONE device scalar to the total.
    """
    L, U = _strict_parts(a)
    A_d = scatter_to_grid(L, grid, "A")
    B_d = scatter_to_grid(U, grid, "B")
    M_d = scatter_to_grid(L, grid, "C")
    _charge_mask_planning_transfer(M_d)
    totals: List[float] = []

    def postprocess(bi, c_batch):
        return _batch_value_sum(c_batch, grid=grid)

    def consumer(bi, batch_sum, col_map):
        totals.append(float(_to_host(batch_sum)))
        return None

    batched_summa3d(
        A_d, B_d, grid, per_process_memory=per_process_memory,
        consumer=consumer, path="sparse", semiring=sr.PLUS_TIMES,
        spec=PlanSpec(mask=M_d), postprocess=postprocess,
    )
    return int(round(sum(totals)))


def _host_mask_filter(rr, cc, vv, mask) -> int:
    """Per-entry host mask filter — the kept §V-B oracle (and the thing the
    device path must never call; tests patch this to count invocations)."""
    total = 0
    for r, c, v in zip(rr.tolist(), cc.tolist(), vv.tolist()):
        if (r, c) in mask:  # apply the A-mask (element-wise ⊙)
            total += int(round(v))
    return total


def triangle_count_host(a: SparseCOO, grid: Grid,
                        per_process_memory: int = 1 << 26) -> int:
    """Host-filter reference: full (unmasked) L·U product, every batch pulled
    to numpy and masked by a Python set lookup — the pre-masked-path
    implementation, kept as the parity oracle and transfer baseline."""
    nnz = int(a.nnz)
    rows = np.asarray(a.rows[:nnz])
    cols = np.asarray(a.cols[:nnz])
    L, U = _strict_parts(a)
    mask = set(zip(rows[rows > cols].tolist(), cols[rows > cols].tolist()))

    A_d = scatter_to_grid(L, grid, "A")
    B_d = scatter_to_grid(U, grid, "B")
    total = 0

    def consumer(bi, c_batch, col_map):
        nonlocal total
        rr, cc, vv = _sparse_batch_to_global(c_batch, col_map)
        total += _host_mask_filter(rr, cc, vv, mask)

    batched_summa3d(
        A_d, B_d, grid, per_process_memory=per_process_memory,
        consumer=consumer, path="sparse", semiring=sr.PLUS_TIMES,
    )
    return total


def triangle_count_reference(a: SparseCOO) -> int:
    d = (np.asarray(a.to_dense()) != 0).astype(np.int64)
    d = d & d.T
    np.fill_diagonal(d, 0)
    return int(np.trace(d @ d @ d)) // 6


# ---------------------------------------------------------------------------
# Overlap detection — on-grid BELLA filter (+ optional candidate mask)
# ---------------------------------------------------------------------------
def overlap_pairs(
    a: SparseCOO,  # (nseqs × nkmers) indicator
    grid: Grid,
    min_shared: int = 2,
    per_process_memory: int = 1 << 26,
    candidates: Optional[SparseCOO] = None,
) -> List[Tuple[int, int, int]]:
    """AA^T batched; emit (i, j, shared) pairs with shared >= min_shared,
    i < j. Each batch is filtered ON the grid and discarded
    (memory-constrained use): the device postprocess compacts survivors and
    reduces the surviving-pair count to a scalar, so the host only
    reassembles coordinates — it never filters.

    ``candidates`` (an nseqs × nseqs structural mask of known candidate
    pairs, the PASTIS regime) additionally gates the multiply itself via the
    masked path — non-candidate products are dropped before the compress and
    the plan budgets survivors only.
    """
    at = a.transpose().sort_rowmajor()
    A_d = scatter_to_grid(a, grid, "A")
    B_d = scatter_to_grid(at, grid, "B")
    M_d = (
        scatter_to_grid(candidates, grid, "C")
        if candidates is not None else None
    )
    if M_d is not None:
        _charge_mask_planning_transfer(M_d)
    pieces = []
    nseqs = a.shape[0]

    def postprocess(bi, c_batch):
        # the batch width is the column dimension divided by the plan's
        # batch count, so nb is recoverable from the batch itself — no
        # plan probe needed before the driver runs
        num_batches = nseqs // c_batch.shape[1]
        return _overlap_filter(
            c_batch, bi, grid=grid, num_batches=num_batches,
            min_shared=int(min_shared),
        )

    def consumer(bi, payload, col_map):
        filtered, cnt, maxc, ovf = payload
        assert int(_to_host(ovf)) == 0
        # survivor-sized pull: slice the front-compacted batch to the max
        # per-tile survivor count before any array crosses to the host
        shrunk = _shrink_batch(filtered, int(_to_host(maxc)))
        rr, cc, vv = _sparse_batch_to_global(shrunk, col_map)
        assert len(rr) == int(_to_host(cnt)), (len(rr), cnt)
        pieces.append((rr, cc, vv))
        return None

    batched_summa3d(
        A_d, B_d, grid, per_process_memory=per_process_memory,
        consumer=consumer, path="sparse", postprocess=postprocess,
        spec=PlanSpec(mask=M_d),
    )
    rows = np.concatenate([p[0] for p in pieces])
    cols = np.concatenate([p[1] for p in pieces])
    vals = np.concatenate([p[2] for p in pieces])
    order = np.lexsort((cols, rows))
    return [
        (int(r), int(c), int(round(v)))
        for r, c, v in zip(rows[order], cols[order], vals[order])
    ]


def _host_pair_filter(rr, cc, vv, min_shared) -> List[Tuple[int, int, int]]:
    """Per-entry host pair filter — the kept §V-B oracle (patched by tests
    to prove the device path never filters on the host)."""
    out = []
    for r, c, v in zip(rr.tolist(), cc.tolist(), vv.tolist()):
        if r < c and v >= min_shared:
            out.append((int(r), int(c), int(round(v))))
    return out


def overlap_pairs_host(
    a: SparseCOO,
    grid: Grid,
    min_shared: int = 2,
    per_process_memory: int = 1 << 26,
) -> List[Tuple[int, int, int]]:
    """Host-filter reference: every full batch pulled to numpy and filtered
    entry-by-entry in Python — the pre-device-filter implementation, kept as
    the parity oracle and transfer baseline."""
    at = a.transpose().sort_rowmajor()
    A_d = scatter_to_grid(a, grid, "A")
    B_d = scatter_to_grid(at, grid, "B")
    pairs: List[Tuple[int, int, int]] = []

    def consumer(bi, c_batch, col_map):
        rr, cc, vv = _sparse_batch_to_global(c_batch, col_map)
        pairs.extend(_host_pair_filter(rr, cc, vv, min_shared))
        return None

    batched_summa3d(
        A_d, B_d, grid, per_process_memory=per_process_memory,
        consumer=consumer, path="sparse",
    )
    return sorted(pairs)


def overlap_pairs_reference(a: SparseCOO, min_shared: int = 2):
    d = np.asarray(a.to_dense())
    c = d @ d.T
    out = []
    n = c.shape[0]
    for i in range(n):
        for j in range(i + 1, n):
            if c[i, j] >= min_shared:
                out.append((i, j, int(round(c[i, j]))))
    return sorted(out)


# ---------------------------------------------------------------------------
# APSP — min-plus iterated squaring (tropical semiring), resilient-ready
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class APSPConfig:
    """All-pairs shortest paths by iterated squaring over MIN_PLUS.

    D ← D ⊗ D doubles the hop horizon each iteration; with an explicit zero
    diagonal the iterate is entrywise non-increasing, D_k covers all paths of
    ≤ 2^k hops, and the fixpoint (exact triplet equality between successive
    iterates) IS the shortest-path matrix — at the fixpoint each entry's min
    over candidates includes D[i,j] + 0 via the diagonal, so equality is
    exact in float, not approximate. Absent entries are an implicit +inf
    (unreachable); only finite distances are ever stored.
    """

    max_iters: Optional[int] = None  # None: ceil(log2(n-1)) + 1
    per_process_memory: int = 1 << 26
    force_num_batches: Optional[int] = None
    lookahead: int = 2
    r_bytes: int = 12
    # local dispatch; ESC and the hash accumulator are semiring-generic
    local_path: str = "auto"


@dataclasses.dataclass
class APSPLoopState:
    """Device-resident iterate (A/B operands of the next squaring) +
    plan-signature floors (the checkpointed unit; mirrors
    `mcl.MCLLoopState`)."""

    A: DistSparse
    B: DistSparse
    it: int
    history: List[dict]
    report: RunReport
    floors: PlanFloors = dataclasses.field(default_factory=PlanFloors)
    lp_arg: object = "auto"


def _apsp_triplets(d: SparseCOO):
    k = int(d.nnz)
    return (np.asarray(d.rows[:k]), np.asarray(d.cols[:k]),
            np.asarray(d.vals[:k]))


def apsp_init(a: SparseCOO) -> SparseCOO:
    """D_0: edge weights with an explicit zero diagonal (dedup by MIN —
    a self-loop never beats distance 0)."""
    n = a.shape[0]
    rr, cc, vv = _apsp_triplets(a)
    rows = np.concatenate([rr, np.arange(n, dtype=rr.dtype)])
    cols = np.concatenate([cc, np.arange(n, dtype=cc.dtype)])
    vals = np.concatenate([vv.astype(np.float32), np.zeros(n, np.float32)])
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    best = np.full(len(uniq), np.inf, np.float32)
    np.minimum.at(best, inv, vals)
    return from_numpy_coo(
        (uniq // n).astype(np.int32), (uniq % n).astype(np.int32),
        best, (n, n),
    )


def _apsp_cold_state(a: SparseCOO, grid: Grid) -> APSPLoopState:
    """Iteration-0 state: D_0 scattered ONCE as both operands (the only
    scatters of a whole run — the loop stays on-grid after this)."""
    d0 = apsp_init(a)
    return APSPLoopState(
        A=_mcl._scatter(d0, grid, "A"), B=_mcl._scatter(d0, grid, "B"),
        it=0, history=[], report=RunReport(),
    )


def _apsp_caps(n: int, grid: Grid, cfg: APSPConfig) -> Tuple[int, int, int]:
    """Reassembly capacities for the next iterate's operands. APSP never
    prunes, so the only safe static bound is the dense tile (every (row,
    col) of a tile at most once) — exact, so reassembly overflow is
    impossible; tiny at the studied scales, and the reserved-bytes charge
    keeps the multiply honest about the kept operands."""
    tm = n // grid.pr
    w = n // grid.pc
    wl = w // grid.l
    cap_a = rup8(max(8, tm * wl))
    cap_b = rup8(max(8, wl * w))
    return cap_a, cap_b, cfg.r_bytes * (cap_a + cap_b)


@partial(jax.jit, static_argnames=("grid",))
def _dist_equal_nnz(x: DistSparse, y: DistSparse, grid: Grid):
    """Exact equality of two same-layout DistSparse iterates ON the grid +
    the first argument's total nnz, as two replicated device scalars — the
    APSP fixpoint check without a host gather. Tiles are canonicalized by a
    row-major sort (entries are key-unique), so prefix comparison over the
    smaller static capacity plus nnz equality is exact; at most two
    executables per run (iteration 1 compares the reassembled cap against
    the initial scatter cap, later iterations compare like caps)."""
    kmin = min(x.cap, y.cap)

    def step(x_t: DistSparse, y_t: DistSparse):
        tx = _squeeze_tile(x_t).sort_rowmajor()
        ty = _squeeze_tile(y_t).sort_rowmajor()
        neq = (tx.nnz != ty.nnz).astype(jnp.int32)
        idx = jnp.arange(kmin, dtype=jnp.int32)
        live = idx < jnp.minimum(tx.nnz, ty.nnz)
        mism = live & (
            (tx.rows[:kmin] != ty.rows[:kmin])
            | (tx.cols[:kmin] != ty.cols[:kmin])
            | (tx.vals[:kmin] != ty.vals[:kmin])
        )
        bad = _psum_grid(neq + jnp.sum(mism.astype(jnp.int32)))
        return bad, _psum_grid(tx.nnz)

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(
        step, mesh=grid.mesh,
        in_specs=(dist_spec(x, spec3), dist_spec(y, spec3)),
        out_specs=(spec0, spec0), check_vma=False,
    )
    return fn(x, y)


def _apsp_step(
    state: APSPLoopState, grid: Grid, cfg: APSPConfig, verbose: bool = False,
    injector=None, slack: Optional[float] = None,
) -> Tuple[APSPLoopState, RunReport, bool]:
    """ONE squaring D ← D ⊗ D, device-resident: the batched products
    reassemble into the next iterate's operands on the grid
    (``summa3d.reassemble_operands``, like MCL) and the fixpoint test is an
    on-grid exact comparison — only three scalars cross to the host per
    iteration, and zero ``gather_to_global``/``scatter_to_grid`` calls
    happen inside the loop."""
    it = state.it
    t0 = time.perf_counter()
    n = state.A.shape[0]
    cap_a, cap_b, reserved = _apsp_caps(n, grid, cfg)
    batches: List[DistSparse] = []

    def consumer(bi, c_batch, col_map):
        if injector is not None:
            injector.maybe_straggle_batch(it, bi)
            injector.maybe_preempt(it, batch=bi)
        batches.append(c_batch)
        return None

    res = batched_summa3d(
        state.A, state.B, grid, per_process_memory=cfg.per_process_memory,
        consumer=consumer, path="sparse", semiring=sr.MIN_PLUS,
        spec=PlanSpec(
            local_path=state.lp_arg, r_bytes=cfg.r_bytes,
            reserved_bytes=reserved,
            force_num_batches=cfg.force_num_batches,
            **({"slack": slack} if slack is not None else {}),
        ),
        floors=state.floors.replace(caps_pow2=True),
        exec_spec=ExecSpec(lookahead=cfg.lookahead),
    )
    state.floors = state.floors.merged(res.floors())
    state.lp_arg = res.local_path
    a_next, b_next, ovf = reassemble_operands(
        tuple(batches), grid, cap_a, cap_b
    )
    bad, nnz_dev = _dist_equal_nnz(a_next, state.A, grid=grid)
    # ONE host sync per iteration, scalars only (fixpoint + accounting)
    done = int(_to_host(bad)) == 0
    nnz = int(_to_host(nnz_dev))
    overflow = int(_to_host(ovf))
    assert overflow == 0, f"iter {it}: reassembly overflow {overflow}"
    state.A, state.B = a_next, b_next
    dt = time.perf_counter() - t0
    state.history.append({
        "iter": it, "nnz": nnz, "wall_ms": dt * 1e3,
        "retries": res.num_retries, "replans": res.report.replans,
    })
    if verbose:
        print(f"[apsp] iter={it} nnz={nnz} wall={dt*1e3:.1f}ms")
    state.it = it + 1
    state.report = state.report.merged(res.report)
    return state, res.report, done


def _apsp_max_iters(n: int, cfg: APSPConfig) -> int:
    if cfg.max_iters is not None:
        return cfg.max_iters
    return int(np.ceil(np.log2(max(n - 1, 2)))) + 1


def apsp_iterate(
    a: SparseCOO, grid: Grid, cfg: Optional[APSPConfig] = None,
    verbose: bool = False,
) -> Tuple[SparseCOO, List[dict]]:
    """All-pairs shortest paths on the batched multiply; returns the distance
    matrix (absent = unreachable) and per-iteration stats."""
    cfg = cfg or APSPConfig()
    state = _apsp_cold_state(a, grid)
    max_iters = _apsp_max_iters(a.shape[0], cfg)
    while state.it < max_iters:
        state, _, done = _apsp_step(state, grid, cfg, verbose)
        if done:
            break
    final = distsparse.gather_to_global(state.A)
    _mcl._TRANSFER_BYTES[0] += _mcl._dist_bytes(state.A)
    return final, state.history


def apsp_iterate_resilient(
    a: SparseCOO, grid: Grid, cfg: Optional[APSPConfig],
    rc, injector=None, verbose: bool = False,
) -> Tuple[SparseCOO, List[dict], RunReport]:
    """`apsp_iterate` under the durability harness (see
    `runtime.resilient.run_iterated` and `mcl.mcl_iterate_resilient` — same
    contract: checkpoint iterate + plan signature, refuse corrupt restores,
    bitwise trajectory parity after a resume)."""
    from ..runtime.resilient import run_iterated

    cfg = cfg or APSPConfig()
    n = a.shape[0]
    tile_a = (n // grid.pr, n // grid.pc // grid.l)
    tile_b = (n // grid.pr // grid.l, n // grid.pc)

    def encode(state: APSPLoopState):
        arrays: dict = {}
        _mcl._dist_to_arrays(state.A, "A", arrays)
        _mcl._dist_to_arrays(state.B, "B", arrays)
        meta = {
            "workload": "apsp",
            "it": state.it,
            "history": state.history,
            "report": state.report.to_dict(),
            "plan_sig": {
                "floors": state.floors.to_meta(),
                "local_path": state.lp_arg,
            },
        }
        return arrays, meta

    def decode(arrays, meta) -> APSPLoopState:
        sig = meta["plan_sig"]
        return APSPLoopState(
            # bitwise tile restore, re-device_put with the current shardings
            A=_mcl._dist_from_arrays(arrays, "A", grid, (n, n), tile_a, "A"),
            B=_mcl._dist_from_arrays(arrays, "B", grid, (n, n), tile_b, "B"),
            it=int(meta["it"]), history=list(meta["history"]),
            report=RunReport.from_dict(meta["report"]),
            floors=PlanFloors.from_meta(sig["floors"]),
            lp_arg=sig["local_path"],
        )

    def step_fn(state, it, inj):
        return _apsp_step(state, grid, cfg, verbose, injector=inj,
                          slack=inj.capacity_slack(it))

    result = run_iterated(
        rc=rc, max_iters=_apsp_max_iters(n, cfg),
        cold_start=lambda: _apsp_cold_state(a, grid),
        step_fn=step_fn, encode=encode, decode=decode,
        injector=injector, verbose=verbose,
    )
    state = result.state
    final = distsparse.gather_to_global(state.A)
    _mcl._TRANSFER_BYTES[0] += _mcl._dist_bytes(state.A)
    return final, state.history, state.report.merged(dataclasses.replace(
        result.report, retries=0, sel_retries=0, replans=0, ladder_blocked=0,
        degraded_batches=(),
    ))


def apsp_reference(a: SparseCOO) -> np.ndarray:
    """Dense numpy Floyd–Warshall (absent = +inf, zero diagonal)."""
    n = a.shape[0]
    d = np.full((n, n), np.inf, np.float64)
    rr, cc, vv = _apsp_triplets(a)
    np.minimum.at(d, (rr, cc), vv.astype(np.float64))
    np.fill_diagonal(d, np.minimum(np.diag(d), 0.0))
    for k in range(n):
        d = np.minimum(d, d[:, k:k + 1] + d[k:k + 1, :])
    return d
