"""SUMMA2D/3D sparse multiply on the grid mesh (paper Alg. 1 + Alg. 2).

One shard_map'd step computes a full 3D multiply for one batch:

  1. A-Broadcast / B-Broadcast (Alg. 1 lines 5-6): realized as
     ``lax.all_gather`` along the grid row/column axes — the bulk equivalent
     of the per-stage broadcasts (same α-β bandwidth: every tile traverses
     its communicator once; see benchmarks/bench_comm_model.py for the
     Table II reconciliation). Because the contraction ranges of the
     gathered stage tiles are disjoint, all `pc` stages fuse into ONE local
     multiply over the concatenated entry lists (contraction index =
     stage * (w/l) + local index) — Local-Multiply and Merge-Layer collapse
     into the same sort-free accumulation, which is the TPU rendering of the
     paper's "merge once after all stages" observation (§III-A).
  2. Local-Multiply (Alg. 1 line 7): dense-accumulator path (spmm into a
     dense D tile — identity-hash accumulator) or sparse path with a
     plan-driven switch between ESC (expand-sort-compress) and the
     hash-accumulator multiply (``local_spgemm.spgemm_hash``: O(output)
     scratch instead of O(flops)), both for any semiring.
  3. AllToAll-Fiber + Merge-Fiber (Alg. 2 lines 4-6): dense path lowers the
     pair to ONE ``lax.psum_scatter`` over the layer axis (all-to-all + local
     add is exactly reduce-scatter); sparse path runs ColSplit as a single
     partitioned, order-preserving split into all l pieces, then the literal
     ``lax.all_to_all`` followed by a sort-free (segmented, merge-not-sort)
     merge.

``summa3d_fused_step`` additionally fuses the batch's block-cyclic column
selection into the same SPMD program with the batch index as a traced scalar:
one executable serves every batch, and the pipelined driver
(``batched.batched_summa3d``) dispatches batch i+1 while batch i computes,
reading the device-resident overflow flags only when it drains its window.

Each phase of the per-batch step runs under a ``jax.named_scope`` whose name
starts with ``spgemm.`` (``spgemm.select``, ``spgemm.gather``, the local
multiply's ``spgemm.esc.*`` / ``spgemm.hash``, ``spgemm.split``,
``spgemm.fiber_exchange``, ``spgemm.merge``): the scope lands in every HLO
op's ``op_name`` and in a profiler trace's ``tf_op``, so a trace splits the
fused step's device time by phase. Scopes change no computation.

``reassemble_operands`` closes the loop for iterated multiplies (MCL-style
A ← f(A·A), §V-C): the batched C outputs are redistributed into fresh A-kind
and B-kind operands entirely on the grid — the A route is a pure local
column remap (C is distributed like A, layer-aligned), the B route one
partitioned layer split + ``all_to_all`` — so an application iterate never
round-trips through ``gather_to_global``/``scatter_to_grid``.

Sentinel discipline: before gathering, every device rewrites its padding
entries to the *global* contraction sentinel (k_tot) so offset arithmetic
cannot alias padding onto real coordinates; values are zero as a second
guarantee.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from . import semiring as sr
from . import sortkeys
from .distsparse import DistSparse, dist_spec
from .grid import COL_AX, LAYER_AX, ROW_AX, Grid
from .local_spgemm import (
    mask_indicator,
    merge_sparse,
    spgemm_esc,
    spgemm_hash,
    spmm,
)
from .sparse import SparseCOO, concat as sparse_concat

Array = jnp.ndarray

#: Trace-time counters (a trace == a compile for the module-level jits).
#: ``summa3d_fused_step`` bumps its entry every time jit re-traces it, so
#: tests can assert the batched driver hits the jit cache across MCL
#: iterations instead of recompiling per capacity plan.
TRACE_COUNTS = {"fused_step": 0}


@dataclasses.dataclass(frozen=True)
class BatchCaps:
    """Static capacities for one batch of the multiply (symbolic-step output)."""

    flops_cap: int  # ESC expansion slots per process
    d_cap: int  # unmerged D tile entries per process (sparse path)
    piece_cap: int  # per-fiber-piece entries (sparse path)
    c_cap: int  # merged C tile entries per process (sparse path)

    def doubled(self) -> "BatchCaps":
        """Next capacity plan for the overflow-retry loop (§IV-A)."""
        return BatchCaps(
            flops_cap=self.flops_cap * 2, d_cap=self.d_cap * 2,
            piece_cap=self.piece_cap * 2, c_cap=self.c_cap * 2,
        )


@dataclasses.dataclass(frozen=True)
class HashCaps:
    """Static parameters of the hash-accumulator local multiply (jit-static).

    ``table_cap`` (power of two) sizes the open-addressing table — the
    O(nnz_out·load_factor) resident scratch the plan budgets instead of
    O(flops). ``chunk_cap`` partial products are enumerated per chunk into a
    single reused buffer; ``num_chunks`` chunks cover the planned flops
    bound. ``max_probes`` linear-probe rounds before an insert is dropped
    and counted (overflow → driver retry).
    """

    table_cap: int
    chunk_cap: int
    num_chunks: int
    max_probes: int = 32

    def doubled(self) -> "HashCaps":
        # chunk_cap is a bandwidth knob, not a soundness cap — growing the
        # chunk *count* (and the table + probe bound) is what clears drops
        return HashCaps(
            table_cap=self.table_cap * 2,
            chunk_cap=self.chunk_cap,
            num_chunks=self.num_chunks * 2,
            max_probes=min(self.max_probes * 2, 256),
        )


def _squeeze_tile(d: DistSparse) -> SparseCOO:
    """Inside shard_map: (1,1,1,cap) blocks -> local SparseCOO tile."""
    return SparseCOO(
        d.rows.reshape(-1),
        d.cols.reshape(-1),
        d.vals.reshape(-1),
        d.nnz.reshape(()),
        d.tile_shape,
    )


def _gather_A(a: SparseCOO) -> SparseCOO:
    """A-Broadcast: gather stage tiles along the grid row; re-index columns
    to the per-layer contraction space (stage s occupies [s*wl, (s+1)*wl))."""
    tm, wl = a.shape
    s = lax.axis_index(COL_AX)
    pc = lax.axis_size(COL_AX)
    k_tot = pc * wl
    valid = a.valid_mask()
    rows = jnp.where(valid, a.rows, tm)
    cols = jnp.where(valid, a.cols + s * wl, k_tot)
    vals = jnp.where(valid, a.vals, 0)
    g_rows = lax.all_gather(rows, COL_AX).reshape(-1)
    g_cols = lax.all_gather(cols, COL_AX).reshape(-1)
    g_vals = lax.all_gather(vals, COL_AX).reshape(-1)
    cap = g_rows.shape[0]
    # padding is self-masking (zero vals + sentinels); declare all slots live
    return SparseCOO(g_rows, g_cols, g_vals, jnp.int32(cap), (tm, k_tot))


def _gather_B(b: SparseCOO) -> SparseCOO:
    """B-Broadcast: gather stage tiles along the grid column; re-index rows
    to the per-layer contraction space (stage i occupies [i*wl, (i+1)*wl))."""
    wl, tn = b.shape
    i = lax.axis_index(ROW_AX)
    pr = lax.axis_size(ROW_AX)
    k_tot = pr * wl
    valid = b.valid_mask()
    rows = jnp.where(valid, b.rows + i * wl, k_tot)
    cols = jnp.where(valid, b.cols, tn)
    vals = jnp.where(valid, b.vals, 0)
    g_rows = lax.all_gather(rows, ROW_AX).reshape(-1)
    g_cols = lax.all_gather(cols, ROW_AX).reshape(-1)
    g_vals = lax.all_gather(vals, ROW_AX).reshape(-1)
    cap = g_rows.shape[0]
    return SparseCOO(g_rows, g_cols, g_vals, jnp.int32(cap), (k_tot, tn))


# ---------------------------------------------------------------------------
# Dense-accumulator path — two broadcast schedules
# ---------------------------------------------------------------------------
#  "allgather": bulk realization — both operands gathered once (same α-β
#      bandwidth as √(p/l) broadcasts, √(p/l)× the tile memory). Fast and
#      simple; the default.
#  "ring": Cannon-style memory-constrained realization — initial skew
#      (A[i,j] ← A[i,(j+i) mod pc], B[i,j] ← B[(i+j) mod pr, j]) followed by
#      per-stage multiply + unit ppermute shifts. O(1) extra tiles: the
#      schedule the paper's memory-constrained regime actually wants (§IV-A
#      counts unmerged results against the same budget the gathered copies
#      would eat). The skew runs as a tile-index gather OUTSIDE shard_map
#      (XLA partitions it into collective-permutes).
def _skew(d: DistSparse, kind: str, grid: Grid) -> DistSparse:
    pr, pc = grid.pr, grid.pc
    i = jnp.arange(pr)[:, None]
    j = jnp.arange(pc)[None, :]
    if kind == "A":  # shift row i left by i: new[i,j] = old[i, (j+i) % pc]
        src = (j + i) % pc
        gather = lambda x: jnp.take_along_axis(
            x, src[:, :, None, None].astype(jnp.int32), axis=1
        ) if x.ndim == 4 else jnp.take_along_axis(
            x, src[:, :, None].astype(jnp.int32), axis=1
        )
    else:  # B: shift col j up by j: new[i,j] = old[(i+j) % pr, j]
        src = (i + j) % pr
        gather = lambda x: jnp.take_along_axis(
            x, src[:, :, None, None].astype(jnp.int32), axis=0
        ) if x.ndim == 4 else jnp.take_along_axis(
            x, src[:, :, None].astype(jnp.int32), axis=0
        )
    return DistSparse(
        rows=gather(d.rows), cols=gather(d.cols), vals=gather(d.vals),
        nnz=gather(d.nnz), shape=d.shape, tile_shape=d.tile_shape,
        grid_shape=d.grid_shape, kind=d.kind,
    )


def summa3d_dense_step(
    a: DistSparse, b_batch: DistSparse, grid: Grid,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    schedule: str = "allgather",
) -> Array:
    """One batched-SUMMA3D step, dense-accumulator path.

    ``b_batch`` is the batch's column block of B (still kind="B" layout,
    tn = w/b). Returns the C batch as stacked dense tiles
    (pr, pc, l, tm, tn/l) — fiber merge included (psum_scatter).
    """
    assert semiring.add_kind == "sum", "dense path requires a sum monoid"
    tm_a, wl_a = a.tile_shape
    _, tn_b = b_batch.tile_shape
    l = grid.l
    assert tn_b % l == 0

    if schedule == "ring":
        assert grid.pr == grid.pc, "Cannon ring needs a square layer grid"
        a = _skew(a, "A", grid)
        b_batch = _skew(b_batch, "B", grid)

        def step(a_t: DistSparse, b_t: DistSparse) -> Array:
            a_loc = _squeeze_tile(a_t)
            b_loc = _squeeze_tile(b_t)
            pc = grid.pc
            ring_a = [(s, (s - 1) % pc) for s in range(pc)]  # shift left
            ring_b = [(s, (s - 1) % pc) for s in range(pc)]  # shift up

            def stage(t, carry):
                ar, ac, av, br, bc, bv, acc = carry
                # local multiply of the aligned stage tiles; local indices
                # already pair up (both tiles come from the same k-block)
                a_cur = SparseCOO(ar, ac, jnp.where(ar < tm_a, av, 0),
                                  jnp.int32(ar.shape[0]), (tm_a, wl_a))
                b_dense = SparseCOO(br, bc, jnp.where(bc < tn_b, bv, 0),
                                    jnp.int32(br.shape[0]),
                                    (wl_a, tn_b)).to_dense()
                acc = acc + spmm(a_cur, b_dense, semiring)
                ar = lax.ppermute(ar, COL_AX, ring_a)
                ac = lax.ppermute(ac, COL_AX, ring_a)
                av = lax.ppermute(av, COL_AX, ring_a)
                br = lax.ppermute(br, ROW_AX, ring_b)
                bc = lax.ppermute(bc, ROW_AX, ring_b)
                bv = lax.ppermute(bv, ROW_AX, ring_b)
                return ar, ac, av, br, bc, bv, acc

            init = (
                a_loc.rows, a_loc.cols, a_loc.vals,
                b_loc.rows, b_loc.cols, b_loc.vals,
                jnp.zeros((tm_a, tn_b), jnp.float32),
            )
            *_, d_tile = lax.fori_loop(0, grid.pc, stage, init)
            c_tile = lax.psum_scatter(
                d_tile, LAYER_AX, scatter_dimension=1, tiled=True
            )
            return c_tile[None, None, None]
    else:
        def step(a_t: DistSparse, b_t: DistSparse) -> Array:
            a_loc = _squeeze_tile(a_t)
            b_loc = _squeeze_tile(b_t)
            a_cat = _gather_A(a_loc)
            b_cat = _gather_B(b_loc)
            b_dense = b_cat.to_dense()  # (k_tot, tn_b) — narrow by batching
            d_tile = spmm(a_cat, b_dense, semiring)  # (tm, tn_b) accumulator
            # AllToAll-Fiber + Merge-Fiber == reduce-scatter along the fiber
            c_tile = lax.psum_scatter(
                d_tile, LAYER_AX, scatter_dimension=1, tiled=True
            )  # (tm, tn_b/l)
            return c_tile[None, None, None]

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    in_specs = (
        DistSparse(rows=spec3, cols=spec3, vals=spec3, nnz=spec3,
                   shape=a.shape, tile_shape=a.tile_shape,
                   grid_shape=a.grid_shape, kind=a.kind),
        DistSparse(rows=spec3, cols=spec3, vals=spec3, nnz=spec3,
                   shape=b_batch.shape, tile_shape=b_batch.tile_shape,
                   grid_shape=b_batch.grid_shape, kind=b_batch.kind),
    )
    fn = shard_map(
        step, mesh=grid.mesh, in_specs=in_specs, out_specs=spec3,
        check_vma=False,
    )
    return fn(a, b_batch)


# ---------------------------------------------------------------------------
# Sparse (ESC / hash) path
# ---------------------------------------------------------------------------
def _pmax_grid(x: Array) -> Array:
    return lax.pmax(lax.pmax(lax.pmax(x, ROW_AX), COL_AX), LAYER_AX)


def _psum_grid(x: Array) -> Array:
    return lax.psum(lax.psum(lax.psum(x, ROW_AX), COL_AX), LAYER_AX)


def _sparse_tile_body(
    a_loc: SparseCOO, b_loc: SparseCOO, l: int, caps: BatchCaps,
    semiring: sr.Semiring, sorted_merge: bool,
    mask: SparseCOO = None, mask_complement: bool = False,
    hashc: "HashCaps" = None,
) -> Tuple[SparseCOO, Array]:
    """Per-device sparse pipeline (inside shard_map): gather → local multiply
    → partitioned ColSplit → AllToAll-Fiber → Merge-Fiber.

    ``hashc`` selects the local multiply: None runs ESC; a ``HashCaps`` runs
    the hash-accumulator multiply, O(table + chunk) scratch instead of
    O(flops) — the plan-driven switch the symbolic step emits. Both take any
    semiring and produce a row-major-sorted D tile, so the downstream
    split/merge invariants are identical.

    ``mask`` (a SparseCOO over the D tile's (tm, tn_b) output space) runs the
    masked/filtered formulation: both paths intersect the partial products'
    packed keys against the mask's sorted keys before they are stored, so
    only surviving coordinates consume ``caps.d_cap`` and everything downstream
    (ColSplit pieces, the fiber exchange, Merge-Fiber) carries survivors
    only, which is where the masked memory/traffic win lives.
    """
    tm_a, _ = a_loc.shape
    _, tn_b = b_loc.shape
    piece_w = tn_b // l
    with jax.named_scope("spgemm.gather"):
        a_cat = _gather_A(a_loc)
        b_cat = _gather_B(b_loc)
    mkeys = None
    if mask is not None:
        with jax.named_scope("spgemm.select"):
            mkeys = sortkeys.sorted_mask_keys(
                mask.rows, mask.cols, mask.valid_mask(), (tm_a, tn_b)
            )
    if hashc is not None:
        with jax.named_scope("spgemm.hash"):
            d_tile, ovf_mul = spgemm_hash(
                a_cat, b_cat, out_cap=caps.d_cap,
                table_cap=hashc.table_cap, chunk_cap=hashc.chunk_cap,
                num_chunks=hashc.num_chunks, semiring=semiring,
                mask_keys=mkeys, mask_complement=mask_complement,
                max_probes=hashc.max_probes,
            )  # (tm, tn_b) sparse, row-major sorted
    else:
        d_tile, ovf_mul = spgemm_esc(
            a_cat, b_cat, out_cap=caps.d_cap, flops_cap=caps.flops_cap,
            semiring=semiring, mask_keys=mkeys,
            mask_complement=mask_complement,
        )  # (tm, tn_b) sparse, row-major sorted
    if l == 1:
        # one fiber layer: the D tile is the only piece and stays here
        parts, ovf_split = [d_tile], 0
    else:
        # ColSplit (Alg. 2 line 4): one partitioned split into all l
        # pieces, order-preserving (pieces stay row-major sorted)
        with jax.named_scope("spgemm.split"):
            pr_, pc_, pv_, pn_, ovf_split = d_tile.split_col_blocks(
                l, caps.piece_cap
            )
        # AllToAll-Fiber (Alg. 2 line 5)
        with jax.named_scope("spgemm.fiber_exchange"):
            pr_ = lax.all_to_all(pr_, LAYER_AX, split_axis=0, concat_axis=0)
            pc_ = lax.all_to_all(pc_, LAYER_AX, split_axis=0, concat_axis=0)
            pv_ = lax.all_to_all(pv_, LAYER_AX, split_axis=0, concat_axis=0)
            pn_ = lax.all_to_all(
                pn_[:, None], LAYER_AX, split_axis=0, concat_axis=0
            )[:, 0]
        parts = [
            SparseCOO(pr_[k], pc_[k], pv_[k], pn_[k], (tm_a, piece_w))
            for k in range(l)
        ]
    # Merge-Fiber (Alg. 2 line 6): sort-free merge of the l pieces
    with jax.named_scope("spgemm.merge"):
        c_tile, ovf_merge = merge_sparse(
            parts, caps.c_cap, semiring, assume_sorted=sorted_merge
        )
    return c_tile, ovf_mul + ovf_split + ovf_merge


def summa3d_sparse_step(
    a: DistSparse, b_batch: DistSparse, grid: Grid, caps: BatchCaps,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    sorted_merge: bool = True,
    hashc: HashCaps = None,
) -> Tuple[DistSparse, Array]:
    """One batched-SUMMA3D step, sparse path. Returns (C tiles, overflow).

    C tiles come back as a DistSparse with tile shape (tm, tn_b/l); the
    global column mapping is block-cyclic (see batched.batch_column_map).
    overflow > 0 means a static capacity was exceeded — the driver retries
    with the next larger capacity plan (paper robustness, §IV-A).

    ``sorted_merge=True`` runs Merge-Fiber as a segmented k-way merge: the l
    received pieces are column splits of row-major-sorted local-multiply
    outputs, so they arrive sorted and only need merging, never re-sorting
    (§IV-D). ``hashc`` switches the local multiply to the hash accumulator.
    """
    tm_a, _ = a.tile_shape
    _, tn_b = b_batch.tile_shape
    l = grid.l
    assert tn_b % l == 0
    piece_w = tn_b // l

    def step(a_t: DistSparse, b_t: DistSparse):
        c_tile, ovf = _sparse_tile_body(
            _squeeze_tile(a_t), _squeeze_tile(b_t), l, caps, semiring,
            sorted_merge, hashc=hashc,
        )
        return (
            c_tile.rows[None, None, None],
            c_tile.cols[None, None, None],
            c_tile.vals[None, None, None],
            c_tile.nnz[None, None, None],
            _pmax_grid(ovf),
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    in_specs = (dist_spec(a, spec3), dist_spec(b_batch, spec3))
    fn = shard_map(
        step, mesh=grid.mesh, in_specs=in_specs,
        out_specs=(spec3, spec3, spec3, spec3, spec0),
        check_vma=False,
    )
    rows, cols, vals, nnz, ovf = fn(a, b_batch)
    m, n = a.shape
    c = DistSparse(
        rows=rows, cols=cols, vals=vals, nnz=nnz,
        shape=(m, b_batch.shape[1]),
        tile_shape=(tm_a, piece_w),
        grid_shape=a.grid_shape,
        kind="C",
    )
    return c, ovf


# ---------------------------------------------------------------------------
# Fused per-batch step (selection + multiply in ONE shard_map)
# ---------------------------------------------------------------------------
def summa3d_fused_step(
    a: DistSparse,
    b_full: DistSparse,
    batch,
    mask: DistSparse = None,
    *,
    grid: Grid,
    num_batches: int,
    sel_cap: int,
    caps: BatchCaps = None,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    sorted_merge: bool = True,
    path: str = "sparse",
    hashc: HashCaps = None,
    mask_cap: int = 0,
    mask_complement: bool = False,
):
    """Batch-select + SUMMA3D multiply fused into one SPMD step (Alg. 4
    line 5-6 without the host in the loop).

    ``batch`` stays a traced scalar, so ONE executable serves every batch —
    the driver can dispatch batch i+1 while batch i is still computing
    (async dispatch), and the selected B block never round-trips through a
    separate jit boundary. Returns ``(c_batch, ovf)`` where ``ovf`` is an
    i32[2] device array ``[selection_overflow, multiply_overflow]`` — the
    driver keeps it device-resident and only syncs when it drains its
    pipeline window.

    ``mask`` is an optional C-layout ``DistSparse`` over the full output
    space (the §V-B masked-SpGEMM operand). It is layer-aligned with C:
    batch ``bi``'s piece on layer k is exactly local columns
    [bi·wbl, (bi+1)·wbl) of mask tile (i, j, k), so building the D-tile mask
    is one batch-slice selection (``mask_cap`` entries, exact from the
    symbolic mask counts) plus one ``all_gather`` along the fiber — the mask
    never leaves the grid and one executable still serves every batch. The
    local multiply then filters partial products before its compress
    (``mask_complement`` flips strict ⊙M into ⊙¬M).
    """
    TRACE_COUNTS["fused_step"] += 1
    tm_a, _ = a.tile_shape
    tn_full = b_full.tile_shape[1]
    assert tn_full % num_batches == 0, (tn_full, num_batches)
    wb = tn_full // num_batches
    l = grid.l
    assert wb % l == 0
    piece_w = wb // l
    if path == "dense":
        assert semiring.add_kind == "sum", "dense path requires a sum monoid"
    if mask is not None:
        assert mask.kind in ("A", "C"), mask.kind
        # C layout: tile (m/pr, n/pc/l); each batch is a wbl-wide slice of it
        assert mask.tile_shape == (tm_a, tn_full // l), (
            mask.tile_shape, (tm_a, tn_full // l)
        )
        wbl = mask.tile_shape[1] // num_batches
        assert wbl * num_batches == mask.tile_shape[1], (
            mask.tile_shape, num_batches
        )

    def step(a_t: DistSparse, b_t: DistSparse, batch_, *rest):
        rest = list(rest)
        mask_t = rest.pop(0) if mask is not None else None
        a_loc = _squeeze_tile(a_t)
        b_loc = _squeeze_tile(b_t)
        with jax.named_scope("spgemm.select"):
            # Batch-Select (Alg. 4 line 5): block-cyclic column selection
            sel, ovf_sel = b_loc.select_cols_blockcyclic(
                batch_, num_batches, l, new_cap=sel_cap
            )
            ovf_sel = _pmax_grid(ovf_sel)
            mask_cat, ovf_mask = None, jnp.int32(0)
            if mask_t is not None:
                # Mask-Select: slice this batch's columns out of the local
                # mask tile, then gather the l layer pieces along the fiber
                # — layer t owns D columns [t*wbl, (t+1)*wbl) of the batch.
                m_loc = _squeeze_tile(mask_t)
                msel, ovf_mask = m_loc.select_col_block(
                    batch_ * wbl, wbl, new_cap=mask_cap
                )
                ovf_mask = _pmax_grid(ovf_mask)
                k_ax = lax.axis_index(LAYER_AX)
                mv = msel.valid_mask()
                mrows = jnp.where(mv, msel.rows, tm_a)
                mcols = jnp.where(mv, k_ax * wbl + msel.cols, wb)
                g_mr = lax.all_gather(mrows, LAYER_AX).reshape(-1)
                g_mc = lax.all_gather(mcols, LAYER_AX).reshape(-1)
                gcap = g_mr.shape[0]
                # all slots declared live; padding is sentinel-coded (tm, wb)
                mask_cat = SparseCOO(
                    g_mr, g_mc, jnp.ones((gcap,), jnp.float32),
                    jnp.int32(gcap), (tm_a, wb),
                )
        if path == "dense":
            with jax.named_scope("spgemm.gather"):
                a_cat = _gather_A(a_loc)
                b_cat = _gather_B(sel)
            d_tile = spmm(a_cat, b_cat.to_dense(), semiring)
            if mask_cat is not None:
                d_tile = jnp.where(
                    mask_indicator(mask_cat, mask_complement), d_tile, 0.0
                )
            c_tile = lax.psum_scatter(
                d_tile, LAYER_AX, scatter_dimension=1, tiled=True
            )
            return c_tile[None, None, None], jnp.stack([ovf_sel, ovf_mask])
        c_tile, ovf_mul = _sparse_tile_body(
            a_loc, sel, l, caps, semiring, sorted_merge,
            hashc=hashc, mask=mask_cat, mask_complement=mask_complement,
        )
        return (
            c_tile.rows[None, None, None],
            c_tile.cols[None, None, None],
            c_tile.vals[None, None, None],
            c_tile.nnz[None, None, None],
            jnp.stack([ovf_sel, _pmax_grid(ovf_mul) + ovf_mask]),
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    in_specs = [dist_spec(a, spec3), dist_spec(b_full, spec3), spec0]
    args = [a, b_full, jnp.int32(batch)]
    if mask is not None:
        in_specs.append(dist_spec(mask, spec3))
        args.append(mask)
    if path == "dense":
        out_specs = (spec3, spec0)
    else:
        out_specs = (spec3, spec3, spec3, spec3, spec0)
    fn = shard_map(
        step, mesh=grid.mesh, in_specs=tuple(in_specs), out_specs=out_specs,
        check_vma=False,
    )
    if path == "dense":
        c_tiles, ovf = fn(*args)
        return c_tiles, ovf
    rows, cols, vals, nnz, ovf = fn(*args)
    m, _ = a.shape
    c = DistSparse(
        rows=rows, cols=cols, vals=vals, nnz=nnz,
        shape=(m, b_full.shape[1] // num_batches),
        tile_shape=(tm_a, piece_w),
        grid_shape=a.grid_shape,
        kind="C",
    )
    return c, ovf


# ---------------------------------------------------------------------------
# On-grid operand reassembly (device-resident iteration, paper §V-C)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("grid", "cap_a", "cap_b"))
def reassemble_operands(
    c_batches, grid: Grid, cap_a: int, cap_b: int
) -> Tuple[DistSparse, DistSparse, Array]:
    """Turn the batched C outputs of one multiply into the next iteration's
    A-kind and B-kind operands WITHOUT leaving the device grid.

    ``c_batches`` is the (tuple of) per-batch C ``DistSparse`` results of
    ``batched_summa3d`` (kind "C", tile (tm, wb/l)) — e.g. the pruned batches
    of an MCL expansion. Batch bi's local column c of tile (i, j, k) is
    global column j·w + (k·nb + bi)·wbl + c (``batch_column_map``), which
    lands in row block i / column block j of BOTH target distributions — so
    reassembly is fiber-local: a partitioned layer split (reusing
    ``SparseCOO.split_col_blocks``) + one ``all_to_all`` over the layer axis
    per operand, plus local index remapping. One jitted SPMD step, no
    ``gather_to_global``/``scatter_to_grid`` round-trip.

    Requires the square layout the paper (and MCL) uses: m == n, pr == pc.
    Returns ``(a_next, b_next, overflow)`` where overflow counts entries
    dropped because ``cap_a``/``cap_b`` (static per-tile capacities) were
    exceeded — with capacities at the post-prune hard bound it is always 0.
    """
    c_batches = tuple(c_batches)
    nb = len(c_batches)
    c0 = c_batches[0]
    pr, pc, l = c0.grid_shape
    tm, wbl = c0.tile_shape
    m = c0.shape[0]
    w = wbl * l * nb  # full column-block width = n/pc
    n = w * pc
    assert m == n and pr == pc, (
        f"on-grid reassembly requires the square layout, got m={m} n={n} "
        f"grid {pr}x{pc}x{l}"
    )
    wl = w // l  # per-layer slice width (A cols / B rows)

    def step(*c_ts):
        k_ax = lax.axis_index(LAYER_AX)
        tiles = [_squeeze_tile(t) for t in c_ts]
        # concatenate the nb batch tiles into one entry list over the FULL
        # local column block [0, w): batch bi local col c -> (k·nb + bi)·wbl + c.
        # Padding is rewritten to explicit sentinels so every slot can be
        # declared live for the split below.
        rows_l, offs_l, vals_l = [], [], []
        for bi, t in enumerate(tiles):
            valid = t.valid_mask()
            rows_l.append(jnp.where(valid, t.rows, tm))
            offs_l.append(
                jnp.where(valid, (k_ax * nb + bi) * wbl + t.cols, w)
            )
            vals_l.append(jnp.where(valid, t.vals, 0))
        rows = jnp.concatenate(rows_l)
        offs = jnp.concatenate(offs_l)
        vals = jnp.concatenate(vals_l)
        cap_tot = rows.shape[0]

        # ---- A-kind route: layer k's batch offsets span exactly
        # [k·wl, (k+1)·wl) (the batch_column_map algebra), so every entry's
        # destination layer EQUALS its source layer — no fiber exchange at
        # all, just the local per-batch column remap (off - k·wl = bi·wbl+c)
        # and one nb-way concat/compact.
        a_parts = [
            SparseCOO(t.rows, bi * wbl + t.cols, t.vals, t.nnz, (tm, wl))
            for bi, t in enumerate(tiles)
        ]
        a_tile, ovf_a2 = sparse_concat(a_parts, cap_a)

        # ---- B-kind route: destination layer = row // wl (split on rows by
        # transposing the roles: split_col_blocks keys on .cols)
        ent_b = SparseCOO(offs, rows, vals, jnp.int32(cap_tot), (w, tm))
        br, bc, bv, bn, ovf_b = ent_b.split_col_blocks(l, cap_b)
        br = lax.all_to_all(br, LAYER_AX, split_axis=0, concat_axis=0)
        bc = lax.all_to_all(bc, LAYER_AX, split_axis=0, concat_axis=0)
        bv = lax.all_to_all(bv, LAYER_AX, split_axis=0, concat_axis=0)
        bn = lax.all_to_all(bn[:, None], LAYER_AX, split_axis=0, concat_axis=0)[:, 0]
        # received pieces carry (rows=global-block col offset, cols=local B row)
        b_parts = [SparseCOO(bc[k], br[k], bv[k], bn[k], (wl, w)) for k in range(l)]
        b_tile, ovf_b2 = sparse_concat(b_parts, cap_b)

        ovf = _pmax_grid(ovf_a2 + ovf_b + ovf_b2)
        return (
            a_tile.rows[None, None, None], a_tile.cols[None, None, None],
            a_tile.vals[None, None, None], a_tile.nnz[None, None, None],
            b_tile.rows[None, None, None], b_tile.cols[None, None, None],
            b_tile.vals[None, None, None], b_tile.nnz[None, None, None],
            ovf,
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    spec0 = jax.sharding.PartitionSpec()
    fn = shard_map(
        step, mesh=grid.mesh,
        in_specs=tuple(dist_spec(c, spec3) for c in c_batches),
        out_specs=(spec3,) * 8 + (spec0,),
        check_vma=False,
    )
    ar, ac, av, an, br, bc, bv, bn, ovf = fn(*c_batches)
    a_next = DistSparse(rows=ar, cols=ac, vals=av, nnz=an, shape=(m, n),
                        tile_shape=(tm, wl), grid_shape=(pr, pc, l), kind="A")
    b_next = DistSparse(rows=br, cols=bc, vals=bv, nnz=bn, shape=(m, n),
                        tile_shape=(wl, w), grid_shape=(pr, pc, l), kind="B")
    return a_next, b_next, ovf
