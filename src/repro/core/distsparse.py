"""Distributed sparse matrices on the 3D grid (paper Fig. 1 distributions).

A ``DistSparse`` stores one padded-COO tile per grid point, stacked into
arrays of shape (pr, pc, l, cap) and sharded with spec P("gr","gc","gl") —
inside ``shard_map`` each device sees its (1,1,1,cap) tile. Indices are
LOCAL tile coordinates; the global↔local maps below implement the paper's
three distributions exactly:

  kind="A": 2D blocks (w × w), each process-column block split column-wise
            into l layer slices → tile (w × w/l).       [Fig. 1(c,d,e)]
  kind="B": 2D blocks (w × w), each process-row block split row-wise into
            l layer slices → tile (w/l × w).            [Fig. 1(f,g,h)]
  kind="C": distributed like A (paper §III-B chooses this).

where w = n_rows/pr (= n_cols/pc; square layer grids). Contraction alignment
(verified by tests): A tile (i,s,k) covers global columns
s·w + k·(w/l) + [0,w/l), and B tile (s,j,k) covers the same global rows —
so per-layer 2D SUMMA contracts stage-s tiles directly.

Column-reduction helpers (device-resident MCL, paper §V-C): a global column
of an A/C-kind matrix lives in the pr tiles of one (j, k) grid column, and a
B-kind column spans the pr×l tiles of one grid column — so per-column
sums/maxima are one local segment reduction plus a ``psum``/``pmax`` over
the owning mesh axes, never a host gather. ``local_col_reduce`` is the
inside-``shard_map`` building block (used by the fused MCL postprocess);
``dist_col_reduce`` is the standalone jitted wrapper over a ``DistSparse``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from .grid import COL_AX, LAYER_AX, ROW_AX, Grid
from .sparse import SparseCOO, from_numpy_coo

Array = jnp.ndarray


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("rows", "cols", "vals", "nnz"),
    meta_fields=("shape", "tile_shape", "grid_shape", "kind"),
)
@dataclasses.dataclass(frozen=True)
class DistSparse:
    rows: Array  # i32[pr, pc, l, cap] — local tile row indices
    cols: Array  # i32[pr, pc, l, cap]
    vals: Array  # f32[pr, pc, l, cap]
    nnz: Array  # i32[pr, pc, l]
    shape: Tuple[int, int]  # global (m, n)
    tile_shape: Tuple[int, int]  # local (tm, tn)
    grid_shape: Tuple[int, int, int]  # (pr, pc, l)
    kind: str  # "A" | "B" | "C"

    @property
    def cap(self) -> int:
        return self.rows.shape[-1]

    def local(self, i: int, j: int, k: int) -> SparseCOO:
        """Host-side view of one tile (for tests / reassembly)."""
        return SparseCOO(
            self.rows[i, j, k],
            self.cols[i, j, k],
            self.vals[i, j, k],
            self.nnz[i, j, k],
            self.tile_shape,
        )


def dist_spec(d: DistSparse, spec) -> DistSparse:
    """The ``shard_map`` in_specs/out_specs pytree for one ``DistSparse``:
    every data field carries ``spec``, the meta fields are copied. Single
    construction site — used by summa3d, the symbolic step, and the MCL
    postprocess, so a new data field only has to be threaded here."""
    return DistSparse(rows=spec, cols=spec, vals=spec, nnz=spec,
                      shape=d.shape, tile_shape=d.tile_shape,
                      grid_shape=d.grid_shape, kind=d.kind)


def tile_shape_for(kind: str, shape: Tuple[int, int], grid: Grid) -> Tuple[int, int]:
    m, n = shape
    if kind in ("A", "C"):
        return (m // grid.pr, n // grid.pc // grid.l)
    if kind == "B":
        return (m // grid.pr // grid.l, n // grid.pc)
    raise ValueError(kind)


def _tile_layout(a: SparseCOO, grid: Grid, kind: str):
    """Tile-index math shared by scatter/count: returns
    ``(tile_id, lr, lc, vals, tm, tn, counts)`` for the block layout of
    ``kind`` on ``grid`` (tile_id row-major over (pr, pc, l))."""
    m, n = a.shape
    pr, pc, l = grid.pr, grid.pc, grid.l
    if kind in ("A", "C"):
        assert m % pr == 0 and n % (pc * l) == 0, (a.shape, (pr, pc, l))
    else:
        assert m % (pr * l) == 0 and n % pc == 0, (a.shape, (pr, pc, l))
    nnz = int(a.nnz)
    g_rows = np.asarray(a.rows[:nnz])
    g_cols = np.asarray(a.cols[:nnz])
    vals = np.asarray(a.vals[:nnz])

    if kind in ("A", "C"):
        w, wl = n // pc, n // pc // l
        ti = g_rows // (m // pr)
        lr = g_rows % (m // pr)
        tj = g_cols // w
        off = g_cols % w
        tk = off // wl
        lc = off % wl
        tm, tn = m // pr, wl
    else:
        w, wl = m // pr, m // pr // l
        ti = g_rows // w
        off = g_rows % w
        tk = off // wl
        lr = off % wl
        tj = g_cols // (n // pc)
        lc = g_cols % (n // pc)
        tm, tn = wl, n // pc

    tile_id = (ti * pc + tj) * l + tk
    counts = np.bincount(tile_id, minlength=pr * pc * l)
    return tile_id, lr, lc, vals, tm, tn, counts


def tile_nnz_counts(a: SparseCOO, grid: Grid, kind: str) -> np.ndarray:
    """Per-tile nnz of ``a`` scattered as ``kind`` on ``grid`` (flat,
    row-major over (pr, pc, l)) WITHOUT moving any data — the input to
    capacity quantization (the serving engine's plan-cache key uses the
    pow2-rounded max so repeat traffic shares one scatter capacity)."""
    *_, counts = _tile_layout(a, grid, kind)
    return counts


def scatter_to_grid(
    a: SparseCOO, grid: Grid, kind: str, cap_slack: float = 1.3,
    min_cap: int = 8, cap: Optional[int] = None,
) -> DistSparse:
    """Host-side: partition a global SparseCOO into grid tiles (paper Fig. 1).

    Capacity = max tile nnz × slack, uniform across tiles (SPMD requires a
    static shape; the slack absorbs mild imbalance, and the symbolic step is
    the principled sizing mechanism for the multiply outputs). An explicit
    ``cap`` overrides the data-derived capacity (it must hold the fullest
    tile) — the serving engine passes a pow2-quantized cap so equally-sized
    inputs land in one static signature.
    """
    m, n = a.shape
    pr, pc, l = grid.pr, grid.pc, grid.l
    tile_id, lr, lc, vals, tm, tn, counts = _tile_layout(a, grid, kind)
    nnz = int(a.nnz)
    if cap is None:
        cap = max(int(np.ceil(counts.max() * cap_slack)), min_cap)
    else:
        assert cap >= counts.max(), (cap, int(counts.max()))

    rows_t = np.full((pr * pc * l, cap), tm, np.int32)
    cols_t = np.full((pr * pc * l, cap), tn, np.int32)
    vals_t = np.zeros((pr * pc * l, cap), vals.dtype)
    order = np.argsort(tile_id, kind="stable")
    slot = np.arange(nnz) - np.concatenate([[0], np.cumsum(counts)])[tile_id[order]]
    rows_t[tile_id[order], slot] = lr[order]
    cols_t[tile_id[order], slot] = lc[order]
    vals_t[tile_id[order], slot] = vals[order]

    shard = grid.tile_sharding()
    nnz_shard = jax.sharding.NamedSharding(grid.mesh, jax.sharding.PartitionSpec(*grid.axis_names))
    return DistSparse(
        rows=jax.device_put(rows_t.reshape(pr, pc, l, cap), shard),
        cols=jax.device_put(cols_t.reshape(pr, pc, l, cap), shard),
        vals=jax.device_put(vals_t.reshape(pr, pc, l, cap), shard),
        nnz=jax.device_put(counts.reshape(pr, pc, l).astype(np.int32), nnz_shard),
        shape=(m, n),
        tile_shape=(tm, tn),
        grid_shape=(pr, pc, l),
        kind=kind,
    )


def gather_to_global(d: DistSparse) -> SparseCOO:
    """Host-side inverse of scatter_to_grid (tests / small outputs only)."""
    m, n = d.shape
    pr, pc, l = d.grid_shape
    tm, tn = d.tile_shape
    rows_l, cols_l, vals_l = [], [], []
    R = np.asarray(d.rows)
    C = np.asarray(d.cols)
    V = np.asarray(d.vals)
    N = np.asarray(d.nnz)
    for i in range(pr):
        for j in range(pc):
            for k in range(l):
                cnt = int(N[i, j, k])
                lr, lc = R[i, j, k, :cnt], C[i, j, k, :cnt]
                v = V[i, j, k, :cnt]
                if d.kind in ("A", "C"):
                    w = n // pc
                    wl = w // l
                    gr = i * tm + lr
                    gc = j * w + k * wl + lc
                else:
                    w = m // pr
                    wl = w // l
                    gr = i * w + k * wl + lr
                    gc = j * tn + lc
                rows_l.append(gr)
                cols_l.append(gc)
                vals_l.append(v)
    rows = np.concatenate(rows_l) if rows_l else np.zeros(0, np.int32)
    cols = np.concatenate(cols_l) if cols_l else np.zeros(0, np.int32)
    vals = np.concatenate(vals_l) if vals_l else np.zeros(0, np.float32)
    if len(rows) == 0:
        from .sparse import empty

        return empty((m, n), cap=8)
    return from_numpy_coo(rows, cols, vals, (m, n))


# ---------------------------------------------------------------------------
# Distributed column reductions (device-resident MCL building blocks)
# ---------------------------------------------------------------------------
def col_reduce_axes(kind: str) -> Tuple[str, ...]:
    """Mesh axes a per-LOCAL-column reduction must be combined over so every
    process reads the reduction of the full GLOBAL column it holds a piece of.

    A/C-kind: a global column is owned by one (grid column, layer) pair and
    split across the pr row blocks → reduce over the row axis. B-kind: a
    global column spans the whole pr×l fiber plane of its grid column.
    """
    if kind in ("A", "C"):
        return (ROW_AX,)
    if kind == "B":
        return (ROW_AX, LAYER_AX)
    raise ValueError(kind)


def local_col_reduce(
    vals: Array, cols: Array, valid: Array, tn: int, op: str = "sum",
    axes: Tuple[str, ...] = (ROW_AX,),
) -> Array:
    """Inside-``shard_map`` per-column reduction: segment-reduce ``vals`` by
    local column, then combine over ``axes`` (``psum`` for sum, ``pmax`` for
    max). Returns f32[tn], replicated along the reduced axes. ``op="max"``
    treats empty columns as 0 (MCL values are nonnegative)."""
    segids = jnp.where(valid, cols, tn)
    v = jnp.where(valid, vals, 0.0)
    if op == "sum":
        out = jax.ops.segment_sum(v, segids, num_segments=tn + 1)[:tn]
        for ax in axes:
            out = lax.psum(out, ax)
    elif op == "max":
        out = jax.ops.segment_max(v, segids, num_segments=tn + 1)[:tn]
        out = jnp.where(jnp.isfinite(out), out, 0.0)  # empty segments -> -inf
        for ax in axes:
            out = lax.pmax(out, ax)
    else:
        raise ValueError(op)
    return out


@partial(jax.jit, static_argnames=("grid", "op"))
def dist_col_reduce(d: DistSparse, grid: Grid, op: str = "sum") -> Array:
    """Per-GLOBAL-column reduction of a ``DistSparse``, computed on-grid.

    Returns a (pr, pc, l, tn) stacked array: entry [i, j, k, c] is the
    reduction (sum or max of values) over the full global column that local
    column ``c`` of tile (i, j, k) belongs to — replicated along the mesh
    axes the reduction ran over (``col_reduce_axes``). No host transfer.

    This is the STANDALONE wrapper (one shard_map per call) for callers and
    tests that need a column reduction outside an existing SPMD step; the
    MCL batch postprocess inlines ``local_col_reduce`` inside its own
    shard_map instead, so normalization fuses with the prune.
    """
    _, tn = d.tile_shape
    axes = col_reduce_axes(d.kind)

    def step(d_t: DistSparse) -> Array:
        t = SparseCOO(
            d_t.rows.reshape(-1), d_t.cols.reshape(-1), d_t.vals.reshape(-1),
            d_t.nnz.reshape(()), d.tile_shape,
        )
        out = local_col_reduce(
            t.vals.astype(jnp.float32), t.cols, t.valid_mask(), tn, op, axes
        )
        return out[None, None, None]

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    fn = shard_map(step, mesh=grid.mesh, in_specs=(dist_spec(d, spec3),),
                   out_specs=spec3, check_vma=False)
    return fn(d)


def dist_col_sums(d: DistSparse, grid: Grid) -> Array:
    """Distributed column sums — see ``dist_col_reduce``."""
    return dist_col_reduce(d, grid, op="sum")
