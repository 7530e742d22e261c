"""Fixed-capacity sparse matrix formats for JAX.

JAX requires static shapes, so a sparse matrix is stored as padded COO with a
static *capacity* and a dynamic valid count ``nnz``:

    rows : i32[cap]   row index of each entry; padding entries hold ``m`` (sentinel)
    cols : i32[cap]   col index;              padding entries hold ``n``
    vals : f32[cap]   value;                  padding entries hold 0

Invariants (checked by ``tests/test_sparse.py`` property tests):
  * entries [0, nnz) are valid, entries [nnz, cap) are padding
  * sentinel indices are exactly (m, n) so scatter-based ops can route padding
    into a discard bucket and sorts push padding to the end.

This is the JAX analogue of the paper's per-process CSC tiles: capacity plays
the role of the allocation the symbolic step (Alg. 3) sizes. Ops that can
overflow capacity return an ``overflow`` count so callers (the batched driver)
can re-run the symbolic step with a bigger ``b`` — mirroring the paper's
robustness argument (§IV-A).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import sortkeys

Array = jnp.ndarray


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("rows", "cols", "vals", "nnz"),
    meta_fields=("shape",),
)
@dataclasses.dataclass(frozen=True)
class SparseCOO:
    rows: Array  # i32[cap]
    cols: Array  # i32[cap]
    vals: Array  # dtype[cap]
    nnz: Array  # i32 scalar — number of valid entries
    shape: Tuple[int, int]  # static (m, n)

    # ------------------------------------------------------------------ basics
    @property
    def cap(self) -> int:
        return self.rows.shape[0]

    @property
    def dtype(self):
        return self.vals.dtype

    def valid_mask(self) -> Array:
        return jnp.arange(self.cap, dtype=jnp.int32) < self.nnz

    def to_dense(self) -> Array:
        m, n = self.shape
        out = jnp.zeros((m + 1, n + 1), dtype=self.vals.dtype)
        out = out.at[self.rows, self.cols].add(self.vals)
        return out[:m, :n]

    def transpose(self) -> "SparseCOO":
        m, n = self.shape
        return SparseCOO(self.cols, self.rows, self.vals, self.nnz, (n, m))

    # ------------------------------------------------------------- reordering
    def sort_rowmajor(self, engine: str = "auto") -> "SparseCOO":
        """Sort entries by (row, col). Padding (sentinels) sorts to the end.

        ``engine="auto"`` packs (row, col) into one monotonic i32 key and runs
        a single-key ``lax.sort`` (stable — bit-identical to the lexsort path);
        ``"lexsort"`` forces the two-key sort carrying the values, no
        permutation gathers (parity reference, and the fallback when the
        packed key would overflow i32).
        """
        m, n = self.shape
        if engine != "lexsort" and sortkeys.fits_i32(m, n):
            key = sortkeys.pack_rowmajor(self.rows, self.cols, n)
            key, vals = jax.lax.sort((key, self.vals), num_keys=1)
            rows, cols = sortkeys.unpack_rowmajor(key, n)
            return SparseCOO(rows, cols, vals, self.nnz, self.shape)
        rows, cols, vals = sortkeys.sort_two_keys(self.rows, self.cols, self.vals)
        return SparseCOO(rows, cols, vals, self.nnz, self.shape)

    def sort_colmajor(self, engine: str = "auto") -> "SparseCOO":
        """Sort entries by (col, row) — CSC-like ordering used by local SpGEMM.

        Engines as in ``sort_rowmajor``: one packed key when (col, row) fits
        i32, else (and for ``"lexsort"``) the two-key sort carrying the
        values, no permutation gathers.
        """
        m, n = self.shape
        if engine != "lexsort" and sortkeys.fits_i32(m, n):
            key = sortkeys.pack_colmajor(self.rows, self.cols, m)
            key, vals = jax.lax.sort((key, self.vals), num_keys=1)
            rows, cols = sortkeys.unpack_colmajor(key, m)
            return SparseCOO(rows, cols, vals, self.nnz, self.shape)
        cols, rows, vals = sortkeys.sort_two_keys(self.cols, self.rows, self.vals)
        return SparseCOO(rows, cols, vals, self.nnz, self.shape)

    # ------------------------------------------------------------- reshaping
    def with_capacity(self, new_cap: int) -> "SparseCOO":
        """Grow (pad) or shrink (must have nnz <= new_cap) the capacity."""
        m, n = self.shape
        if new_cap >= self.cap:
            pad = new_cap - self.cap
            rows = jnp.concatenate([self.rows, jnp.full((pad,), m, jnp.int32)])
            cols = jnp.concatenate([self.cols, jnp.full((pad,), n, jnp.int32)])
            vals = jnp.concatenate([self.vals, jnp.zeros((pad,), self.vals.dtype)])
            return SparseCOO(rows, cols, vals, self.nnz, self.shape)
        # Shrink: keep the first new_cap entries (caller guarantees nnz<=new_cap;
        # entries beyond nnz are padding so this is lossless under the invariant).
        return SparseCOO(
            self.rows[:new_cap],
            self.cols[:new_cap],
            self.vals[:new_cap],
            jnp.minimum(self.nnz, new_cap),
            self.shape,
        )

    def compact(self, keep: Array, new_cap: int) -> Tuple["SparseCOO", Array]:
        """Keep entries where ``keep`` (bool[cap]) is set, repacked densely.

        Returns (matrix with capacity ``new_cap``, overflow count). Entries that
        do not fit in ``new_cap`` are dropped and counted in overflow.
        """
        m, n = self.shape
        keep = keep & self.valid_mask()
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1  # destination slot
        total = jnp.maximum(pos[-1] + 1, 0) if self.cap > 0 else jnp.int32(0)
        write = keep & (pos < new_cap)
        dest = jnp.where(write, pos, new_cap)  # discard bucket at new_cap
        rows = jnp.full((new_cap + 1,), m, jnp.int32).at[dest].set(
            jnp.where(write, self.rows, m)
        )[:new_cap]
        cols = jnp.full((new_cap + 1,), n, jnp.int32).at[dest].set(
            jnp.where(write, self.cols, n)
        )[:new_cap]
        vals = jnp.zeros((new_cap + 1,), self.vals.dtype).at[dest].set(
            jnp.where(write, self.vals, 0)
        )[:new_cap]
        new_nnz = jnp.minimum(total, new_cap).astype(jnp.int32)
        overflow = (total - new_nnz).astype(jnp.int32)
        return SparseCOO(rows, cols, vals, new_nnz, (m, n)), overflow

    # ----------------------------------------------------------- column slicing
    def select_col_block(self, lo, width: int, new_cap: int):
        """Entries with lo <= col < lo+width, columns remapped to [0, width)."""
        m, n = self.shape
        keep = (self.cols >= lo) & (self.cols < lo + width)
        shifted = SparseCOO(
            self.rows,
            jnp.where(keep, self.cols - lo, width),
            self.vals,
            self.nnz,
            (m, width),
        )
        return shifted.compact(keep, new_cap)

    def split_col_blocks(self, num_pieces: int, piece_cap: int):
        """Partitioned ColSplit (Alg. 2 line 4): all ``num_pieces`` column
        pieces in ONE pass instead of ``num_pieces`` sequential
        ``select_col_block`` scans.

        Entry e goes to piece ``col // (n/num_pieces)``; its slot within the
        piece is its rank among same-piece entries (a running count per
        piece), so the original entry order is preserved per piece — a
        row-major-sorted input yields row-major-sorted pieces, exactly the
        invariant the segmented Merge-Fiber relies on. Columns are remapped
        to [0, n/num_pieces).

        Returns ``(rows, cols, vals, nnz, overflow)`` where the first three
        are (num_pieces, piece_cap) sentinel-padded arrays, ``nnz`` is
        i32[num_pieces], and ``overflow`` counts entries dropped because a
        piece exceeded ``piece_cap``.
        """
        m, n = self.shape
        assert n % num_pieces == 0, (n, num_pieces)
        piece_w = n // num_pieces
        valid = self.valid_mask()
        piece = jnp.where(valid, self.cols // piece_w, num_pieces)
        # rank within piece: one 1-D running count per piece. A (cap,
        # num_pieces) one-hot would be laid out with its narrow minor dim
        # padded to 128 lanes on the TPU — 128/num_pieces x the memory.
        rank = jnp.zeros_like(piece)
        counts = []
        for p in range(num_pieces):
            hit = (piece == p).astype(jnp.int32)
            rank = jnp.where(piece == p, jnp.cumsum(hit) - hit, rank)
            counts.append(jnp.sum(hit))
        counts = jnp.stack(counts)  # (num_pieces,)
        ok = valid & (piece < num_pieces) & (rank < piece_cap)
        flat = num_pieces * piece_cap
        dest = jnp.where(ok, piece * piece_cap + rank, flat)  # discard bucket
        rows = jnp.full((flat + 1,), m, jnp.int32).at[dest].set(
            jnp.where(ok, self.rows, m)
        )[:flat]
        cols = jnp.full((flat + 1,), piece_w, jnp.int32).at[dest].set(
            jnp.where(ok, self.cols - piece * piece_w, piece_w)
        )[:flat]
        vals = jnp.zeros((flat + 1,), self.vals.dtype).at[dest].set(
            jnp.where(ok, self.vals, 0)
        )[:flat]
        nnz = jnp.minimum(counts, piece_cap)
        overflow = jnp.sum(jnp.maximum(counts - piece_cap, 0)).astype(jnp.int32)
        shape2 = (num_pieces, piece_cap)
        return (
            rows.reshape(shape2), cols.reshape(shape2), vals.reshape(shape2),
            nnz, overflow,
        )

    def select_cols_blockcyclic(
        self, batch, num_batches: int, num_layers: int, new_cap: int
    ):
        """Paper Fig. 1(i): block-cyclic column selection for batch ``batch``.

        The local column range is divided into ``num_batches * num_layers``
        blocks of width w; batch i owns blocks {i, i+b, i+2b, ...} (l of them),
        remapped contiguously. This balances Merge-Fiber load (§IV-B).
        """
        m, n = self.shape
        nblocks = num_batches * num_layers
        assert n % nblocks == 0, f"ncols {n} must divide into {nblocks} blocks"
        w = n // nblocks
        blk = self.cols // w
        keep = (blk % num_batches) == batch
        new_col = (blk // num_batches) * w + self.cols % w
        width = n // num_batches
        shifted = SparseCOO(
            self.rows,
            jnp.where(keep & self.valid_mask(), new_col, width),
            self.vals,
            self.nnz,
            (m, width),
        )
        return shifted.compact(keep, new_cap)

    # ------------------------------------------------------------- statistics
    def col_counts(self) -> Array:
        """nnz per column — i32[n]. Used by the symbolic step (Alg. 3)."""
        m, n = self.shape
        ones = self.valid_mask().astype(jnp.int32)
        return jax.ops.segment_sum(ones, self.cols, num_segments=n + 1)[:n]

    def row_counts(self) -> Array:
        m, n = self.shape
        ones = self.valid_mask().astype(jnp.int32)
        return jax.ops.segment_sum(ones, self.rows, num_segments=m + 1)[:m]

    # -------------------------------------------------------------- pruning
    def prune_threshold(self, thresh, new_cap: int):
        """Drop entries with |val| < thresh (MCL-style pruning)."""
        return self.compact(jnp.abs(self.vals) >= thresh, new_cap)

    def scale_cols(self, scale: Array) -> "SparseCOO":
        """Multiply each column j by scale[j] (MCL column normalization)."""
        m, n = self.shape
        s = jnp.concatenate([scale, jnp.ones((1,), scale.dtype)])
        return SparseCOO(
            self.rows, self.cols, self.vals * s[self.cols], self.nnz, self.shape
        )


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------
def empty(shape: Tuple[int, int], cap: int, dtype=jnp.float32) -> SparseCOO:
    m, n = shape
    return SparseCOO(
        jnp.full((cap,), m, jnp.int32),
        jnp.full((cap,), n, jnp.int32),
        jnp.zeros((cap,), dtype),
        jnp.int32(0),
        shape,
    )


def from_dense(x: Array, cap: int) -> SparseCOO:
    """Jit-compatible dense→COO; entries beyond ``cap`` are dropped."""
    m, n = x.shape
    rows, cols = jnp.nonzero(x, size=cap, fill_value=(m, n))
    nnz = jnp.minimum(jnp.sum(x != 0), cap).astype(jnp.int32)
    vals = jnp.where(jnp.arange(cap) < nnz, x[rows, cols], 0).astype(x.dtype)
    return SparseCOO(rows.astype(jnp.int32), cols.astype(jnp.int32), vals, nnz, (m, n))


def from_dense_overflow(x: Array, cap: int) -> Tuple[SparseCOO, Array]:
    """Jit-compatible dense→COO that also reports how many nonzeros did not
    fit in ``cap`` — the sparsify step of dense-accumulator local multiplies,
    which must follow the same §IV-A overflow-retry discipline as ESC."""
    s = from_dense(x, cap)
    total = jnp.sum(x != 0).astype(jnp.int32)
    return s, jnp.maximum(total - cap, 0)


def from_numpy_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray, shape, cap: int = None
) -> SparseCOO:
    """Host-side constructor (dedups duplicate coordinates by summing)."""
    m, n = shape
    key = rows.astype(np.int64) * n + cols.astype(np.int64)
    uniq, inv = np.unique(key, return_inverse=True)
    acc = np.zeros(len(uniq), dtype=vals.dtype)
    np.add.at(acc, inv, vals)
    r, c = (uniq // n).astype(np.int32), (uniq % n).astype(np.int32)
    nnz = len(uniq)
    cap = cap or nnz
    assert cap >= nnz, f"capacity {cap} < nnz {nnz}"
    pr = np.full(cap, m, np.int32)
    pc = np.full(cap, n, np.int32)
    pv = np.zeros(cap, vals.dtype)
    pr[:nnz], pc[:nnz], pv[:nnz] = r, c, acc
    return SparseCOO(jnp.asarray(pr), jnp.asarray(pc), jnp.asarray(pv), jnp.int32(nnz), (m, n))


def coalesce(a: SparseCOO, new_cap: int, engine: str = "auto"):
    """Merge duplicate (row, col) entries by summation; output row-major sorted.

    This is the 'compress' of ESC and the core of the paper's Merge steps for
    the sparse path. Returns (merged, overflow count). ``engine`` selects the
    packed-key sort/compress path (see ``repro.core.sortkeys``): "auto" uses
    the sort-free bucket scan for small key spaces, a single-key packed sort
    otherwise, and "lexsort" pins the two-key sort carrying the values (no
    permutation gathers), the path for key spaces past i32.
    """
    m, n = a.shape
    rows, cols, vals, nnz, overflow = sortkeys.coalesce_entries(
        a.rows, a.cols, a.vals, a.valid_mask(), (m, n), new_cap,
        add_kind="sum", engine=engine,
    )
    return SparseCOO(rows, cols, vals, nnz, (m, n)), overflow


def concat(mats, new_cap: int):
    """Stack entry lists of same-shape matrices (no dedup — follow with coalesce)."""
    shape = mats[0].shape
    for x in mats:
        assert x.shape == shape
    rows = jnp.concatenate([x.rows for x in mats])
    cols = jnp.concatenate([x.cols for x in mats])
    vals = jnp.concatenate([x.vals for x in mats])
    # compact valid entries to the front (the stacked entry list interleaves
    # padding, so treat every slot as candidate and mask with `keep`).
    keep = jnp.concatenate([x.valid_mask() for x in mats])
    stacked = SparseCOO(rows, cols, vals, jnp.int32(rows.shape[0]), shape)
    return stacked.compact(keep, new_cap)


def hstack_remap(mats, widths, new_cap: int):
    """Concatenate matrices side by side: block j's columns shift by sum(widths[:j]).

    Used by the batched driver's ColConcat (Alg. 4 line 7) and Merge-Fiber
    column reassembly.
    """
    m = mats[0].shape[0]
    offs = np.concatenate([[0], np.cumsum(widths)]).astype(np.int32)
    total_n = int(offs[-1])
    rows, cols, vals, masks = [], [], [], []
    for x, off, w in zip(mats, offs[:-1], widths):
        assert x.shape[0] == m
        rows.append(x.rows)
        cols.append(jnp.where(x.valid_mask(), x.cols + off, total_n))
        vals.append(x.vals)
        masks.append(x.valid_mask())
    stacked = SparseCOO(
        jnp.concatenate(rows),
        jnp.concatenate(cols),
        jnp.concatenate(vals),
        jnp.int32(sum(x.cap for x in mats)),
        (m, total_n),
    )
    return stacked.compact(jnp.concatenate(masks), new_cap)
