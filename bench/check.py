"""The comparison that decides ``correct``: every batch the window counted
against a float64 scipy product of the same columns.

The reference is plain scipy on the host operands, in float64. It imports
nothing of the program and takes nothing the program made except the
columns each batch says it holds (which columns a batch covers is the
program's layout; their contents are checked here).

Numbers compared, each with its limit from the configuration file:

* ``pattern_diff``: entries present on one side only, plus duplicate
  coordinates in the program's batch. Exact: limit 0.
* ``max_rel_err``: the largest |program − reference| / |reference| over the
  entries both hold (every value is a sum of positive products, so the
  reference never cancels to zero).
* ``repeated_columns``: columns that two batches of one call both claim,
  or one batch claims twice. Exact: limit 0.
* ``batches_checked``: batches the window counted; at least 1.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sps


@dataclasses.dataclass
class HostBatch:
    """One batch as the consumer copied it to host memory."""

    call: int  # index of the multiply call in the window
    index: int  # batch index within the call
    arrival: float  # clock reading once the copy was complete
    col_map: np.ndarray  # (pc, l, w) global column of each local column
    rows: np.ndarray  # (pr, pc, l, cap) local tile rows
    cols: np.ndarray  # (pr, pc, l, cap) local tile columns
    vals: np.ndarray  # (pr, pc, l, cap)
    nnz: np.ndarray  # (pr, pc, l)
    tile_rows: int

    @property
    def columns(self) -> np.ndarray:
        return np.asarray(self.col_map).ravel()

    def global_entries(self):
        """(rows, cols, vals) of the batch in global coordinates."""
        cap = self.rows.shape[-1]
        valid = np.arange(cap) < self.nnz[..., None]
        i, j, k, s = np.nonzero(valid)
        local = self.cols[i, j, k, s].astype(np.int64)
        # a local column outside the tile has no global column: -1
        inside = (local >= 0) & (local < self.col_map.shape[-1])
        gcols = np.where(
            inside, self.col_map[j, k, np.where(inside, local, 0)], -1)
        return (i.astype(np.int64) * self.tile_rows + self.rows[i, j, k, s],
                gcols, self.vals[i, j, k, s])


@dataclasses.dataclass
class BatchCheck:
    pattern_diff: int
    max_rel_err: float
    c_nnz: int  # entries of C[:, batch] in the reference


class Reference:
    """float64 scipy product C[:, J] = A · B[:, J] of the host operands."""

    def __init__(self, a: sps.spmatrix, b: sps.spmatrix):
        self.a = sps.csr_matrix(a, dtype=np.float64)
        self.b = sps.csc_matrix(b, dtype=np.float64)

    def columns(self, cols: np.ndarray) -> sps.csc_matrix:
        c = (self.a @ self.b[:, cols]).tocsc()
        c.sort_indices()
        return c


def compare(ref_cols: sps.csc_matrix, rows, local_cols, vals) -> BatchCheck:
    """Compare entries (rows, local column, value) of the program with the
    reference block ``ref_cols`` (global rows × the batch's columns)."""
    m, w = ref_cols.shape
    vals = np.asarray(vals, np.float64)
    ok = ((rows >= 0) & (rows < m) & (local_cols >= 0) & (local_cols < w))
    out_of_range = int((~ok).sum())
    rows, local_cols, vals = rows[ok], local_cols[ok], vals[ok]
    got = sps.coo_matrix((vals, (rows, local_cols)), shape=(m, w)).tocsc()
    got.sum_duplicates()
    got.sort_indices()
    duplicates = len(rows) - got.nnz
    if (np.array_equal(got.indptr, ref_cols.indptr)
            and np.array_equal(got.indices, ref_cols.indices)):
        missing_or_extra = 0
        g, r = got.data, ref_cols.data
    else:
        gk = (np.repeat(np.arange(w, dtype=np.int64), np.diff(got.indptr)) * m
              + got.indices)
        rk = (np.repeat(np.arange(w, dtype=np.int64),
                        np.diff(ref_cols.indptr)) * m + ref_cols.indices)
        common, ig, ir = np.intersect1d(gk, rk, assume_unique=True,
                                        return_indices=True)
        missing_or_extra = len(gk) + len(rk) - 2 * len(common)
        g, r = got.data[ig], ref_cols.data[ir]
    if len(r):
        with np.errstate(divide="ignore", invalid="ignore"):
            rel = np.abs(g - r) / np.abs(r)
        worst = float(np.nanmax(np.where(np.isnan(rel), np.inf, rel)))
    else:
        worst = 0.0
    return BatchCheck(
        pattern_diff=int(missing_or_extra + duplicates + out_of_range),
        max_rel_err=worst, c_nnz=int(ref_cols.nnz),
    )


def check_batch(ref: Reference, batch: HostBatch) -> BatchCheck:
    cols = batch.columns
    sorted_cols = np.sort(cols)
    rows, gcols, vals = batch.global_entries()
    # a global column id maps to its position in the sorted batch columns
    pos = np.searchsorted(sorted_cols, gcols)
    pos = np.clip(pos, 0, len(sorted_cols) - 1)
    local_cols = np.where(sorted_cols[pos] == gcols, pos, -1)
    return compare(ref.columns(sorted_cols), rows, local_cols, vals)


def repeated_columns(batches) -> int:
    """Columns claimed more than once within one call of the window."""
    total = 0
    by_call: dict = {}
    for b in batches:
        by_call.setdefault(b.call, []).append(b.columns)
    for cols in by_call.values():
        allc = np.concatenate(cols)
        total += len(allc) - len(np.unique(allc))
    return total


def check_window(ref: Reference, batches, limits: dict):
    """Check every counted batch. Returns (correct, failed batches,
    per-batch results, the numbers compared as {name: {value, limit}})."""
    results = [check_batch(ref, b) for b in batches]
    numbers = {
        "pattern_diff": {
            "value": sum(r.pattern_diff for r in results),
            "limit": limits["pattern_diff"]},
        "max_rel_err": {
            "value": max((r.max_rel_err for r in results), default=0.0),
            "limit": limits["max_rel_err"]},
        "repeated_columns": {
            "value": repeated_columns(batches),
            "limit": limits["repeated_columns"]},
        "batches_checked": {"value": len(batches), "limit": ">= 1"},
    }
    failed = sum(
        1 for r in results
        if r.pattern_diff > limits["pattern_diff"]
        or not r.max_rel_err <= limits["max_rel_err"]
    )
    correct = (
        len(batches) >= 1 and failed == 0
        and numbers["repeated_columns"]["value"] <= limits["repeated_columns"]
    )
    return correct, failed, results, numbers
