"""Work and traffic of a batch of C = A·B, counted from the host operands.

These counts are the benchmark's own: the same whatever local path, engine
or batching the program runs, so a change to the program cannot change
what a batch is worth.

* products of a batch of columns J: Σ_{j∈J} Σ_{k: B[k,j]≠0} nnz(A[:,k]),
  the semiring multiplications the product needs;
* the least bytes a batch's step moves: 12 B (row, column, value) for
  every entry of A that the batch's products read, every entry of B[:, J]
  and every entry of C[:, J] written.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sps

ENTRY_BYTES = 12  # int32 row + int32 column + float32 value


class ProductCounts:
    """Per-column counts of A·B for operands given as scipy matrices."""

    def __init__(self, a: sps.spmatrix, b: sps.spmatrix):
        a = sps.csc_matrix(a)
        self.b = sps.csc_matrix(b)
        self.a_col_nnz = np.diff(a.indptr).astype(np.int64)
        # column j of B reads column k of A once per entry B[k, j]
        csum = np.concatenate(
            [[0], np.cumsum(self.a_col_nnz[self.b.indices])]
        )
        self.col_products = csum[self.b.indptr[1:]] - csum[self.b.indptr[:-1]]

    def products(self, cols) -> int:
        """Products of the columns ``cols`` of C."""
        return int(self.col_products[np.asarray(cols)].sum())

    def a_entries_read(self, cols) -> int:
        """Entries of A in the columns k that B[:, cols] names: each is read
        at least once by the batch's products."""
        sub = self.b[:, np.asarray(cols)]
        ks = np.unique(sub.indices)
        return int(self.a_col_nnz[ks].sum())

    def b_entries(self, cols) -> int:
        return int(np.diff(self.b.indptr)[np.asarray(cols)].sum())

    def least_bytes(self, cols, c_nnz: int) -> int:
        """Bytes a step computing C[:, cols] (``c_nnz`` entries) must move
        through HBM at least: what it reads of A and B and writes of C."""
        return ENTRY_BYTES * (
            self.a_entries_read(cols) + self.b_entries(cols) + int(c_nnz)
        )


def least_time_s(products: int, least_bytes: int, peaks: dict):
    """(seconds, bound) of the roofline: the larger of two multiply-adds
    per product at the bf16 peak and the bytes at the HBM peak."""
    compute_s = 2.0 * products / peaks["bf16_flops_per_s"]
    memory_s = least_bytes / peaks["hbm_bytes_per_s"]
    if memory_s >= compute_s:
        return memory_s, "bytes"
    return compute_s, "compute"
