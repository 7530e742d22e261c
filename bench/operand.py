"""Operands on the host, made from a seed by a generator found by name.

A configuration file names its generator under ``"generator"``, the
generator's keyword arguments under ``"params"`` and a fixed
``"structure_seed"``. The generator is ``bench/generators/<name>.py``, whose
``generate(**params, structure=, values=, relabel=)`` returns an
``Operand``; a new generator is a new file there. The benchmark keeps its
own generators so that a change to the program cannot move the yardstick;
their distributions are those of the program's ``repro.core.gen``,
vectorised.

The structure (which entries exist) comes from the structure seed; the
run's ``--seed`` draws the values and relabels the vertices within aligned
groups of ``RELABEL_GROUP``. So every seed gives the same sizes: the same
nonzeros, products and output nonzeros, and the same products in every
batch of columns aligned to the group. The program's static shapes (tile,
selection and multiply capacities) depend only on those sizes, so every
seed runs the programs the first run compiled.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sps


@dataclasses.dataclass(frozen=True)
class Operand:
    """A square sparse matrix on the host, entries in row-major order."""

    rows: np.ndarray  # int32
    cols: np.ndarray  # int32
    vals: np.ndarray  # float32
    n: int

    @property
    def nnz(self) -> int:
        return int(self.rows.shape[0])

    def to_scipy(self, dtype=np.float64) -> sps.csr_matrix:
        """The same matrix as a scipy CSR of ``dtype`` (the float32 values
        widened, so the reference sees exactly what the program is given)."""
        indptr = np.zeros(self.n + 1, np.int64)
        np.cumsum(np.bincount(self.rows, minlength=self.n), out=indptr[1:])
        return sps.csr_matrix(
            (self.vals.astype(dtype), self.cols.astype(np.int32), indptr),
            shape=(self.n, self.n),
        )


# vertices are relabelled within aligned groups of this many: the program's
# batches and tiles are aligned blocks of at least this width, so every
# batch keeps its columns' work
RELABEL_GROUP = 64


def rng(seed) -> np.random.Generator:
    # any whole number: SeedSequence takes non-negative ints of any size
    return np.random.default_rng(int(seed) % (1 << 64))


def relabelling(n: int, rng: np.random.Generator,
                group: int = RELABEL_GROUP) -> np.ndarray:
    """A permutation of range(n) that moves each vertex only within its
    aligned group of ``group``."""
    g = min(group, n)
    local = rng.permuted(np.tile(np.arange(g), (n // g, 1)), axis=1)
    return (local + np.arange(0, n, g)[:, None]).ravel()


def canonical(rows, cols, vals, n) -> sps.csr_matrix:
    """Sum duplicate coordinates and sort: what ``from_numpy_coo`` does."""
    m = sps.coo_matrix((vals, (rows, cols)), shape=(n, n)).tocsr()
    m.sum_duplicates()
    m.sort_indices()
    return m


def from_scipy(m: sps.csr_matrix) -> Operand:
    n = m.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(m.indptr))
    return Operand(rows, m.indices.astype(np.int32),
                   m.data.astype(np.float32), n)


def generate(generator, config: dict, seed: int) -> Operand:
    """The operand of ``config`` (a configuration file's contents) from
    ``generator`` (the module its ``"generator"`` names)."""
    relabel, values = rng(seed).spawn(2)
    return generator.generate(structure=rng(config["structure_seed"]),
                              values=values, relabel=relabel,
                              **config["params"])
