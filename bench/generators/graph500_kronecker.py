"""Graph 500 Kronecker (R-MAT) graph, symmetrized.

``edge_factor · 2^scale`` edges, each placed by ``scale`` quadrant draws
with probabilities (a, b, c, 1 − a − b − c), vertex labels randomly
permuted as the specification does, then symmetrized and deduplicated (self
loops kept); values uniform in [value_low, value_high) (the distribution of
``gen.rmat`` and ``gen.symmetrized`` with weights). Streams as in
``protein_similarity``.
"""
from __future__ import annotations

import numpy as np

from bench.operand import canonical, from_scipy, relabelling


def generate(scale: int, edge_factor: int, a: float, b: float, c: float,
             permute_vertices: bool, value_low: float, value_high: float, *,
             structure, values, relabel):
    rng = structure
    n = 1 << scale
    label = relabelling(n, relabel)
    m = edge_factor * n
    rows = np.zeros(m, np.int64)
    cols = np.zeros(m, np.int64)
    ab, abc = a + b, a + b + c
    for lvl in range(scale):
        u = rng.random(m)
        rows |= (u >= ab).astype(np.int64) << lvl
        cols |= (((u >= a) & (u < ab)) | (u >= abc)).astype(np.int64) << lvl
    if permute_vertices:
        perm = rng.permutation(n)
        rows, cols = perm[rows], perm[cols]
    rows, cols = label[rows], label[cols]
    r = np.concatenate([rows, cols])
    c_ = np.concatenate([cols, rows])
    del rows, cols
    pattern = canonical(r, c_, np.ones(len(r), np.float32), n)
    pattern.data = values.uniform(value_low, value_high,
                                  pattern.nnz).astype(np.float32)
    return from_scipy(pattern)
