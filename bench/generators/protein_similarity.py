"""Protein-similarity network: planted families in a sparse background.

Planted families of ``family`` proteins with in-family edge probability
``intra_p``, ``background_per_node · n`` uniform background edges,
symmetrized, with self loops, duplicate edges summed, values uniform in
[value_low, value_high), columns normalized to sum 1 as HipMCL does before
its first expansion (the distribution of ``gen.protein_similarity_like``,
vectorised). ``structure``, ``values`` and ``relabel`` are the random
streams of the entries, of their values and of the vertices' relabelling.
"""
from __future__ import annotations

import numpy as np

from bench.operand import canonical, from_scipy, relabelling


def generate(log2_n: int, family: int, intra_p: float,
             background_per_node: float, value_low: float, value_high: float,
             column_normalize: bool, *, structure, values, relabel):
    rng = structure
    n = 1 << log2_n
    label = relabelling(n, relabel)
    blocks = n // family
    bs = n // blocks
    sizes = np.full(blocks, bs, np.int64)
    sizes[-1] = n - bs * (blocks - 1)
    cnt = rng.binomial(sizes * sizes, intra_p)
    blk = np.repeat(np.arange(blocks, dtype=np.int64), cnt)
    base = blk * bs
    rows = rng.integers(0, sizes[blk]) + base
    cols = rng.integers(0, sizes[blk]) + base
    del blk, base
    bg = max(int(n * background_per_node), 1)
    rows = label[np.concatenate([rows, rng.integers(0, n, bg)])]
    cols = label[np.concatenate([cols, rng.integers(0, n, bg)])]
    loops = np.arange(n, dtype=np.int64)
    r = np.concatenate([rows, cols, loops])
    c = np.concatenate([cols, rows, loops])
    del rows, cols
    vals = values.uniform(value_low, value_high, len(r)).astype(np.float32)
    m = canonical(r, c, vals, n)
    del r, c, vals
    if column_normalize:
        m.data = m.data.astype(np.float64)
        sums = np.bincount(m.indices, weights=m.data, minlength=n)
        sums[sums == 0] = 1.0
        m.data = (m.data / sums[m.indices]).astype(np.float32)
    return from_scipy(m)
