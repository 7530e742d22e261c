"""The benchmark's counts of work and bytes, and its generators, against
scipy on small matrices."""
from __future__ import annotations

import numpy as np
import pytest
import scipy.sparse as sps

from bench import counting, operand
from bench.conftest import TEST_PEAKS


def _random(n, density, seed):
    m = sps.random(n, n, density=density, random_state=seed, format="csr",
                   dtype=np.float64)
    m.data += 0.5
    return m


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_products_match_pattern_product(seed):
    a, b = _random(60, 0.08, seed), _random(60, 0.1, seed + 10)
    counts = counting.ProductCounts(a, b)
    pa = (a != 0).astype(np.int64)
    pb = (b != 0).astype(np.int64)
    rng = np.random.default_rng(seed)
    cols = np.sort(rng.choice(60, 17, replace=False))
    # every product A[i,k]·B[k,j] is one unit of the pattern product's sum
    assert counts.products(cols) == int((pa @ pb[:, cols]).sum())
    assert counts.products(np.arange(60)) == int((pa @ pb).sum())


@pytest.mark.parametrize("seed", [0, 1])
def test_least_bytes_counts_reads_and_writes(seed):
    a, b = _random(50, 0.1, seed), _random(50, 0.06, seed + 5)
    counts = counting.ProductCounts(a, b)
    cols = np.arange(0, 50, 3)
    sub = sps.csc_matrix(b)[:, cols]
    ks = np.unique(sub.indices)
    a_read = sps.csc_matrix(a)[:, ks].nnz
    c_nnz = (a @ sub).nnz
    assert counts.a_entries_read(cols) == a_read
    assert counts.b_entries(cols) == sub.nnz
    assert counts.least_bytes(cols, c_nnz) == 12 * (a_read + sub.nnz + c_nnz)


def test_least_time_takes_the_larger_bound():
    t, bound = counting.least_time_s(10**6, 12 * 10**6, TEST_PEAKS)
    assert bound == "bytes" and t == pytest.approx(12e6 / 819e9)
    t, bound = counting.least_time_s(10**15, 12, TEST_PEAKS)
    assert bound == "compute" and t == pytest.approx(2e15 / 197e12)


def _streams(seed):
    rng = np.random.default_rng(seed)
    return dict(structure=rng, values=rng, relabel=rng)


def test_protein_generator_statistics(bench_spec):
    gen = bench_spec.cell("protein-2e18.square-sync").generator
    op = gen.generate(
        log2_n=12, family=256, intra_p=0.28, background_per_node=0.5,
        value_low=0.3, value_high=1.0, column_normalize=True,
        **_streams(3))
    m = op.to_scipy()
    pattern = (m != 0).astype(np.int8)
    assert (pattern != pattern.T).nnz == 0  # symmetric structure
    assert np.all(m.diagonal() > 0)  # self loops
    np.testing.assert_allclose(np.asarray(m.sum(axis=0)).ravel(), 1.0,
                               rtol=1e-5)
    per_col = op.nnz / op.n
    assert 108.0 < per_col < 115.0  # ~111, Eukarya's density
    # entries come row-major, as the program's host constructor leaves them
    key = op.rows.astype(np.int64) * op.n + op.cols
    assert np.all(np.diff(key) > 0)


def test_graph500_generator_statistics(bench_spec):
    gen = bench_spec.cell("rmat-s20.square-sync").generator
    op = gen.generate(
        scale=10, edge_factor=16, a=0.57, b=0.19, c=0.19,
        permute_vertices=True, value_low=0.5, value_high=1.0,
        **_streams(4))
    m = op.to_scipy()
    pattern = (m != 0).astype(np.int8)
    assert (pattern != pattern.T).nnz == 0
    assert op.vals.min() >= 0.5 and op.vals.max() < 1.0
    degrees = np.diff(m.indptr)
    assert degrees.max() > 20 * np.median(degrees)  # power-law hubs


CELLS = ["protein-2e18.square-sync", "rmat-s20.square-sync"]


def _small(cell):
    config = cell.config
    key = "log2_n" if "log2_n" in config["params"] else "scale"
    small = dict(config, params=dict(config["params"], **{key: 10}))
    return lambda seed: operand.generate(cell.generator, small, seed)


@pytest.mark.parametrize("name", CELLS)
def test_generators_repeat_from_the_seed(name, bench_spec):
    generate = _small(bench_spec.cell(name))
    seed = 3 * 2**31 + 7  # seeds above 32 bits
    one, two = generate(seed), generate(seed)
    other = generate(seed + 1)
    assert np.array_equal(one.rows, two.rows)
    assert np.array_equal(one.cols, two.cols)
    assert np.array_equal(one.vals, two.vals)
    assert not np.array_equal(one.vals, other.vals)
    assert not (np.array_equal(one.rows, other.rows)
                and np.array_equal(one.cols, other.cols))


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_gives_the_same_sizes(name, bench_spec):
    """Seeds relabel vertices within aligned groups and redraw values: the
    nonzeros, output nonzeros and the products of every aligned group of
    columns (so of every batch the program plans) are the same."""
    generate = _small(bench_spec.cell(name))
    sizes = []
    for seed in (1, 2**33 + 5, 987654321):
        a = generate(seed).to_scipy()
        per_col = counting.ProductCounts(a, a).col_products
        group = per_col.reshape(-1, operand.RELABEL_GROUP).sum(axis=1)
        sizes.append((a.nnz, (a @ a).nnz, tuple(group)))
    assert sizes[0] == sizes[1] == sizes[2]


def test_relabelling_stays_in_aligned_groups():
    perm = operand.relabelling(256, np.random.default_rng(0), group=64)
    assert sorted(perm) == list(range(256))
    assert np.array_equal(perm // 64, np.arange(256) // 64)
    assert not np.array_equal(perm, np.arange(256))
