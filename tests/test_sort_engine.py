"""Packed-key sort engine: parity vs the legacy lexsort
path (randomized, over PLUS_TIMES / MIN_PLUS / MAX_TIMES), merge overflow
reporting, the segmented sorted merge, and the bitonic Pallas kernel."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import local_spgemm as lsp
from repro.core import semiring as sr
from repro.core import sortkeys as sk
from repro.core import sparse as sp
from repro.kernels import sort_engine as se
from repro.testing import given, settings, strategies as st

SEMIRINGS = [sr.PLUS_TIMES, sr.MIN_PLUS, sr.MAX_TIMES]


def dense_random(rng, m, n, density):
    x = rng.random((m, n)).astype(np.float32)
    mask = rng.random((m, n)) < density
    return np.where(mask, x + 0.1, 0.0).astype(np.float32)


def random_entries(rng, m, n, cap, valid_p=0.8):
    rows = jnp.asarray(rng.integers(0, m, cap).astype(np.int32))
    cols = jnp.asarray(rng.integers(0, n, cap).astype(np.int32))
    valid = jnp.asarray(rng.random(cap) < valid_p)
    vals = jnp.asarray((rng.random(cap) + 0.1).astype(np.float32))
    return rows, cols, vals, valid


def duplicate_heavy_entries(rng, cap=256):
    """Entries on a 4 x 3 tile (~21 per coordinate) whose values span eight
    decades with both signs, so a coordinate's f32 sum depends on the order
    its entries are added in."""
    m, n = 4, 3
    rows, cols, _, valid = random_entries(rng, m, n, cap, valid_p=0.9)
    mag = 10.0 ** rng.integers(-4, 5, cap)
    vals = (rng.standard_normal(cap) * mag).astype(np.float32)
    return (m, n), (rows, cols, jnp.asarray(vals), valid)


def lexsort_gather_coalesce(rows, cols, vals, valid, shape, new_cap, add_kind):
    """The compress as it was written with a permutation: ``jnp.lexsort``,
    rows, cols and values gathered by it, then the boundary scan and the
    slot reduction. The reference the two-key engine must match bit for
    bit."""
    m, n = shape
    rows = jnp.where(valid, rows, m)
    cols = jnp.where(valid, cols, n)
    order = jnp.lexsort((cols, rows))
    rows, cols, vals = rows[order], cols[order], vals[order]
    vmask = rows < m
    new_key = jnp.concatenate([
        jnp.ones((1,), bool),
        (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1]),
    ]) & vmask
    seg = jnp.cumsum(new_key.astype(jnp.int32)) - 1
    total = jnp.maximum(seg[-1] + 1, 0)
    seg = jnp.where(vmask & (seg < new_cap), seg, new_cap)
    out_rows = jnp.full((new_cap + 1,), m, jnp.int32).at[seg].min(rows)
    out_cols = jnp.full((new_cap + 1,), n, jnp.int32).at[seg].min(cols)
    if add_kind == "sum":
        out_vals = jnp.zeros((new_cap + 1,), vals.dtype).at[seg].add(
            jnp.where(seg < new_cap, vals, 0)
        )
    elif add_kind == "min":
        out_vals = jnp.full((new_cap + 1,), jnp.inf, vals.dtype).at[seg].min(vals)
    else:
        out_vals = jnp.full((new_cap + 1,), -jnp.inf, vals.dtype).at[seg].max(vals)
    nnz = jnp.minimum(total, new_cap).astype(jnp.int32)
    pad = jnp.arange(new_cap) >= nnz
    return (
        jnp.where(pad, m, out_rows[:new_cap]),
        jnp.where(pad, n, out_cols[:new_cap]),
        jnp.where(pad, 0, out_vals[:new_cap]).astype(vals.dtype),
        nnz,
        (total - nnz).astype(jnp.int32),
    )


def lexsort_gather_sort(a, primary, secondary):
    """``a`` sorted by ``jnp.lexsort`` on two of its fields plus gathers."""
    order = jnp.lexsort((getattr(a, secondary), getattr(a, primary)))
    return tuple(np.asarray(getattr(a, f)[order]) for f in ("rows", "cols", "vals"))


def with_duplicates(a, rng):
    """``a`` with every entry repeated once under a fresh value (the copy's
    padding included), so equal coordinates must keep their input order."""
    fresh = jnp.asarray(rng.random(a.cap).astype(np.float32))
    return sp.SparseCOO(
        jnp.concatenate([a.rows, a.rows]), jnp.concatenate([a.cols, a.cols]),
        jnp.concatenate([a.vals, fresh]), a.nnz, a.shape,
    )


def equations(jaxpr):
    """Every equation of ``jaxpr``, nested jaxprs (jit, cumsum) included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def assert_entries_equal(got, want, context=""):
    for name, x, y in zip(("rows", "cols", "vals", "nnz", "ovf"), got, want):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5,
            err_msg=f"{context}: {name}",
        )


# ---------------------------------------------------------------------------
# packed-key sort parity vs lexsort
# ---------------------------------------------------------------------------
class TestPackedSortParity:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), density=st.floats(0.05, 0.6))
    def test_sort_rowmajor_bitexact(self, seed, density):
        rng = np.random.default_rng(seed)
        x = dense_random(rng, 13, 11, density)
        a = with_duplicates(sp.from_dense(jnp.asarray(x), cap=13 * 11 + 5), rng)
        packed = a.sort_rowmajor(engine="auto")
        legacy = a.sort_rowmajor(engine="lexsort")
        ref = lexsort_gather_sort(a, "rows", "cols")
        for f, want in zip(("rows", "cols", "vals"), ref):
            np.testing.assert_array_equal(np.asarray(getattr(packed, f)), want, f)
            np.testing.assert_array_equal(np.asarray(getattr(legacy, f)), want, f)

    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**16), density=st.floats(0.05, 0.6))
    def test_sort_colmajor_bitexact(self, seed, density):
        rng = np.random.default_rng(seed)
        x = dense_random(rng, 9, 17, density)
        a = with_duplicates(sp.from_dense(jnp.asarray(x), cap=9 * 17 + 3), rng)
        packed = a.sort_colmajor(engine="auto")
        legacy = a.sort_colmajor(engine="lexsort")
        ref = lexsort_gather_sort(a, "cols", "rows")
        for f, want in zip(("rows", "cols", "vals"), ref):
            np.testing.assert_array_equal(np.asarray(getattr(packed, f)), want, f)
            np.testing.assert_array_equal(np.asarray(getattr(legacy, f)), want, f)

    @pytest.mark.parametrize("op", ["coalesce", "count_unique", "sort_rowmajor",
                                    "sort_colmajor"])
    def test_lexsort_engine_is_one_two_key_sort(self, op):
        """The two-key engine sorts once on (row, col), or (col, row), with
        the values riding along: no permutation is gathered by."""
        m, n, cap = 1 << 18, 1 << 14, 64
        rows = jnp.zeros((cap,), jnp.int32)
        vals = jnp.zeros((cap,), jnp.float32)
        valid = jnp.ones((cap,), bool)
        fns = {
            "coalesce": lambda r, c, v: sk.coalesce_entries(
                r, c, v, valid, (m, n), 32, "sum", "lexsort"),
            "count_unique": lambda r, c, v: sk.count_unique(
                r, c, valid, (m, n), "lexsort"),
            "sort_rowmajor": lambda r, c, v: sp.SparseCOO(
                r, c, v, jnp.int32(cap), (m, n)).sort_rowmajor("lexsort"),
            "sort_colmajor": lambda r, c, v: sp.SparseCOO(
                r, c, v, jnp.int32(cap), (m, n)).sort_colmajor("lexsort"),
        }
        eqns = list(equations(jax.make_jaxpr(fns[op])(rows, rows, vals).jaxpr))
        sorts = [e for e in eqns if e.primitive.name == "sort"]
        assert [e.params["num_keys"] for e in sorts] == [2]
        assert sorts[0].params["is_stable"]
        assert not [e for e in eqns if e.primitive.name == "gather"]


# ---------------------------------------------------------------------------
# coalesce engines parity over semirings
# ---------------------------------------------------------------------------
class TestCoalesceEngines:
    @pytest.mark.parametrize("ring", ["plus_times", "min_plus", "max_times"])
    @pytest.mark.parametrize("inputs", ["scattered", "duplicate_heavy"])
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_engines_match_lexsort(self, seed, ring, inputs):
        """The two-key engine equals ``jnp.lexsort`` plus gathers bit for
        bit; the packed and bucket engines equal it to rounding."""
        semiring = sr.get(ring)
        rng = np.random.default_rng(seed)
        if inputs == "scattered":
            shape, new_cap = (14, 10), 64
            entries = random_entries(rng, *shape, 96)
        else:
            shape, new_cap = (4, 3), 12
            entries = duplicate_heavy_entries(rng)[1]
            if ring == "plus_times":
                # the input must tell summation orders apart
                rows, cols, vals, valid = entries
                fwd = lexsort_gather_coalesce(*entries, shape, new_cap, "sum")[2]
                rev = lexsort_gather_coalesce(
                    rows[::-1], cols[::-1], vals[::-1], valid[::-1],
                    shape, new_cap, "sum")[2]
                assert not np.array_equal(np.asarray(fwd), np.asarray(rev))
        ref = lexsort_gather_coalesce(*entries, shape, new_cap, semiring.add_kind)
        got = sk.coalesce_entries(
            *entries, shape, new_cap, semiring.add_kind, "lexsort"
        )
        for name, x, y in zip(("rows", "cols", "vals", "nnz", "ovf"), got, ref):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
        if inputs == "scattered":
            for eng in ("packed", "bucket"):
                got = sk.coalesce_entries(
                    *entries, shape, new_cap, semiring.add_kind, eng
                )
                assert_entries_equal(got, ref, f"{ring}/{eng}")

    def test_auto_picks_bucket_for_small_tiles(self):
        assert sk.choose_engine(100, 100, 1000) == "bucket"

    def test_auto_falls_back_above_table_budget(self):
        big = 1 << 13
        assert sk.choose_engine(big, big, 1000) == "packed"

    @pytest.mark.parametrize("shape", [
        (1 << 17, 1 << 17),
        (1 << 18, 1 << 14),  # protein-2e18.square-sync's batch tile (b = 16)
        (1 << 20, 1 << 11),  # rmat-s20.square-sync's batch tile (b = 512)
    ])
    def test_lexsort_when_key_overflows_i32(self, shape):
        assert sk.choose_engine(*shape, 1000) == "lexsort"

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16))
    def test_esc_engine_parity(self, seed):
        rng = np.random.default_rng(seed)
        A = dense_random(rng, 10, 12, 0.3)
        B = dense_random(rng, 12, 9, 0.3)
        a = sp.from_dense(jnp.asarray(A), cap=10 * 12 + 1)
        b = sp.from_dense(jnp.asarray(B), cap=12 * 9 + 1)
        outs = {}
        for eng in ("lexsort", "packed", "bucket"):
            c, ovf = lsp.spgemm_esc(
                a, b, out_cap=10 * 9 + 1, flops_cap=2048, engine=eng
            )
            assert int(ovf) == 0
            outs[eng] = c
        for eng in ("packed", "bucket"):
            for f in ("rows", "cols", "nnz"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(outs[eng], f)),
                    np.asarray(getattr(outs["lexsort"], f)), (eng, f),
                )
            np.testing.assert_allclose(
                np.asarray(outs[eng].vals), np.asarray(outs["lexsort"].vals),
                rtol=1e-5, atol=1e-6,
            )

    def test_symbolic_exact_engine_parity(self):
        rng = np.random.default_rng(3)
        A = dense_random(rng, 11, 13, 0.4)
        B = dense_random(rng, 13, 7, 0.4)
        a = sp.from_dense(jnp.asarray(A), cap=11 * 13 + 1)
        b = sp.from_dense(jnp.asarray(B), cap=13 * 7 + 1)
        expect = int(((A @ B) != 0).sum())
        for eng in ("lexsort", "packed", "bucket"):
            assert int(lsp.local_symbolic_exact(a, b, 4096, engine=eng)) == expect


# ---------------------------------------------------------------------------
# merge_sparse: overflow reporting + segmented sorted merge
# ---------------------------------------------------------------------------
class TestMergeSparse:
    @settings(max_examples=10, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        ring=st.sampled_from(["plus_times", "min_plus", "max_times"]),
    )
    def test_sorted_merge_matches_unsorted(self, seed, ring):
        semiring = sr.get(ring)
        rng = np.random.default_rng(seed)
        xs = [dense_random(rng, 12, 8, 0.35) for _ in range(3)]
        # parts are row-major sorted (the Merge-Fiber precondition)
        parts = [
            sp.from_dense(jnp.asarray(x), cap=40).sort_rowmajor() for x in xs
        ]
        m1, o1 = lsp.merge_sparse(parts, 96, semiring, assume_sorted=True)
        m2, o2 = lsp.merge_sparse(parts, 96, semiring, assume_sorted=False,
                                  engine="lexsort")
        assert int(o1) == int(o2) == 0
        for f in ("rows", "cols", "nnz"):
            np.testing.assert_array_equal(
                np.asarray(getattr(m1, f)), np.asarray(getattr(m2, f)), f
            )
        np.testing.assert_allclose(
            np.asarray(m1.vals), np.asarray(m2.vals), rtol=1e-5, atol=1e-6
        )

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**16), parts=st.integers(1, 5))
    def test_overflow_reported_consistently(self, seed, parts):
        """Overflow = distinct-coordinate count minus out_cap, identically
        across engines and the sorted merge (satellite: overflow reporting)."""
        rng = np.random.default_rng(seed)
        xs = [dense_random(rng, 9, 9, 0.5) for _ in range(parts)]
        # cap = 9·9 holds every entry: a smaller cap truncates the input and
        # breaks the expected count below
        mats = [sp.from_dense(jnp.asarray(x), cap=81) for x in xs]
        distinct = int((sum((x != 0).astype(np.int64) for x in xs) != 0).sum())
        out_cap = max(distinct // 2, 1)
        expect_ovf = distinct - out_cap
        for kwargs in (
            dict(engine="lexsort"),
            dict(engine="packed"),
            dict(engine="bucket"),
            dict(assume_sorted=True),
        ):
            ps = (
                [x.sort_rowmajor() for x in mats]
                if kwargs.get("assume_sorted")
                else mats
            )
            merged, ovf = lsp.merge_sparse(ps, out_cap, sr.PLUS_TIMES, **kwargs)
            assert int(ovf) == expect_ovf, kwargs
            assert int(merged.nnz) == out_cap, kwargs
            # surviving prefix is the row-major smallest coordinate set
            keys = (
                np.asarray(merged.rows[: out_cap]) * 10
                + np.asarray(merged.cols[: out_cap])
            )
            assert np.all(np.diff(keys) > 0), kwargs

    @pytest.mark.parametrize("local_path", ["esc", "hash"])
    def test_one_layer_grid_sorted_merge_matches_unsorted(self, local_path):
        """On a 1x1x1 grid the batched product is the same whether
        Merge-Fiber takes the sorted single piece or re-merges it."""
        from repro.core import gen
        from repro.core.batched import batched_summa3d
        from repro.core.distsparse import gather_to_global, scatter_to_grid
        from repro.core.grid import make_grid
        from repro.core.specs import ExecSpec, PlanSpec

        grid = make_grid(1, 1, 1)
        a = gen.protein_similarity_like(256, blocks=4, intra_p=0.3, seed=2)
        A = scatter_to_grid(a, grid, "A")
        B = scatter_to_grid(a, grid, "B")
        got = {}
        for sorted_merge in (True, False):
            outs = []
            batched_summa3d(
                A, B, grid, 1 << 30,
                consumer=lambda bi, c, cm: outs.append(gather_to_global(c)),
                spec=PlanSpec(local_path=local_path, force_num_batches=2),
                exec_spec=ExecSpec(sorted_merge=sorted_merge),
            )
            got[sorted_merge] = [
                tuple(np.asarray(x)[: int(c.nnz)]
                      for x in (c.rows, c.cols, c.vals))
                for c in outs
            ]
        assert len(got[True]) == 2
        for mine, theirs in zip(got[True], got[False]):
            for x, y in zip(mine, theirs):
                np.testing.assert_array_equal(x, y)

    def test_merge_empty_parts(self):
        parts = [sp.empty((6, 6), cap=8) for _ in range(3)]
        for kwargs in (dict(engine="bucket"), dict(assume_sorted=True)):
            merged, ovf = lsp.merge_sparse(parts, 10, sr.PLUS_TIMES, **kwargs)
            assert int(ovf) == 0 and int(merged.nnz) == 0


# ---------------------------------------------------------------------------
# bitonic Pallas kernel
# ---------------------------------------------------------------------------
class TestBitonicKernel:
    @pytest.mark.parametrize("n", [8, 128, 500, 2048])
    def test_matches_lax_sort(self, n):
        rng = np.random.default_rng(n)
        keys = jnp.asarray(rng.integers(0, 300, n).astype(np.int32))
        vals = jnp.asarray(rng.random(n).astype(np.float32))
        k1, v1 = se.sort_pairs(keys, vals, use_pallas=True, interpret=True)
        k2, v2 = jax.lax.sort((keys, vals), num_keys=1)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))
        # network is unstable: compare per-key value multisets via sums
        s1 = jax.ops.segment_sum(v1, k1, num_segments=301)
        s2 = jax.ops.segment_sum(v2, k2, num_segments=301)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-4)

    def test_large_sizes_fall_back_to_xla(self):
        n = se.MAX_BITONIC_ELEMS + 8
        rng = np.random.default_rng(0)
        keys = jnp.asarray(rng.integers(0, 99, n).astype(np.int32))
        vals = jnp.asarray(rng.random(n).astype(np.float32))
        k1, _ = se.sort_pairs(keys, vals, use_pallas=True, interpret=True)
        assert np.all(np.diff(np.asarray(k1)) >= 0)
