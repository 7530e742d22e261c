"""Per-kernel tests: Pallas (interpret=True) vs pure-jnp oracles, with
shape/dtype sweeps and block-shape sweeps."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import sparse as sp
from repro.kernels import ops, ref
from repro.kernels.densify import densify_pallas
from repro.kernels.spgemm_acc import spgemm_paired_pallas
from repro.kernels.spmm import spmm_pallas


def dense_random(rng, m, n, density, dtype=np.float32):
    x = rng.standard_normal((m, n)).astype(dtype)
    mask = rng.random((m, n)) < density
    return np.where(mask, x, 0.0).astype(dtype)


SHAPES = [(8, 8, 8), (16, 24, 8), (33, 17, 9), (64, 40, 128), (128, 128, 130)]
DTYPES = [np.float32, jnp.bfloat16]


class TestSpMMKernel:
    @pytest.mark.parametrize("m,k,n", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_sweep_vs_ref(self, m, k, n, dtype):
        rng = np.random.default_rng(m * 1000 + k * 10 + n)
        A = dense_random(rng, m, k, 0.3)
        B = dense_random(rng, k, n, 0.8).astype(dtype)
        a = sp.from_dense(jnp.asarray(A), cap=m * k // 2 + m)
        vals = jnp.where(a.valid_mask(), a.vals, 0).astype(dtype)
        got = spmm_pallas(a.rows, a.cols, vals, jnp.asarray(B), m,
                          m_blk=16, n_blk=128, k_blk=16, nnz_blk=32)
        want = ref.spmm_ref(a.rows, a.cols, vals, jnp.asarray(B), m)
        tol = 1e-5 if dtype == np.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_block_shape_sweep(self):
        rng = np.random.default_rng(7)
        m, k, n = 40, 24, 48
        A = dense_random(rng, m, k, 0.4)
        B = dense_random(rng, k, n, 0.9)
        a = sp.from_dense(jnp.asarray(A), cap=600)
        vals = jnp.where(a.valid_mask(), a.vals, 0)
        want = A @ B
        for m_blk, n_blk, k_blk, nnz_blk in [(8, 128, 8, 8), (40, 128, 24, 600),
                                             (16, 128, 16, 64)]:
            got = spmm_pallas(a.rows, a.cols, vals, jnp.asarray(B), m,
                              m_blk=m_blk, n_blk=n_blk, k_blk=k_blk, nnz_blk=nnz_blk)
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)

    def test_ops_wrapper_pallas_matches_jnp(self):
        rng = np.random.default_rng(3)
        A = dense_random(rng, 20, 30, 0.3)
        B = dense_random(rng, 30, 16, 0.9)
        a = sp.from_dense(jnp.asarray(A), cap=250)
        got_p = ops.spmm(a, jnp.asarray(B), use_pallas=True)
        got_j = ops.spmm(a, jnp.asarray(B), use_pallas=False)
        np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_j),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_j), A @ B, rtol=1e-4, atol=1e-5)


class TestPairedSpGEMMKernel:
    @pytest.mark.parametrize("m,k,n", SHAPES[:4])
    def test_sweep_vs_ref(self, m, k, n):
        rng = np.random.default_rng(m + k + n)
        A = dense_random(rng, m, k, 0.3)
        B = dense_random(rng, k, n, 0.3)
        a = sp.from_dense(jnp.asarray(A), cap=m * k // 2 + m)
        b = sp.from_dense(jnp.asarray(B), cap=k * n // 2 + n)
        av = jnp.where(a.valid_mask(), a.vals, 0)
        bv = jnp.where(b.valid_mask(), b.vals, 0)
        got = spgemm_paired_pallas(a.rows, a.cols, av, b.rows, b.cols, bv, m, n,
                                   m_blk=16, n_blk=128, a_blk=32, b_blk=32)
        np.testing.assert_allclose(np.asarray(got), A @ B, rtol=1e-4, atol=1e-4)

    def test_unsorted_entries(self):
        """Sort-free: arbitrary entry order must give identical results."""
        rng = np.random.default_rng(11)
        m, k, n = 24, 16, 24
        A = dense_random(rng, m, k, 0.4)
        B = dense_random(rng, k, n, 0.4)
        a = sp.from_dense(jnp.asarray(A), cap=200)
        b = sp.from_dense(jnp.asarray(B), cap=200)
        av = jnp.where(a.valid_mask(), a.vals, 0)
        bv = jnp.where(b.valid_mask(), b.vals, 0)
        perm = rng.permutation(200)
        got = spgemm_paired_pallas(
            a.rows[perm], a.cols[perm], av[perm], b.rows, b.cols, bv, m, n,
            m_blk=8, n_blk=128, a_blk=40, b_blk=40,
        )
        np.testing.assert_allclose(np.asarray(got), A @ B, rtol=1e-4, atol=1e-4)

    def test_ops_wrapper(self):
        rng = np.random.default_rng(5)
        A = dense_random(rng, 16, 12, 0.4)
        B = dense_random(rng, 12, 8, 0.4)
        a = sp.from_dense(jnp.asarray(A), cap=100)
        b = sp.from_dense(jnp.asarray(B), cap=60)
        got_p = ops.spgemm_paired(a, b, use_pallas=True)
        got_j = ops.spgemm_paired(a, b, use_pallas=False)
        np.testing.assert_allclose(np.asarray(got_p), np.asarray(got_j),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_j), A @ B, rtol=1e-4, atol=1e-5)


class TestDensifyKernel:
    @pytest.mark.parametrize("m,n", [(8, 8), (17, 33), (64, 128), (130, 60)])
    def test_sweep_vs_ref(self, m, n):
        rng = np.random.default_rng(m * n)
        X = dense_random(rng, m, n, 0.3)
        a = sp.from_dense(jnp.asarray(X), cap=m * n // 2 + m)
        vals = jnp.where(a.valid_mask(), a.vals, 0)
        got = densify_pallas(a.rows, a.cols, vals, m, n,
                             m_blk=16, n_blk=128, nnz_blk=64)
        np.testing.assert_allclose(np.asarray(got), X, rtol=1e-6)

    def test_duplicate_coords_accumulate(self):
        rows = jnp.array([1, 1, 2, 1], jnp.int32)
        cols = jnp.array([3, 3, 0, 3], jnp.int32)
        vals = jnp.array([1.0, 2.0, 5.0, 3.0], jnp.float32)
        got = densify_pallas(rows, cols, vals, 4, 4, m_blk=8, n_blk=128, nnz_blk=8)
        assert got[1, 3] == 6.0 and got[2, 0] == 5.0

    def test_ops_wrapper(self):
        rng = np.random.default_rng(9)
        X = dense_random(rng, 12, 20, 0.4)
        a = sp.from_dense(jnp.asarray(X), cap=120)
        np.testing.assert_allclose(
            np.asarray(ops.densify(a, use_pallas=True)), X, rtol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(ops.densify(a, use_pallas=False)), X, rtol=1e-6
        )


class TestKernelIntegration:
    def test_dense_acc_spgemm_via_kernels(self):
        """densify(B batch block) + spmm == paired kernel == dense oracle —
        the two kernel realizations of the batched local multiply agree."""
        rng = np.random.default_rng(21)
        m, k, n = 32, 24, 16
        A = dense_random(rng, m, k, 0.3)
        B = dense_random(rng, k, n, 0.3)
        a = sp.from_dense(jnp.asarray(A), cap=300)
        b = sp.from_dense(jnp.asarray(B), cap=200)
        bd = ops.densify(b, use_pallas=True)
        c1 = ops.spmm(a, bd, use_pallas=True)
        c2 = ops.spgemm_paired(a, b, use_pallas=True)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(c1), A @ B, rtol=1e-4, atol=1e-4)


class TestColPruneKernel:
    """Per-column top-k threshold (MCL batch consumption, paper §V-C)."""

    @pytest.mark.parametrize("m,n,k", [(32, 16, 4), (64, 128, 8), (17, 33, 3)])
    def test_threshold_keeps_at_most_k(self, m, n, k):
        from repro.kernels.col_prune import col_topk_threshold_pallas

        rng = np.random.default_rng(m * n + k)
        x = rng.standard_normal((m, n)).astype(np.float32)
        t = np.asarray(col_topk_threshold_pallas(jnp.asarray(x), k))
        counts = (np.abs(x) >= t[None, :]).sum(0)
        assert (counts <= k).all(), counts.max()
        # threshold must not over-prune: at least k kept unless impossible
        # (bisection resolves ties to <= k; with distinct values == k)
        assert (counts >= min(k, m) - 1).all(), counts.min()

    def test_matches_sorted_oracle_distinct_values(self):
        from repro.kernels.col_prune import (
            col_topk_threshold_pallas,
            col_topk_threshold_ref,
        )

        rng = np.random.default_rng(5)
        m, n, k = 48, 24, 6
        x = rng.permutation(m * n).reshape(m, n).astype(np.float32) + 1.0
        t_k = np.asarray(col_topk_threshold_pallas(jnp.asarray(x), k))
        t_r = np.asarray(col_topk_threshold_ref(jnp.asarray(x), k))
        kept_k = (np.abs(x) >= t_k[None, :])
        kept_r = (np.abs(x) >= t_r[None, :])
        np.testing.assert_array_equal(kept_k, kept_r)

    def test_row_blocks_match_single_block(self):
        """Streaming the column through several row blocks (with a padded
        last block) gives the bracket of the one-block run, bit for bit."""
        from repro.kernels.col_prune import col_topk_bounds_pallas

        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((300, 40)).astype(np.float32))
        lo1, hi1 = col_topk_bounds_pallas(x, 7, m_blk=512)
        lo3, hi3 = col_topk_bounds_pallas(x, 7, m_blk=128)
        np.testing.assert_array_equal(np.asarray(lo1), np.asarray(lo3))
        np.testing.assert_array_equal(np.asarray(hi1), np.asarray(hi3))

    @pytest.mark.parametrize("m,n,k", [(32, 8, 4), (40, 16, 5)])
    def test_parity_with_numpy_topk_including_ties(self, m, n, k):
        """Threshold selection vs exact numpy top-k with REPEATED values.

        The kernel keeps the largest set with |{x >= t}| <= k. When ties
        straddle the k-th position that set is exactly the entries STRICTLY
        greater than the k-th value (numpy's top-k keeps an arbitrary tie
        subset); without a straddling tie it equals numpy's top-k set."""
        from repro.kernels.col_prune import col_topk_threshold_pallas

        rng = np.random.default_rng(m * n * k)
        # quantized values -> many exact ties, including at the k boundary
        x = (rng.integers(0, 6, (m, n)) * 0.125).astype(np.float32)
        t = np.asarray(col_topk_threshold_pallas(jnp.asarray(x), k))
        for j in range(n):
            col = x[:, j]
            kept = col >= t[j]
            kth = np.sort(col)[::-1][k - 1]  # numpy's exact k-th largest
            if (col >= kth).sum() > k:  # tie straddles the boundary
                np.testing.assert_array_equal(kept, col > kth)
            else:
                np.testing.assert_array_equal(kept, col >= kth)
            assert kept.sum() <= k
