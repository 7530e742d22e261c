"""Fast-lane tests for the device-resident MCL building blocks.

These run on a 1x1x1 grid (single device — the fast lane keeps the default
host platform), so the distributed column reductions, the fused per-batch
prune step, and the on-grid operand reassembly are exercised in-process
against numpy oracles; the full 8-device parity cases live in
``tests/app_cases.py`` (slow lane).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import sparse as sp
from repro.core.batched import batched_summa3d, plan_batches
from repro.core.distsparse import (
    dist_col_reduce,
    dist_col_sums,
    gather_to_global,
    scatter_to_grid,
)
from repro.core.grid import make_grid
from repro.core.summa3d import reassemble_operands
from repro.sparse_apps.mcl import (
    MCLConfig,
    _col_normalize_np,
    _mcl_prune_sparse,
    _prune_topk_np,
    mcl_iterate,
    mcl_iterate_host,
)


@pytest.fixture(scope="module")
def grid1():
    return make_grid(1, 1, 1)


def _dense_mat(n, density, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.1, 1.0, (n, n)).astype(np.float32)
    x = np.where(rng.random((n, n)) < density, x, 0.0).astype(np.float32)
    return x


class TestDistColReduce:
    @pytest.mark.parametrize("kind", ["A", "B"])
    @pytest.mark.parametrize("op", ["sum", "max"])
    def test_matches_numpy(self, grid1, kind, op, n=24):
        x = _dense_mat(n, 0.4, seed=n + ord(kind))
        d = scatter_to_grid(sp.from_dense(jnp.asarray(x), cap=400), grid1, kind)
        reduce = (
            (lambda d_, g_: dist_col_sums(d_, g_)) if op == "sum"
            else (lambda d_, g_: dist_col_reduce(d_, g_, op="max"))
        )
        got = np.asarray(reduce(d, grid1))[0, 0, 0]
        want = x.sum(axis=0) if op == "sum" else x.max(axis=0)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestMclPruneStep:
    def test_matches_host_prune_math(self, grid1, n=16, k=3):
        """Fused inflate+normalize+top-k == the numpy reference pipeline
        (distinct values, so threshold selection == exact top-k)."""
        x = _dense_mat(n, 0.5, seed=7)
        d = scatter_to_grid(sp.from_dense(jnp.asarray(x), cap=200), grid1, "C")
        cfg = MCLConfig(inflation=2.0, prune_threshold=1e-4, max_per_col=k)
        pruned, stats = _mcl_prune_sparse(
            d, grid=grid1, inflation=cfg.inflation, thresh=cfg.prune_threshold,
            k=k, new_cap=200,
        )
        cnt = int(np.asarray(pruned.nnz)[0, 0, 0])
        got = np.zeros((n, n), np.float32)
        got[np.asarray(pruned.rows)[0, 0, 0, :cnt],
            np.asarray(pruned.cols)[0, 0, 0, :cnt]] = (
            np.asarray(pruned.vals)[0, 0, 0, :cnt])

        rr, cc = np.nonzero(x)
        vv = x[rr, cc].astype(np.float64) ** cfg.inflation
        vv = _col_normalize_np(rr, cc, vv, n)
        rr, cc, vv = _prune_topk_np(rr, cc, vv, n, cfg.prune_threshold, k)
        vv = _col_normalize_np(rr, cc, vv, n)
        want = np.zeros((n, n), np.float32)
        want[rr, cc] = vv
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        assert int(np.asarray(stats["nnz"])) == len(rr)
        assert int(np.asarray(stats["overflow"])) == 0
        # chaos agrees with the host definition on the same values
        colmax = np.zeros(n); colsq = np.zeros(n)
        np.maximum.at(colmax, cc, vv)
        np.add.at(colsq, cc, vv ** 2)
        np.testing.assert_allclose(
            float(np.asarray(stats["chaos"])), (colmax - colsq).max(),
            rtol=1e-4, atol=1e-5,
        )

    def test_keeps_at_most_k_per_column(self, grid1, n=16, k=2):
        x = _dense_mat(n, 0.8, seed=9)
        d = scatter_to_grid(sp.from_dense(jnp.asarray(x), cap=300), grid1, "C")
        pruned, _ = _mcl_prune_sparse(
            d, grid=grid1, inflation=2.0, thresh=1e-4, k=k, new_cap=300,
        )
        cnt = int(np.asarray(pruned.nnz)[0, 0, 0])
        cols = np.asarray(pruned.cols)[0, 0, 0, :cnt]
        assert np.bincount(cols, minlength=n).max() <= k

    def test_tied_columns_keep_exactly_k(self, grid1, n=16, k=2):
        """Regression: a column of EQUAL values (uniform-weight graph column
        after normalization — every entry ties at the k boundary) must keep
        exactly k entries, not be annihilated by the bisection threshold."""
        x = np.zeros((n, n), np.float32)
        deg = 5  # > k: the tie straddles the top-k boundary in every column
        for j in range(n):
            x[(np.arange(deg) + j) % n, j] = 1.0  # uniform column values
        d = scatter_to_grid(sp.from_dense(jnp.asarray(x), cap=200), grid1, "C")
        pruned, stats = _mcl_prune_sparse(
            d, grid=grid1, inflation=2.0, thresh=1e-4, k=k, new_cap=200,
        )
        cnt = int(np.asarray(pruned.nnz)[0, 0, 0])
        cols = np.asarray(pruned.cols)[0, 0, 0, :cnt]
        counts = np.bincount(cols, minlength=n)
        np.testing.assert_array_equal(counts, np.full(n, k))
        assert int(np.asarray(stats["nnz"])) == n * k
        # survivors are renormalized: each kept entry is 1/k
        vals = np.asarray(pruned.vals)[0, 0, 0, :cnt]
        np.testing.assert_allclose(vals, 1.0 / k, rtol=1e-5)


class TestReassembleOperands:
    @pytest.mark.parametrize("nb", [1, 2, 4])
    def test_roundtrip_from_batches(self, grid1, nb, n=16):
        """Batched C outputs -> next A/B operands on-grid: both gather back
        to the multiply's dense result, with zero overflow at the hard-bound
        capacities."""
        xa = _dense_mat(n, 0.4, seed=11)
        xb = _dense_mat(n, 0.4, seed=13)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=200), grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=200), grid1, "B")
        batches = []
        res = batched_summa3d(
            A, B, grid1, per_process_memory=1 << 30,
            consumer=lambda bi, c, cm: batches.append(c),
            path="sparse", force_num_batches=nb,
        )
        assert res.plan.num_batches == nb
        cap = 1024
        a2, b2, ovf = reassemble_operands(tuple(batches), grid1, cap, cap)
        assert int(ovf) == 0
        want = xa @ xb
        np.testing.assert_allclose(
            np.asarray(gather_to_global(a2).to_dense()), want,
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(gather_to_global(b2).to_dense()), want,
            rtol=1e-4, atol=1e-5,
        )
        assert a2.kind == "A" and b2.kind == "B"


class TestPlanReservedBytes:
    def test_reserved_bytes_tightens_plan(self, grid1, n=32):
        x = _dense_mat(n, 0.5, seed=17)
        a = sp.from_dense(jnp.asarray(x), cap=800)
        A = scatter_to_grid(a, grid1, "A")
        B = scatter_to_grid(a, grid1, "B")
        # the ESC step holds ~5 r-byte records per partial product, so
        # 256 KB plans a few batches and 64 KB left plans about 16
        base = plan_batches(A, B, grid1, per_process_memory=1 << 18)
        tight = plan_batches(
            A, B, grid1, per_process_memory=1 << 18, reserved_bytes=3 << 16
        )
        assert tight.num_batches > base.num_batches
        with pytest.raises(MemoryError):
            plan_batches(
                A, B, grid1, per_process_memory=1 << 18, reserved_bytes=1 << 18
            )


class TestDeviceLoopSingleDevice:
    def test_device_matches_host_on_1x1x1(self, grid1, n=24):
        """Whole device-resident loop == host reference, in-process."""
        x = _dense_mat(n, 0.5, seed=23)
        rr, cc = np.nonzero(x)
        vv = _col_normalize_np(rr, cc, x[rr, cc].astype(np.float64), n)
        a = sp.from_numpy_coo(rr, cc, vv.astype(np.float32), (n, n))
        cfg = MCLConfig(max_iters=4, per_process_memory=1 << 24, max_per_col=8)
        _, hist_d = mcl_iterate(a, grid1, cfg)
        _, hist_h = mcl_iterate_host(a, grid1, cfg)
        assert [h["nnz"] for h in hist_d] == [h["nnz"] for h in hist_h]
        np.testing.assert_allclose(
            [h["chaos"] for h in hist_d], [h["chaos"] for h in hist_h],
            rtol=1e-3, atol=1e-5,
        )


class TestFusedStepCompileCount:
    def test_pow2_caps_hit_jit_cache(self, grid1, n=24):
        """ROADMAP MCL follow-up (b): per-iteration capacity drift must NOT
        recompile the fused step. With pow2-quantized, running-max floored
        capacities (and the local path pinned after iteration 1) a
        4-iteration MCL run traces the fused step at most twice — iteration
        1's scattered operands vs. the reassembled operands of iterations
        2+ — and a repeat run of the same loop traces NOTHING."""
        from repro.core import summa3d

        x = _dense_mat(n, 0.5, seed=23)
        rr, cc = np.nonzero(x)
        vv = _col_normalize_np(rr, cc, x[rr, cc].astype(np.float64), n)
        a = sp.from_numpy_coo(rr, cc, vv.astype(np.float32), (n, n))
        cfg = MCLConfig(max_iters=4, per_process_memory=1 << 24,
                        max_per_col=8, force_num_batches=2)
        t0 = summa3d.TRACE_COUNTS["fused_step"]
        _, hist = mcl_iterate(a, grid1, cfg)
        assert len(hist) == 4, "need a multi-iteration run to prove caching"
        first = summa3d.TRACE_COUNTS["fused_step"] - t0
        assert first <= 2, f"fused step traced {first}x in one MCL run"
        t1 = summa3d.TRACE_COUNTS["fused_step"]
        _, hist2 = mcl_iterate(a, grid1, cfg)
        assert len(hist2) == 4
        repeat = summa3d.TRACE_COUNTS["fused_step"] - t1
        assert repeat == 0, f"repeat run recompiled the fused step {repeat}x"

    def test_unforced_batch_count_pinned(self, grid1, n=24):
        """With memory-driven planning (force_num_batches=None, the default
        config) the batch count is floored at its running max, so a
        sparsifying iterate cannot shrink nb mid-run and re-trace; the
        repeat-run contract holds for the default config too."""
        from repro.core import summa3d

        x = _dense_mat(n, 0.6, seed=29)
        rr, cc = np.nonzero(x)
        vv = _col_normalize_np(rr, cc, x[rr, cc].astype(np.float64), n)
        a = sp.from_numpy_coo(rr, cc, vv.astype(np.float32), (n, n))
        cfg = MCLConfig(max_iters=4, per_process_memory=1 << 17, max_per_col=4)
        _, hist = mcl_iterate(a, grid1, cfg)
        assert len(hist) >= 3
        nbs = [h["batches"] for h in hist]
        assert nbs == sorted(nbs), f"batch count shrank mid-run: {nbs}"
        t0 = summa3d.TRACE_COUNTS["fused_step"]
        mcl_iterate(a, grid1, cfg)
        repeat = summa3d.TRACE_COUNTS["fused_step"] - t0
        assert repeat == 0, f"repeat run recompiled the fused step {repeat}x"
