"""Fast-lane tests for the masked (filtered-semiring) SpGEMM path (§V-B).

Single-device (1x1x1) coverage of the masked pipeline: the symbolic mask
counts against a dense reference, the masked plan's capacity ordering
(incl. the empty-mask and full-mask edges), the fused multiply under strict
and complement masks across batch counts, and hash/ESC parity behind the
plan switch. The 8-device R-MAT parity cases (triangle counting, overlap
detection) live in ``tests/app_cases.py`` (slow lane).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import sparse as sp
from repro.core.batched import batched_summa3d, plan_batches, symbolic3d_counts
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.symbolic import rup_pow2
from repro.sparse_apps.mcl import _sparse_batch_to_global


@pytest.fixture(scope="module")
def grid1():
    return make_grid(1, 1, 1)


def _rand_sparse(n, density, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.5, 1.0, (n, n)).astype(np.float32)
    return np.where(rng.random((n, n)) < density, x, 0.0).astype(np.float32)


def _mask_coo(mask_dense):
    m, n = mask_dense.shape
    mr, mc = np.nonzero(mask_dense)
    return sp.from_numpy_coo(
        mr, mc, np.ones(len(mr), np.float32), (m, n), cap=max(len(mr), 8)
    )


def _operands(grid, n=32, seed=0):
    xa = _rand_sparse(n, 0.3, seed)
    xb = _rand_sparse(n, 0.3, seed + 1)
    A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024), grid, "A")
    B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024), grid, "B")
    return xa, xb, A, B


def _multiply(A, B, grid, nb, mask=None, complement=False, local_path="auto"):
    n = B.shape[1]
    got = np.zeros((A.shape[0], n), np.float32)

    def consumer(bi, c, cm):
        rr, cc, vv = _sparse_batch_to_global(c, cm)
        got[rr, cc] += vv

    res = batched_summa3d(
        A, B, grid, per_process_memory=1 << 26, consumer=consumer,
        path="sparse", force_num_batches=nb, mask=mask,
        mask_complement=complement, local_path=local_path,
    )
    return got, res


class TestMaskedSymbolicCounts:
    def test_mask_colcounts_exact(self, grid1, n=32):
        """The emitted mask counts are EXACT per-(tile, column) nnz — the
        guarantee that sizes the mask-slice selection without overflow."""
        xa, xb, A, B = _operands(grid1, n)
        mask_dense = np.random.default_rng(7).random((n, n)) < 0.2
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        counts = symbolic3d_counts(A, B, grid1, mask=M)
        np.testing.assert_array_equal(
            counts.mask_colcounts[0, 0, 0], mask_dense.sum(axis=0)
        )

    def test_masked_bounds_sound_vs_dense_reference(self, grid1, n=32):
        """The masked plan capacities bound the true masked product: running
        the multiply at plan capacities never overflows (zero retries) and
        the dense-reference masked product is reproduced exactly."""
        xa, xb, A, B = _operands(grid1, n)
        mask_dense = np.random.default_rng(11).random((n, n)) < 0.15
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        for nb in (1, 2, 4):
            got, res = _multiply(A, B, grid1, nb, mask=M)
            assert res.num_retries == 0
            np.testing.assert_allclose(
                got, (xa @ xb) * mask_dense, rtol=1e-4, atol=1e-5
            )

    def test_masked_caps_below_unmasked(self, grid1, n=32):
        """A sparse strict mask must shrink the planned D/C capacities (the
        §V-B memory win the batch plan is supposed to realize)."""
        _, _, A, B = _operands(grid1, n)
        mask_dense = np.random.default_rng(13).random((n, n)) < 0.1
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        pm = plan_batches(A, B, grid1, per_process_memory=1 << 26, mask=M)
        pu = plan_batches(A, B, grid1, per_process_memory=1 << 26)
        assert pm.caps.d_cap < pu.caps.d_cap
        assert pm.caps.c_cap < pu.caps.c_cap
        assert pm.caps.piece_cap <= pu.caps.piece_cap
        assert pm.max_unmerged_nnz < pu.max_unmerged_nnz
        assert pm.mask_sel_cap > 0

    def test_masked_batch_count_below_unmasked(self, grid1, n=32):
        """Under a budget that forces the unmasked multiply to batch, the
        masked plan needs strictly fewer batches (same shared budget math
        the graph bench and R-MAT slow case assert against)."""
        from repro.core.batched import probe_memory_budget

        _, _, A, B = _operands(grid1, n)
        mask_dense = np.random.default_rng(17).random((n, n)) < 0.05
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        ppm = probe_memory_budget(A, B, grid1)
        pu = plan_batches(A, B, grid1, per_process_memory=ppm)
        pm = plan_batches(A, B, grid1, per_process_memory=ppm, mask=M)
        assert pu.num_batches > 1
        assert pm.num_batches < pu.num_batches


class TestMaskedMultiply:
    @pytest.mark.parametrize("complement", [False, True])
    @pytest.mark.parametrize("nb", [1, 2])
    def test_matches_dense_reference(self, grid1, complement, nb, n=32):
        xa, xb, A, B = _operands(grid1, n)
        mask_dense = np.random.default_rng(19).random((n, n)) < 0.2
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        got, res = _multiply(A, B, grid1, nb, mask=M, complement=complement)
        keep = ~mask_dense if complement else mask_dense
        np.testing.assert_allclose(got, (xa @ xb) * keep, rtol=1e-4, atol=1e-5)
        assert res.num_retries == 0

    def test_empty_mask_yields_empty_product(self, grid1, n=32):
        xa, xb, A, B = _operands(grid1, n)
        M = scatter_to_grid(_mask_coo(np.zeros((n, n), bool)), grid1, "C")
        got, res = _multiply(A, B, grid1, 2, mask=M)
        np.testing.assert_array_equal(got, np.zeros((n, n), np.float32))
        assert res.num_retries == 0
        # the plan collapsed to the floor capacities, not the full product
        pu = plan_batches(A, B, grid1, per_process_memory=1 << 26)
        assert res.plan.caps.d_cap < pu.caps.d_cap

    def test_full_mask_equals_unmasked(self, grid1, n=32):
        xa, xb, A, B = _operands(grid1, n)
        M = scatter_to_grid(_mask_coo(np.ones((n, n), bool)), grid1, "C")
        got_m, _ = _multiply(A, B, grid1, 2, mask=M)
        got_u, _ = _multiply(A, B, grid1, 2)
        np.testing.assert_allclose(got_m, got_u, rtol=1e-6)
        np.testing.assert_allclose(got_m, xa @ xb, rtol=1e-4, atol=1e-5)

    def test_empty_complement_mask_equals_unmasked(self, grid1, n=32):
        xa, xb, A, B = _operands(grid1, n)
        M = scatter_to_grid(_mask_coo(np.zeros((n, n), bool)), grid1, "C")
        got, _ = _multiply(A, B, grid1, 2, mask=M, complement=True)
        np.testing.assert_allclose(got, xa @ xb, rtol=1e-4, atol=1e-5)

    def test_hash_matches_esc_under_mask(self, grid1, n=32):
        """The masked filter is applied identically by the ESC packed-key
        intersect and the hash path's reject-at-insert probe."""
        xa, xb, A, B = _operands(grid1, n, seed=29)
        mask_dense = np.random.default_rng(23).random((n, n)) < 0.2
        M = scatter_to_grid(_mask_coo(mask_dense), grid1, "C")
        got_esc, _ = _multiply(A, B, grid1, 2, mask=M, local_path="esc")
        got_hash, res = _multiply(A, B, grid1, 2, mask=M, local_path="hash")
        assert res.local_path == "hash"
        np.testing.assert_allclose(got_hash, got_esc, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(got_esc, (xa @ xb) * mask_dense,
                                   rtol=1e-4, atol=1e-5)


class TestPow2Rounding:
    def test_rup_pow2(self):
        assert [rup_pow2(x) for x in (1, 2, 3, 8, 9, 1000)] == [
            1, 2, 4, 8, 16, 1024,
        ]

    def test_caps_pow2_and_floor(self, grid1, n=32):
        from repro.core.summa3d import BatchCaps

        _, _, A, B = _operands(grid1, n)
        p = plan_batches(A, B, grid1, per_process_memory=1 << 26,
                         caps_pow2=True)
        for c in (p.caps.flops_cap, p.caps.d_cap, p.caps.piece_cap,
                  p.caps.c_cap):
            assert c == rup_pow2(c)  # powers of two
        floor = BatchCaps(1 << 20, 1 << 20, 1 << 20, 1 << 20)
        pf = plan_batches(A, B, grid1, per_process_memory=1 << 26,
                          caps_pow2=True, caps_floor=floor,
                          sel_cap_floor=12345)
        assert pf.caps == floor
        assert pf.sel_cap >= 12345
