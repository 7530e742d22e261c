"""Fast-lane tests for structure-aware placement (``core/placement.py``).

Three contracts lock the layer in:

  * Distribution contract — the pluggable ``BLOCK_CYCLIC`` reproduces the
    historical ``fold_block_cyclic`` / ``batching_plan_columns`` math
    bit-for-bit over a (pr, pc, l, nb) sweep, its vectorized column map
    matches the triple-loop reference, and the driver rejects distributions
    the fused step cannot execute.
  * Permutation invariance (property-based, hypothesis with the
    ``repro.testing`` fallback) — permute → multiply → unpermute equals the
    unpermuted run EXACTLY across {plus_times, min_plus, max_times} ×
    {unmasked, strict mask} × {esc, hash} local paths. Values are
    small integers so even plus_times f32 sums are order-exact.
  * Plan ordering on skew (host oracle, no devices) — a degree-spread
    R-MAT plan needs no more batches and no more padded transfer bytes
    than block-cyclic at the same ``per_process_memory``, and strictly
    fewer total padded bytes (the BENCH_graph placement-summary claim).

Plus the rectangular-grid oracle coverage the autotuner's new (pr, pc, 1)
candidates rely on (the 8-device device-parity case lives in
``tests/distributed_cases.py``).
"""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import gen
from repro.core import semiring as sr
from repro.core import sparse as sp
from repro.core.batched import (
    PlanInputs,
    batch_column_map,
    batched_summa3d,
    plan_from_symbolic,
)
from repro.core.grid import make_grid
from repro.core.placement import (
    BLOCK_CYCLIC,
    Distribution,
    Placement,
    compute_placement,
    multiply_placed,
)
from repro.core.specs import PlanFloors, PlanSpec
from repro.core.symbolic import (
    batching_plan_columns,
    fold_block_cyclic,
    host_symbolic_counts,
)
from repro.testing import given, settings, strategies as st
from repro.tune import padded_comm_volume

_GRID1 = None


def grid1():
    """Module-cached 1×1×1 grid (plain function, not a fixture: the
    hypothesis fallback erases test signatures, so property tests cannot
    take pytest fixtures)."""
    global _GRID1
    if _GRID1 is None:
        _GRID1 = make_grid(1, 1, 1)
    return _GRID1


def _rand_int_sparse(n, density, rng, cap=512):
    """Random COO with small-INTEGER f32 values (1..4): any summation order
    is exact in f32, so permuted plus_times products are bit-comparable."""
    m = rng.random((n, n)) < density
    rr, cc = np.nonzero(m)
    vals = rng.integers(1, 5, size=rr.shape[0]).astype(np.float32)
    return sp.from_numpy_coo(rr, cc, vals, (n, n), cap=cap)


# ---------------------------------------------------------------------------
# Distribution contract
# ---------------------------------------------------------------------------
class TestDistributionContract:
    GRID_SWEEP = [(1, 1, 1), (2, 2, 1), (2, 2, 2), (3, 3, 3),
                  (4, 2, 1), (2, 4, 1), (1, 4, 1)]

    def test_fold_reproduces_fold_block_cyclic_bit_for_bit(self):
        rng = np.random.default_rng(0)
        for pr, pc, l in self.GRID_SWEEP:
            for nb in (1, 2, 3, 4):
                n = nb * l * 3  # any width divisible by nb·l
                x = rng.integers(0, 100, size=(pr, pc, l, n))
                np.testing.assert_array_equal(
                    BLOCK_CYCLIC.fold(x, nb, l), fold_block_cyclic(x, nb, l)
                )

    def test_round_batches_reproduces_batching_plan_columns(self):
        for n in (12, 24, 48, 64, 96):
            for l in (1, 2, 4):
                if n % l:
                    continue
                for nb in (1, 2, 3, 5, 7):
                    if nb > n // l:  # finer than the column structure allows
                        with pytest.raises(MemoryError):
                            BLOCK_CYCLIC.round_batches(n, nb, l)
                        continue
                    assert (
                        BLOCK_CYCLIC.round_batches(n, nb, l)
                        == batching_plan_columns(n, nb, l)
                    )

    def test_fold_batch_slices_reference(self):
        rng = np.random.default_rng(1)
        for pr, pc, l in [(1, 1, 1), (2, 2, 2), (4, 2, 1)]:
            for nb in (1, 2, 4):
                wl = nb * 5
                x = rng.integers(0, 9, size=(pr, pc, l, wl))
                got = BLOCK_CYCLIC.fold_batch_slices(x, nb)
                ref = x.reshape(pr, pc, l, nb, wl // nb).sum(axis=-1)
                np.testing.assert_array_equal(got, ref)

    def test_batch_column_map_matches_triple_loop_reference(self):
        def ref(n, pc, l, nb, batch):
            w = n // pc
            wb = w // nb
            wbl = w // (nb * l)
            out = np.zeros((pc, l, wb // l), np.int64)
            for j in range(pc):
                for k in range(l):
                    for c in range(wb // l):
                        d_col = k * (wb // l) + c
                        t, within = d_col // wbl, d_col % wbl
                        out[j, k, c] = j * w + (t * nb + batch) * wbl + within
            return out

        for n, pc, l, nb in [(64, 2, 2, 2), (48, 2, 1, 4), (96, 4, 1, 2),
                             (32, 1, 1, 4), (64, 1, 2, 2)]:
            grid = SimpleNamespace(pc=pc, l=l)
            for batch in range(nb):
                np.testing.assert_array_equal(
                    batch_column_map(n, grid, nb, batch),
                    ref(n, pc, l, nb, batch),
                )
                # every batch covers each of its columns exactly once
                cols = batch_column_map(n, grid, nb, batch).ravel()
                assert len(set(cols.tolist())) == cols.size

    def test_explicit_block_cyclic_spec_plans_identically(self):
        a = gen.erdos_renyi(64, 4.0, seed=2)
        b = gen.erdos_renyi(64, 4.0, seed=3)
        counts = host_symbolic_counts(a, b, (2, 2, 2))
        inputs = PlanInputs.from_host(a, b, (2, 2, 2))
        p0 = plan_from_symbolic(
            counts, inputs, 1 << 30, PlanSpec(local_path="esc"), PlanFloors()
        )
        p1 = plan_from_symbolic(
            counts, inputs, 1 << 30,
            PlanSpec(local_path="esc", distribution=BLOCK_CYCLIC),
            PlanFloors(),
        )
        assert (p0.num_batches, p0.caps, p0.sel_cap, p0.mask_sel_cap) == (
            p1.num_batches, p1.caps, p1.sel_cap, p1.mask_sel_cap
        )
        assert (p0.local_path, p0.total_flops, p0.max_unmerged_nnz) == (
            p1.local_path, p1.total_flops, p1.max_unmerged_nnz
        )
        np.testing.assert_array_equal(p0.per_batch_flops, p1.per_batch_flops)

    def test_driver_rejects_non_block_cyclic_distribution(self):
        class RowwiseDistribution(Distribution):
            name = "rowwise"

        grid = grid1()
        rng = np.random.default_rng(4)
        a = _rand_int_sparse(16, 0.2, rng)
        from repro.core.distsparse import scatter_to_grid

        A = scatter_to_grid(a, grid, "A")
        B = scatter_to_grid(a, grid, "B")
        with pytest.raises(ValueError, match="block-cyclic"):
            batched_summa3d(
                A, B, grid, 1 << 22, lambda bi, c, cm: None,
                spec=PlanSpec(distribution=RowwiseDistribution()),
            )

    def test_driver_rejects_strategy_string_placement(self):
        grid = grid1()
        rng = np.random.default_rng(5)
        a = _rand_int_sparse(16, 0.2, rng)
        from repro.core.distsparse import scatter_to_grid

        A = scatter_to_grid(a, grid, "A")
        B = scatter_to_grid(a, grid, "B")
        with pytest.raises(ValueError, match="multiply_placed"):
            batched_summa3d(
                A, B, grid, 1 << 22, lambda bi, c, cm: None,
                spec=PlanSpec(placement="degree"),
            )


# ---------------------------------------------------------------------------
# Placement permutations
# ---------------------------------------------------------------------------
class TestPlacementPermutations:
    def test_identity_placement_is_identity(self):
        p = Placement.identity(8, 12, 16)
        assert p.is_identity
        np.testing.assert_array_equal(
            p.original_cols(np.arange(16)), np.arange(16)
        )

    def test_strategies_produce_bijections_with_exact_inverses(self):
        a = gen.symmetrized(gen.rmat(5, edge_factor=4, seed=1))
        for strategy in ("degree", "rcm"):
            p = compute_placement(a, a, strategy)
            for perm, inv in [(p.row_perm, p.row_inv), (p.k_perm, p.k_inv),
                              (p.col_perm, p.col_inv)]:
                n = perm.shape[0]
                assert sorted(perm.tolist()) == list(range(n))
                np.testing.assert_array_equal(inv[perm], np.arange(n))

    def test_apply_then_invert_roundtrips_structure(self):
        rng = np.random.default_rng(6)
        a = _rand_int_sparse(32, 0.2, rng)
        b = _rand_int_sparse(32, 0.2, rng)
        p = compute_placement(a, b, "degree")
        ap = p.apply_a(a)
        nnz = int(ap.nnz)
        rows = p.original_rows(np.asarray(ap.rows[:nnz]))
        cols = p.k_inv[np.asarray(ap.cols[:nnz])]
        got = np.zeros((32, 32), np.float32)
        got[rows, cols] = np.asarray(ap.vals[:nnz])
        want = np.zeros((32, 32), np.float32)
        want[np.asarray(a.rows[: a.nnz]), np.asarray(a.cols[: a.nnz])] = (
            np.asarray(a.vals[: a.nnz])
        )
        np.testing.assert_array_equal(got, want)

    def test_degree_spreads_hubs_across_aligned_blocks(self):
        """R-MAT hubs concentrate at low indices; after the degree spread
        every aligned half/quarter holds a near-equal share of the nnz —
        the property that lowers the fold maxima the caps derive from."""
        a = gen.symmetrized(gen.rmat(6, edge_factor=8, seed=5))
        n = a.shape[1]
        colc = np.bincount(np.asarray(a.cols[: a.nnz]), minlength=n)
        p = compute_placement(a, a, "degree")
        placed = np.zeros(n, np.int64)
        placed[p.col_perm] = colc
        for blocks in (2, 4):
            before = colc.reshape(blocks, -1).sum(axis=1)
            after = placed.reshape(blocks, -1).sum(axis=1)
            assert after.max() < before.max()

    def test_rcm_requires_square_operands(self):
        a = gen.erdos_renyi(16, 2.0, seed=0, square=False, ncols=32)
        with pytest.raises(ValueError, match="square"):
            compute_placement(a, gen.erdos_renyi(32, 2.0, seed=1), "rcm")

    def test_unknown_strategy_raises(self):
        a = gen.erdos_renyi(16, 2.0, seed=0)
        with pytest.raises(ValueError, match="unknown placement strategy"):
            compute_placement(a, a, "hypergraph")


# ---------------------------------------------------------------------------
# Property-based permutation invariance (the tentpole guarantee)
# ---------------------------------------------------------------------------
_SEMIRINGS = {
    "plus_times": sr.PLUS_TIMES,
    "min_plus": sr.MIN_PLUS,
    "max_times": sr.MAX_TIMES,
}


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=10_000),
    semiring=st.sampled_from(sorted(_SEMIRINGS)),
    masked=st.booleans(),
    path=st.sampled_from(["esc", "hash"]),
    strategy=st.sampled_from(["degree", "rcm"]),
)
def test_permute_multiply_unpermute_is_exact(
    seed, semiring, masked, path, strategy
):
    grid = grid1()
    rng = np.random.default_rng(seed)
    n = 16
    a = _rand_int_sparse(n, 0.25, rng)
    b = _rand_int_sparse(n, 0.25, rng)
    mask = _rand_int_sparse(n, 0.3, rng) if masked else None
    spec = PlanSpec(local_path=path, force_num_batches=2)
    kwargs = dict(semiring=_SEMIRINGS[semiring], spec=spec, mask=mask)
    base = multiply_placed(
        a, b, grid, 1 << 22, placement=Placement.identity(n, n, n), **kwargs
    )
    placed = multiply_placed(a, b, grid, 1 << 22, strategy=strategy, **kwargs)
    assert placed.placement.strategy == strategy
    fill = np.inf if semiring == "min_plus" else 0.0
    np.testing.assert_array_equal(
        placed.to_dense(fill), base.to_dense(fill),
        err_msg=f"{semiring}/{path}/{strategy} masked={masked} seed={seed}",
    )


def test_placed_plus_times_matches_dense_reference():
    """Anchor the invariance suite: the identity-placement run itself is
    the true product (not merely self-consistent)."""
    grid = grid1()
    rng = np.random.default_rng(7)
    n = 16
    a = _rand_int_sparse(n, 0.25, rng)
    b = _rand_int_sparse(n, 0.25, rng)
    placed = multiply_placed(
        a, b, grid, 1 << 22, strategy="degree",
        spec=PlanSpec(local_path="esc", force_num_batches=2),
    )
    xa = np.zeros((n, n), np.float32)
    xa[np.asarray(a.rows[: a.nnz]), np.asarray(a.cols[: a.nnz])] = (
        np.asarray(a.vals[: a.nnz])
    )
    xb = np.zeros((n, n), np.float32)
    xb[np.asarray(b.rows[: b.nnz]), np.asarray(b.cols[: b.nnz])] = (
        np.asarray(b.vals[: b.nnz])
    )
    np.testing.assert_array_equal(placed.to_dense(), xa @ xb)


# ---------------------------------------------------------------------------
# Plan ordering on R-MAT skew (host oracle — no devices)
# ---------------------------------------------------------------------------
class TestPlacementPlanOrdering:
    GRID_SHAPE = (2, 2, 2)
    R_BYTES = 12

    def _plan(self, a, b, ppm):
        counts = host_symbolic_counts(a, b, self.GRID_SHAPE)
        inputs = PlanInputs.from_host(a, b, self.GRID_SHAPE)
        return plan_from_symbolic(
            counts, inputs, ppm, PlanSpec(local_path="esc"), PlanFloors()
        )

    def test_degree_rmat_plan_never_worse_and_strictly_fewer_padded_bytes(
        self,
    ):
        a = gen.symmetrized(gen.rmat(7, edge_factor=8, seed=5))
        # the probe_memory_budget math, host-side: inputs + 1/3 of the
        # probed unmerged output, so the block-cyclic plan must batch
        probe = self._plan(a, a, 1 << 30)
        ppm = self.R_BYTES * 2 * int(a.nnz) + max(
            self.R_BYTES * probe.max_unmerged_nnz // 3, 256
        )
        base = self._plan(a, a, ppm)
        placement = compute_placement(a, a, "degree")
        placed = self._plan(placement.apply_a(a), placement.apply_b(a), ppm)
        v_base = padded_comm_volume(base, self.GRID_SHAPE, self.R_BYTES)
        v_placed = padded_comm_volume(placed, self.GRID_SHAPE, self.R_BYTES)
        assert base.num_batches > 1  # the budget actually forces batching
        assert placed.num_batches <= base.num_batches
        assert v_placed.all_to_all_bytes <= v_base.all_to_all_bytes
        assert v_placed.gather_bytes <= v_base.gather_bytes
        assert v_placed.total_bytes < v_base.total_bytes

    def test_padded_volume_terms(self):
        a = gen.erdos_renyi(64, 4.0, seed=9)
        plan = self._plan(a, a, 1 << 30)
        v = padded_comm_volume(plan, self.GRID_SHAPE, self.R_BYTES)
        pr, _, l = self.GRID_SHAPE
        nb = plan.num_batches
        assert v.all_to_all_bytes == (
            nb * self.R_BYTES * plan.caps.piece_cap * (l - 1)
        )
        assert v.gather_bytes == nb * self.R_BYTES * plan.sel_cap * (pr - 1)
        assert v.total_bytes == v.all_to_all_bytes + v.gather_bytes
        # single-process grids move nothing
        v1 = padded_comm_volume(plan, (1, 1, 1), self.R_BYTES)
        assert v1.total_bytes == 0


# ---------------------------------------------------------------------------
# Rectangular-grid host oracle (autotuner candidate coverage)
# ---------------------------------------------------------------------------
class TestRectangularOracle:
    @pytest.mark.parametrize("grid_shape", [(4, 2, 1), (2, 4, 1), (1, 4, 1)])
    def test_rectangular_percol_matches_dense_reference(self, grid_shape):
        a = gen.erdos_renyi(64, 4.0, seed=11)
        b = gen.erdos_renyi(64, 4.0, seed=12)
        pr, pc, l = grid_shape
        counts = host_symbolic_counts(a, b, grid_shape)
        # per-(row block, output column) flops from the dense patterns
        pa = np.zeros((64, 64), bool)
        pa[np.asarray(a.rows[: a.nnz]), np.asarray(a.cols[: a.nnz])] = True
        pb = np.zeros((64, 64), bool)
        pb[np.asarray(b.rows[: b.nnz]), np.asarray(b.cols[: b.nnz])] = True
        tn = 64 // pc
        for i in range(pr):
            a_colc = pa[i * (64 // pr):(i + 1) * (64 // pr)].sum(axis=0)
            want = a_colc @ pb  # flops per output column for row block i
            got = np.concatenate([counts.percol[i, j, 0] for j in range(pc)])
            np.testing.assert_array_equal(got, want)
        assert counts.percol.shape == (pr, pc, l, tn)

    def test_rectangular_multi_layer_rejected(self):
        a = gen.erdos_renyi(64, 4.0, seed=13)
        with pytest.raises(AssertionError):
            host_symbolic_counts(a, a, (4, 2, 2))
        with pytest.raises(AssertionError):
            make_grid(4, 2, 2)
