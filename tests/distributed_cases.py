"""Distributed SUMMA correctness cases — run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (see test_distributed.py).

Each case asserts against the dense reference. Invoked as:
    python tests/distributed_cases.py <case_name>
"""
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import gen
from repro.core import semiring as sr
from repro.core import sparse as sp
from repro.core.batched import (
    batch_column_map,
    batched_summa3d,
    plan_batches,
    symbolic3d,
)
from repro.core.distsparse import DistSparse, gather_to_global, scatter_to_grid
from repro.core.grid import make_grid
from repro.core.specs import PlanSpec
from repro.core.summa3d import BatchCaps, summa3d_dense_step, summa3d_sparse_step
from repro.core.symbolic import esc_step_bytes


def _rand_square(n, density, seed, cap_slack=2.0):
    rng = np.random.default_rng(seed)
    x = rng.random((n, n)).astype(np.float32)
    mask = rng.random((n, n)) < density
    x = np.where(mask, x + 0.1, 0.0).astype(np.float32)
    return x, sp.from_dense(jnp.asarray(x), cap=int(mask.sum() * cap_slack) + 8)


def reconstruct_dense_c(c_tiles: np.ndarray, grid, col_map: np.ndarray, m: int, n: int):
    """Assemble global dense C (m × n) from stacked (pr,pc,l,tm,wbl) tiles."""
    pr, pc, l, tm, wbl = c_tiles.shape
    out = np.zeros((m, n), np.float32)
    for i in range(pr):
        for j in range(pc):
            for k in range(l):
                out[i * tm : (i + 1) * tm, col_map[j, k]] = c_tiles[i, j, k]
    return out


def reconstruct_sparse_c(c: DistSparse, grid, col_map: np.ndarray, m: int, n: int):
    pr, pc, l = c.grid_shape
    tm, wbl = c.tile_shape
    out = np.zeros((m, n), np.float32)
    R, C, V, N = (np.asarray(c.rows), np.asarray(c.cols), np.asarray(c.vals),
                  np.asarray(c.nnz))
    for i in range(pr):
        for j in range(pc):
            for k in range(l):
                cnt = int(N[i, j, k])
                gr = i * tm + R[i, j, k, :cnt]
                gc = col_map[j, k][C[i, j, k, :cnt]]
                np.add.at(out, (gr, gc), V[i, j, k, :cnt])
    return out


def case_scatter_gather_roundtrip():
    grid = make_grid(2, 2, 2)
    for kind in ("A", "B"):
        x, a = _rand_square(32, 0.2, seed=3)
        d = scatter_to_grid(a, grid, kind)
        back = gather_to_global(d)
        np.testing.assert_allclose(np.asarray(back.to_dense()), x, rtol=1e-6)
    print("OK scatter_gather_roundtrip")


def case_dense_path_full_multiply():
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.25, seed=5)
    xb, b = _rand_square(n, 0.25, seed=7)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    c_tiles = np.asarray(summa3d_dense_step(A, B, grid))
    col_map = batch_column_map(n, grid, 1, 0)
    got = reconstruct_dense_c(c_tiles, grid, col_map, n, n)
    np.testing.assert_allclose(got, xa @ xb, rtol=1e-4, atol=1e-5)
    print("OK dense_path_full_multiply")


def case_sparse_path_full_multiply():
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.25, seed=11)
    xb, b = _rand_square(n, 0.25, seed=13)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    caps = BatchCaps(flops_cap=8192, d_cap=4096, piece_cap=2048, c_cap=2048)
    c, ovf = summa3d_sparse_step(A, B, grid, caps)
    assert int(ovf) == 0, f"overflow {int(ovf)}"
    col_map = batch_column_map(n, grid, 1, 0)
    got = reconstruct_sparse_c(c, grid, col_map, n, n)
    np.testing.assert_allclose(got, xa @ xb, rtol=1e-4, atol=1e-5)
    print("OK sparse_path_full_multiply")


def case_symbolic_flops_exact():
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.3, seed=17)
    xb, b = _rand_square(n, 0.3, seed=19)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    percol = symbolic3d(A, B, grid)  # (pr,pc,l,tn_b)
    total = int(percol.sum())
    expect = int(((xa != 0).sum(0) * (xb != 0).sum(1)).sum())
    assert total == expect, (total, expect)
    print("OK symbolic_flops_exact")


def case_plan_batches_bounds():
    grid = make_grid(2, 2, 2)
    n = 32
    _, a = _rand_square(n, 0.3, seed=23)
    _, b = _rand_square(n, 0.3, seed=29)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    # generous memory -> 1 batch
    plan1 = plan_batches(A, B, grid, per_process_memory=1 << 30)
    assert plan1.num_batches == 1, plan1
    # tight memory -> multiple batches, Alg3 count >= Eq2 bound
    r = 12
    need = r * (int(np.asarray(A.nnz).max()) + int(np.asarray(B.nnz).max()))
    # ~3 batches: a third of the ESC step's bytes at b = 1
    step = esc_step_bytes(PlanSpec().slack * plan1.max_unmerged_nnz, r)
    budget = need + max(step // 3, 1)
    plan2 = plan_batches(A, B, grid, per_process_memory=budget)
    assert plan2.num_batches > 1
    if plan2.lower_bound > 0:
        assert plan2.num_batches >= plan2.lower_bound, plan2
    assert plan2.per_batch_flops.sum() == plan2.total_flops
    print("OK plan_batches_bounds")


def _run_batched(n, density, nb_force, l, path, seed=31):
    grid = make_grid(2, 2, l)
    xa, a = _rand_square(n, density, seed=seed)
    xb, b = _rand_square(n, density, seed=seed + 1)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    acc = np.zeros((n, n), np.float32)

    def consumer(bi, c_batch, col_map):
        if path == "dense":
            acc_part = reconstruct_dense_c(np.asarray(c_batch), grid, col_map, n, n)
        else:
            acc_part = reconstruct_sparse_c(c_batch, grid, col_map, n, n)
        acc[:] += acc_part
        return float(acc_part.sum())

    res = batched_summa3d(
        A, B, grid, per_process_memory=1 << 30, consumer=consumer, path=path,
        force_num_batches=nb_force,
    )
    np.testing.assert_allclose(acc, xa @ xb, rtol=1e-4, atol=1e-5)
    return res


def case_batched_dense_invariance():
    for nb in (1, 2, 4):
        _run_batched(32, 0.25, nb, l=2, path="dense")
    print("OK batched_dense_invariance")


def case_batched_sparse_invariance():
    for nb in (1, 2, 4):
        _run_batched(32, 0.25, nb, l=2, path="sparse")
    print("OK batched_sparse_invariance")


def case_layer1_grid():
    # l=1 degenerates to 2D SUMMA (paper Alg. 1); 2x2x1 grid on 4 devices
    for path in ("dense", "sparse"):
        _run_batched(32, 0.3, 2, l=1, path=path, seed=41)
    print("OK layer1_grid")


def case_symbolic_driven_batching():
    """End-to-end: tight memory budget forces b>1 via the symbolic step."""
    grid = make_grid(2, 2, 2)
    n = 64
    xa, a = _rand_square(n, 0.15, seed=43)
    xb, b = _rand_square(n, 0.15, seed=47)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    plan_free = plan_batches(A, B, grid, per_process_memory=1 << 30)
    r = 12
    need = r * (int(np.asarray(A.nnz).max()) + int(np.asarray(B.nnz).max()))
    budget = need + max(r * plan_free.max_unmerged_nnz // 3, 1)
    acc = np.zeros((n, n), np.float32)

    def consumer(bi, c_batch, col_map):
        acc[:] += reconstruct_sparse_c(c_batch, grid, col_map, n, n)

    res = batched_summa3d(
        A, B, grid, per_process_memory=budget, consumer=consumer, path="sparse"
    )
    assert res.plan.num_batches > 1, res.plan
    np.testing.assert_allclose(acc, xa @ xb, rtol=1e-4, atol=1e-5)
    print(f"OK symbolic_driven_batching (b={res.plan.num_batches})")


def case_semiring_or_and():
    """Boolean structure product over the or_and semiring (symbolic use)."""
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.2, seed=53)
    xb, b = _rand_square(n, 0.2, seed=59)
    # boolean-ize values
    a = sp.SparseCOO(a.rows, a.cols, jnp.where(a.valid_mask(), 1.0, 0.0), a.nnz, a.shape)
    b = sp.SparseCOO(b.rows, b.cols, jnp.where(b.valid_mask(), 1.0, 0.0), b.nnz, b.shape)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    caps = BatchCaps(flops_cap=8192, d_cap=4096, piece_cap=2048, c_cap=2048)
    c, ovf = summa3d_sparse_step(A, B, grid, caps, semiring=sr.OR_AND)
    assert int(ovf) == 0
    col_map = batch_column_map(n, grid, 1, 0)
    got = reconstruct_sparse_c(c, grid, col_map, n, n)
    expect = (((xa != 0).astype(np.float32) @ (xb != 0)) > 0).astype(np.float32)
    np.testing.assert_allclose(got, expect)
    print("OK semiring_or_and")


def case_overflow_retry():
    """Tiny slack must trigger the retry path yet still converge."""
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.4, seed=61)
    xb, b = _rand_square(n, 0.4, seed=67)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    acc = np.zeros((n, n), np.float32)

    def consumer(bi, c_batch, col_map):
        acc[:] += reconstruct_sparse_c(c_batch, grid, col_map, n, n)

    res = batched_summa3d(
        A, B, grid, per_process_memory=1 << 30, consumer=consumer, path="sparse",
        slack=0.05, force_num_batches=2, max_retries=8,
    )
    np.testing.assert_allclose(acc, xa @ xb, rtol=1e-4, atol=1e-5)
    assert res.num_retries > 0
    print(f"OK overflow_retry (retries={res.num_retries})")


def _collect_batches(path):
    """Consumer capturing each batch's raw tiles for exact cross-driver
    comparison (pipelined vs serial must be the same program)."""
    store = {}

    def consumer(bi, c_batch, col_map):
        if path == "dense":
            store[bi] = (np.asarray(c_batch),)
        else:
            store[bi] = (
                np.asarray(c_batch.rows), np.asarray(c_batch.cols),
                np.asarray(c_batch.vals), np.asarray(c_batch.nnz),
            )
        return bi

    return store, consumer


def _run_driver_pair(A, B, grid, path, semiring, nb, **kw):
    """Run pipelined + serial drivers, assert identical per-batch output."""
    stores = {}
    for pipelined in (True, False):
        store, consumer = _collect_batches(path)
        res = batched_summa3d(
            A, B, grid, per_process_memory=1 << 30, consumer=consumer,
            path=path, semiring=semiring, force_num_batches=nb,
            pipelined=pipelined, **kw,
        )
        assert res.consumed == list(range(res.plan.num_batches))
        stores[pipelined] = store
    assert stores[True].keys() == stores[False].keys()
    for bi in stores[True]:
        for x, y in zip(stores[True][bi], stores[False][bi]):
            np.testing.assert_array_equal(x, y)
    return stores[True]


def case_pipelined_serial_parity():
    """Pipelined scheduler == serial scheduler, batch for batch, over
    {PLUS_TIMES, MIN_PLUS} x {sparse, dense path} x {1, multi}-batch plans
    (dense path requires a sum monoid, so MIN_PLUS runs sparse-only)."""
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.25, seed=83)
    xb, b = _rand_square(n, 0.25, seed=89)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    for nb in (1, 4):
        for path in ("sparse", "dense"):
            batches = _run_driver_pair(A, B, grid, path, sr.PLUS_TIMES, nb)
            # PLUS_TIMES also checks against the dense reference
            acc = np.zeros((n, n), np.float32)
            for bi, tiles in batches.items():
                col_map = batch_column_map(n, grid, nb, bi)
                if path == "dense":
                    acc += reconstruct_dense_c(tiles[0], grid, col_map, n, n)
                else:
                    c = DistSparse(
                        rows=tiles[0], cols=tiles[1], vals=tiles[2],
                        nnz=tiles[3], shape=(n, n // nb),
                        tile_shape=(n // 2, n // 2 // nb // 2),
                        grid_shape=(2, 2, 2), kind="C",
                    )
                    acc += reconstruct_sparse_c(c, grid, col_map, n, n)
            np.testing.assert_allclose(acc, xa @ xb, rtol=1e-4, atol=1e-5)
        _run_driver_pair(A, B, grid, "sparse", sr.MIN_PLUS, nb)
    # MIN_PLUS correctness vs the tropical dense reference (single batch)
    batches = _run_driver_pair(A, B, grid, "sparse", sr.MIN_PLUS, 1)
    ai = np.where(xa != 0, xa, np.inf)
    bi_ = np.where(xb != 0, xb, np.inf)
    ref = (ai[:, :, None] + bi_[None, :, :]).min(axis=1)
    got = np.full((n, n), np.inf, np.float32)
    col_map = batch_column_map(n, grid, 1, 0)
    rows, cols, vals, nnzs = batches[0]
    tm, wbl = n // 2, n // 4
    for i in range(2):
        for j in range(2):
            for k in range(2):
                cnt = int(nnzs[i, j, k])
                gr = i * tm + rows[i, j, k, :cnt]
                gc = col_map[j, k][cols[i, j, k, :cnt]]
                got[gr, gc] = vals[i, j, k, :cnt]
    finite = np.isfinite(ref)
    np.testing.assert_allclose(got[finite], ref[finite], rtol=1e-5, atol=1e-6)
    assert not np.isfinite(got[~finite]).any()
    print("OK pipelined_serial_parity")


def case_pipelined_overflow_retry():
    """Beaten capacities in the pipelined schedule must drop to the
    synchronous retry loop and still converge — on both local-multiply
    engines — and stay batch-identical to the serial schedule."""
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.4, seed=101)
    xb, b = _rand_square(n, 0.4, seed=103)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    for local_path in ("esc", "hash"):
        acc = np.zeros((n, n), np.float32)

        def consumer(bi, c_batch, col_map, acc=acc):
            acc += reconstruct_sparse_c(c_batch, grid, col_map, n, n)

        res = batched_summa3d(
            A, B, grid, per_process_memory=1 << 30, consumer=consumer,
            path="sparse", slack=0.05, force_num_batches=2, max_retries=8,
            pipelined=True, local_path=local_path,
        )
        np.testing.assert_allclose(acc, xa @ xb, rtol=1e-4, atol=1e-4)
        assert res.local_path == local_path, (res.local_path, local_path)
        assert res.num_retries > 0, f"{local_path} hit no retries"
    _run_driver_pair(
        A, B, grid, "sparse", sr.PLUS_TIMES, 2, slack=0.05, max_retries=8
    )
    print(f"OK pipelined_overflow_retry (retries={res.num_retries})")


def case_rectangular_aat():
    """AA^T on a kmer-like rectangular matrix (paper §V-G, BELLA use case)."""
    grid = make_grid(2, 2, 2)
    nseqs, nkmers = 32, 64
    a = gen.kmer_like(nseqs, nkmers, 4, seed=71)
    at = a.transpose().sort_rowmajor()
    xa = np.asarray(a.to_dense())
    A = scatter_to_grid(a, grid, "A")
    Bt = scatter_to_grid(at, grid, "B")
    caps = BatchCaps(flops_cap=8192, d_cap=4096, piece_cap=2048, c_cap=2048)
    c, ovf = summa3d_sparse_step(A, Bt, grid, caps)
    assert int(ovf) == 0
    col_map = batch_column_map(nseqs, grid, 1, 0)
    got = reconstruct_sparse_c(c, grid, col_map, nseqs, nseqs)
    np.testing.assert_allclose(got, xa @ xa.T, rtol=1e-4, atol=1e-5)
    print("OK rectangular_aat")




def case_ring_schedule_matches():
    """Cannon ring schedule == allgather schedule == dense reference
    (paper-faithful memory-constrained broadcast realization)."""
    grid = make_grid(2, 2, 2)
    n = 32
    xa, a = _rand_square(n, 0.3, seed=77)
    xb, b = _rand_square(n, 0.3, seed=79)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    col_map = batch_column_map(n, grid, 1, 0)
    got_ag = reconstruct_dense_c(
        np.asarray(summa3d_dense_step(A, B, grid)), grid, col_map, n, n
    )
    got_ring = reconstruct_dense_c(
        np.asarray(summa3d_dense_step(A, B, grid, schedule="ring")),
        grid, col_map, n, n,
    )
    np.testing.assert_allclose(got_ring, got_ag, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_ring, xa @ xb, rtol=1e-4, atol=1e-5)
    # also on an l=1 grid (pure 2D Cannon)
    grid1 = make_grid(2, 2, 1)
    A1 = scatter_to_grid(a, grid1, "A")
    B1 = scatter_to_grid(b, grid1, "B")
    col1 = batch_column_map(n, grid1, 1, 0)
    got1 = reconstruct_dense_c(
        np.asarray(summa3d_dense_step(A1, B1, grid1, schedule="ring")),
        grid1, col1, n, n,
    )
    np.testing.assert_allclose(got1, xa @ xb, rtol=1e-4, atol=1e-5)
    print("OK ring_schedule_matches")


def case_tune_oracle_parity():
    """The autotuner's host symbolic oracle reproduces the distributed
    symbolic pass BIT-FOR-BIT on a real 2×2×2 grid — counts, the derived
    plan (capacities, batch count, decided local path), and the
    ``PlanInputs.from_host`` capacities vs an actual default scatter — for
    both the unmasked and masked formulations. This is what licenses
    ``repro.tune`` to price candidate grids without scattering anything."""
    from repro.core.batched import PlanInputs, plan_from_symbolic, \
        symbolic3d_counts
    from repro.core.specs import PlanFloors, PlanSpec
    from repro.core.symbolic import host_symbolic_counts

    grid = make_grid(2, 2, 2)
    a = gen.rmat(6, edge_factor=8, seed=5)
    b = gen.rmat(6, edge_factor=8, seed=6)
    mask = gen.erdos_renyi(64, 4.0, seed=7)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(b, grid, "B")
    M = scatter_to_grid(mask, grid, "C")

    for m_host, m_dev in ((None, None), (mask, M)):
        dev = symbolic3d_counts(A, B, grid, mask=m_dev)
        host = host_symbolic_counts(a, b, (2, 2, 2), mask=m_host)
        np.testing.assert_array_equal(np.asarray(dev.percol), host.percol)
        np.testing.assert_array_equal(np.asarray(dev.b_colcounts),
                                      host.b_colcounts)
        if m_host is None:
            assert host.mask_colcounts is None
        else:
            np.testing.assert_array_equal(np.asarray(dev.mask_colcounts),
                                          host.mask_colcounts)

        spec = PlanSpec(mask=m_dev)
        ppm = 1 << 22
        dev_plan = plan_batches(A, B, grid, per_process_memory=ppm,
                                spec=spec)
        inputs = PlanInputs.from_host(a, b, (2, 2, 2), mask=m_host)
        host_plan = plan_from_symbolic(
            host_symbolic_counts(a, b, (2, 2, 2), mask=m_host), inputs,
            ppm, PlanSpec(mask=m_host), PlanFloors(),
        )
        assert host_plan.num_batches == dev_plan.num_batches
        assert host_plan.caps == dev_plan.caps
        assert host_plan.sel_cap == dev_plan.sel_cap
        assert host_plan.mask_sel_cap == dev_plan.mask_sel_cap
        assert host_plan.local_path == dev_plan.local_path
        assert host_plan.total_flops == dev_plan.total_flops

    # default-scatter capacity parity (the from_host sizing rule)
    inputs = PlanInputs.from_host(a, b, (2, 2, 2))
    assert inputs.cap_a == A.cap and inputs.cap_b == B.cap, (
        inputs.cap_a, A.cap, inputs.cap_b, B.cap)
    print("OK tune_oracle_parity")


def case_rect_grid_oracle_parity():
    """Rectangular single-layer grids: the host symbolic oracle matches the
    device pass BIT-FOR-BIT on a real 4×2×1 mesh (the stage stride is B's
    own tile row count — wrong if A's tile width were used, which only a
    pr ≠ pc grid can detect), the derived plans agree, and the batched
    driver's numeric product is correct — what licenses the autotuner's new
    rectangular (pr, pc, 1) candidates."""
    from repro.core.batched import PlanInputs, plan_from_symbolic, \
        symbolic3d_counts
    from repro.core.specs import PlanFloors, PlanSpec
    from repro.core.symbolic import host_symbolic_counts

    n = 64
    a = gen.rmat(6, edge_factor=8, seed=3)
    b = gen.rmat(6, edge_factor=8, seed=4)
    for pr, pc in ((4, 2), (2, 4)):
        grid = make_grid(pr, pc, 1)
        A = scatter_to_grid(a, grid, "A")
        B = scatter_to_grid(b, grid, "B")
        dev = symbolic3d_counts(A, B, grid)
        host = host_symbolic_counts(a, b, (pr, pc, 1))
        np.testing.assert_array_equal(np.asarray(dev.percol), host.percol)
        np.testing.assert_array_equal(np.asarray(dev.b_colcounts),
                                      host.b_colcounts)

        ppm = 1 << 22
        dev_plan = plan_batches(A, B, grid, per_process_memory=ppm,
                                spec=PlanSpec())
        inputs = PlanInputs.from_host(a, b, (pr, pc, 1))
        host_plan = plan_from_symbolic(
            host, inputs, ppm, PlanSpec(), PlanFloors(),
        )
        assert host_plan.num_batches == dev_plan.num_batches
        assert host_plan.caps == dev_plan.caps
        assert host_plan.sel_cap == dev_plan.sel_cap
        assert host_plan.local_path == dev_plan.local_path
        assert host_plan.total_flops == dev_plan.total_flops

        # numeric correctness of the batched driver on the rectangle
        got = np.zeros((n, n), np.float32)

        def consumer(bi, c, col_map):
            got[:] += reconstruct_sparse_c(c, grid, col_map, n, n)

        batched_summa3d(A, B, grid, 1 << 30, consumer)
        xa = np.asarray(a.to_dense())
        xb = np.asarray(b.to_dense())
        np.testing.assert_allclose(got, xa @ xb, rtol=1e-4, atol=1e-4)
    print("OK rect_grid_oracle_parity")


def case_fused_step_phase_scopes():
    """On a layered grid (l = 2) every gather, scatter, sort and reduce of
    the compiled fused step lies under one phase scope, and the fiber
    all-to-alls under ``spgemm.fiber_exchange``."""
    from phase_scopes import checked_ops, hlo_text, outside_one_phase, scopes

    from repro.core.batched import _fused_jit

    grid = make_grid(2, 2, 2)
    _, a = _rand_square(32, 0.2, seed=71)
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(a, grid, "B")
    for local_path in ("esc", "hash"):
        plan = plan_batches(A, B, grid, 1 << 26, spec=PlanSpec(
            local_path=local_path, force_num_batches=2))
        hlo = hlo_text(_fused_jit.lower(
            A, B, jnp.int32(0), None, grid=grid, num_batches=2,
            sel_cap=plan.sel_cap, caps=plan.caps, semiring=sr.PLUS_TIMES,
            sorted_merge=True, path="sparse", hashc=plan.hash_caps,
            mask_cap=0, mask_complement=False,
        ))
        assert outside_one_phase(hlo) == [], local_path
        found = {scopes(name)[0] for _, name in checked_ops(hlo)}
        assert {"spgemm.select", "spgemm.split", "spgemm.merge"} <= found
        a2a = [line for line in hlo.splitlines() if " all-to-all(" in line]
        assert a2a and all("/spgemm.fiber_exchange/" in line for line in a2a)
    print("OK fused_step_phase_scopes")


def _collect_cases():
    return {
        name[len("case_"):]: fn
        for name, fn in list(globals().items())
        if name.startswith("case_")
    }


CASES = _collect_cases()

if __name__ == "__main__":
    CASES = _collect_cases()  # include cases defined after this block
    which = sys.argv[1] if len(sys.argv) > 1 else None
    if which:
        CASES[which]()
    else:
        for name, fn in CASES.items():
            fn()
