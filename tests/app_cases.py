"""SpGEMM application correctness cases (subprocess, 8 host devices)."""
import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from repro.core import gen
from repro.core.grid import make_grid
from repro.core.specs import PlanSpec
from repro.sparse_apps.graph_algorithms import (
    overlap_pairs,
    overlap_pairs_host,
    overlap_pairs_reference,
    triangle_count,
    triangle_count_host,
    triangle_count_reference,
)
from repro.sparse_apps.mcl import (
    MCLConfig,
    clusters_from_matrix,
    mcl_iterate,
    mcl_iterate_host,
)


def _stochastic_blocks(n, blocks, intra_p, seed):
    """Column-normalized planted-cluster input (MCL operates on a
    column-stochastic matrix)."""
    from repro.core.sparse import from_numpy_coo
    from repro.sparse_apps.mcl import _col_normalize_np

    a = gen.protein_similarity_like(n, blocks=blocks, intra_p=intra_p, seed=seed)
    nnz = int(a.nnz)
    rows = np.asarray(a.rows[:nnz])
    cols = np.asarray(a.cols[:nnz])
    vals = np.asarray(a.vals[:nnz]).astype(np.float64)
    vals = _col_normalize_np(rows, cols, vals, n).astype(np.float32)
    return from_numpy_coo(rows, cols, vals, (n, n), cap=nnz)


def _labels(final, n):
    nnz = int(final.nnz)
    return clusters_from_matrix(
        np.asarray(final.rows[:nnz]), np.asarray(final.cols[:nnz]), n
    )


def case_mcl_clusters_blocks():
    """MCL on a 4-block stochastic block matrix must recover ~4 clusters."""
    grid = make_grid(2, 2, 2)
    n = 64
    a = _stochastic_blocks(n, blocks=4, intra_p=0.6, seed=3)
    final, hist = mcl_iterate(
        a, grid, MCLConfig(max_iters=12, per_process_memory=1 << 24), verbose=True
    )
    labels = _labels(final, n)
    ncl = len(set(labels.tolist()))
    assert 2 <= ncl <= 10, f"expected block-ish clustering, got {ncl} clusters"
    # chaos decreased
    assert hist[-1]["chaos"] < hist[0]["chaos"]
    print(f"OK mcl_clusters_blocks (clusters={ncl}, iters={len(hist)})")


def case_mcl_device_matches_host():
    """Device-resident MCL == host-loop reference on a planted two-cluster
    graph: identical per-iteration nnz trajectory, matching final cluster
    partition, chaos converged and decreasing into convergence — including
    under a FORCED multi-batch plan (per-batch pruning exercised) and under
    a tight ``max_per_col`` so the distributed top-k selection actually
    binds (values are distinct, so threshold selection == exact top-k)."""
    grid = make_grid(2, 2, 2)
    n = 64
    a = _stochastic_blocks(n, blocks=2, intra_p=0.6, seed=3)
    for nb, k in ((None, 64), (4, 64), (4, 4)):
        cfg = MCLConfig(max_iters=12, per_process_memory=1 << 24,
                        force_num_batches=nb, max_per_col=k)
        fin_d, hist_d = mcl_iterate(a, grid, cfg)
        fin_h, hist_h = mcl_iterate_host(a, grid, cfg)
        lab_d, lab_h = _labels(fin_d, n), _labels(fin_h, n)
        if k == 64:
            assert len(set(lab_d.tolist())) == 2, set(lab_d.tolist())
        else:  # aggressive top-k may over-fragment; parity is the claim
            assert len(set(lab_d.tolist())) >= 2, set(lab_d.tolist())
        # same partition (labels are representatives, compare co-membership)
        for i in range(n):
            np.testing.assert_array_equal(lab_d == lab_d[i], lab_h == lab_h[i])
        assert [h["nnz"] for h in hist_d] == [h["nnz"] for h in hist_h], (
            hist_d, hist_h)
        if k < 64:  # top-k must have actually pruned below the k=64 runs
            assert hist_d[0]["nnz"] <= n * k, hist_d[0]
        chaos = [h["chaos"] for h in hist_d]
        assert chaos[-1] < cfg.converge_tol, chaos
        assert chaos[-1] < chaos[0] and chaos[-1] < chaos[-2] < chaos[-3], chaos
        # device path moves only stat scalars per iteration; host loop moves
        # the matrix every batch
        assert max(h["host_bytes"] for h in hist_d) < 1024, hist_d
        assert min(h["host_bytes"] for h in hist_h) > 10240, hist_h
    print("OK mcl_device_matches_host")


def case_mcl_dense_path():
    """Dense-path device pipeline (col_prune Pallas postprocess + vectorized
    extraction) matches the sparse device path and the host reference."""
    grid = make_grid(2, 2, 2)
    n = 64
    a = _stochastic_blocks(n, blocks=2, intra_p=0.6, seed=3)
    cfg_d = MCLConfig(max_iters=8, per_process_memory=1 << 24, path="dense",
                      force_num_batches=2, max_per_col=8)
    fin_dense, hist_dense = mcl_iterate(a, grid, cfg_d)
    cfg_s = MCLConfig(max_iters=8, per_process_memory=1 << 24, path="sparse",
                      force_num_batches=2, max_per_col=8)
    _, hist_sparse = mcl_iterate(a, grid, cfg_s)
    cfg_h = MCLConfig(max_iters=8, per_process_memory=1 << 24, path="dense",
                      force_num_batches=2, max_per_col=8)
    fin_host, hist_host = mcl_iterate_host(a, grid, cfg_h)
    assert [h["nnz"] for h in hist_dense] == [h["nnz"] for h in hist_sparse]
    assert [h["nnz"] for h in hist_dense] == [h["nnz"] for h in hist_host]
    lab_d, lab_h = _labels(fin_dense, n), _labels(fin_host, n)
    for i in range(n):
        np.testing.assert_array_equal(lab_d == lab_d[i], lab_h == lab_h[i])
    print("OK mcl_dense_path")


def case_mcl_tied_topk_distributed():
    """k-boundary ties split across GRID ROW BLOCKS: a uniform-degree graph
    (every column = equal values, degree > k) must keep exactly k entries
    per column on both device paths — the distributed rank fill must
    allocate the tie quota consistently across the pr row blocks."""
    import jax
    import jax.numpy as jnp

    from repro.core.distsparse import scatter_to_grid
    from repro.core.sparse import from_dense
    from repro.sparse_apps.mcl import _mcl_prune_dense, _mcl_prune_sparse

    grid = make_grid(2, 2, 2)
    n, deg, k = 64, 12, 5  # deg rows per column straddle both row blocks
    x = np.zeros((n, n), np.float32)
    for j in range(n):
        x[(j + np.arange(0, deg * 5, 5)) % n, j] = 1.0  # spread across blocks
    d = scatter_to_grid(from_dense(jnp.asarray(x), cap=2048), grid, "C")
    pruned, stats = _mcl_prune_sparse(
        d, grid=grid, inflation=2.0, thresh=1e-4, k=k, new_cap=2048,
    )
    assert int(np.asarray(stats["nnz"])) == n * k, int(np.asarray(stats["nnz"]))
    # per-column counts == k exactly, assembled across all tiles
    R = np.asarray(pruned.rows); C = np.asarray(pruned.cols)
    N = np.asarray(pruned.nnz)
    tm, wbl = pruned.tile_shape
    counts = np.zeros(n, np.int64)
    pr, pc, l = pruned.grid_shape
    w = n // pc
    for i in range(pr):
        for j in range(pc):
            for kk in range(l):
                cnt = int(N[i, j, kk])
                np.add.at(counts, j * w + kk * wbl + C[i, j, kk, :cnt], 1)
    np.testing.assert_array_equal(counts, np.full(n, k))
    # dense path: same tie semantics through the col_prune kernel
    tiles = np.zeros((pr, pc, l, tm, wbl), np.float32)
    for i in range(pr):
        for j in range(pc):
            for kk in range(l):
                tiles[i, j, kk] = x[i * tm:(i + 1) * tm,
                                    j * w + kk * wbl:j * w + (kk + 1) * wbl]
    dev = jax.device_put(jnp.asarray(tiles), grid.tile_sharding())
    out, stats_d = _mcl_prune_dense(
        dev, grid=grid, inflation=2.0, thresh=1e-4, k=k,
    )
    assert int(np.asarray(stats_d["nnz"])) == n * k
    print("OK mcl_tied_topk_distributed")


def case_mcl_no_host_roundtrip():
    """The sparse device-resident loop performs ZERO gather_to_global /
    scatter_to_grid calls inside the iteration loop: exactly two scatters
    (initial operands) and one gather (final matrix) over a whole run."""
    from repro.core import distsparse

    calls = {"scatter": 0, "gather": 0}
    real_scatter, real_gather = distsparse.scatter_to_grid, distsparse.gather_to_global

    def counting_scatter(*args, **kwargs):
        calls["scatter"] += 1
        return real_scatter(*args, **kwargs)

    def counting_gather(*args, **kwargs):
        calls["gather"] += 1
        return real_gather(*args, **kwargs)

    distsparse.scatter_to_grid = counting_scatter
    distsparse.gather_to_global = counting_gather
    try:
        grid = make_grid(2, 2, 2)
        n = 64
        a = _stochastic_blocks(n, blocks=2, intra_p=0.6, seed=5)
        _, hist = mcl_iterate(
            a, grid,
            MCLConfig(max_iters=6, per_process_memory=1 << 24,
                      force_num_batches=2),
        )
    finally:
        distsparse.scatter_to_grid = real_scatter
        distsparse.gather_to_global = real_gather
    assert len(hist) >= 3, "need a multi-iteration run to prove residency"
    assert calls["scatter"] == 2, calls  # initial A and B only
    assert calls["gather"] == 1, calls  # final matrix only
    print(f"OK mcl_no_host_roundtrip (iters={len(hist)}, calls={calls})")


def case_triangle_count_exact():
    grid = make_grid(2, 2, 2)
    a = gen.symmetrized(gen.erdos_renyi(48, 6.0, seed=9))
    got = triangle_count(a, grid)
    want = triangle_count_reference(a)
    assert got == want, (got, want)
    print(f"OK triangle_count_exact (triangles={got})")


def case_triangle_masked_rmat():
    """Masked triangle counting on R-MAT skew at 8 devices: the on-device
    masked path matches both the host-filter oracle and the dense reference,
    the masked plan needs strictly fewer batches and strictly smaller
    capacities than the unmasked plan under the same memory budget, and the
    device path performs ZERO host-side per-entry filtering (call-counted
    like ``mcl_no_host_roundtrip``)."""
    from repro.core.batched import plan_batches, probe_memory_budget
    from repro.core.distsparse import scatter_to_grid
    from repro.sparse_apps import graph_algorithms as ga
    from repro.sparse_apps.mcl import reset_transfer_bytes, transfer_bytes

    grid = make_grid(2, 2, 2)
    a = gen.symmetrized(gen.rmat(6, edge_factor=8, seed=5))  # n=64, power-law
    want = triangle_count_reference(a)

    # --- plan comparison under a budget that forces the unmasked run to batch
    L, U = ga._strict_parts(a)
    A_d = scatter_to_grid(L, grid, "A")
    B_d = scatter_to_grid(U, grid, "B")
    M_d = scatter_to_grid(L, grid, "C")
    ppm = probe_memory_budget(A_d, B_d, grid)  # unmasked b ~ 3-4
    pu = plan_batches(A_d, B_d, grid, per_process_memory=ppm,
                      spec=PlanSpec(local_path="esc"))
    pm = plan_batches(A_d, B_d, grid, per_process_memory=ppm,
                      spec=PlanSpec(mask=M_d, local_path="esc"))
    assert pu.num_batches > 1, pu.num_batches
    assert pm.num_batches < pu.num_batches, (pm.num_batches, pu.num_batches)
    assert pm.caps.d_cap < pu.caps.d_cap, (pm.caps, pu.caps)
    assert pm.caps.c_cap < pu.caps.c_cap, (pm.caps, pu.caps)

    # --- device path: no host-side per-entry filtering, scalars-only traffic
    calls = {"mask_filter": 0, "to_global": 0}
    real_filter = ga._host_mask_filter
    real_to_global = ga._sparse_batch_to_global

    def counting_filter(*args, **kwargs):
        calls["mask_filter"] += 1
        return real_filter(*args, **kwargs)

    def counting_to_global(*args, **kwargs):
        calls["to_global"] += 1
        return real_to_global(*args, **kwargs)

    ga._host_mask_filter = counting_filter
    ga._sparse_batch_to_global = counting_to_global
    try:
        reset_transfer_bytes()
        got = triangle_count(a, grid, per_process_memory=ppm)
        device_bytes = transfer_bytes()
        assert calls == {"mask_filter": 0, "to_global": 0}, calls
        reset_transfer_bytes()
        got_host = triangle_count_host(a, grid, per_process_memory=ppm)
        host_bytes = transfer_bytes()
        assert calls["mask_filter"] > 0 and calls["to_global"] > 0, calls
    finally:
        ga._host_mask_filter = real_filter
        ga._sparse_batch_to_global = real_to_global
    assert got == want == got_host, (got, want, got_host)
    # device path: one scalar per batch + the one-time mask count-vector
    # pull the planner makes (counts are computed on-grid now, so only the
    # (pr, pc, l, w_l) i32 array crosses); host oracle moves every full batch
    pr_, pc_, l_ = M_d.grid_shape
    mask_pull = pr_ * pc_ * l_ * M_d.tile_shape[1] * 4
    assert device_bytes <= mask_pull + 64, (device_bytes, mask_pull)
    assert host_bytes > 10 * device_bytes, (host_bytes, device_bytes)
    print(f"OK triangle_masked_rmat (triangles={got}, "
          f"batches {pm.num_batches}<{pu.num_batches}, "
          f"bytes {device_bytes}<<{host_bytes})")


def case_masked_multibatch_grid():
    """The masked fused step's mask-slice ↔ block-cyclic-batch alignment is
    only nontrivial when num_batches > 1 AND layers > 1 (the batch slice is
    fiber-gathered with per-layer column offsets): exact parity with the
    dense reference at nb ∈ {2, 4} × {strict, complement} on the 2x2x2
    grid, on both local multiplies (ESC and the hash accumulator)."""
    import jax.numpy as jnp

    from repro.core.batched import batched_summa3d
    from repro.core.distsparse import scatter_to_grid
    from repro.core.sparse import from_dense, from_numpy_coo
    from repro.sparse_apps.mcl import _sparse_batch_to_global

    grid = make_grid(2, 2, 2)
    n = 64
    rng = np.random.default_rng(41)
    xa = np.where(rng.random((n, n)) < 0.2,
                  rng.uniform(0.5, 1, (n, n)), 0).astype(np.float32)
    xb = np.where(rng.random((n, n)) < 0.2,
                  rng.uniform(0.5, 1, (n, n)), 0).astype(np.float32)
    mask_dense = rng.random((n, n)) < 0.15
    mr, mc = np.nonzero(mask_dense)
    A = scatter_to_grid(from_dense(jnp.asarray(xa), cap=1024), grid, "A")
    B = scatter_to_grid(from_dense(jnp.asarray(xb), cap=1024), grid, "B")
    M = scatter_to_grid(
        from_numpy_coo(mr, mc, np.ones(len(mr), np.float32), (n, n)),
        grid, "C",
    )
    for complement in (False, True):
        for nb in (2, 4):
            for local_path in ("auto", "esc", "hash"):
                got = np.zeros((n, n), np.float32)

                def consumer(bi, c, cm):
                    rr, cc, vv = _sparse_batch_to_global(c, cm)
                    got[rr, cc] += vv

                res = batched_summa3d(
                    A, B, grid, per_process_memory=1 << 26,
                    consumer=consumer, path="sparse",
                    spec=PlanSpec(force_num_batches=nb, mask=M,
                                  mask_complement=complement,
                                  local_path=local_path),
                )
                keep = ~mask_dense if complement else mask_dense
                np.testing.assert_allclose(
                    got, (xa @ xb) * keep, rtol=1e-4, atol=1e-4,
                )
                assert res.num_retries == 0, (complement, nb, local_path)
    print("OK masked_multibatch_grid")


def case_overlap_pairs_exact():
    grid = make_grid(2, 2, 2)
    a = gen.kmer_like(32, 64, 5, seed=17)
    got = overlap_pairs(a, grid, min_shared=2)
    want = overlap_pairs_reference(a, min_shared=2)
    assert got == want, (len(got), len(want))
    print(f"OK overlap_pairs_exact (pairs={len(got)})")


def case_overlap_device_filter():
    """Overlap detection with the BELLA filter applied ON the grid: parity
    with the host-filter oracle and the dense reference, zero host-side
    per-entry filtering on the device path (call-counted), and the optional
    candidate-pair mask (PASTIS regime) gating the multiply itself."""
    from repro.core.sparse import from_numpy_coo
    from repro.sparse_apps import graph_algorithms as ga

    grid = make_grid(2, 2, 2)
    a = gen.kmer_like(32, 64, 5, seed=31)
    want = overlap_pairs_reference(a, min_shared=2)

    calls = {"pair_filter": 0}
    real_filter = ga._host_pair_filter

    def counting_filter(*args, **kwargs):
        calls["pair_filter"] += 1
        return real_filter(*args, **kwargs)

    ga._host_pair_filter = counting_filter
    try:
        got = overlap_pairs(a, grid, min_shared=2)
        assert calls["pair_filter"] == 0, calls
        got_host = overlap_pairs_host(a, grid, min_shared=2)
        assert calls["pair_filter"] > 0, calls
    finally:
        ga._host_pair_filter = real_filter
    assert got == want == got_host, (len(got), len(want), len(got_host))

    # candidate mask (PASTIS): candidates ⊇ true pairs reproduces the full
    # result; candidates ⊂ true pairs restricts the output to the mask.
    nseqs = a.shape[0]
    rng = np.random.default_rng(3)
    extra_r = rng.integers(0, nseqs, 40)
    extra_c = rng.integers(0, nseqs, 40)
    cr = np.concatenate([[p[0] for p in want], extra_r])
    cc = np.concatenate([[p[1] for p in want], extra_c])
    cands = from_numpy_coo(cr, cc, np.ones(len(cr), np.float32),
                           (nseqs, nseqs))
    got_c = overlap_pairs(a, grid, min_shared=2, candidates=cands)
    assert got_c == want, (len(got_c), len(want))
    half = want[: len(want) // 2]
    cands_half = from_numpy_coo(
        np.array([p[0] for p in half]), np.array([p[1] for p in half]),
        np.ones(len(half), np.float32), (nseqs, nseqs),
    )
    got_h = overlap_pairs(a, grid, min_shared=2, candidates=cands_half)
    assert got_h == half, (len(got_h), len(half))

    # survivor-sized transfer: the device→host pull is sliced down to the
    # max per-tile survivor count before any array moves. With an impossible
    # threshold every batch shrinks to the floor capacity of 8.
    seen_caps = []
    real_to_global2 = ga._sparse_batch_to_global

    def spying_to_global(c, col_map):
        seen_caps.append(int(c.rows.shape[-1]))
        return real_to_global2(c, col_map)

    ga._sparse_batch_to_global = spying_to_global
    try:
        none = overlap_pairs(a, grid, min_shared=10 ** 6)
        exact_again = overlap_pairs(a, grid, min_shared=2)
    finally:
        ga._sparse_batch_to_global = real_to_global2
    assert none == []
    assert exact_again == want, (len(exact_again), len(want))
    nb_seen = len(seen_caps)
    assert seen_caps and min(seen_caps) == 8, seen_caps
    print(f"OK overlap_device_filter (pairs={len(got)}, "
          f"candidates {len(got_c)}/{len(got_h)}, "
          f"shrunk caps {seen_caps[:nb_seen]})")


def case_mcl_kill_and_resume():
    """Durability at 8 devices: a run preempted mid-flight and resumed from
    its checkpoint reproduces the uninterrupted run bitwise — identical
    nnz/chaos trajectory, identical final cluster partition — and replans to
    the identical fused-step static signature (zero extra retraces)."""
    import tempfile

    from repro.core import summa3d
    from repro.runtime.resilient import ResilientConfig, SpgemmFailureInjector
    from repro.sparse_apps.mcl import mcl_iterate_resilient

    grid = make_grid(2, 2, 2)
    n = 64
    a = _stochastic_blocks(n, blocks=2, intra_p=0.6, seed=3)
    cfg = MCLConfig(max_iters=8, per_process_memory=1 << 24, max_per_col=8)
    final0, hist0 = mcl_iterate(a, grid, cfg)

    with tempfile.TemporaryDirectory() as d:
        rc = ResilientConfig(ckpt_dir=d, ckpt_every=1)
        inj = SpgemmFailureInjector(preempt_iters=(3,))
        tc0 = summa3d.TRACE_COUNTS["fused_step"]
        final1, hist1, rep = mcl_iterate_resilient(a, grid, cfg, rc,
                                                   injector=inj)
        tc1 = summa3d.TRACE_COUNTS["fused_step"]

    assert rep.restarts == 1, rep
    assert tc1 - tc0 == 0, (tc0, tc1)
    assert [(h["nnz"], h["chaos"]) for h in hist1] == \
           [(h["nnz"], h["chaos"]) for h in hist0]
    lab0, lab1 = _labels(final0, n), _labels(final1, n)
    for i in range(n):
        np.testing.assert_array_equal(lab1 == lab1[i], lab0 == lab0[i])
    nnz0, nnz1 = int(final0.nnz), int(final1.nnz)
    assert nnz0 == nnz1
    np.testing.assert_array_equal(np.asarray(final1.rows[:nnz1]),
                                  np.asarray(final0.rows[:nnz0]))
    np.testing.assert_array_equal(np.asarray(final1.cols[:nnz1]),
                                  np.asarray(final0.cols[:nnz0]))
    np.testing.assert_array_equal(np.asarray(final1.vals[:nnz1]),
                                  np.asarray(final0.vals[:nnz0]))
    assert rep.checkpoint_bytes > 0, rep
    print(f"OK mcl_kill_and_resume (iters={len(hist1)}, "
          f"ckpt_bytes={rep.checkpoint_bytes}, restarts={rep.restarts})")


def case_apsp_min_plus():
    """APSP iterated squaring over MIN_PLUS at 8 devices == numpy
    Floyd-Warshall, including unreachable pairs (implicit +inf)."""
    from repro.sparse_apps.graph_algorithms import (
        APSPConfig,
        apsp_iterate,
        apsp_reference,
    )

    grid = make_grid(2, 2, 2)
    n = 64
    rng = np.random.default_rng(11)
    from repro.core.sparse import from_numpy_coo
    w = rng.random((n, n)).astype(np.float32) * 9 + 1
    mask = rng.random((n, n)) < 0.06
    np.fill_diagonal(mask, False)
    r, c = np.nonzero(mask)
    a = from_numpy_coo(r.astype(np.int32), c.astype(np.int32), w[r, c], (n, n))
    D, hist = apsp_iterate(a, grid, APSPConfig(per_process_memory=1 << 24))
    ref = apsp_reference(a)
    got = np.full((n, n), np.inf, np.float64)
    k = int(D.nnz)
    got[np.asarray(D.rows[:k]), np.asarray(D.cols[:k])] = np.asarray(D.vals[:k])
    assert (np.isfinite(got) == np.isfinite(ref)).all()
    fin = np.isfinite(ref)
    np.testing.assert_allclose(got[fin], ref[fin], rtol=1e-5)
    print(f"OK apsp_min_plus (iters={len(hist)}, reachable={int(fin.sum())})")


def _counting_roundtrip(body):
    """Run ``body`` with counting wrappers over scatter/gather; returns the
    call counts — the shared harness of the no-host-roundtrip cases."""
    from repro.core import distsparse

    calls = {"scatter": 0, "gather": 0}
    real_scatter = distsparse.scatter_to_grid
    real_gather = distsparse.gather_to_global

    def counting_scatter(*args, **kwargs):
        calls["scatter"] += 1
        return real_scatter(*args, **kwargs)

    def counting_gather(*args, **kwargs):
        calls["gather"] += 1
        return real_gather(*args, **kwargs)

    distsparse.scatter_to_grid = counting_scatter
    distsparse.gather_to_global = counting_gather
    try:
        body()
    finally:
        distsparse.scatter_to_grid = real_scatter
        distsparse.gather_to_global = real_gather
    return calls


def case_apsp_no_host_roundtrip():
    """The APSP iterate is device-resident like MCL's: two scatters (initial
    D as A- and B-kind) and one gather (the converged distance matrix) over
    the whole iterated-squaring run — zero round-trips inside the loop."""
    from repro.sparse_apps.graph_algorithms import APSPConfig, apsp_iterate

    out = {}

    def body():
        grid = make_grid(2, 2, 2)
        n = 64
        rng = np.random.default_rng(11)
        from repro.core.sparse import from_numpy_coo
        w = rng.random((n, n)).astype(np.float32) * 9 + 1
        mask = rng.random((n, n)) < 0.06
        np.fill_diagonal(mask, False)
        r, c = np.nonzero(mask)
        a = from_numpy_coo(r.astype(np.int32), c.astype(np.int32),
                           w[r, c], (n, n))
        _, out["hist"] = apsp_iterate(
            a, grid, APSPConfig(per_process_memory=1 << 24)
        )

    calls = _counting_roundtrip(body)
    assert len(out["hist"]) >= 3, "need a multi-iteration run"
    assert calls["scatter"] == 2, calls  # initial A and B only
    assert calls["gather"] == 1, calls  # final distance matrix only
    print(f"OK apsp_no_host_roundtrip (iters={len(out['hist'])}, "
          f"calls={calls})")


def case_mcl_dense_no_host_roundtrip():
    """The MCL dense path now matches the sparse path's residency contract:
    scatter twice before the loop, gather once after convergence — the
    pruned dense batches are sparsified on-device and reassembled on-grid."""
    out = {}

    def body():
        grid = make_grid(2, 2, 2)
        n = 64
        a = _stochastic_blocks(n, blocks=2, intra_p=0.6, seed=5)
        _, out["hist"] = mcl_iterate(
            a, grid,
            MCLConfig(max_iters=6, per_process_memory=1 << 24,
                      force_num_batches=2, path="dense", max_per_col=8),
        )

    calls = _counting_roundtrip(body)
    assert len(out["hist"]) >= 3, "need a multi-iteration run"
    assert calls["scatter"] == 2, calls  # initial A and B only
    assert calls["gather"] == 1, calls  # final matrix only
    print(f"OK mcl_dense_no_host_roundtrip (iters={len(out['hist'])}, "
          f"calls={calls})")


def case_serve_mixed_traffic():
    """The serving engine at 8 devices under mixed repeat/novel traffic:
    every request matches the dense oracle, repeat signatures hit the plan
    cache, and the repeats cost ZERO extra fused-step retraces."""
    from repro.core import summa3d
    from repro.serve import MultiplyRequest, ServeConfig, SpgemmEngine

    grid = make_grid(2, 2, 2)
    n = 64
    a0 = gen.erdos_renyi(n, 4.0, seed=40)
    b0 = gen.erdos_renyi(n, 4.0, seed=41)
    eng = SpgemmEngine(grid, ServeConfig(per_process_memory=1 << 24))

    def dense(s):
        m = np.zeros(s.shape, np.float64)
        k = int(s.nnz)
        m[np.asarray(s.rows)[:k], np.asarray(s.cols)[:k]] = (
            np.asarray(s.vals)[:k]
        )
        return m

    # warm the cache with the repeat signature, then measure the repeats
    eng.submit(MultiplyRequest(rid=0, a=a0, b=b0))
    eng.run_to_completion()
    pairs = {0: (a0, b0)}
    t0 = summa3d.TRACE_COUNTS["fused_step"]
    for rid in (1, 2, 3, 4):
        eng.submit(MultiplyRequest(rid=rid, a=a0, b=b0))
        pairs[rid] = (a0, b0)
    eng.run_to_completion()
    repeat_traces = summa3d.TRACE_COUNTS["fused_step"] - t0
    # the acceptance criterion: identical signature → zero extra retraces
    assert repeat_traces == 0, repeat_traces
    # interleave novel signatures (these may legitimately retrace)
    for i, rid in enumerate((5, 6, 7, 8)):
        an = gen.erdos_renyi(n, 4.0, seed=500 + 2 * i)
        bn = gen.erdos_renyi(n, 4.0, seed=501 + 2 * i)
        eng.submit(MultiplyRequest(rid=rid, a=an, b=bn))
        pairs[rid] = (an, bn)
    # done accumulates across run_to_completion calls: all nine requests
    results = eng.run_to_completion()
    assert len(results) == 9 and all(r.status == "ok" for r in results)
    for r in results:
        ra, rb = pairs[r.rid]
        np.testing.assert_allclose(
            dense(r.c), dense(ra) @ dense(rb), rtol=1e-5, atol=1e-6
        )
    assert eng.stats["hits"] >= 4, eng.stats  # the a0·b0 repeats all hit
    print(f"OK serve_mixed_traffic (requests={len(results)}, "
          f"stats={eng.stats}, extra_traces={repeat_traces})")


def case_placement_rmat_volume():
    """Structure-aware placement on a REAL 2×2×2 mesh: the degree-spread
    permutation (a) plans no more batches and strictly fewer capacity-padded
    transfer bytes than block-cyclic at the same constrained budget, and
    (b) the end-to-end placed multiply (permute → scatter → batched driver
    → invert) reproduces the unpermuted R-MAT product exactly."""
    from repro.core.batched import plan_batches, probe_memory_budget
    from repro.core.distsparse import scatter_to_grid
    from repro.core.placement import Placement, compute_placement, \
        multiply_placed
    from repro.tune import padded_comm_volume

    grid = make_grid(2, 2, 2)
    gs = (2, 2, 2)
    a = gen.symmetrized(gen.rmat(7, edge_factor=8, seed=5))
    n = a.shape[0]
    A = scatter_to_grid(a, grid, "A")
    B = scatter_to_grid(a, grid, "B")
    ppm = probe_memory_budget(A, B, grid)
    base_plan = plan_batches(A, B, grid, per_process_memory=ppm,
                             spec=PlanSpec(local_path="esc"))
    placement = compute_placement(a, a, "degree")
    Ap = scatter_to_grid(placement.apply_a(a), grid, "A")
    Bp = scatter_to_grid(placement.apply_b(a), grid, "B")
    placed_plan = plan_batches(Ap, Bp, grid, per_process_memory=ppm,
                               spec=PlanSpec(local_path="esc"))
    v_base = padded_comm_volume(base_plan, gs)
    v_placed = padded_comm_volume(placed_plan, gs)
    assert base_plan.num_batches > 1, base_plan.num_batches
    assert placed_plan.num_batches <= base_plan.num_batches
    assert v_placed.all_to_all_bytes <= v_base.all_to_all_bytes
    assert v_placed.total_bytes < v_base.total_bytes, (
        v_placed.total_bytes, v_base.total_bytes)

    # end-to-end correctness on the mesh: placed == unpermuted, exactly
    spec = PlanSpec(local_path="esc")
    base = multiply_placed(a, a, grid, ppm,
                           placement=Placement.identity(n, n, n), spec=spec)
    placed = multiply_placed(a, a, grid, ppm, placement=placement, spec=spec)
    np.testing.assert_array_equal(placed.to_dense(), base.to_dense())
    print(f"OK placement_rmat_volume (batches {base_plan.num_batches}->"
          f"{placed_plan.num_batches}, padded bytes {v_base.total_bytes}->"
          f"{v_placed.total_bytes})")


CASES = {n[len("case_"):]: f for n, f in list(globals().items())
         if n.startswith("case_")}

if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else None
    if which:
        CASES[which]()
    else:
        for f in CASES.values():
            f()
