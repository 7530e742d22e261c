"""Paper §V-B graph workloads — masked vs unmasked SpGEMM (BENCH_graph.json).

Triangle counting on an R-MAT power-law graph compares the two formulations
the repo keeps:

  * masked_device — ``triangle_count``: the L-mask is a device-resident
    operand; the symbolic step budgets survivors only (smaller capacities,
    fewer batches), the local multiply drops non-mask partial products
    before its compress, and one f32 scalar per batch crosses to the host.
  * host_filter — ``triangle_count_host``: the full (unmasked) L·U product,
    every batch pulled to numpy and masked by a Python set lookup — the
    pre-masked-path baseline.

Overlap detection (AA^T, BELLA filter) compares the on-grid filter
(``overlap_pairs``) against the pull-and-filter host oracle.

``run_graph_suite`` emits JSON rows for BENCH_graph.json: the masked vs
unmasked *plans* (capacities, batch count), per-path wall-ms
and host-transfer bytes, and an acceptance summary asserting the §V-B claim
(masked capacities and batch count strictly below unmasked on the R-MAT
case). CPU wall times are NOT TPU predictions; the reproduced claim is the
capacity/batch/transfer shape.
"""
import time

import jax

from repro.core import gen
from repro.core.batched import plan_batches, probe_memory_budget
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.specs import PlanSpec
from repro.sparse_apps import graph_algorithms as ga
from repro.sparse_apps.mcl import reset_transfer_bytes, transfer_bytes

from .common import emit


def _plan_row(variant, plan):
    return dict(
        op="plan", variant=variant, wall_ms=0.0,
        batches=plan.num_batches,
        flops_cap=plan.caps.flops_cap, d_cap=plan.caps.d_cap,
        piece_cap=plan.caps.piece_cap, c_cap=plan.caps.c_cap,
        sel_cap=plan.sel_cap, mask_sel_cap=plan.mask_sel_cap,
        max_unmerged_nnz=plan.max_unmerged_nnz,
    )


def _timed(fn, *args, **kwargs):
    fn(*args, **kwargs)  # warm the jit caches (compile time excluded)
    reset_transfer_bytes()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, (time.perf_counter() - t0) * 1e3, transfer_bytes()


def run_graph_suite(scale: int = 7, edge_factor: int = 8) -> list:
    """The ``--suite graph`` entry: returns JSON-ready rows."""
    grid = make_grid(2, 2, 2)
    a = gen.symmetrized(gen.rmat(scale, edge_factor=edge_factor, seed=5))
    n = a.shape[0]
    rows = []

    # ---- plans under a budget that forces the unmasked multiply to batch
    # (probe_memory_budget is the same math the slow-lane R-MAT case uses)
    L, U = ga._strict_parts(a)
    A_d = scatter_to_grid(L, grid, "A")
    B_d = scatter_to_grid(U, grid, "B")
    M_d = scatter_to_grid(L, grid, "C")
    ppm = probe_memory_budget(A_d, B_d, grid)
    pu = plan_batches(A_d, B_d, grid, per_process_memory=ppm,
                      spec=PlanSpec(local_path="esc"))
    pm = plan_batches(A_d, B_d, grid, per_process_memory=ppm,
                      spec=PlanSpec(mask=M_d, local_path="esc"))
    rows.append(dict(_plan_row("triangle_unmasked", pu), n=n,
                     per_process_memory=ppm))
    rows.append(dict(_plan_row("triangle_masked", pm), n=n,
                     per_process_memory=ppm))

    # ---- timed triangle counting, device-masked vs host-filter
    tri_m, ms_m, bytes_m = _timed(ga.triangle_count, a, grid,
                                  per_process_memory=ppm)
    tri_h, ms_h, bytes_h = _timed(ga.triangle_count_host, a, grid,
                                  per_process_memory=ppm)
    assert tri_m == tri_h, (tri_m, tri_h)
    rows.append(dict(op="triangle", variant="masked_device", wall_ms=ms_m,
                     host_bytes=bytes_m, triangles=tri_m, n=n))
    rows.append(dict(op="triangle", variant="host_filter", wall_ms=ms_h,
                     host_bytes=bytes_h, triangles=tri_h, n=n))

    # ---- overlap detection, on-grid filter vs host filter
    kmer = gen.kmer_like(64, 128, 6, seed=17)
    ov_d, ms_d, bytes_d = _timed(ga.overlap_pairs, kmer, grid, min_shared=2)
    ov_h, ms_oh, bytes_oh = _timed(ga.overlap_pairs_host, kmer, grid,
                                   min_shared=2)
    assert ov_d == ov_h, (len(ov_d), len(ov_h))
    rows.append(dict(op="overlap", variant="device_filter", wall_ms=ms_d,
                     host_bytes=bytes_d, pairs=len(ov_d), nseqs=64))
    rows.append(dict(op="overlap", variant="host_filter", wall_ms=ms_oh,
                     host_bytes=bytes_oh, pairs=len(ov_h), nseqs=64))

    # ---- structure-aware placement: degree-spread vs block-cyclic volume
    # (same R-MAT skew, square a·a multiply, same probe-budget math; the
    # Table II volumes are permutation-invariant, so the comparison is on
    # the capacity-PADDED transfer bytes the plan actually moves)
    from repro.core.placement import compute_placement
    from repro.tune import padded_comm_volume

    gs = (grid.pr, grid.pc, grid.l)
    Aq = scatter_to_grid(a, grid, "A")
    Bq = scatter_to_grid(a, grid, "B")
    ppm_q = probe_memory_budget(Aq, Bq, grid)
    p_base = plan_batches(Aq, Bq, grid, per_process_memory=ppm_q,
                          spec=PlanSpec(local_path="esc"))
    placement = compute_placement(a, a, "degree")
    Apl = scatter_to_grid(placement.apply_a(a), grid, "A")
    Bpl = scatter_to_grid(placement.apply_b(a), grid, "B")
    p_deg = plan_batches(Apl, Bpl, grid, per_process_memory=ppm_q,
                         spec=PlanSpec(local_path="esc"))
    v_base = padded_comm_volume(p_base, gs)
    v_deg = padded_comm_volume(p_deg, gs)
    for variant, plan, vol in (("block_cyclic", p_base, v_base),
                               ("degree", p_deg, v_deg)):
        rows.append(dict(
            op="placement", variant=variant, wall_ms=0.0, n=n,
            per_process_memory=ppm_q, batches=plan.num_batches,
            sel_cap=plan.sel_cap, piece_cap=plan.caps.piece_cap,
            all_to_all_bytes=vol.all_to_all_bytes,
            gather_bytes=vol.gather_bytes, padded_bytes=vol.total_bytes,
        ))
    # acceptance: degree-spread plans no more batches and strictly fewer
    # padded transfer bytes (the all_to_all term alone may tie — the
    # layer-split piece cap can absorb the whole reduction into fewer,
    # larger batches)
    placement_ok = (
        p_deg.num_batches <= p_base.num_batches
        and v_deg.all_to_all_bytes <= v_base.all_to_all_bytes
        and v_deg.total_bytes < v_base.total_bytes
    )
    assert placement_ok, (p_deg.num_batches, p_base.num_batches,
                          v_deg, v_base)
    rows.append(dict(
        op="summary", variant="placement_volume", wall_ms=0.0,
        batches_block_cyclic=p_base.num_batches,
        batches_degree=p_deg.num_batches,
        padded_bytes_block_cyclic=v_base.total_bytes,
        padded_bytes_degree=v_deg.total_bytes,
        volume_reduction=v_base.total_bytes / max(v_deg.total_bytes, 1),
        degree_below_block_cyclic=placement_ok,
    ))

    # ---- acceptance: the §V-B masked claim on the R-MAT case
    ok = (
        pm.num_batches < pu.num_batches
        and pm.caps.d_cap < pu.caps.d_cap
        and pm.caps.c_cap < pu.caps.c_cap
    )
    assert ok, (pm, pu)
    rows.append(dict(
        op="summary", variant="masked_vs_unmasked", wall_ms=ms_m,
        batches_masked=pm.num_batches, batches_unmasked=pu.num_batches,
        d_cap_masked=pm.caps.d_cap, d_cap_unmasked=pu.caps.d_cap,
        c_cap_masked=pm.caps.c_cap, c_cap_unmasked=pu.caps.c_cap,
        triangle_transfer_reduction=bytes_h / max(bytes_m, 1),
        masked_below_unmasked=ok,
    ))
    return rows


def run(scale: int = 7) -> None:
    if len(jax.devices()) < 8:
        emit("graph/skipped", 0, "needs 8 host devices")
        return
    for row in run_graph_suite(scale=scale):
        if row["op"] == "plan":
            emit(f"graph/plan_{row['variant']}", 0,
                 f"b={row['batches']} d_cap={row['d_cap']} "
                 f"c_cap={row['c_cap']}")
        elif row["op"] in ("triangle", "overlap"):
            emit(f"graph/{row['op']}_{row['variant']}", row["wall_ms"] * 1e3,
                 f"host_bytes={row['host_bytes']}")
        elif row["op"] == "placement":
            emit(f"graph/placement_{row['variant']}", 0,
                 f"b={row['batches']} padded_bytes={row['padded_bytes']}")
        elif row["variant"] == "placement_volume":
            emit("graph/summary_placement", 0,
                 f"b {row['batches_degree']}<={row['batches_block_cyclic']} "
                 f"volume_red={row['volume_reduction']:.2f}x")
        else:
            emit("graph/summary", row["wall_ms"] * 1e3,
                 f"b {row['batches_masked']}<{row['batches_unmasked']} "
                 f"transfer_red={row['triangle_transfer_reduction']:.0f}x")
