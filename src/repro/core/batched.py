"""BatchedSUMMA3D (paper Alg. 4) + the distributed symbolic step (Alg. 3).

The driver mirrors the paper's phase structure, pipelined so the host stays
out of the per-batch loop (§IV-A: numeric batches stream through the
communicators once symbolic planning is done):

  1. SYMBOLIC3D: one communication-avoiding pass that computes per-process
     flops upper bounds. Instead of broadcasting tiles, it reduces A's
     per-column counts along grid rows (psum) and gathers them along grid
     columns — the paper's observation that the symbolic step has the same
     communicator structure but a far lighter payload (§IV-A, Fig. 8). The
     same pass also emits B's per-column entry counts (exact per-batch
     selection capacities — no heuristic, no spurious selection retries).
  2. Host-side batch planning: b from Alg. 3 line 12 (+ Eq. 2 lower-bound
     check), rounded up for block-cyclic divisibility; static capacities for
     the numeric pass derived from the symbolic per-column vectors; the
     local-multiply path (ESC or hash). This is the paper's
     symbolic→numeric split — in JAX it also fixes the static shapes the
     compiler needs.
  3. Pipelined per-batch schedule: selection + multiply are FUSED into one
     jitted SPMD step (``summa3d.summa3d_fused_step``) whose batch index is
     a traced scalar — one executable for all batches. The driver dispatches
     batch i+1 (and up to ``lookahead`` more) before reading batch i's
     overflow flags, which stay device-resident; under async dispatch the
     next batch's selection and gathers overlap the previous multiply, and
     the consumer's host-side work overlaps device compute.
  4. A device-side ``postprocess`` hook transforms each batch product
     IMMEDIATELY after the fused step, before any host involvement — the
     HipMCL integration (§V-C): MCL fuses inflation + distributed column
     normalization + top-k pruning here, so the raw product never reaches
     the host. The host ``consumer`` then sees the hook's output (or the raw
     batch when no hook is set) and may store/discard it — C is never
     materialized whole unless asked. ``plan_batches(reserved_bytes=...)``
     lets such consumers charge their kept outputs against the per-process
     budget (memory-constrained consumption).

Overflow robustness: if a static capacity is exceeded (sparsity estimate
beaten by correlation structure), the flags come back nonzero and the driver
falls back to the synchronous retry loop for that batch — selection capacity
grows first, then the multiply capacities (2× per attempt) — bounded, logged,
and tested. ``pipelined=False`` keeps the fully synchronous schedule (one
host round-trip per batch), which doubles as the benchmark baseline.

Host spans (``core/spans.py``; recorded only under ``jax.profiler.trace``):
``spgemm.call`` around one ``batched_summa3d`` call; ``spgemm.plan`` around
``plan_batches`` with its children ``spgemm.symbolic`` (the device pass and
the counts' copy to the host) and ``spgemm.plan_host`` (operand facts and
``plan_from_symbolic``); ``spgemm.ladder_ceiling`` (the retry ladder's
memory ceiling); per batch ``spgemm.dispatch`` (``batch=``),
``spgemm.wait`` (the host blocks on the overflow flags) and
``spgemm.consume`` (the consumer call); ``spgemm.retry`` (``batch=``,
``kind=sel|mul``), one per count in ``RunReport.retries``, around the
capacity growth; and ``spgemm.replan`` (``batch=``, ``split=``) around each
finer sub-plan of a degraded batch. A retried or degraded batch's own
dispatches and waits follow those two spans.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map

from . import semiring as sr
from . import sortkeys
from .distsparse import DistSparse, dist_spec
from .grid import COL_AX, LAYER_AX, ROW_AX, Grid
from .summa3d import (
    BatchCaps,
    HashCaps,
    _squeeze_tile,
    summa3d_dense_step,
    summa3d_fused_step,
    summa3d_sparse_step,
)
from .placement import BLOCK_CYCLIC, Placement
from .sparse import hstack_remap
from .spans import span
from .specs import ExecSpec, PlanFloors, PlanSpec, resolve_specs
from .symbolic import (
    HASH_LOAD_FACTOR,
    HASH_SLOT_BYTES,
    SymbolicCounts,
    batch_count,
    batch_count_lower_bound,
    dense_step_bytes,
    esc_step_bytes,
    estimate_mem_c_bytes,
    rup8 as _rup8,
    rup_pow2 as _rup_pow2,
)

# auto-dispatch threshold: the hash path pays a per-chunk insert pass, so it
# must buy at least this compression factor (flops per merged survivor)
# before the plan prefers it over ESC.
HASH_CF_THRESHOLD = 2.0

# partial products enumerated per reused chunk buffer of the hash path
HASH_CHUNK_CAP = 4096

# cached compiles: one per (grid, caps, semiring, tile-shape) combination —
# the batch index is a traced scalar so all batches share one executable.
_dense_jit = jax.jit(summa3d_dense_step, static_argnames=("grid", "semiring"))
_sparse_jit = jax.jit(
    summa3d_sparse_step,
    static_argnames=(
        "grid", "caps", "semiring", "sorted_merge", "hashc",
    ),
)
_fused_jit = jax.jit(
    summa3d_fused_step,
    static_argnames=(
        "grid", "num_batches", "sel_cap", "caps", "semiring", "sorted_merge",
        "path", "hashc", "mask_cap", "mask_complement",
    ),
)

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# Distributed symbolic step (Alg. 3)
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("grid",))
def _symbolic3d_jit(
    a: DistSparse, b: DistSparse, mask: Optional[DistSparse], grid: Grid
):
    """One jitted executable per (grid, operand-structure) — the shard_map is
    built inside the traced function, so re-running the planner hits the jit
    cache instead of rebuilding (and re-lowering) the SPMD program.

    ``mask`` (masked plans) rides the same pass: its exact per-(tile, local
    column) entry counts are computed on-grid and returned as one more count
    vector, so masked planning never round-trips the mask's structure
    through the host (ROADMAP carry-over (d))."""
    _, tn_b = b.tile_shape
    wl_b, _ = b.tile_shape

    def step(a_t: DistSparse, b_t: DistSparse, *rest):
        a_loc = _squeeze_tile(a_t)
        b_loc = _squeeze_tile(b_t)
        # A col counts restricted to OUR row block, over the per-layer
        # contraction range, ordered by stage (matches _gather_A indexing)
        cc_local = a_loc.col_counts()  # (wl_a,)
        cc_full = lax.all_gather(cc_local, COL_AX).reshape(-1)  # (k_tot,)
        # every row block's count vector (needed because our B entries
        # contribute to every process in our grid column's row group)
        cc_all = lax.all_gather(cc_full, ROW_AX)  # (pr, k_tot)
        k_tot = cc_full.shape[0]
        cc_all_pad = jnp.concatenate(
            [cc_all, jnp.zeros((cc_all.shape[0], 1), jnp.int32)], axis=1
        )
        # B entries in OUR tile: contraction index = i_own*wl_b + local row
        # (matches _gather_B indexing — the stride is B's OWN tile row
        # count, which equals A's tile width only on square layer grids)
        i_own = lax.axis_index(ROW_AX)
        valid = b_loc.valid_mask()
        k_idx = jnp.where(valid, b_loc.rows + i_own * wl_b, k_tot)
        contrib = cc_all_pad[:, k_idx]  # (pr, capB): per target row block
        contrib = jnp.where(valid[None, :], contrib, 0)
        segids = jnp.where(valid, b_loc.cols, tn_b)
        percol_all = jax.ops.segment_sum(
            contrib.T, segids, num_segments=tn_b + 1
        )[:tn_b].T  # (pr, tn_b): row i = our entries' contribution to block-row i
        # sum over the row group -> each process reads its own row
        percol_all = lax.psum(percol_all, ROW_AX)
        percol = percol_all[i_own]
        # extra for the numeric pass, free on the same communicators:
        # B per-column entry counts (exact selection capacities)
        bcc = b_loc.col_counts()  # (tn_b,)
        outs = (percol[None, None, None], bcc[None, None, None])
        if rest:
            # exact per-(tile, local column) mask counts, on-grid
            mcc = _squeeze_tile(rest[0]).col_counts()  # (wl,)
            outs = outs + (mcc[None, None, None],)
        return outs

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    in_specs = [dist_spec(d, spec3) for d in (a, b)]
    out_specs = (spec3, spec3)
    args = [a, b]
    if mask is not None:
        in_specs.append(dist_spec(mask, spec3))
        out_specs = out_specs + (spec3,)
        args.append(mask)
    fn = shard_map(
        step, mesh=grid.mesh, in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )
    return fn(*args)


def _mask_tile_colcounts(mask: DistSparse) -> np.ndarray:
    """Exact per-(tile, local column) mask entry counts — (pr, pc, l, wl).

    Host numpy ORACLE of the on-grid mask counts ``_symbolic3d_jit`` now
    emits (the planner path no longer round-trips the mask's cols/nnz
    arrays); kept for parity testing — mask values never matter, and the
    result is exact, so the mask-selection capacity it sizes cannot
    overflow.
    """
    C = np.asarray(mask.cols)
    N = np.asarray(mask.nnz)
    pr, pc, l, cap = C.shape
    tn = mask.tile_shape[1]
    valid = np.arange(cap)[None, None, None, :] < N[..., None]
    tile = np.arange(pr * pc * l).reshape(pr, pc, l, 1)
    flat = tile * (tn + 1) + np.where(valid, C, tn)
    cnt = np.bincount(flat.ravel(), minlength=pr * pc * l * (tn + 1))
    return cnt.reshape(pr, pc, l, tn + 1)[..., :tn].astype(np.int64)


def symbolic3d_counts(
    a: DistSparse, b: DistSparse, grid: Grid, mask: Optional[DistSparse] = None
) -> SymbolicCounts:
    """Run the distributed symbolic step; see ``SymbolicCounts``.

    ``mask`` (C-layout, same global shape as the product) additionally emits
    the masked output counts the §V-B applications plan with — computed
    inside the same jitted shard_map pass, so only the (pr, pc, l, wl)
    count vectors ever reach the host.
    """
    mask_cc = None
    if mask is not None:
        assert mask.kind in ("A", "C"), mask.kind
        assert mask.shape == (a.shape[0], b.shape[1]), (mask.shape, a.shape, b.shape)
        percol, bcc, mcc = _symbolic3d_jit(a, b, mask, grid)
        mask_cc = np.asarray(mcc).astype(np.int64)
    else:
        percol, bcc = _symbolic3d_jit(a, b, None, grid)
    return SymbolicCounts(
        percol=np.asarray(percol),
        b_colcounts=np.asarray(bcc),
        mask_colcounts=mask_cc,
    )


def symbolic3d(a: DistSparse, b: DistSparse, grid: Grid) -> np.ndarray:
    """Per-(process, local column of B) flops upper bound.

    Returns host array of shape (pr, pc, l, tn_b):
      flops[i,j,k,c] = Σ_{t ∈ B(:, block j, layer k), col(t)=c}
                           nnz(A^(k)(row-block i, k_idx(t)))

    which is exactly the number of partial products process (i,j,k) forms for
    output column c in the numeric step (A gathered over the grid row, B over
    the grid column group). ``symbolic3d_counts`` exposes the fuller payload
    (including the masked output counts when its ``mask`` is given).
    """
    return symbolic3d_counts(a, b, grid).percol


@dataclasses.dataclass(frozen=True)
class BatchPlan:
    """Host-side plan produced by the symbolic step."""

    num_batches: int
    lower_bound: int  # Eq. (2)
    caps: BatchCaps
    total_flops: int  # Σ multiply ops (global)
    max_unmerged_nnz: int  # max over processes, b=1 (mask-filtered if masked)
    per_batch_flops: np.ndarray  # (num_batches,) global flops per batch
    sel_cap: int = 0  # exact per-batch selection capacity (B entries)
    mask_sel_cap: int = 0  # exact per-batch mask-slice capacity (masked only)
    local_path: str = "esc"  # plan-driven local-multiply decision (b=1 view)
    hash_caps: Optional[HashCaps] = None  # static hash caps (local_path="hash")
    compression_est: float = 1.0  # flops per merged survivor (b=1, max proc)
    path_reason: str = ""  # why the planner picked ``local_path`` (and b)


def plan_batches(
    a: DistSparse,
    b: DistSparse,
    grid: Grid,
    per_process_memory: int,
    spec: Optional[PlanSpec] = None,
    floors: Optional[PlanFloors] = None,
    **legacy,
) -> BatchPlan:
    """Run the symbolic step and derive b + static capacities (host math).

    The planning policy lives on ``spec`` (`PlanSpec`) and the cross-plan
    capacity pins on ``floors`` (`PlanFloors`); the old keyword surface
    (``r_bytes=``, ``slack=``, ``caps_floor=``, …) is still accepted for one
    release and mapped onto the specs with a ``DeprecationWarning``. A bare
    call (no spec) keeps the historical ``local_path="esc"`` default; a
    passed spec uses its own default ("auto" — the driver's semantics).

    ``spec.local_path`` drives the local-multiply decision recorded on the
    plan. Each path is budgeted at what its compiled step holds: "esc" at
    ``ESC_SLOT_RECORDS`` r-byte records per expansion slot (expansion,
    sort and outputs, slack included); "hash" at the hash-accumulator's
    O(nnz_out·load_factor) resident bytes instead of O(flops) — high
    compression-factor multiplies then need strictly fewer batches at the
    same ``per_process_memory``; "dense" (set by the driver for
    ``path="dense"``) at the dense step's f32 buffers, whose gather of one
    B row per gathered A entry grows with the batch width; "auto" picks
    "hash" when the estimated compression factor clears
    ``HASH_CF_THRESHOLD`` and ESC otherwise. The hash table packs a D tile's
    (row, col) into one i32 key, so the plan only runs hash where that key
    fits: a forced "hash" raises b until it does, "auto" takes ESC instead.
    ``plan.path_reason`` says which rule decided. ``floors.hash_caps``
    floors the derived ``HashCaps`` elementwise (iterated-multiply
    jit-cache stability, like ``floors.caps``).

    ``spec.reserved_bytes`` is subtracted from the per-process budget before the
    Alg. 3 batch count: memory the caller has already committed per process
    to the CONSUMED outputs (e.g. the pruned batches a memory-constrained MCL
    iteration keeps on-device for the next iterate, §V-C) — so the budget
    honors what actually lives alongside the unmerged batch results.

    ``mask`` switches on masked planning (§V-B): with a strict mask
    (``mask_complement=False``) the surviving output structure is bounded by
    the mask's exact per-column counts, so the unmerged budget, the batch
    count, and the D/piece/C capacities all shrink to survivors —
    per column c of process (i,j,k):

      unmerged ≤ min(flops[c], mask[c] · nnz(B_gathered(:, c)))  (pre-merge)
      merged D ≤ min(flops[c], mask[c])                          (post-merge)
      merged C ≤ min(Σ_k flops[k][c], mask[c])

    (a complement mask excludes structure, so it cannot tighten counts —
    the plan stays at the unmasked bounds and only the numeric filter runs).
    ``mask_sel_cap`` is sized from the exact mask counts, so the per-batch
    mask-slice selection can never overflow.

    Memory-model semantics: the Alg. 3 budget charges r·nnz of *stored*
    unmerged results — in the paper's hash SpGEMM partial products are
    consumed on the fly, and the masked counts above model exactly the
    stored survivors. Our ESC rendering does materialize an UNMASKED
    ``flops_cap`` expansion scratch per batch (the filter runs between
    expansion and compress), so on memory-bound hardware that transient is
    the masked path's true high-water mark; gating the expansion itself is
    the ROADMAP follow-up that removes it.

    ``floors.caps_pow2`` rounds every derived capacity up to the next power
    of two and ``floors.caps``/``floors.sel_cap`` take an elementwise max
    with a previous plan's capacities — together they keep the fused step's
    static signature stable across the iterations of an iterated multiply
    (MCL), so per-iteration cap drift hits the jit cache instead of
    recompiling.
    """
    spec, floors, _ = resolve_specs(
        spec, floors, None, legacy, default_local_path="esc",
        where="plan_batches", allow_exec=False,
    )
    with span("spgemm.plan"):
        with span("spgemm.symbolic"):
            counts = symbolic3d_counts(a, b, grid, mask=spec.mask)
        with span("spgemm.plan_host"):
            inputs = PlanInputs(
                tm_a=a.tile_shape[0],
                max_nnz_a=int(np.asarray(a.nnz).max()),
                max_nnz_b=int(np.asarray(b.nnz).max()),
                nnz_a=int(np.asarray(a.nnz).sum()),
                nnz_b=int(np.asarray(b.nnz).sum()),
                cap_a=a.cap,
                cap_b=b.cap,
                p=grid.p,
                cap_mask=spec.mask.cap if spec.mask is not None else None,
                k_dim=a.shape[1],
            )
            return plan_from_symbolic(
                counts, inputs, per_process_memory, spec, floors
            )


@dataclasses.dataclass(frozen=True)
class PlanInputs:
    """Scalar operand facts ``plan_from_symbolic`` needs besides the count
    vectors — constructible from scattered operands (``plan_batches``) or
    from host COO + a candidate grid shape (``PlanInputs.from_host``, the
    autotuner's no-device oracle path)."""

    tm_a: int  # A/C tile rows (m // pr)
    max_nnz_a: int  # max per-tile nnz of scattered A
    max_nnz_b: int
    nnz_a: int  # global nnz(A)
    nnz_b: int
    cap_a: int  # static per-tile capacity of scattered A
    cap_b: int
    p: int  # process count pr*pc*l
    cap_mask: Optional[int] = None
    k_dim: int = 0  # contraction dimension (A's columns; dense-path plans)

    @classmethod
    def from_host(cls, a, b, grid_shape: Tuple[int, int, int],
                  mask=None, cap_slack: float = 1.3, min_cap: int = 8,
                  ) -> "PlanInputs":
        """Build the scalar facts for a CANDIDATE grid from host COO —
        per-tile nnz maxima via the layout math (no scatter), static
        capacities via ``scatter_to_grid``'s default sizing rule, so the
        oracle plan matches what a default scatter would produce."""
        from .symbolic import host_tile_counts

        def _cap(counts):
            return max(int(np.ceil(counts.max() * cap_slack)), min_cap)

        ca = host_tile_counts(a, grid_shape, "A")
        cb = host_tile_counts(b, grid_shape, "B")
        pr, pc, l = grid_shape
        return cls(
            tm_a=a.shape[0] // pr,
            max_nnz_a=int(ca.max()),
            max_nnz_b=int(cb.max()),
            nnz_a=int(a.nnz),
            nnz_b=int(b.nnz),
            cap_a=_cap(ca),
            cap_b=_cap(cb),
            p=pr * pc * l,
            cap_mask=(
                _cap(host_tile_counts(mask, grid_shape, "C"))
                if mask is not None else None
            ),
            k_dim=a.shape[1],
        )


def plan_from_symbolic(
    counts: SymbolicCounts,
    inputs: PlanInputs,
    per_process_memory: int,
    spec: PlanSpec,
    floors: PlanFloors,
) -> BatchPlan:
    """Pure host planning math — ``plan_batches`` minus the device pass.

    Everything downstream of the symbolic counts is numpy over count
    vectors, so the SAME function plans a real multiply (counts from the
    distributed pass) and prices a hypothetical one (counts from
    ``symbolic.host_symbolic_counts`` for a candidate grid the operands were
    never scattered to) — which is what lets ``repro.tune`` enumerate grids
    without touching a device.
    """
    r_bytes, slack = spec.r_bytes, spec.slack
    force_num_batches = spec.force_num_batches
    reserved_bytes = spec.reserved_bytes
    mask_complement = spec.mask_complement
    local_path = spec.local_path
    caps_pow2, caps_floor = floors.caps_pow2, floors.caps
    sel_cap_floor, num_batches_floor = floors.sel_cap, floors.num_batches
    hash_caps_floor = floors.hash_caps
    if reserved_bytes >= per_process_memory:
        raise MemoryError(
            f"reserved output bytes ({reserved_bytes}) exceed per-process "
            f"memory ({per_process_memory})"
        )
    per_process_memory = per_process_memory - reserved_bytes
    # pluggable tile→batch distribution: every fold below routes through it
    # (BLOCK_CYCLIC delegates to the historical fold_block_cyclic math)
    dist = spec.distribution if spec.distribution is not None else BLOCK_CYCLIC
    percol = counts.percol  # (pr, pc, l, tn_b)
    pr, pc, l, tn_b = percol.shape
    masked = counts.mask_colcounts is not None and not mask_complement
    if masked:
        # mcount[i, j, c]: mask entries of (row block i, col block j) at
        # block-local column c — the (l, wl) mask tiles laid out layer-major
        # cover exactly the w = tn_b local columns of the block.
        mcount = counts.mask_colcounts.reshape(pr, pc, tn_b)
        bcolg = counts.b_colcounts.sum(axis=0, keepdims=True)  # (1,pc,l,tn_b)
        unmerged_percol = np.minimum(percol, mcount[:, :, None, :] * bcolg)
        merged_d_percol = np.minimum(percol, mcount[:, :, None, :])
    else:
        unmerged_percol = percol
        merged_d_percol = percol
    per_process_flops = percol.sum(axis=-1)  # (pr, pc, l)
    max_unmerged = int(unmerged_percol.sum(axis=-1).max())
    total_flops = int(per_process_flops.sum())
    max_nnz_a = inputs.max_nnz_a
    max_nnz_b = inputs.max_nnz_b

    # hash-path resident bound (O(output)): the table holds MERGED
    # survivors, and a D-tile column cannot exceed tm_a distinct rows
    assert local_path in ("auto", "esc", "hash", "dense"), local_path
    tm_a = inputs.tm_a
    max_hash_nnz = int(np.minimum(merged_d_percol, tm_a).sum(axis=-1).max())
    compression_est = max_unmerged / max(max_hash_nnz, 1)
    budget_hash = local_path == "hash" or (
        local_path == "auto" and compression_est >= HASH_CF_THRESHOLD
    )

    def batches_for(hash_budget: bool) -> int:
        if force_num_batches is not None:
            return dist.round_batches(tn_b, force_num_batches, l)
        # the compiled step's bytes at b = 1, converted back to r-byte units
        # for Alg. 3 (every term is linear in the batch width)
        if local_path == "dense":
            # (pc gathered A tiles) x width gathers, B block and accumulator
            step_bytes = dense_step_bytes(
                tn_b, pc * inputs.cap_a, inputs.k_dim // l, tm_a
            )
        elif hash_budget:
            # the stored intermediate is the table, not the expansion
            step_bytes = estimate_mem_c_bytes(
                max_unmerged, compression_est, r_bytes,
                local_path="hash", load_factor=HASH_LOAD_FACTOR,
            )
        else:
            # ESC: the expansion, sort and outputs of flops_cap slots, which
            # carry the plan's slack over the counted (survivor) products
            step_bytes = esc_step_bytes(slack * max_unmerged, r_bytes)
        budget_nnz = max(-(-step_bytes // r_bytes), 1)
        # num_batches is part of the fused step's static signature; the
        # floor (a previous iteration's count — more batches is always
        # valid) keeps iterated multiplies on one executable as nnz drifts.
        nb_ = max(
            batch_count(
                budget_nnz, max_nnz_a, max_nnz_b, per_process_memory,
                r=r_bytes,
            ),
            num_batches_floor,
        )
        return dist.round_batches(tn_b, nb_, l)

    def hash_key_fits(nb_: int) -> bool:
        # the hash table packs the (row, col) of a tm_a × wb D tile into
        # one i32 key (spgemm_hash asserts the same)
        return sortkeys.fits_i32(tm_a, tn_b // nb_)

    nb = batches_for(budget_hash)
    if local_path == "dense":
        path_reason = "dense path"
    elif local_path == "auto":
        path_reason = (
            f"auto: compression {compression_est:.3g} "
            f"{'>=' if budget_hash else '<'} {HASH_CF_THRESHOLD}"
        )
    else:
        path_reason = f"{local_path} requested"
    if budget_hash and not hash_key_fits(nb):
        if local_path == "hash":
            if force_num_batches is not None:
                raise ValueError(
                    f"hash path forced at {nb} batches, but the packed key "
                    f"of a {tm_a}x{tn_b // nb} D tile overflows i32"
                )
            nb0 = nb
            while not hash_key_fits(nb):
                nb = dist.round_batches(tn_b, nb + 1, l)
            path_reason += (
                f"; b raised {nb0} -> {nb} so the packed key of a "
                f"{tm_a}x{tn_b // nb} D tile fits i32"
            )
        else:
            budget_hash = False
            path_reason += (
                f", but the packed key of a {tm_a}x{tn_b // nb} D tile "
                f"overflows i32 -> esc"
            )
            nb = batches_for(False)

    # per-(process, batch, piece) flops via the distribution's fold
    flops_pbp = dist.fold(percol, nb, l)  # (pr,pc,l,nb,l)
    per_batch_proc = flops_pbp.sum(axis=-1)  # (pr,pc,l,nb)
    max_batch_flops = int(per_batch_proc.max())
    # D-tile bounds come from the mask-filtered counts (the filter runs
    # before the compress, so survivors alone occupy the static buffers)
    d_pbp = dist.fold(merged_d_percol, nb, l)
    max_batch_d = int(d_pbp.sum(axis=-1).max())
    max_piece_flops = int(d_pbp.max())
    # merged C piece bound: sum over source layers, mask-capped per column
    merged_col = percol.sum(axis=2)  # (pr, pc, tn_b)
    if masked:
        merged_col = np.minimum(merged_col, mcount)
    merged_piece = dist.fold(merged_col, nb, l).max()

    wb = tn_b // nb
    flops_cap = _rup8(max(int(max_batch_flops * slack), 64))
    d_cap = _rup8(
        min(max(int(max_batch_d * slack), 64), flops_cap, tm_a * wb)
    )
    piece_cap = _rup8(min(max(int(max_piece_flops * slack), 64), tm_a * (wb // l)))
    c_cap = _rup8(min(max(int(merged_piece * slack), 64), tm_a * (wb // l)))
    caps = BatchCaps(flops_cap=flops_cap, d_cap=d_cap, piece_cap=piece_cap, c_cap=c_cap)

    # exact per-batch selection capacity: max over (process, batch) of the
    # number of B entries the distribution's selection keeps — from the
    # symbolic B-column counts, so the first batch can never trigger a
    # spurious selection retry on skewed inputs.
    sel_per_batch = dist.fold(counts.b_colcounts, nb, l).sum(axis=-1)
    sel_cap = min(_rup8(max(int(sel_per_batch.max()), 8)), inputs.cap_b)

    # exact per-batch mask-slice capacity: batch bi selects the contiguous
    # local columns [bi·wbl, (bi+1)·wbl) of every mask tile.
    mask_sel_cap = 0
    if counts.mask_colcounts is not None:
        per_batch_mask = dist.fold_batch_slices(counts.mask_colcounts, nb)
        mask_sel_cap = min(
            _rup8(max(int(per_batch_mask.max()), 8)), inputs.cap_mask
        )

    if caps_pow2:
        caps = BatchCaps(*(_rup_pow2(x) for x in dataclasses.astuple(caps)))
        sel_cap = min(_rup_pow2(sel_cap), inputs.cap_b)
        if counts.mask_colcounts is not None:
            mask_sel_cap = min(_rup_pow2(mask_sel_cap), inputs.cap_mask)
    if caps_floor is not None:
        caps = BatchCaps(*(
            max(x, y) for x, y in zip(
                dataclasses.astuple(caps), dataclasses.astuple(caps_floor)
            )
        ))
    sel_cap = max(sel_cap, sel_cap_floor)

    # Eq. (2) lower bound (global memory form) for reporting/validation
    mem_c = r_bytes * int(per_process_flops.sum())
    try:
        lb = batch_count_lower_bound(
            mem_c, per_process_memory * inputs.p,
            inputs.nnz_a, inputs.nnz_b, r=r_bytes,
        )
    except MemoryError:
        lb = -1

    # plan-driven local-multiply decision + static hash caps. Both derive
    # from the already-quantized/floored capacities, so iterated runs with
    # pow2 caps keep ONE fused-step executable per decided path.
    decided = "dense" if local_path == "dense" else (
        "hash" if budget_hash else "esc"
    )
    hash_caps = None
    if decided == "hash":
        chunk = min(caps.flops_cap, _rup8(HASH_CHUNK_CAP))
        num_chunks = -(-caps.flops_cap // chunk)
        table = _rup_pow2(max(int(HASH_LOAD_FACTOR * caps.d_cap), 64))
        hash_caps = HashCaps(
            table_cap=table, chunk_cap=chunk, num_chunks=num_chunks
        )
        if hash_caps_floor is not None:
            hash_caps = HashCaps(
                table_cap=max(hash_caps.table_cap, hash_caps_floor.table_cap),
                chunk_cap=max(hash_caps.chunk_cap, hash_caps_floor.chunk_cap),
                num_chunks=max(
                    hash_caps.num_chunks, hash_caps_floor.num_chunks
                ),
                max_probes=max(
                    hash_caps.max_probes, hash_caps_floor.max_probes
                ),
            )

    per_batch_flops = per_batch_proc.sum(axis=(0, 1, 2))  # (nb,)
    return BatchPlan(
        num_batches=nb,
        lower_bound=lb,
        caps=caps,
        total_flops=total_flops,
        max_unmerged_nnz=max_unmerged,
        per_batch_flops=per_batch_flops,
        sel_cap=sel_cap,
        mask_sel_cap=mask_sel_cap,
        local_path=decided,
        hash_caps=hash_caps,
        compression_est=float(compression_est),
        path_reason=path_reason,
    )


def probe_memory_budget(
    a: DistSparse, b: DistSparse, grid: Grid,
    r_bytes: int = 12, fraction: int = 3, floor: int = 256,
) -> int:
    """A per-process budget that forces the (unmasked) plan to batch:
    inputs plus 1/``fraction`` of the ESC step's bytes for the probed
    unmerged output (so the ESC plan takes about ``fraction`` batches).

    Shared by the graph bench and the slow-lane R-MAT cases so both assert
    the §V-B masked-vs-unmasked claim against the SAME budget math (the
    symbolic probe is jit-cached — replanning is cheap).
    """
    probe = plan_batches(a, b, grid, per_process_memory=1 << 30,
                         spec=PlanSpec(local_path="esc", r_bytes=r_bytes))
    inputs = r_bytes * (
        int(np.asarray(a.nnz).max()) + int(np.asarray(b.nnz).max())
    )
    step = esc_step_bytes(PlanSpec().slack * probe.max_unmerged_nnz, r_bytes)
    return inputs + max(step // fraction, floor)


def batch_column_map(n: int, grid: Grid, num_batches: int, batch: int) -> np.ndarray:
    """Global columns covered by ``batch``, in C-tile order.

    Returns g[j, k, c] of shape (pc, l, wb/l): the global column of local
    column c in C tile (:, j, k) for this batch. Inverse of the block-cyclic
    selection + fiber split (delegates to the distribution object — the
    triple-loop reference lives in the placement contract tests).
    """
    return BLOCK_CYCLIC.batch_column_map(n, grid.pc, grid.l, num_batches, batch)


# ---------------------------------------------------------------------------
# The batched driver (Alg. 4) — pipelined scheduler
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RunReport:
    """Structured robustness accounting for one driver run or iterated loop.

    ``batched_summa3d`` fills the ladder fields (retries / replans /
    degradations); the resilient iterated loops (`runtime/resilient.py`)
    merge per-iteration reports and add the checkpoint / straggler / restart
    fields. JSON round-trips via `to_dict`/`from_dict` so the report itself
    survives a checkpoint.
    """

    retries: int = 0  # overflow retry dispatches (sync ladder steps)
    sel_retries: int = 0  # selection-capacity retries among those
    replans: int = 0  # batches replanned at finer batching (degradation)
    ladder_blocked: int = 0  # cap doublings refused by the memory ceiling
    degraded_batches: Tuple[Tuple[int, int], ...] = ()  # (batch, split)
    straggler_events: int = 0  # EWMA watchdog firings (iterated loops)
    restarts: int = 0  # preemption restore-and-continue count
    refused_restores: int = 0  # corrupt checkpoints refused at restore
    checkpoint_stalls: int = 0  # saves that blocked on a prior in-flight write
    checkpoint_stall_s: float = 0.0
    checkpoint_bytes: int = 0  # total checkpoint bytes written

    def merged(self, other: "RunReport") -> "RunReport":
        """Field-wise accumulation (counts add, degradations concatenate)."""
        return RunReport(
            retries=self.retries + other.retries,
            sel_retries=self.sel_retries + other.sel_retries,
            replans=self.replans + other.replans,
            ladder_blocked=self.ladder_blocked + other.ladder_blocked,
            degraded_batches=self.degraded_batches + other.degraded_batches,
            straggler_events=self.straggler_events + other.straggler_events,
            restarts=self.restarts + other.restarts,
            refused_restores=self.refused_restores + other.refused_restores,
            checkpoint_stalls=self.checkpoint_stalls + other.checkpoint_stalls,
            checkpoint_stall_s=self.checkpoint_stall_s + other.checkpoint_stall_s,
            checkpoint_bytes=self.checkpoint_bytes + other.checkpoint_bytes,
        )

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["degraded_batches"] = [list(x) for x in self.degraded_batches]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "RunReport":
        d = dict(d)
        d["degraded_batches"] = tuple(
            tuple(int(v) for v in x) for x in d.get("degraded_batches", ())
        )
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def plan_footprint(
    caps: BatchCaps,
    sel_cap: int,
    hash_caps: Optional[HashCaps],
    *,
    r_bytes: int,
    max_nnz_a: int,
    max_nnz_b: int,
    reserved_bytes: int = 0,
) -> int:
    """Per-process bytes a capacity plan commits to, aligned with the
    planner's budget: ``r`` bytes per stored entry of inputs + selection +
    the batch's step (``ESC_SLOT_RECORDS`` r-byte records per ESC expansion
    slot, or the hash table + merged survivors). The retry ladder prices
    cap doublings against this model, and the serving engine prices each
    admitted request with it.
    """
    if hash_caps is not None:
        inter = hash_caps.table_cap * HASH_SLOT_BYTES + r_bytes * caps.d_cap
    else:
        inter = esc_step_bytes(caps.flops_cap, r_bytes)
    return r_bytes * (max_nnz_a + max_nnz_b + sel_cap) + inter + reserved_bytes


def _retry_kind(ovf: np.ndarray) -> str:
    """Which capacity an overflow retry grows (``grow`` in
    ``batched_summa3d``): the selection first, else the multiply."""
    return "sel" if ovf[0] > 0 else "mul"


class _LadderBlocked(Exception):
    """Raised inside the retry ladder when the next cap doubling would blow
    the per-process memory ceiling — caught by the degradation path, which
    replans the batch at finer batching instead of OOMing."""


@partial(jax.jit, static_argnames=("grid",))
def _merge_split_batches(parts: Tuple[DistSparse, ...], grid: Grid) -> DistSparse:
    """Column-concat ``d`` sub-batch products (finer plan ``nb·d``) back into
    ONE batch of the original ``nb``-batch plan.

    Block-cyclic algebra: original batch ``bi`` under plan ``nb`` covers the
    same global columns as batches ``{d·bi, …, d·bi+d−1}`` under plan
    ``nb·d``, and sub-batch ``d·bi+q``'s tile layer holds exactly slice
    ``q`` (width ``wbl/d``) of every original batch block — so the merge is
    an offset column concat + row-major resort. The merged entry set equals
    the undegraded batch's, so consumers see an identical product (only the
    static cap is the sum of the sub caps).
    """
    parts = tuple(parts)
    sub_w = parts[0].tile_shape[1]
    widths = [sub_w] * len(parts)
    cap = sum(p.cap for p in parts)
    tm = parts[0].tile_shape[0]
    wbl = sub_w * len(parts)

    def step(*tiles):
        mats = [_squeeze_tile(t) for t in tiles]
        merged, _ = hstack_remap(mats, widths, cap)  # cap = Σ caps: lossless
        merged = merged.sort_rowmajor()
        return (
            merged.rows[None, None, None], merged.cols[None, None, None],
            merged.vals[None, None, None], merged.nnz[None, None, None],
        )

    spec3 = jax.sharding.PartitionSpec(ROW_AX, COL_AX, LAYER_AX)
    fn = shard_map(
        step, mesh=grid.mesh,
        in_specs=tuple(dist_spec(p, spec3) for p in parts),
        out_specs=(spec3,) * 4,
        check_vma=False,
    )
    rows, cols, vals, nnz = fn(*parts)
    c0 = parts[0]
    return DistSparse(
        rows=rows, cols=cols, vals=vals, nnz=nnz,
        shape=(c0.shape[0], c0.shape[1] * len(parts)),
        tile_shape=(tm, wbl), grid_shape=c0.grid_shape, kind="C",
    )


@dataclasses.dataclass
class BatchedResult:
    plan: BatchPlan
    num_retries: int
    consumed: list  # consumer outputs per batch
    local_path: str = "esc"  # local multiply actually executed
    hash_caps: Optional[HashCaps] = None  # the static HashCaps used (hash)
    report: RunReport = dataclasses.field(default_factory=RunReport)

    def floors(self) -> PlanFloors:
        """The capacities this run actually used, as a `PlanFloors` an
        iterated caller merges into its next plan — ONE field replaces the
        old caps/sel/nb/hash attribute quartet (pow2 quantization on,
        since that is the whole point of pinning)."""
        return PlanFloors(
            caps=self.plan.caps,
            sel_cap=self.plan.sel_cap,
            num_batches=self.plan.num_batches,
            hash_caps=self.hash_caps,
            caps_pow2=True,
        )


def batched_summa3d(
    a: DistSparse,
    b: DistSparse,
    grid: Grid,
    per_process_memory: int,
    consumer: Callable[[int, object, np.ndarray], object],
    path: str = "sparse",
    semiring: sr.Semiring = sr.PLUS_TIMES,
    spec: Optional[PlanSpec] = None,
    floors: Optional[PlanFloors] = None,
    exec_spec: Optional[ExecSpec] = None,
    postprocess: Optional[Callable[[int, object], object]] = None,
    **legacy,
) -> BatchedResult:
    """Multiply A·B in batches; the consumer sees each batch then it's freed.

    The knob surface is three frozen specs: ``spec`` (`PlanSpec` — mask,
    local path, slack, reserved bytes), ``floors``
    (`PlanFloors` — cross-iteration capacity pins, fold a previous run's
    ``BatchedResult.floors()`` in via ``merged()``), and ``exec_spec``
    (`ExecSpec` — pipelined schedule, lookahead, retry budget, degradation).
    The old keyword surface (``slack=``, ``lookahead=``, ``caps_floor=``, …)
    is accepted for one release and mapped onto the specs with a
    ``DeprecationWarning``.

    consumer(batch_idx, c_batch, global_col_map) -> anything; c_batch is a
    DistSparse (path="sparse") or stacked dense tiles (path="dense").
    ``exec_spec.sorted_merge`` selects the segmented (merge-not-sort)
    Merge-Fiber in the per-batch sparse step.

    ``spec.mask`` runs the masked/filtered SpGEMM (§V-B): a C-layout
    ``DistSparse`` whose structure gates the output — consumers receive
    C ⊙ M (or C ⊙ ¬M under ``mask_complement=True``). The mask stays
    device-resident: the plan budgets only surviving entries (strict mode),
    and each batch's mask slice is selected + fiber-gathered inside the
    fused step. ``floors`` quantizes (``caps_pow2``) and floors the planned
    capacities (see ``plan_batches``) so iterated callers reuse one
    fused-step executable across iterations.

    ``postprocess(batch_idx, c_batch) -> c_batch'`` is the DEVICE-side
    per-batch hook (HipMCL integration, §V-C): a jitted transform applied to
    the raw batch product immediately after the fused SPMD step and BEFORE
    the host consumer — under the pipelined schedule it is dispatched
    together with the batch, so e.g. inflation+normalize+prune run on-grid
    while later batches are still multiplying, and only the postprocessed
    batch is ever offered to the host. The consumer then receives the hook's
    return value (which may be any pytree, e.g. ``(pruned, stats)``) in place
    of the raw batch. On an overflow retry the hook re-runs on the retried
    product. ``spec.reserved_bytes`` flows into ``plan_batches``:
    per-process memory already committed to the consumed outputs.

    ``exec_spec.pipelined=True`` (default) runs the Alg. 4 loop as a
    lookahead window: batch i+1..i+lookahead are dispatched before batch i's
    device-resident overflow flags are read, so selection/gather of the next
    batch overlaps the previous multiply and the consumer's host work
    overlaps device compute. A nonzero flag drops that batch to the
    synchronous retry loop (capacities ×2 per attempt — selection first,
    multiply second). ``pipelined=False`` is the serial schedule: one host
    sync per batch. Consumers are always invoked in batch order.

    ``spec.local_path`` is the plan-driven dispatch over the ESC and
    hash-accumulator local multiplies (both any semiring): "auto" (default)
    lets the plan pick — hash when the compression factor clears
    ``HASH_CF_THRESHOLD`` and its packed key fits i32 (the plan then
    budgets O(nnz_out·load_factor) resident bytes, so high-cf multiplies
    batch less), else ESC; "hash"/"esc" force a path. One ``local_path``
    decision is made per plan (not per batch) so iterated runs keep ONE
    executable per path; ``floors.hash_caps`` keeps its static caps
    monotone across iterations.

    ``exec_spec.degrade`` (default on) bounds the retry ladder at a
    per-process memory ceiling: when doubling the multiply caps would exceed
    ``max(per_process_memory, footprint(planned caps))`` — the planned-caps
    arm keeps legitimately-over-budget plans (slack, uncharged scratch)
    runnable while refusing runaway growth beyond them — the failing batch
    is REPLANNED at finer batching (its columns run as ``d`` sub-batches
    under a ``nb·d`` plan, then column-concat back to the original batch
    extent) instead of OOMing. Every retry/replan lands in the structured
    ``BatchedResult.report`` (`RunReport`). ``degrade=False`` restores the
    unbounded ladder.

    The call runs inside the host span ``spgemm.call`` (module docstring).
    """
    with span("spgemm.call"):
        spec, floors, ex = resolve_specs(
            spec, floors, exec_spec, legacy, default_local_path="auto",
            where="batched_summa3d",
        )
        return _batched_summa3d(
            a, b, grid, per_process_memory, consumer, path, semiring, spec,
            floors, ex, postprocess,
        )


def _batched_summa3d(
    a, b, grid, per_process_memory, consumer, path, semiring, spec, floors,
    ex, postprocess,
) -> BatchedResult:
    """``batched_summa3d`` with its specs resolved, inside its span."""
    placement = spec.placement
    if placement is not None and not isinstance(placement, Placement):
        raise ValueError(
            f"spec.placement must be a core.placement.Placement whose "
            f"permutations the operands ALREADY carry, got {placement!r} — "
            f"use placement.multiply_placed (or compute_placement + "
            f"apply_a/apply_b) to permute host operands before scattering"
        )
    if spec.distribution is not None and (
        getattr(spec.distribution, "name", None) != BLOCK_CYCLIC.name
    ):
        raise ValueError(
            f"the fused device step implements only the block-cyclic "
            f"distribution; got {spec.distribution!r}. Custom Distribution "
            f"objects are planner-side — price them via plan_from_symbolic."
        )
    r_bytes, slack = spec.r_bytes, spec.slack
    reserved_bytes = spec.reserved_bytes
    mask, mask_complement = spec.mask, spec.mask_complement
    local_path = spec.local_path
    pipelined = ex.pipelined
    max_retries, degrade = ex.max_retries, ex.degrade
    sorted_merge = ex.sorted_merge
    assert local_path in ("auto", "esc", "hash"), local_path
    # the dense path plans its own footprint; the plan only budgets the
    # hash path when the driver could dispatch it
    plan_local_path = "dense" if path == "dense" else local_path
    plan = plan_batches(
        a, b, grid, per_process_memory,
        spec=spec.replace(local_path=plan_local_path), floors=floors,
    )
    nb = plan.num_batches
    n_cols = b.shape[1]

    use_hash = path == "sparse" and plan.local_path == "hash"
    hc = plan.hash_caps if use_hash else None
    if use_hash:
        assert hc is not None, "hash dispatch requires planned HashCaps"

    caps, sel_cap, mask_cap = plan.caps, plan.sel_cap, plan.mask_sel_cap
    retries = 0
    rep = {"sel_retries": 0, "replans": 0, "ladder_blocked": 0,
           "degraded": []}

    # --- bounded retry ladder (graceful degradation) -----------------------
    # The ceiling takes a max with the PLANNED caps' footprint
    # (`plan_footprint`): a plan is allowed to exceed the strict budget
    # (slack and uncharged scratch make that routine at tight budgets), but
    # the ladder may never grow beyond whichever is larger.
    with span("spgemm.ladder_ceiling"):
        max_nnz_a = int(np.asarray(a.nnz).max())
        max_nnz_b = int(np.asarray(b.nnz).max())

    def _footprint(caps_: BatchCaps, sel_cap_: int, hc_) -> int:
        return plan_footprint(
            caps_, sel_cap_, hc_, r_bytes=r_bytes, max_nnz_a=max_nnz_a,
            max_nnz_b=max_nnz_b, reserved_bytes=reserved_bytes,
        )

    ladder_ceiling = max(per_process_memory, _footprint(caps, sel_cap, hc))

    def dispatch(
        bi: int, caps_: BatchCaps, sel_cap_: int, hc_, mask_cap_: int
    ):
        """Async-dispatch one fused batch step; nothing blocks here."""
        with span("spgemm.dispatch", batch=bi):
            return _fused_jit(
                a, b, jnp.int32(bi), mask, grid=grid, num_batches=nb,
                sel_cap=sel_cap_, caps=caps_, semiring=semiring,
                sorted_merge=sorted_merge, path=path, hashc=hc_,
                mask_cap=mask_cap_, mask_complement=mask_complement,
            )

    def wait(bi: int, ovf) -> np.ndarray:
        """The host's sync point: batch bi's overflow flags, read back."""
        with span("spgemm.wait", batch=bi):
            return np.asarray(ovf)

    # capacities actually used, including retry growth — reported on the
    # returned plan so iterated callers (MCL) floor their NEXT plan on
    # reality instead of replaying a known-too-small estimate every
    # iteration. Dispatch defaults stay at the planned values within this
    # run: the pipelined and serial schedules must remain batch-identical
    # (each batch's retry ladder grows from the same base).
    used = {"caps": caps, "sel": sel_cap, "hashc": hc,
            "mask": mask_cap}

    def grow(
        o: np.ndarray, caps_: BatchCaps, sel_cap_: int, hc_,
        mask_cap_: int, record: bool = True,
    ):
        """Next capacity plan after an overflow: selection first (a truncated
        selection makes the multiply flags unreliable), multiply second.
        The mask-slice capacity is exact, but it is doubled alongside the
        multiply caps anyway so the retry ladder stays monotone.

        With ``degrade`` on, a multiply-cap doubling that would exceed the
        memory ceiling raises `_LadderBlocked` instead — the caller replans
        at finer batching. ``record=False`` (degraded sub-batches) skips the
        ``used``-floor bookkeeping: sub-plan caps live in a different static
        signature space than the reported plan."""
        if o[0] > 0:
            sel_cap_ = min(_rup8(max(sel_cap_ * 2, 8)), b.cap)
            rep["sel_retries"] += 1
        elif o[1] > 0:
            cand_caps = caps_.doubled()
            cand_hc = hc_.doubled() if hc_ is not None else None
            if degrade and _footprint(cand_caps, sel_cap_, cand_hc) > ladder_ceiling:
                rep["ladder_blocked"] += 1
                raise _LadderBlocked(
                    f"cap doubling to {cand_caps} exceeds the "
                    f"{ladder_ceiling}-byte ceiling"
                )
            caps_, hc_ = cand_caps, cand_hc
            if mask is not None:
                mask_cap_ = min(mask_cap_ * 2, mask.cap)
        if not record:
            return caps_, sel_cap_, hc_, mask_cap_
        used["sel"] = max(used["sel"], sel_cap_)
        used["mask"] = max(used["mask"], mask_cap_)
        used["caps"] = BatchCaps(*(
            max(x, y) for x, y in zip(
                dataclasses.astuple(used["caps"]), dataclasses.astuple(caps_)
            )
        ))
        if hc_ is not None:
            used["hashc"] = HashCaps(
                table_cap=max(used["hashc"].table_cap, hc_.table_cap),
                chunk_cap=max(used["hashc"].chunk_cap, hc_.chunk_cap),
                num_chunks=max(used["hashc"].num_chunks, hc_.num_chunks),
                max_probes=max(used["hashc"].max_probes, hc_.max_probes),
            )
        return caps_, sel_cap_, hc_, mask_cap_

    def run_batch_sync(
        bi: int, caps_: BatchCaps, sel_cap_: int, hc_, mask_cap_: int,
        dispatch_fn=None, record: bool = True,
    ):
        """The kept, tested synchronous retry loop (§IV-A robustness)."""
        nonlocal retries
        dispatch_fn = dispatch_fn or dispatch
        for _ in range(max_retries + 1):
            c_batch, ovf = dispatch_fn(bi, caps_, sel_cap_, hc_, mask_cap_)
            o = wait(bi, ovf)
            if not o.any():
                return c_batch
            retries += 1
            with span("spgemm.retry", batch=bi, kind=_retry_kind(o)):
                caps_, sel_cap_, hc_, mask_cap_ = grow(
                    o, caps_, sel_cap_, hc_, mask_cap_, record=record
                )
        raise RuntimeError(
            f"batch {bi}: capacity overflow persisted after {max_retries} retries"
        )

    def run_batch_degraded(bi: int):
        """Graceful degradation: batch ``bi``'s columns rerun as ``d``
        sub-batches under a finer ``nb·d`` plan (whose caps fit the budget by
        construction), then merge back to the original batch extent. Split
        factor doubles while a sub-batch still hits the ceiling; a split
        finer than the column structure allows surfaces as RuntimeError."""
        forced = "dense" if path == "dense" else (
            "hash" if use_hash else "esc"
        )
        d = 2
        while True:
            try:
                # a fresh sub-plan: caller floors do not apply (sub-batch
                # caps live in their own static-signature space)
                with span("spgemm.replan", batch=bi, split=d):
                    sub = plan_batches(
                        a, b, grid, per_process_memory,
                        spec=spec.replace(
                            local_path=forced, force_num_batches=nb * d,
                        ),
                    )
            except MemoryError as e:
                raise RuntimeError(
                    f"batch {bi}: memory ceiling hit and no finer batching "
                    f"fits (split {d}x): {e}"
                ) from e
            nb_f = sub.num_batches
            if nb_f % nb != 0:
                # divisibility rounding broke sub-batch alignment — go finer
                d = nb_f // nb + 1
                continue
            d_eff = nb_f // nb
            sub_hc = sub.hash_caps if use_hash else None

            def sub_dispatch(sj, caps_, sel_cap_, hc_, mask_cap_):
                with span("spgemm.dispatch", batch=sj):
                    return _fused_jit(
                        a, b, jnp.int32(sj), mask, grid=grid,
                        num_batches=nb_f, sel_cap=sel_cap_, caps=caps_,
                        semiring=semiring, sorted_merge=sorted_merge,
                        path=path, hashc=hc_, mask_cap=mask_cap_,
                        mask_complement=mask_complement,
                    )

            try:
                parts = [
                    run_batch_sync(
                        d_eff * bi + q, sub.caps, sub.sel_cap, sub_hc,
                        sub.mask_sel_cap, dispatch_fn=sub_dispatch,
                        record=False,
                    )
                    for q in range(d_eff)
                ]
            except _LadderBlocked:
                d = d_eff * 2  # a sub-batch still over budget: split finer
                continue
            rep["replans"] += 1
            rep["degraded"].append((bi, d_eff))
            if path == "dense":
                return jnp.concatenate(parts, axis=-1)
            return _merge_split_batches(tuple(parts), grid)

    def run_batch_guarded(
        bi: int, caps_: BatchCaps, sel_cap_: int, hc_, mask_cap_: int
    ):
        try:
            return run_batch_sync(bi, caps_, sel_cap_, hc_, mask_cap_)
        except _LadderBlocked:
            return run_batch_degraded(bi)

    consumed = []

    def post(bi: int, c_batch):
        """Apply the device-side hook (async — nothing blocks here)."""
        return postprocess(bi, c_batch) if postprocess is not None else c_batch

    def consume(bi: int, c_post) -> None:
        with span("spgemm.consume", batch=bi):
            consumed.append(consumer(bi, c_post, _col_map(bi)))

    def finish(bi: int, c_post, ovf) -> None:
        """Sync point: read batch bi's flags, retry if beaten, consume."""
        nonlocal retries
        o = wait(bi, ovf)
        if o.any():
            retries += 1
            # the speculatively postprocessed batch was built from a garbage
            # product — recompute synchronously and re-run the hook on it
            try:
                with span("spgemm.retry", batch=bi, kind=_retry_kind(o)):
                    grown = grow(o, caps, sel_cap, hc, mask_cap)
                c_batch = run_batch_sync(bi, *grown)
            except _LadderBlocked:
                c_batch = run_batch_degraded(bi)
            c_post = post(bi, c_batch)
        consume(bi, c_post)

    def _col_map(bi: int) -> np.ndarray:
        col_map = batch_column_map(n_cols, grid, nb, bi)
        if placement is not None:
            # operands are permuted: hand consumers ORIGINAL column ids so
            # downstream reassembly never sees placement space (rows stay
            # permuted — multiply_placed inverts them after collection)
            col_map = placement.original_cols(col_map)
        return col_map

    if not pipelined:
        for bi in range(nb):
            c_batch = post(
                bi, run_batch_guarded(bi, caps, sel_cap, hc, mask_cap)
            )
            consume(bi, c_batch)
    else:
        # deferred import: runtime.resilient imports this module (RunReport)
        from ..runtime.driver import LookaheadWindow

        window = LookaheadWindow.from_exec(ex, finish)
        for bi in range(nb):
            c_batch, ovf = dispatch(bi, caps, sel_cap, hc, mask_cap)
            window.push(bi, post(bi, c_batch), ovf)
        window.drain()
    # report the capacities actually used (incl. any retry growth) so
    # iterated callers floor their next plan on reality, not the estimate
    plan = dataclasses.replace(
        plan, caps=used["caps"], sel_cap=used["sel"],
        mask_sel_cap=used["mask"], hash_caps=used["hashc"],
    )
    executed = "dense" if path == "dense" else (
        "hash" if use_hash else "esc"
    )
    report = RunReport(
        retries=retries, sel_retries=rep["sel_retries"],
        replans=rep["replans"], ladder_blocked=rep["ladder_blocked"],
        degraded_batches=tuple(rep["degraded"]),
    )
    return BatchedResult(
        plan=plan, num_retries=retries, consumed=consumed,
        local_path=executed, hash_caps=used["hashc"],
        report=report,
    )
