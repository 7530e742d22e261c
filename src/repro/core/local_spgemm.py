"""Local (per-process) SpGEMM algorithms — the paper's §IV-D layer, TPU-adapted.

The paper replaces sorted heap accumulation with *sort-free hash* SpGEMM/merge
on CPUs. On TPU there is no efficient per-lane random scatter, so we adapt the
insight (unsorted accumulation into a direct-addressed structure) as:

  * ``spgemm_dense_acc`` — scatter/accumulate into a **dense accumulator**
    (a perfect hash table with the identity hash). Batching (Alg. 4) makes the
    output column block narrow, so the accumulator fits on-chip; this is the
    default local multiply of the batched distributed algorithm and is backed
    by a Pallas VMEM kernel (``repro.kernels.spgemm_acc``).
  * ``spgemm_esc`` — expand–sort–compress, keeping *inputs unsorted* and only
    producing sorted output at the final compress, mirroring the paper's
    sortedness observation. Sorting maps to TPU-friendly sorting networks.
  * ``spgemm_hash`` — hash-accumulator multiply (``kernels/spgemm_hash``):
    partial products enumerated in chunks and inserted into an
    open-addressing table, O(output) scratch. Same (C, overflow) contract as
    ``spgemm_esc``; the batch plan picks between them per workload.
  * ``spmm`` — sparse × dense (used by MoE dispatch and the dense-acc path).
  * ``local_symbolic`` — Alg. 3's LocalSymbolic: flops (upper bound) and exact
    output nnz of a local product, without forming values.

All functions are jit-compatible with static capacities.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from . import semiring as sr
from . import sortkeys
from .sparse import SparseCOO

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# SpMM: sparse A (m×k) times dense B (k×n) -> dense (m×n)
# ---------------------------------------------------------------------------
def spmm(a: SparseCOO, b_dense: Array, semiring: sr.Semiring = sr.PLUS_TIMES) -> Array:
    """Gather rows of B by A's column index, scale, segment-reduce by A's row.

    O(cap_A × n) work, fully vectorized; the Pallas kernel in
    ``repro.kernels.spmm`` implements the same contraction with VMEM tiling.
    """
    m, k = a.shape
    assert b_dense.shape[0] == k, (a.shape, b_dense.shape)
    n = b_dense.shape[1]
    # pad B with a zero row for sentinel column indices
    b_pad = jnp.concatenate([b_dense, jnp.zeros((1, n), b_dense.dtype)], axis=0)
    gathered = b_pad[a.cols]  # (cap, n)
    prods = semiring.mul(a.vals[:, None], gathered)
    prods = jnp.where(a.valid_mask()[:, None], prods, semiring.zero)
    out = semiring.segment_reduce(prods, a.rows, num_segments=m + 1)[:m]
    if semiring.add_kind != "sum":
        out = jnp.where(jnp.isfinite(out), out, semiring.zero)  # empty segments
    return out


# ---------------------------------------------------------------------------
# Dense-accumulator SpGEMM: sparse × sparse -> dense block
# ---------------------------------------------------------------------------
def spgemm_dense_acc(
    a: SparseCOO,
    b: SparseCOO,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    *,
    out_cap: int = None,
    flops_cap: int = None,
    return_overflow: bool = False,
) -> Array:
    """C = A·B with a dense (m × n_b) accumulator.

    TPU-native local multiply for the batched algorithm: ``b`` is a narrow
    column block (n_b = n/(b·grid)), so the dense accumulator is small. B is
    scattered to dense once (its nnz is small per batch), then a single SpMM
    streams A's nonzeros through the accumulator.

    min/max semirings can't use a 0-initialized dense B (structural zeros
    would participate), so they route through ``spgemm_esc`` and densify the
    sparse result onto a ``semiring.zero`` background. ``out_cap``/``flops_cap``
    bound that fallback's static capacities. The defaults (m*n_b and
    cap_A*cap_B) are hard upper bounds — overflow is impossible with them.
    Callers passing *tighter* symbolic-step caps must set
    ``return_overflow=True`` (returns ``(dense, overflow)``) and check it,
    as a beaten estimate silently drops contributions otherwise (§IV-A
    retry discipline). For sum semirings overflow is always 0 (the dense
    accumulator cannot overflow).
    """
    m, k = a.shape
    k2, nb = b.shape
    assert k == k2, (a.shape, b.shape)
    if semiring.add_kind == "sum":
        b_dense = b.to_dense()
        out = spmm(a, b_dense, semiring)
        return (out, jnp.int32(0)) if return_overflow else out
    out_cap = out_cap if out_cap is not None else m * nb
    flops_cap = flops_cap if flops_cap is not None else max(a.cap * b.cap, 1)
    c, overflow = spgemm_esc(
        a, b, out_cap=out_cap, flops_cap=flops_cap, semiring=semiring
    )
    dense = jnp.full((m + 1, nb + 1), semiring.zero, c.vals.dtype)
    safe_vals = jnp.where(c.valid_mask(), c.vals, semiring.zero)
    if semiring.add_kind == "min":
        dense = dense.at[c.rows, c.cols].min(safe_vals)
    else:
        dense = dense.at[c.rows, c.cols].max(safe_vals)
    out = dense[:m, :nb]
    return (out, overflow) if return_overflow else out


# ---------------------------------------------------------------------------
# ESC SpGEMM: expand - sort - compress (sparse × sparse -> sparse)
# ---------------------------------------------------------------------------
def _expand(a_csc: SparseCOO, b: SparseCOO, flops_cap: int, semiring: sr.Semiring):
    """Enumerate all partial products of A·B.

    ``a_csc`` must be column-major sorted. For each valid B entry t=(k,j,vB),
    the products are A's column-k entries scaled by vB. Expansion uses the
    standard offsets+cumsum trick with a static bound ``flops_cap``.

    Returns (rows, cols, vals, valid, total_flops) each of length flops_cap.
    """
    m, k_dim = a_csc.shape
    _, n = b.shape
    # column pointer of A: start of each column in the sorted entry list
    colcount = a_csc.col_counts()  # i32[k]
    colptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(colcount).astype(jnp.int32)]
    )  # i32[k+1]
    ccount_pad = jnp.concatenate([colcount, jnp.zeros((1,), jnp.int32)])
    colptr_pad = jnp.concatenate([colptr, jnp.zeros((1,), jnp.int32)])

    bm = b.valid_mask()
    cnt = jnp.where(bm, ccount_pad[b.cols], 0)  # products per B entry (cap_b,)
    starts = jnp.cumsum(cnt) - cnt  # segment starts (exclusive cumsum)
    total = starts[-1] + cnt[-1] if b.cap > 0 else jnp.int32(0)

    # B-entry index per expanded slot e: scatter t at each (non-empty) segment
    # start, then running max. Segments tile [starts[t], starts[t]+cnt[t])
    # contiguously, so the largest start <= e identifies e's segment.
    e = jnp.arange(flops_cap, dtype=jnp.int32)
    starts_clip = jnp.where((cnt > 0) & (starts < flops_cap), starts, flops_cap)
    tvals = jnp.arange(b.cap, dtype=jnp.int32)
    buf = jnp.zeros((flops_cap + 1,), jnp.int32).at[starts_clip].max(tvals)
    t_of_e = jax.lax.cummax(buf[:flops_cap])
    t_of_e = jnp.clip(t_of_e, 0, b.cap - 1)
    within = e - starts[t_of_e]  # offset within A's column
    valid = (e < jnp.minimum(total, flops_cap)) & (within >= 0)

    bk = b.cols[t_of_e]  # contraction index k
    ai = colptr_pad[bk] + within  # index into sorted A entries
    ai = jnp.clip(ai, 0, a_csc.cap - 1)
    out_rows = jnp.where(valid, a_csc.rows[ai], m)
    out_cols = jnp.where(valid, b.rows[t_of_e], n)  # note: B entry (k, j) -> col j
    vals = semiring.mul(a_csc.vals[ai], b.vals[t_of_e])
    vals = jnp.where(valid, vals, semiring.zero)
    return out_rows, out_cols, vals, valid, total


def spgemm_esc(
    a: SparseCOO,
    b: SparseCOO,
    out_cap: int,
    flops_cap: int,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    a_is_colsorted: bool = False,
    engine: str = "auto",
    mask_keys: Array = None,
    mask_complement: bool = False,
) -> Tuple[SparseCOO, Array]:
    """Sparse × sparse → sparse via expand–sort–compress.

    Inputs need not be sorted (paper §IV-D: sort-free inputs); only the final
    output is row-major sorted. Returns (C, overflow-count) where overflow > 0
    means out_cap or flops_cap was too small (caller increases b / capacity).

    ``mask_keys`` (ascending packed row-major (row, col) keys of the output
    space, from ``sortkeys.sorted_mask_keys``) switches on the masked
    (filtered-semiring) formulation: expanded partial products are
    intersected against the mask BEFORE the compress, so only surviving
    coordinates consume ``out_cap`` — C = (A·B) ⊙ M for
    ``mask_complement=False``, C = (A·B) ⊙ ¬M for ``mask_complement=True``.
    Coordinate filtering commutes with the coordinate-wise merge, so this is
    exact for every semiring.

    The three phases run under the named scopes ``spgemm.esc.sort_a`` (A to
    column-major order), ``spgemm.esc.expand`` (the partial products and the
    mask filter) and ``spgemm.esc.compress``, which a profiler trace reads.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    with jax.named_scope("spgemm.esc.sort_a"):
        a_csc = a if a_is_colsorted else a.sort_colmajor()
    # B entries as (k, j): transpose so cols hold k, rows hold j
    bt = b.transpose()  # shape (n, k); entries (j, k) with rows=j? No: see below
    # SparseCOO(b).transpose() swaps arrays: rows=old cols (j->k?), careful:
    # b entry is (row=k, col=j). After transpose: row=j, col=k, shape (n, k).
    with jax.named_scope("spgemm.esc.expand"):
        rows, cols, vals, valid, total = _expand(a_csc, bt, flops_cap, semiring)
        flop_overflow = jnp.maximum(total - flops_cap, 0)
        if mask_keys is not None:
            key = sortkeys.pack_rowmajor(rows, cols, n)
            hit = sortkeys.keys_in_sorted(key, mask_keys)
            valid = valid & (~hit if mask_complement else hit)

    expanded = SparseCOO(rows, cols, vals, jnp.int32(flops_cap), (m, n))
    # compress: packed-key engine (bucket scan / single-key sort — the one
    # ordering step of the whole pipeline; see repro.core.sortkeys)
    with jax.named_scope("spgemm.esc.compress"):
        merged, overflow = _coalesce_semiring(
            expanded, valid, out_cap, semiring, engine
        )
    return merged, overflow + flop_overflow


def _coalesce_semiring(
    x: SparseCOO, valid: Array, new_cap: int, semiring: sr.Semiring,
    engine: str = "auto",
):
    """coalesce() generalized over semirings; `valid` marks live entries.

    Dispatches to the packed-key engine (``repro.core.sortkeys``): sort-free
    bucket scan for small key spaces, single-key packed sort otherwise,
    ``engine="lexsort"`` for the two-key sort carrying the values (no
    permutation gathers; key spaces past i32).
    """
    m, n = x.shape
    rows, cols, vals, nnz, overflow = sortkeys.coalesce_entries(
        x.rows, x.cols, x.vals, valid, (m, n), new_cap,
        add_kind=semiring.add_kind, engine=engine,
    )
    return SparseCOO(rows, cols, vals, nnz, (m, n)), overflow


def spgemm_hash(
    a: SparseCOO,
    b: SparseCOO,
    out_cap: int,
    table_cap: int,
    chunk_cap: int,
    num_chunks: int,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    a_is_colsorted: bool = False,
    mask_keys: Array = None,
    mask_complement: bool = False,
    max_probes: int = 32,
) -> Tuple[SparseCOO, Array]:
    """Sparse × sparse → sparse via a hash accumulator — O(output) scratch.

    The paper's memory-constrained claim wants partial products consumed on
    the fly, not materialized: unlike ``spgemm_esc`` (whole O(flops_cap)
    expansion, then sort+compress), this path enumerates the expansion in
    ``num_chunks`` reused chunks of ``chunk_cap`` partial products and inserts
    each chunk into an open-addressing table of ``table_cap`` slots
    (``kernels.spgemm_hash``), semiring-accumulating on probe hits. Resident
    scratch is O(table_cap + chunk_cap) = O(nnz(C)·load_factor + const)
    instead of O(flops) — the win the plan budgets when the compression
    factor flops/nnz(C) is high.

    Masked entries are rejected *at insert* (membership probe of the packed
    key against ``mask_keys``, same strict/complement semantics as
    ``spgemm_esc``), so the table only ever holds survivors.

    Output contract matches ``spgemm_esc`` exactly: (row-major-sorted C,
    overflow) where overflow counts dropped inserts (table full /
    ``max_probes`` beaten), enumeration beyond ``num_chunks·chunk_cap``
    flops, and ``out_cap`` violations — one device-resident flag the batched
    driver's retry ladder handles unchanged.
    """
    from ..kernels import spgemm_hash as hashkern

    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    assert table_cap >= 8 and table_cap & (table_cap - 1) == 0, table_cap
    assert sortkeys.fits_i32(m, n), (m, n)

    a_csc = a if a_is_colsorted else a.sort_colmajor()
    bt = b.transpose()  # entries (j, k): rows=j, cols=k
    colcount = a_csc.col_counts()
    colptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(colcount).astype(jnp.int32)]
    )
    ccount_pad = jnp.concatenate([colcount, jnp.zeros((1,), jnp.int32)])
    colptr_pad = jnp.concatenate([colptr, jnp.zeros((1,), jnp.int32)])
    bm = bt.valid_mask()
    cnt = jnp.where(bm, ccount_pad[bt.cols], 0)  # products per B entry
    cum = jnp.cumsum(cnt).astype(jnp.int32)  # inclusive prefix
    total = cum[-1] if bt.cap > 0 else jnp.int32(0)

    add_kind = semiring.add_kind
    table_key0 = jnp.full((table_cap,), hashkern.EMPTY, jnp.int32)
    table_val0 = jnp.full(
        (table_cap,), hashkern.table_init_val(add_kind), a.vals.dtype
    )

    def chunk_body(c, carry):
        tk, tv, dropped = carry
        # enumerate expansion slots [c·chunk_cap, (c+1)·chunk_cap): the B
        # entry of slot e is the first t with cum[t] > e (rank in the
        # inclusive prefix — empty segments are skipped by construction)
        e = c * chunk_cap + jnp.arange(chunk_cap, dtype=jnp.int32)
        t = jnp.searchsorted(cum, e, side="right").astype(jnp.int32)
        t = jnp.clip(t, 0, bt.cap - 1)
        within = e - (cum[t] - cnt[t])
        valid = e < total
        bk = bt.cols[t]  # contraction index k
        ai = jnp.clip(colptr_pad[bk] + within, 0, a_csc.cap - 1)
        rows = a_csc.rows[ai]
        cols = bt.rows[t]  # B entry (k, j) -> output col j
        vals = semiring.mul(a_csc.vals[ai], bt.vals[t])
        key = sortkeys.pack_rowmajor(rows, cols, n)
        if mask_keys is not None:
            hit = sortkeys.keys_in_sorted(key, mask_keys)
            valid = valid & (~hit if mask_complement else hit)
        tk, tv, drop = hashkern.hash_insert(
            tk, tv, key, vals, valid, add_kind=add_kind,
            max_probes=max_probes,
        )
        return tk, tv, dropped + drop

    table_key, table_val, dropped = jax.lax.fori_loop(
        0, num_chunks, chunk_body, (table_key0, table_val0, jnp.int32(0))
    )
    flop_overflow = jnp.maximum(total - num_chunks * chunk_cap, 0)

    # table → sorted COO: EMPTY (INT32_MAX) sorts after every real key and
    # the row-major sentinel, so one sort + sentinel compress finalizes
    skey, svals = jax.lax.sort((table_key, table_val), num_keys=1)
    sent = jnp.int32(sortkeys.key_space(m, n) - 1)
    okey, ovals, nnz, ovf_out = sortkeys.compress_sorted_keys(
        skey, svals, sent, out_cap, add_kind=add_kind
    )
    orows, ocols = sortkeys.unpack_rowmajor(okey, n)
    c_out = SparseCOO(orows, ocols, ovals, nnz, (m, n))
    return c_out, ovf_out + flop_overflow + dropped


def mask_indicator(mask: SparseCOO, complement: bool = False) -> Array:
    """bool (m, n): mask membership as a dense indicator (sentinel-safe).

    The dense-accumulator counterpart of the packed-key mask intersect:
    scatter a presence bit per mask entry, flip for the complement mode.
    Used by the dense SUMMA path, where the product already lives in a
    dense block.
    """
    m, n = mask.shape
    ind = (
        jnp.zeros((m + 1, n + 1), jnp.int32)
        .at[mask.rows, mask.cols]
        .max(mask.valid_mask().astype(jnp.int32))
    )[:m, :n] > 0
    return ~ind if complement else ind


def merge_sparse(
    parts,
    out_cap: int,
    semiring: sr.Semiring = sr.PLUS_TIMES,
    assume_sorted: bool = False,
    engine: str = "auto",
):
    """Merge-Layer / Merge-Fiber for the sparse path: sum duplicate coords.

    Paper §IV-D hash-merge, TPU-adapted. Two regimes:

      * ``assume_sorted=False`` — inputs unsorted; one packed-key coalesce
        over the concatenated entry lists (bucket scan or single-key sort).
      * ``assume_sorted=True`` — every part is already row-major sorted with
        distinct coordinates (true for ESC and hash outputs and their
        column-split pieces, i.e. exactly what Merge-Fiber receives), so the
        parts are *merged*, not re-sorted: a segmented k-way merge-path over
        packed keys (ceil(log2 l) rank/scatter rounds), then a linear
        compress. No sort anywhere. A single such part is already merged
        and only changes capacity.

    Returns (merged, overflow).
    """
    shape = parts[0].shape
    for x in parts:
        assert x.shape == shape
    m, n = shape
    if assume_sorted and len(parts) == 1:
        (x,) = parts
        return x.with_capacity(out_cap), jnp.maximum(x.nnz - out_cap, 0)
    if assume_sorted and engine != "lexsort" and sortkeys.fits_i32(m, n):
        # padding carries (m, n) sentinels == max key, so each part's packed
        # key array is ascending end-to-end and merges keep sentinels last
        keys = [sortkeys.pack_rowmajor(x.rows, x.cols, n) for x in parts]
        vals = [x.vals for x in parts]
        mkey, mvals = sortkeys.merge_sorted_runs(keys, vals)
        sent = jnp.int32(sortkeys.key_space(m, n) - 1)
        okey, ovals, nnz, overflow = sortkeys.compress_sorted_keys(
            mkey, mvals, sent, out_cap, add_kind=semiring.add_kind
        )
        orows, ocols = sortkeys.unpack_rowmajor(okey, n)
        return SparseCOO(orows, ocols, ovals, nnz, (m, n)), overflow
    rows = jnp.concatenate([x.rows for x in parts])
    cols = jnp.concatenate([x.cols for x in parts])
    vals = jnp.concatenate([x.vals for x in parts])
    valid = jnp.concatenate([x.valid_mask() for x in parts])
    stacked = SparseCOO(rows, cols, vals, jnp.int32(rows.shape[0]), shape)
    return _coalesce_semiring(stacked, valid, out_cap, semiring, engine)


# ---------------------------------------------------------------------------
# Symbolic local multiply (Alg. 3 LocalSymbolic)
# ---------------------------------------------------------------------------
def local_symbolic_flops(a: SparseCOO, b: SparseCOO) -> Array:
    """Number of partial products (flops/2) of A·B = Σ_t nnz(A(:, B.row_t)).

    Upper bound on nnz of the *unmerged* local product — exactly what Alg. 3
    accumulates per stage (the per-process unmerged D bound).
    """
    colcount = a.col_counts()
    ccount_pad = jnp.concatenate([colcount, jnp.zeros((1,), jnp.int32)])
    return jnp.sum(jnp.where(b.valid_mask(), ccount_pad[b.rows], 0))


def local_symbolic_exact(
    a: SparseCOO, b: SparseCOO, flops_cap: int, engine: str = "auto"
) -> Array:
    """Exact nnz(A·B) via a boolean ESC without forming values (structure only).

    The distinct-coordinate count runs on the packed-key engine: the bucket
    scan needs no sort at all, the packed fallback sorts one bare key array
    (no payload), and key spaces past i32 sort the bare (row, col) pair on
    two keys.
    """
    m, _ = a.shape
    _, n = b.shape
    a_csc = a.sort_colmajor()
    bt = b.transpose()
    rows, cols, _, valid, total = _expand(a_csc, bt, flops_cap, sr.PLUS_TIMES)
    return sortkeys.count_unique(rows, cols, valid, (m, n), engine=engine)


def nnz_per_col_upper(a_colcounts: Array, b: SparseCOO) -> Array:
    """Per-output-column flops upper bound: ub[j] = Σ_{k in B(:,j)} nnz(A(:,k)).

    Vector form of LocalSymbolic used by the distributed symbolic step to pick
    per-batch capacities (col counts of A travel instead of tiles — the
    lightweight payload that makes Alg. 3 cheap).
    """
    _, n = b.shape
    cc = jnp.concatenate([a_colcounts, jnp.zeros((1,), a_colcounts.dtype)])
    contrib = jnp.where(b.valid_mask(), cc[b.rows], 0)
    return jax.ops.segment_sum(contrib, b.cols, num_segments=n + 1)[:n]
