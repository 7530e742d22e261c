"""Packed-key sort/compress engine — the local SpGEMM hot path (paper §IV-D).

Every ESC compress, duplicate-coordinate merge, and symbolic nnz count in this
repo reduces to one primitive: *group entries by (row, col) and reduce their
values*. The seed implementation ran a full two-key ``jnp.lexsort`` for each of
those. This module packs the coordinate pair into a single monotonic i32 key

    key(row, col) = row * (n + 1) + col          (row-major; sentinel-aware)

so the grouping can run through one of three engines, picked per shape at
trace time:

  * ``"bucket"``  — sort-free occupancy scan: scatter a presence bit per key,
    prefix-sum the bucket table to rank the distinct keys, segment-reduce the
    values. O(cap + key_space) work, no sort at all. This is the TPU rendering
    of Nagasaka-style binned/hashed accumulation (arXiv:1804.01698): the packed
    key is a perfect hash and the bucket table is the accumulator. Used when
    the key space (m+1)(n+1) fits the table budget — exactly the narrow-tile
    regime the paper's batching (Alg. 4) creates.
  * ``"packed"``  — one single-key ``lax.sort`` carrying the values, then a
    linear boundary scan. O(cap log cap) but with a one-word comparator and no
    permutation gathers; the fallback when the key space is too large to scan.
  * ``"lexsort"`` — a two-key sort carrying the values, no permutation
    gathers: one stable ``lax.sort`` on (row, col) that moves the values with
    the keys (``sort_two_keys``), then the same boundary scan. For shapes
    whose packed key would overflow i32 (x64 is disabled under jax defaults),
    and the reference the other engines are tested against.

``choose_engine`` implements the auto policy; all entry points accept an
``engine=`` override so benchmarks and tests can pin a path.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

INT32_MAX = (1 << 31) - 1

#: Max bucket-table cells for the sort-free scan (i32 table; 4 MB at 1<<20).
BUCKET_SCAN_MAX = 1 << 22

#: Don't bother scanning a table more than this many times larger than cap.
BUCKET_SCAN_WASTE = 64


# ---------------------------------------------------------------------------
# key packing
# ---------------------------------------------------------------------------
def key_space(m: int, n: int) -> int:
    """Number of distinct packed keys incl. the (m, n) sentinel."""
    return (m + 1) * (n + 1)


def fits_i32(m: int, n: int) -> bool:
    return key_space(m, n) <= INT32_MAX


def pack_rowmajor(rows: Array, cols: Array, n: int) -> Array:
    """(row, col) -> row * (n+1) + col. Sentinel (m, n) maps to the max key."""
    return rows * jnp.int32(n + 1) + cols


def unpack_rowmajor(key: Array, n: int) -> Tuple[Array, Array]:
    return key // (n + 1), key % (n + 1)


def pack_colmajor(rows: Array, cols: Array, m: int) -> Array:
    """(row, col) -> col * (m+1) + row (CSC ordering)."""
    return cols * jnp.int32(m + 1) + rows


def unpack_colmajor(key: Array, m: int) -> Tuple[Array, Array]:
    return key % (m + 1), key // (m + 1)


def sort_two_keys(primary: Array, secondary: Array, *payloads: Array):
    """Stable sort by (``primary``, ``secondary``) that carries ``payloads``
    along: returns (primary, secondary, *payloads), all sorted. One
    ``lax.sort`` with two keys; no permutation is built and gathered by, so
    equal keys keep their input order exactly as a lexsort plus gathers."""
    return jax.lax.sort((primary, secondary, *payloads), num_keys=2, is_stable=True)


def choose_engine(m: int, n: int, cap: int, engine: str = "auto") -> str:
    """Static (trace-time) engine policy. See module docstring."""
    if engine != "auto":
        assert engine in ("bucket", "packed", "lexsort"), engine
        return engine
    if not fits_i32(m, n):
        return "lexsort"
    ks = key_space(m, n)
    if ks <= BUCKET_SCAN_MAX and ks <= BUCKET_SCAN_WASTE * max(cap, 1):
        return "bucket"
    return "packed"


# ---------------------------------------------------------------------------
# value reduction into output slots (shared by all engines)
# ---------------------------------------------------------------------------
def _reduce_to_slots(vals: Array, seg: Array, new_cap: int, add_kind: str) -> Array:
    """Reduce vals by slot id ``seg``; slot new_cap is the discard bucket."""
    if add_kind == "sum":
        buf = jnp.zeros((new_cap + 1,), vals.dtype).at[seg].add(
            jnp.where(seg < new_cap, vals, 0)
        )
    elif add_kind == "min":
        buf = jnp.full((new_cap + 1,), jnp.inf, vals.dtype).at[seg].min(vals)
    elif add_kind == "max":
        buf = jnp.full((new_cap + 1,), -jnp.inf, vals.dtype).at[seg].max(vals)
    else:
        raise ValueError(f"unknown add_kind {add_kind}")
    return buf[:new_cap]


def _finalize(out_key, out_vals, total, new_cap, sent, dtype):
    nnz = jnp.minimum(total, new_cap).astype(jnp.int32)
    pad = jnp.arange(new_cap) >= nnz
    out_key = jnp.where(pad, sent, out_key)
    out_vals = jnp.where(pad, 0, out_vals).astype(dtype)
    overflow = (total - nnz).astype(jnp.int32)
    return out_key, out_vals, nnz, overflow


# ---------------------------------------------------------------------------
# engine bodies
# ---------------------------------------------------------------------------
def compress_sorted_keys(
    keys: Array, vals: Array, sent, new_cap: int, add_kind: str = "sum"
):
    """Compress an ascending-sorted key array (duplicates adjacent, sentinels
    last) into unique slots. Returns (out_keys, out_vals, nnz, overflow).

    This is the shared tail of the packed-sort engine and the segmented merge
    (whose inputs arrive already sorted — merge, don't re-sort).
    """
    cap = keys.shape[0]
    vmask = keys < sent
    new_key = jnp.ones((cap,), dtype=bool)
    if cap > 1:
        new_key = new_key.at[1:].set(keys[1:] != keys[:-1])
    new_key = new_key & vmask
    seg = jnp.cumsum(new_key.astype(jnp.int32)) - 1
    total = jnp.maximum(seg[-1] + 1, 0)
    seg = jnp.where(vmask & (seg < new_cap), seg, new_cap)
    out_key = jnp.full((new_cap + 1,), sent, jnp.int32).at[seg].min(keys)[:new_cap]
    out_vals = _reduce_to_slots(vals, seg, new_cap, add_kind)
    return _finalize(out_key, out_vals, total, new_cap, sent, vals.dtype)


def _coalesce_packed(key, vals, sent, new_cap, add_kind):
    key, vals = jax.lax.sort((key, vals), num_keys=1)
    return compress_sorted_keys(key, vals, sent, new_cap, add_kind)


def _coalesce_bucket(key, valid, vals, nbuckets, sent, new_cap, add_kind):
    """Sort-free: presence scatter + bucket-table prefix sum ranks the keys."""
    keyc = jnp.where(valid, key, nbuckets)  # discard bucket
    occ = jnp.zeros((nbuckets + 1,), jnp.int32).at[keyc].max(1)[:nbuckets]
    slot_of_bucket = jnp.cumsum(occ) - 1  # rank among occupied, sorted order
    total = jnp.maximum(slot_of_bucket[-1] + 1, 0)
    slot = slot_of_bucket[jnp.clip(keyc, 0, nbuckets - 1)]
    seg = jnp.where(valid & (slot < new_cap), slot, new_cap)
    out_vals = _reduce_to_slots(vals, seg, new_cap, add_kind)
    bdest = jnp.where((occ > 0) & (slot_of_bucket < new_cap), slot_of_bucket, new_cap)
    out_key = jnp.full((new_cap + 1,), sent, jnp.int32).at[bdest].min(
        jnp.arange(nbuckets, dtype=jnp.int32)
    )[:new_cap]
    return _finalize(out_key, out_vals, total, new_cap, sent, vals.dtype)


def _coalesce_lexsort(rows, cols, vals, valid, m, n, new_cap, add_kind):
    """Two-key sort carrying the values, no permutation gathers: (row, col)
    keys for shapes whose packed key overflows i32, then the boundary scan."""
    cap = rows.shape[0]
    rows = jnp.where(valid, rows, m)
    cols = jnp.where(valid, cols, n)
    rows, cols, vals = sort_two_keys(rows, cols, vals)
    vmask = rows < m
    new_key = jnp.ones((cap,), dtype=bool)
    if cap > 1:
        same = (rows[1:] == rows[:-1]) & (cols[1:] == cols[:-1])
        new_key = new_key.at[1:].set(~same)
    new_key = new_key & vmask
    seg = jnp.cumsum(new_key.astype(jnp.int32)) - 1
    total = jnp.maximum(seg[-1] + 1, 0)
    seg = jnp.where(vmask & (seg < new_cap), seg, new_cap)
    out_rows = jnp.full((new_cap + 1,), m, jnp.int32).at[seg].min(rows)[:new_cap]
    out_cols = jnp.full((new_cap + 1,), n, jnp.int32).at[seg].min(cols)[:new_cap]
    out_vals = _reduce_to_slots(vals, seg, new_cap, add_kind)
    nnz = jnp.minimum(total, new_cap).astype(jnp.int32)
    pad = jnp.arange(new_cap) >= nnz
    out_rows = jnp.where(pad, m, out_rows)
    out_cols = jnp.where(pad, n, out_cols)
    out_vals = jnp.where(pad, 0, out_vals).astype(vals.dtype)
    overflow = (total - nnz).astype(jnp.int32)
    return out_rows, out_cols, out_vals, nnz, overflow


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def coalesce_entries(
    rows: Array,
    cols: Array,
    vals: Array,
    valid: Array,
    shape: Tuple[int, int],
    new_cap: int,
    add_kind: str = "sum",
    engine: str = "auto",
):
    """Group duplicate (row, col) coords among ``valid`` entries, reduce values
    by ``add_kind``, and emit row-major sorted entries with (m, n)-sentinel
    padding. Returns (rows, cols, vals, nnz, overflow)."""
    m, n = shape
    eng = choose_engine(m, n, rows.shape[0], engine)
    if eng == "lexsort":
        return _coalesce_lexsort(rows, cols, vals, valid, m, n, new_cap, add_kind)
    sent = jnp.int32(key_space(m, n) - 1)  # == pack(m, n)
    key = jnp.where(valid, pack_rowmajor(rows, cols, n), sent)
    if eng == "bucket":
        okey, ovals, nnz, ovf = _coalesce_bucket(
            key, valid, vals, key_space(m, n), sent, new_cap, add_kind
        )
    else:
        okey, ovals, nnz, ovf = _coalesce_packed(key, vals, sent, new_cap, add_kind)
    out_rows, out_cols = unpack_rowmajor(okey, n)
    return out_rows, out_cols, ovals, nnz, ovf


def count_unique(
    rows: Array, cols: Array, valid: Array, shape: Tuple[int, int],
    engine: str = "auto",
) -> Array:
    """Number of distinct valid (row, col) coords — the symbolic exact-nnz
    count, without forming values. Bucket engine needs no sort at all; packed
    engine sorts a single key array (no payload)."""
    m, n = shape
    eng = choose_engine(m, n, rows.shape[0], engine)
    if eng == "lexsort":
        r = jnp.where(valid, rows, m)
        c = jnp.where(valid, cols, n)
        r, c = sort_two_keys(r, c)
        vmask = r < m
        cap = r.shape[0]
        new_key = jnp.ones((cap,), dtype=bool)
        if cap > 1:
            same = (r[1:] == r[:-1]) & (c[1:] == c[:-1])
            new_key = new_key.at[1:].set(~same)
        return jnp.sum(new_key & vmask).astype(jnp.int32)
    nb = key_space(m, n)
    sent = jnp.int32(nb - 1)
    key = jnp.where(valid, pack_rowmajor(rows, cols, n), sent)
    if eng == "bucket":
        keyc = jnp.where(valid, key, nb)
        occ = jnp.zeros((nb + 1,), jnp.int32).at[keyc].max(1)[:nb]
        return jnp.sum(occ).astype(jnp.int32)
    (skey,) = jax.lax.sort((key,), num_keys=1)
    cap = skey.shape[0]
    new_key = jnp.ones((cap,), dtype=bool)
    if cap > 1:
        new_key = new_key.at[1:].set(skey[1:] != skey[:-1])
    return jnp.sum(new_key & (skey < sent)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# membership against a sorted key set (masked SpGEMM, paper §V-B semantics)
# ---------------------------------------------------------------------------
def keys_in_sorted(keys: Array, sorted_keys: Array) -> Array:
    """bool[cap]: is ``keys[e]`` present in the ascending ``sorted_keys``?

    One ``searchsorted`` + gather — the packed-key rendering of a masked
    (filtered-semiring) SpGEMM: C's candidate coordinates are intersected
    against the mask's key set BEFORE the compress, so non-mask partial
    products never occupy output capacity. Sentinel padding in
    ``sorted_keys`` (max key) can only match a sentinel query, which callers
    already exclude via their ``valid`` mask.
    """
    cap = sorted_keys.shape[0]
    pos = jnp.searchsorted(sorted_keys, keys, side="left").astype(jnp.int32)
    return sorted_keys[jnp.clip(pos, 0, cap - 1)] == keys


def sorted_mask_keys(rows: Array, cols: Array, valid: Array, shape) -> Array:
    """Pack a mask's (row, col) coordinates and sort them ascending — the
    one-time (per batch) preparation for ``keys_in_sorted`` lookups. Padding
    maps to the max (sentinel) key and sorts to the tail."""
    m, n = shape
    assert fits_i32(m, n), (
        f"masked SpGEMM needs an i32-packable key space, got {m}x{n} "
        f"(x64 packed keys are a roadmap follow-up)"
    )
    sent = jnp.int32(key_space(m, n) - 1)
    key = jnp.where(valid, pack_rowmajor(rows, cols, n), sent)
    (skey,) = jax.lax.sort((key,), num_keys=1)
    return skey


# ---------------------------------------------------------------------------
# segmented merge of already-sorted runs (Merge-Fiber fast path)
# ---------------------------------------------------------------------------
def merge_two_sorted(
    keys_a: Array, vals_a: Array, keys_b: Array, vals_b: Array
) -> Tuple[Array, Array]:
    """Merge two ascending key runs (merge-path via ranks): each element's
    output position is its own index plus its rank in the other run. Stable
    across runs (ties: run A first); O((|a|+|b|) log) with no full sort."""
    pa, pb = keys_a.shape[0], keys_b.shape[0]
    pos_a = jnp.arange(pa, dtype=jnp.int32) + jnp.searchsorted(
        keys_b, keys_a, side="left"
    ).astype(jnp.int32)
    pos_b = jnp.arange(pb, dtype=jnp.int32) + jnp.searchsorted(
        keys_a, keys_b, side="right"
    ).astype(jnp.int32)
    out_k = (
        jnp.zeros((pa + pb,), keys_a.dtype).at[pos_a].set(keys_a).at[pos_b].set(keys_b)
    )
    out_v = (
        jnp.zeros((pa + pb,), vals_a.dtype).at[pos_a].set(vals_a).at[pos_b].set(vals_b)
    )
    return out_k, out_v


def merge_sorted_runs(keys_list, vals_list) -> Tuple[Array, Array]:
    """k-way merge of sorted runs by pairwise tree reduction (ceil(log2 k)
    rounds). Sentinel keys (max) stay at the tail throughout."""
    runs = list(zip(keys_list, vals_list))
    assert runs, "need at least one run"
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            (ka, va), (kb, vb) = runs[i], runs[i + 1]
            nxt.append(merge_two_sorted(ka, va, kb, vb))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]
