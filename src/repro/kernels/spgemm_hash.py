"""Hash-accumulator insert for SpGEMM — O(output) scratch.

The ESC local multiply materializes the *whole* expansion (O(flops) entries)
before sorting and compressing it.  Following Nagasaka et al.'s hash SpGEMM
(arXiv:1804.01698), this path instead consumes partial products on the fly:
each (packed row-major key, value) pair is inserted into an open-addressing
table and semiring-accumulated in place on a probe hit, so the resident
structure is O(nnz(C) · load_factor) — the table — plus one bounded, reused
chunk buffer.  High compression-factor batches (flops ≫ nnz(C)) are exactly
where this wins the memory budget.

Insertion is formulated as vectorized probe *rounds* (no per-entry serial
loop):

  round p: every still-unplaced entry probes slot (h0 + p) & (T - 1)
           — a hit on its own key accumulates next reduction;
           — an EMPTY slot is claimed by scatter-min of the key (ties between
             equal keys are harmless: both land on the same slot);
           — losers retry in round p + 1.

Because every entry with the same key follows the *same* probe sequence and
table slots only ever transition EMPTY → key (never mutate), all equal keys
placed in any round resolve to one slot: linear probing's invariant survives
the data-parallel formulation.  Entries unplaced after ``max_probes`` rounds
are *dropped and counted* — the device-resident overflow flag the batched
driver's retry ladder already understands (paper §IV-A: count, don't crash).

Keys are ``sortkeys.pack_rowmajor`` i32 keys; ``EMPTY`` is INT32_MAX, which
also sorts after every real key *and* every sentinel, so the final table →
sorted-COO compaction is one ``lax.sort`` + ``compress_sorted_keys``.

The insert is plain XLA on every backend. The claim is a data-dependent
gather/scatter into an HBM-sized table, which Mosaic does not lower (it
supports only 2-D gathers), so there is no Pallas variant. Losing claims
and unplaced entries scatter to index ``T`` with ``mode="drop"``: the table
is updated in place, never padded and re-sliced per round.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..core.sortkeys import INT32_MAX

# Open-addressing slot: i32 packed key + f32 accumulator.
SLOT_BYTES = 8

# Fibonacci multiplicative hashing: the golden-ratio constant scrambles the
# packed keys' low-entropy structure (row*(n+1)+col clusters by row) before
# the top-bits cut selects a slot.
_FIB = 2654435769

EMPTY = INT32_MAX


def fib_hash(keys: jnp.ndarray, lg_table: int) -> jnp.ndarray:
    """Map i32 keys to [0, 2**lg_table) via Fibonacci hashing (top bits)."""
    assert 1 <= lg_table <= 31, lg_table
    h = jax.lax.shift_right_logical(
        keys.astype(jnp.uint32) * jnp.uint32(_FIB), jnp.uint32(32 - lg_table)
    )
    return h.astype(jnp.int32)


def _insert_rounds(table_key, keys, valid, max_probes: int):
    """Run the vectorized probe rounds; returns (table_key, placed, slot_of).

    Rounds stop once every valid entry is placed (at the planned load
    factor most entries place in the first few probes) or after
    ``max_probes`` rounds, whichever comes first.
    """
    table_cap = table_key.shape[0]
    assert table_cap & (table_cap - 1) == 0, table_cap
    lg = table_cap.bit_length() - 1
    h0 = fib_hash(keys, lg)

    def unplaced(carry):
        p, _, placed, _ = carry
        return (p < max_probes) & jnp.any(valid & ~placed)

    def body(carry):
        p, tk, placed, slot_of = carry
        slot = (h0 + p) & (table_cap - 1)
        cur = tk[slot]
        live = valid & ~placed
        match = live & (cur == keys)
        empty = live & (cur == EMPTY)
        # claim EMPTY slots by scatter-min of the key; index table_cap is out
        # of bounds and dropped, so occupied slots are untouched
        dest = jnp.where(empty, slot, table_cap)
        tk = tk.at[dest].min(jnp.where(empty, keys, EMPTY), mode="drop")
        won = empty & (tk[slot] == keys)
        placed_now = match | won
        slot_of = jnp.where(placed_now, slot, slot_of)
        return p + 1, tk, placed | placed_now, slot_of

    placed0 = jnp.zeros(keys.shape, bool)
    slot0 = jnp.zeros(keys.shape, jnp.int32)
    _, table_key, placed, slot_of = jax.lax.while_loop(
        unplaced, body, (jnp.int32(0), table_key, placed0, slot0)
    )
    return table_key, placed, slot_of


def _accumulate(table_val, vals, placed, slot_of, add_kind: str):
    """Semiring-reduce placed values into their slots (one scatter)."""
    table_cap = table_val.shape[0]
    seg = jnp.where(placed, slot_of, table_cap)  # out of bounds: dropped
    if add_kind == "sum":
        return table_val.at[seg].add(vals.astype(table_val.dtype), mode="drop")
    if add_kind == "min":
        return table_val.at[seg].min(vals.astype(table_val.dtype), mode="drop")
    assert add_kind == "max", add_kind
    return table_val.at[seg].max(vals.astype(table_val.dtype), mode="drop")


def table_init_val(add_kind: str) -> float:
    """Identity of the additive reduce — what EMPTY slots carry until claimed
    (``compress_sorted_keys`` discards them, so the identity never leaks)."""
    return {"sum": 0.0, "min": float("inf"), "max": float("-inf")}[add_kind]


def hash_insert(
    table_key, table_val, keys, vals, valid, *, add_kind: str, max_probes: int
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Insert one chunk of (key, val) partial products into the table.

    Returns (table_key, table_val, dropped): the updated table and the count
    of valid entries that found neither their key nor an EMPTY slot within
    ``max_probes`` rounds (table-full overflow — caller retries with doubled
    caps, exactly like an ESC ``out_cap`` overflow).
    """
    table_key, placed, slot_of = _insert_rounds(
        table_key, keys, valid, max_probes
    )
    table_val = _accumulate(table_val, vals, placed, slot_of, add_kind)
    dropped = jnp.sum((valid & ~placed).astype(jnp.int32))
    return table_key, table_val, dropped
