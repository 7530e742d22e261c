"""Pure-jnp oracles for the Pallas kernels.

Each function mirrors the exact padded-COO semantics of its kernel (sentinel
indices + zero values in padding) so tests can ``assert_allclose`` kernel
output against these under shape/dtype sweeps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def spmm_ref(rows: Array, cols: Array, vals: Array, b: Array, m: int) -> Array:
    """C[m, n] = Σ_t vals[t] * B[cols[t], :] scattered to row rows[t].

    Padding entries carry vals == 0 (their indices may be sentinels >= dims).
    """
    k, n = b.shape
    b_pad = jnp.concatenate([b, jnp.zeros((1, n), b.dtype)], axis=0)
    gathered = b_pad[jnp.clip(cols, 0, k)]  # (cap, n)
    prods = vals[:, None].astype(jnp.float32) * gathered.astype(jnp.float32)
    out = jax.ops.segment_sum(prods, jnp.clip(rows, 0, m), num_segments=m + 1)[:m]
    return out.astype(b.dtype)


def densify_ref(rows: Array, cols: Array, vals: Array, m: int, n: int) -> Array:
    """Scatter-add a padded COO entry list into a dense (m, n) matrix."""
    out = jnp.zeros((m + 1, n + 1), jnp.float32)
    out = out.at[jnp.clip(rows, 0, m), jnp.clip(cols, 0, n)].add(
        vals.astype(jnp.float32)
    )
    return out[:m, :n].astype(vals.dtype)


def spgemm_paired_ref(
    a_rows: Array,
    a_cols: Array,
    a_vals: Array,
    b_rows: Array,
    b_cols: Array,
    b_vals: Array,
    m: int,
    n: int,
) -> Array:
    """C[m, n] = Σ over entry pairs (s, t) with a_cols[s] == b_rows[t] of
    a_vals[s] * b_vals[t] at (a_rows[s], b_cols[t]).

    The match-matrix formulation the Pallas kernel evaluates on the MXU.
    Padding entries have zero values so sentinel-sentinel matches contribute 0.
    """
    match = (a_cols[:, None] == b_rows[None, :]).astype(jnp.float32)
    w = a_vals[:, None].astype(jnp.float32) * b_vals[None, :].astype(jnp.float32) * match
    # scatter pair weights: first along output columns, then output rows
    colsum = jax.ops.segment_sum(
        w.T, jnp.clip(b_cols, 0, n), num_segments=n + 1
    )  # (n+1, capA)
    rowsum = jax.ops.segment_sum(
        colsum.T, jnp.clip(a_rows, 0, m), num_segments=m + 1
    )  # (m+1, n+1)
    return rowsum[:m, :n].astype(a_vals.dtype)
