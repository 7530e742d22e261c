"""Pallas TPU kernel: SpMM — padded-COO sparse A (m×k) times dense B (k×n).

TPU adaptation of the paper's sort-free local accumulation (§IV-D): the
gather (B rows by A's column index) and the scatter (into C rows by A's row
index) are both expressed as one-hot matmuls so they run on the MXU, and the
output tile is a **dense VMEM accumulator** — a perfect hash table with the
identity hash, which is what "unsorted hash accumulation" becomes when the
output block is narrow enough to sit on-chip (the batched algorithm
guarantees that).

Grid: (m_tiles, n_tiles, k_tiles, nnz_blocks); the last two are reduction
axes — the output BlockSpec ignores them so the C tile stays resident in VMEM
across the whole reduction (Pallas revisiting-accumulator pattern).

Per block:
    ksel   = one_hot(a_cols - k_off)          # (nnz_blk, k_blk)
    gath   = ksel @ B_tile                    # (nnz_blk, n_blk)   MXU
    prods  = a_vals[:, None] * gath           # VPU
    rowsel = one_hot(a_rows - m_off).T        # (m_blk, nnz_blk)
    C_tile += rowsel @ prods                  # MXU

Padding entries carry zero values, so sentinel indices contribute nothing
even when they alias a real coordinate after tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

DEFAULT_BLOCKS = dict(m_blk=128, n_blk=128, k_blk=512, nnz_blk=512)


def _spmm_kernel(rows_ref, cols_ref, vals_ref, b_ref, out_ref, *, m_blk, k_blk):
    kk = pl.program_id(2)
    s = pl.program_id(3)

    @pl.when((kk == 0) & (s == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]
    cols = cols_ref[...]
    vals = vals_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)  # (k_blk, n_blk)

    k_off = kk * k_blk
    m_off = pl.program_id(0) * m_blk
    nnz_blk = rows.shape[0]

    ksel = (cols[:, None] - k_off == jax.lax.broadcasted_iota(
        jnp.int32, (nnz_blk, k_blk), 1
    )).astype(jnp.float32)
    gath = jnp.dot(ksel, b, preferred_element_type=jnp.float32)  # (nnz, n_blk)
    prods = vals[:, None] * gath
    rowsel = (rows[None, :] - m_off == jax.lax.broadcasted_iota(
        jnp.int32, (m_blk, nnz_blk), 0
    )).astype(jnp.float32)
    out_ref[...] += jnp.dot(rowsel, prods, preferred_element_type=jnp.float32)


def spmm_pallas(
    rows: jnp.ndarray,
    cols: jnp.ndarray,
    vals: jnp.ndarray,
    b: jnp.ndarray,
    m: int,
    *,
    m_blk: int = None,
    n_blk: int = None,
    k_blk: int = None,
    nnz_blk: int = None,
    interpret: bool = None,
) -> jnp.ndarray:
    """C (m×n, f32) = scatter-accumulate of A's padded COO against dense B.

    Dimensions are padded up to block multiples here; callers pass natural
    shapes. ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    cap = rows.shape[0]
    k, n = b.shape
    m_blk = min(m_blk or DEFAULT_BLOCKS["m_blk"], _rup(m, 8))
    n_blk = min(n_blk or DEFAULT_BLOCKS["n_blk"], _rup(n, 128))
    k_blk = min(k_blk or DEFAULT_BLOCKS["k_blk"], _rup(k, 8))
    nnz_blk = min(nnz_blk or DEFAULT_BLOCKS["nnz_blk"], _rup(cap, 8))

    m_pad, n_pad, k_pad, cap_pad = (
        _rup(m, m_blk),
        _rup(n, n_blk),
        _rup(k, k_blk),
        _rup(cap, nnz_blk),
    )
    rows = _pad1(rows, cap_pad, m_pad)  # sentinel beyond any row tile? zero-val guard
    cols = _pad1(cols, cap_pad, k_pad)
    vals = _pad1(vals, cap_pad, 0)
    b = jnp.pad(b, ((0, k_pad - k), (0, n_pad - n)))

    grid = (m_pad // m_blk, n_pad // n_blk, k_pad // k_blk, cap_pad // nnz_blk)
    out = pl.pallas_call(
        functools.partial(_spmm_kernel, m_blk=m_blk, k_blk=k_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nnz_blk,), lambda i, j, kk, s: (s,)),
            pl.BlockSpec((nnz_blk,), lambda i, j, kk, s: (s,)),
            pl.BlockSpec((nnz_blk,), lambda i, j, kk, s: (s,)),
            pl.BlockSpec((k_blk, n_blk), lambda i, j, kk, s: (kk, j)),
        ],
        out_specs=pl.BlockSpec((m_blk, n_blk), lambda i, j, kk, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rows, cols, vals, b)
    return out[:m, :n]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad1(x, new_len, fill):
    return jnp.pad(x, (0, new_len - x.shape[0]), constant_values=fill)
