"""Pallas TPU kernel: paired sort-free SpGEMM — COO A (m×k) × COO B (k×n) → dense C.

This is the TPU-native rendering of the paper's unsorted-hash local SpGEMM
(§IV-D): instead of hashing partial products, every (A-entry, B-entry) block
pair is matched on the contraction index with an equality **match matrix**
evaluated on the MXU, and accumulated straight into a dense VMEM tile of C
(identity-hash accumulator). No input ordering is required — exactly the
paper's sort-free property — and no intermediate partial-product list is ever
materialized in HBM (the paper's memory-constrained motivation).

Per (m-tile i, n-tile j) output block, reducing over A blocks s and B blocks t:

    match  = (a_cols[:, None] == b_rows[None, :])       # (nbA, nbB)  VPU
    w      = a_vals ⊗ b_vals ⊙ match                    # (nbA, nbB)  VPU
    rowsel = one_hot(a_rows - m_off)                    # (m_blk, nbA)
    colsel = one_hot(b_cols - n_off)                    # (nbB, n_blk)
    C_tile += rowsel @ w @ colsel                       # two MXU matmuls

Work is O(capA × capB) pairings per output tile — the narrow output blocks
produced by batching (Alg. 4) keep capB small, which is what makes this
profitable; the ESC path covers the wide/unbatched regime.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

DEFAULT_BLOCKS = dict(m_blk=128, n_blk=128, a_blk=256, b_blk=256)


def _paired_kernel(
    ar_ref, ac_ref, av_ref, br_ref, bc_ref, bv_ref, out_ref, *, m_blk, n_blk
):
    s = pl.program_id(2)
    t = pl.program_id(3)

    @pl.when((s == 0) & (t == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ar, ac, av = ar_ref[...], ac_ref[...], av_ref[...].astype(jnp.float32)
    br, bc, bv = br_ref[...], bc_ref[...], bv_ref[...].astype(jnp.float32)
    nbA, nbB = ar.shape[0], br.shape[0]
    m_off = pl.program_id(0) * m_blk
    n_off = pl.program_id(1) * n_blk

    match = (ac[:, None] == br[None, :]).astype(jnp.float32)
    w = av[:, None] * bv[None, :] * match  # (nbA, nbB)
    rowsel = (ar[None, :] - m_off == jax.lax.broadcasted_iota(
        jnp.int32, (m_blk, nbA), 0
    )).astype(jnp.float32)
    colsel = (bc[:, None] - n_off == jax.lax.broadcasted_iota(
        jnp.int32, (nbB, n_blk), 1
    )).astype(jnp.float32)
    acc = jnp.dot(rowsel, w, preferred_element_type=jnp.float32)  # (m_blk, nbB)
    out_ref[...] += jnp.dot(acc, colsel, preferred_element_type=jnp.float32)


def spgemm_paired_pallas(
    a_rows, a_cols, a_vals, b_rows, b_cols, b_vals, m: int, n: int,
    *, m_blk=None, n_blk=None, a_blk=None, b_blk=None, interpret: bool = None,
) -> jnp.ndarray:
    """Dense C (m×n, f32) from two padded COO entry lists (zero-valued padding)."""
    capA, capB = a_rows.shape[0], b_rows.shape[0]
    m_blk = min(m_blk or DEFAULT_BLOCKS["m_blk"], _rup(m, 8))
    n_blk = min(n_blk or DEFAULT_BLOCKS["n_blk"], _rup(n, 128))
    a_blk = min(a_blk or DEFAULT_BLOCKS["a_blk"], _rup(capA, 8))
    b_blk = min(b_blk or DEFAULT_BLOCKS["b_blk"], _rup(capB, 8))

    m_pad, n_pad = _rup(m, m_blk), _rup(n, n_blk)
    capA_pad, capB_pad = _rup(capA, a_blk), _rup(capB, b_blk)
    # pad entry lists; use distinct sentinels for the contraction index so
    # padded A entries never match padded B entries (values are 0 anyway,
    # but keeping the match matrix sparse helps nothing — this is belt and
    # braces for the zero-value guarantee).
    a_rows = _pad1(a_rows, capA_pad, m_pad)
    a_cols = _pad1(a_cols, capA_pad, -1)
    a_vals = _pad1(a_vals, capA_pad, 0)
    b_rows = _pad1(b_rows, capB_pad, -2)
    b_cols = _pad1(b_cols, capB_pad, n_pad)
    b_vals = _pad1(b_vals, capB_pad, 0)

    grid = (m_pad // m_blk, n_pad // n_blk, capA_pad // a_blk, capB_pad // b_blk)
    out = pl.pallas_call(
        functools.partial(_paired_kernel, m_blk=m_blk, n_blk=n_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((a_blk,), lambda i, j, s, t: (s,)),
            pl.BlockSpec((a_blk,), lambda i, j, s, t: (s,)),
            pl.BlockSpec((a_blk,), lambda i, j, s, t: (s,)),
            pl.BlockSpec((b_blk,), lambda i, j, s, t: (t,)),
            pl.BlockSpec((b_blk,), lambda i, j, s, t: (t,)),
            pl.BlockSpec((b_blk,), lambda i, j, s, t: (t,)),
        ],
        out_specs=pl.BlockSpec((m_blk, n_blk), lambda i, j, s, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a_rows, a_cols, a_vals, b_rows, b_cols, b_vals)
    return out[:m, :n]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def _pad1(x, new_len, fill):
    return jnp.pad(x, (0, new_len - x.shape[0]), constant_values=fill)
