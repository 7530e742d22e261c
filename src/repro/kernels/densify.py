"""Pallas TPU kernel: densify — scatter a padded COO entry list to a dense tile.

Utility kernel for the dense-accumulator SpGEMM path: the narrow per-batch
column block of B (Alg. 4) is scattered to dense once, then SpMM streams A
through it. Scatter = one-hot matmul (MXU), same idiom as the other kernels.

    colsel = one_hot(cols - n_off)          # (nnz_blk, n_blk)
    rowsel = one_hot(rows - m_off)          # (m_blk, nnz_blk)
    C_tile += rowsel @ (vals[:, None] * colsel)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import resolve_interpret

DEFAULT_BLOCKS = dict(m_blk=128, n_blk=128, nnz_blk=512)


def _densify_kernel(rows_ref, cols_ref, vals_ref, out_ref, *, m_blk, n_blk):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows, cols = rows_ref[...], cols_ref[...]
    vals = vals_ref[...].astype(jnp.float32)
    nb = rows.shape[0]
    m_off = pl.program_id(0) * m_blk
    n_off = pl.program_id(1) * n_blk

    colsel = (cols[:, None] - n_off == jax.lax.broadcasted_iota(
        jnp.int32, (nb, n_blk), 1
    )).astype(jnp.float32)
    rowsel = (rows[None, :] - m_off == jax.lax.broadcasted_iota(
        jnp.int32, (m_blk, nb), 0
    )).astype(jnp.float32)
    out_ref[...] += jnp.dot(
        rowsel, vals[:, None] * colsel, preferred_element_type=jnp.float32
    )


def densify_pallas(
    rows, cols, vals, m: int, n: int,
    *, m_blk=None, n_blk=None, nnz_blk=None, interpret: bool = None,
) -> jnp.ndarray:
    cap = rows.shape[0]
    m_blk = min(m_blk or DEFAULT_BLOCKS["m_blk"], _rup(m, 8))
    n_blk = min(n_blk or DEFAULT_BLOCKS["n_blk"], _rup(n, 128))
    nnz_blk = min(nnz_blk or DEFAULT_BLOCKS["nnz_blk"], _rup(cap, 8))
    m_pad, n_pad, cap_pad = _rup(m, m_blk), _rup(n, n_blk), _rup(cap, nnz_blk)
    rows = jnp.pad(rows, (0, cap_pad - cap), constant_values=m_pad)
    cols = jnp.pad(cols, (0, cap_pad - cap), constant_values=n_pad)
    vals = jnp.pad(vals, (0, cap_pad - cap), constant_values=0)

    grid = (m_pad // m_blk, n_pad // n_blk, cap_pad // nnz_blk)
    out = pl.pallas_call(
        functools.partial(_densify_kernel, m_blk=m_blk, n_blk=n_blk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((nnz_blk,), lambda i, j, s: (s,)),
            pl.BlockSpec((nnz_blk,), lambda i, j, s: (s,)),
            pl.BlockSpec((nnz_blk,), lambda i, j, s: (s,)),
        ],
        out_specs=pl.BlockSpec((m_blk, n_blk), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(rows, cols, vals)
    return out[:m, :n]


def _rup(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult
