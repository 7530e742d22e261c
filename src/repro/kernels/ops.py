"""Jit'd public wrappers around the Pallas kernels.

``use_pallas`` selects the execution path:
  * False (default): pure-jnp oracle path (``ref.py`` semantics).
  * True: pl.pallas_call — compiled on a TPU, interpret mode elsewhere
    (``interpret=None`` resolves through ``kernels.backend`` at call time).
    These kernels are off the SpGEMM main path and have not been compiled
    for the TPU.

These wrappers accept the core ``SparseCOO`` type so the rest of the stack
never touches raw entry lists.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.sparse import SparseCOO
from . import ref
from .densify import densify_pallas
from .sort_engine import sort_pairs as _sort_pairs
from .spgemm_acc import spgemm_paired_pallas
from .spmm import spmm_pallas


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def spmm(a: SparseCOO, b_dense: jnp.ndarray, use_pallas: bool = False,
         interpret: bool = None) -> jnp.ndarray:
    """Sparse (m×k) × dense (k×n) → dense (m×n) f32."""
    m, _ = a.shape
    vals = jnp.where(a.valid_mask(), a.vals, 0)
    if use_pallas:
        return spmm_pallas(a.rows, a.cols, vals, b_dense, m, interpret=interpret)
    return ref.spmm_ref(a.rows, a.cols, vals, b_dense, m)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def spgemm_paired(a: SparseCOO, b: SparseCOO, use_pallas: bool = False,
                  interpret: bool = None) -> jnp.ndarray:
    """Sparse (m×k) × sparse (k×n) → dense (m×n) f32 — sort-free paired kernel."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    av = jnp.where(a.valid_mask(), a.vals, 0)
    bv = jnp.where(b.valid_mask(), b.vals, 0)
    if use_pallas:
        return spgemm_paired_pallas(
            a.rows, a.cols, av, b.rows, b.cols, bv, m, n, interpret=interpret
        )
    return ref.spgemm_paired_ref(a.rows, a.cols, av, b.rows, b.cols, bv, m, n)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def densify(a: SparseCOO, use_pallas: bool = False,
            interpret: bool = None) -> jnp.ndarray:
    """Padded COO → dense (m×n) f32."""
    m, n = a.shape
    vals = jnp.where(a.valid_mask(), a.vals, 0)
    if use_pallas:
        return densify_pallas(a.rows, a.cols, vals, m, n, interpret=interpret)
    return ref.densify_ref(a.rows, a.cols, vals, m, n)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def sort_pairs(keys: jnp.ndarray, vals: jnp.ndarray, use_pallas: bool = False,
               interpret: bool = None):
    """Single-key sort carrying one payload — the packed-key engine's sort
    primitive (bitonic VMEM network under Pallas, ``lax.sort`` otherwise)."""
    return _sort_pairs(keys, vals, use_pallas=use_pallas, interpret=interpret)
