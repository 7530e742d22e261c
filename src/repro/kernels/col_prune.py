"""Pallas TPU kernel: per-column threshold for top-k pruning (MCL hot path).

HipMCL consumes every SpGEMM batch with column-wise selection (paper §V-C:
"keeps top-k entries in each column"). The TPU-native realization avoids
per-column sorting: an iterative per-column threshold refinement (bisection
on value) streams a dense batch block through VMEM and emits, per
column, the bisection bracket (lo, hi): hi is the smallest tested threshold
with |{i : x[i,c] >= hi}| <= k, lo the largest with count > k. The caller
keeps entries >= hi — a masked select, no sort — and breaks k-boundary TIES
from the [lo, hi) band by rank (``sparse_apps.mcl``), since a value repeated
across the boundary would otherwise be pruned entirely.

Grid: (column tiles, THRESH_ITERS + 1 passes, row blocks). Pass 0 takes the
per-column max |x|; each later pass counts, block by block over the rows,
the entries at or above the bracket's midpoint and halves the bracket after
the last row block. Only one (m_blk, n_blk) block is in VMEM at a time, so
the kernel compiles for any column height (a whole 65536-row column block
does not fit VMEM); each pass re-reads the column tile from HBM.

Wired into the MCL pipeline (``sparse_apps.mcl``): the dense-path batch
postprocess row-gathers each column block and runs this kernel for the
per-column thresholds; the sparse path runs the same bisection distributed
(per-column counts ``psum``-reduced over the grid row axis) as a masked
select on the COO entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import resolve_interpret

THRESH_ITERS = 24  # bisection steps — resolves ~1e-7 of the value range


def _col_prune_kernel(x_ref, out_ref, lo_ref, hi_ref, cnt_ref, *, k: int):
    p = pl.program_id(1)  # 0: column max; 1..THRESH_ITERS: bisection
    r = pl.program_id(2)
    last_r = r == pl.num_programs(2) - 1
    x = jnp.abs(x_ref[...].astype(jnp.float32))  # (m_blk, n_blk)

    @pl.when((p == 0) & (r == 0))
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    @pl.when(p == 0)
    def _col_max():
        hi_ref[...] = jnp.maximum(hi_ref[...], jnp.max(x, axis=0, keepdims=True))

        @pl.when(last_r)
        def _():
            hi_ref[...] = hi_ref[...] + 1e-6

    @pl.when(p > 0)
    def _bisect():
        mid = 0.5 * (lo_ref[...] + hi_ref[...])
        c = jnp.sum((x >= mid).astype(jnp.int32), axis=0, keepdims=True)
        cnt_ref[...] = jnp.where(r == 0, c, cnt_ref[...] + c)

        @pl.when(last_r)
        def _():
            # too many survivors -> raise threshold (move lo up), else lower hi
            take_hi = cnt_ref[...] > k
            lo_ref[...] = jnp.where(take_hi, mid, lo_ref[...])
            hi_ref[...] = jnp.where(take_hi, hi_ref[...], mid)

    # bracket: count(>=hi) <= k < count(>=lo)
    out_ref[0:1, :] = lo_ref[...]
    out_ref[1:2, :] = hi_ref[...]


def col_topk_bounds_pallas(
    x: jnp.ndarray, k: int, *, n_blk: int = 128, m_blk: int = 512,
    interpret: bool = None,
):
    """Per-column bisection bracket ``(lo, hi)`` for top-k |value| selection.

    ``hi`` keeps at most k entries (``|x| >= hi``); values in ``[lo, hi)``
    are the k-boundary tie band (empty when no tie straddles k). x: (m, n).
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    m, n = x.shape
    n_blk = min(n_blk, _rup(n, 128))
    m_blk = min(m_blk, _rup(m, 8))
    n_pad, m_pad = _rup(n, n_blk), _rup(m, m_blk)
    # zero padding never counts: every bisection midpoint is > 0
    xp = jnp.pad(x, ((0, m_pad - m), (0, n_pad - n)))
    out = pl.pallas_call(
        functools.partial(_col_prune_kernel, k=int(k)),
        grid=(n_pad // n_blk, THRESH_ITERS + 1, m_pad // m_blk),
        in_specs=[pl.BlockSpec((m_blk, n_blk), lambda j, p, r: (r, j))],
        out_specs=pl.BlockSpec((2, n_blk), lambda j, p, r: (0, j)),
        out_shape=jax.ShapeDtypeStruct((2, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, n_blk), jnp.float32),  # lo
            pltpu.VMEM((1, n_blk), jnp.float32),  # hi
            pltpu.VMEM((1, n_blk), jnp.int32),  # running count
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=resolve_interpret(interpret),
    )(xp)
    return out[0, :n], out[1, :n]


def col_topk_threshold_pallas(
    x: jnp.ndarray, k: int, *, n_blk: int = 128, interpret: bool = None
) -> jnp.ndarray:
    """Per-column |value| threshold keeping at most k entries. x: (m, n)."""
    return col_topk_bounds_pallas(x, k, n_blk=n_blk, interpret=interpret)[1]


def col_topk_threshold_ref(x: jnp.ndarray, k: int) -> jnp.ndarray:
    """Oracle: exact k-th largest |value| per column (sorted)."""
    m, n = x.shape
    a = jnp.abs(x.astype(jnp.float32))
    svals = jnp.sort(a, axis=0)[::-1]  # descending per column
    kth = svals[jnp.minimum(k - 1, m - 1)] if k <= m else jnp.zeros((n,))
    counts = jnp.sum(a >= kth[None, :], axis=0)
    return jnp.where(counts <= k, kth, kth + 0.0)


def _rup(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
