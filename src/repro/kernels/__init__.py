"""Pallas TPU kernels for the paper's compute hot-spots (§IV-D), with
pure-jnp oracles (ref.py) and jit'd wrappers (ops.py).

  spmm.py          sparse × dense   (MoE dispatch; dense-accumulator SpGEMM)
  spgemm_acc.py    COO × COO → dense tile (sort-free paired SpGEMM, the
                   paper's hash-SpGEMM adapted to the MXU/VMEM)
  spgemm_hash.py   hash-accumulator insert rounds of the hash local
                   multiply (plain XLA: a data-dependent table scatter)
  col_prune.py     per-column top-k bisection of the dense MCL path
  sort_engine.py   in-VMEM bitonic sort of packed (row,col)-key/value pairs —
                   the on-chip sort primitive behind the packed-key
                   sort/compress engine in ``repro.core.sortkeys``
  densify.py       COO → dense tile scatter

``backend.resolve_interpret`` makes the one call-time compiled-or-interpret
decision; ``repro.core.sortkeys`` documents the packed-key encoding and
engine policy.
Only ``col_prune`` and ``spgemm_hash`` are on the SpGEMM main path; the
``ops`` wrappers default to their pure-jnp oracles.
"""
from . import ops, ref  # noqa: F401
from .ops import (  # noqa: F401
    densify,
    sort_pairs,
    spgemm_paired,
    spmm,
)
