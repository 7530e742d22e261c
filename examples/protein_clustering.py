"""HipMCL-style protein clustering (paper §V-C, Fig. 3) — end-to-end.

Builds a synthetic protein-similarity network with planted families, runs
Markov clustering where every expansion A·A goes through BatchedSUMMA3D
under a tight memory budget (each batch pruned immediately), and reports the
recovered families.

The grid comes from ``--grid pr,pc,l``, or from the devices JAX sees: 2x2x1
on four devices (one TPU v5e host), 1x1x1 on one. For a multi-device run on
a CPU, ask XLA for host devices first, for example
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` with ``--grid 2,2,2``.

Run:  PYTHONPATH=src python examples/protein_clustering.py [--n 96 --families 6]
"""
import argparse

import numpy as np


def grid_shape_for(num_devices: int):
    """The square single-layer grid that uses the most devices."""
    side = int(np.sqrt(num_devices))
    return side, side, 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--families", type=int, default=4)
    ap.add_argument("--memory", type=int, default=1 << 22,
                    help="per-process bytes (tight -> batching kicks in)")
    ap.add_argument("--grid", type=str, default=None,
                    help="pr,pc,l (default: from the devices present)")
    args = ap.parse_args()

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core import gen
    from repro.core.grid import make_grid
    from repro.core.sparse import from_numpy_coo
    from repro.sparse_apps.mcl import (
        MCLConfig,
        _col_normalize_np,
        clusters_from_matrix,
        mcl_iterate,
    )

    enable_compile_cache()
    if args.grid:
        shape = tuple(int(v) for v in args.grid.split(","))
    else:
        shape = grid_shape_for(len(jax.devices()))
    grid = make_grid(*shape)
    a = gen.protein_similarity_like(args.n, blocks=args.families, intra_p=0.6,
                                    seed=7)
    nnz = int(a.nnz)
    rows = np.asarray(a.rows[:nnz])
    cols = np.asarray(a.cols[:nnz])
    vals = _col_normalize_np(
        rows, cols, np.asarray(a.vals[:nnz]).astype(np.float64), args.n
    )
    a = from_numpy_coo(rows, cols, vals.astype(np.float32), (args.n, args.n),
                       cap=nnz)
    print(f"input: {args.n} proteins, {nnz} similarities, "
          f"{args.families} planted families; grid {'x'.join(map(str, shape))}")

    final, hist = mcl_iterate(
        a, grid,
        MCLConfig(max_iters=15, per_process_memory=args.memory),
        verbose=True,
    )
    nnz = int(final.nnz)
    labels = clusters_from_matrix(
        np.asarray(final.rows[:nnz]), np.asarray(final.cols[:nnz]), args.n
    )
    found = len(set(labels.tolist()))
    print(f"converged in {len(hist)} iterations; clusters found: {found} "
          f"(planted: {args.families})")
    sizes = sorted(np.bincount(np.unique(labels, return_inverse=True)[1]).tolist(),
                   reverse=True)
    print(f"cluster sizes: {sizes[:10]}")


if __name__ == "__main__":
    main()
