"""Paper Table VII / Fig. 15 — local multiply + merge kernel comparison.

The paper compares 'previous' (sorted heap) against 'now' (sort-free hash).
Our TPU adaptation compares, per hot-path op:

  * ESC coalesce — legacy two-key ``lexsort`` vs the packed-key engine
    (single-key sort, and the sort-free bucket scan where the key space
    allows it; see ``repro.core.sortkeys``).
  * Merge-Fiber — unsorted lexsort-merge vs packed engines vs the segmented
    k-way merge that exploits already-sorted fiber pieces (merge, don't
    re-sort).
  * Local multiply — ESC vs the hash accumulator (scratch bytes).

CPU wall times are NOT TPU predictions; the comparison shape (relative cost
of keeping intermediates sorted vs the packed-key engines) is the
reproduced claim. ``run_local_suite``
emits machine-readable rows for BENCH_local_kernels.json (op, variant,
wall_ms, achieved gflops) so the perf trajectory is tracked PR over PR.
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.core import gen
from repro.core import local_spgemm as lsp
from repro.core import semiring as sr
from repro.core import sparse as sp
from repro.core import symbolic as sym

from .common import emit, time_jit


def _note(rows_out, **row):
    """Collect a JSON row when a collector is supplied (CSV-only runs pass
    ``None`` and keep just the emit() side effects)."""
    if rows_out is not None:
        rows_out.append(row)


def _expanded_workload(n, flops_cap, seed=0, valid_p=0.85):
    """An ESC-expansion-shaped entry list: flops_cap slots, duplicate-heavy
    coordinates over an (n, n) tile, a tail of invalid slots."""
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.integers(0, n, flops_cap).astype(np.int32))
    cols = jnp.asarray(rng.integers(0, n, flops_cap).astype(np.int32))
    valid = jnp.asarray(rng.random(flops_cap) < valid_p)
    vals = jnp.asarray((rng.random(flops_cap) + 0.1).astype(np.float32))
    x = sp.SparseCOO(rows, cols, vals, jnp.int32(flops_cap), (n, n))
    return x, valid


def bench_coalesce(rows_out=None, n=512, flops_cap=1 << 17, out_cap=1 << 16):
    """ESC coalesce micro-benchmark: the acceptance comparison (packed vs
    lexsort) plus the individual engines."""
    x, valid = _expanded_workload(n, flops_cap)
    times = {}
    for eng in ("lexsort", "packed", "bucket", "auto"):
        fn = jax.jit(
            lambda xx, vv, e=eng: lsp._coalesce_semiring(
                xx, vv, out_cap, sr.PLUS_TIMES, engine=e
            )[0].vals
        )
        times[eng] = time_jit(fn, x, valid)
        _note(rows_out, **dict(
            op="esc_coalesce", variant=eng, wall_ms=times[eng] / 1e3,
            gflops=flops_cap / times[eng] / 1e3,  # one reduce op per slot
            entries=flops_cap,
        ))
        emit(f"tableVII/esc_coalesce_{eng}", times[eng], f"n={n}")
    speed = times["lexsort"] / max(times["auto"], 1)
    _note(rows_out, **dict(
        op="esc_coalesce", variant="speedup_packed_vs_lexsort",
        wall_ms=0.0, gflops=0.0, speedup=speed,
    ))
    emit("tableVII/esc_coalesce_speedup", 0.0, f"{speed:.2f}x")
    return speed


def bench_merge(rows_out=None, n=512, layers=4, part_cap=1 << 14, out_cap=1 << 16):
    """Merge-Fiber micro-benchmark: engines + the segmented sorted merge."""
    parts = [
        gen.erdos_renyi(n, part_cap / n, seed=10 + i, cap=part_cap).sort_rowmajor()
        for i in range(layers)
    ]
    total = layers * part_cap
    times = {}
    cases = {
        "lexsort": dict(engine="lexsort"),
        "packed": dict(engine="packed"),
        "bucket": dict(engine="bucket"),
        "auto": dict(engine="auto"),
        "segmented_sorted": dict(assume_sorted=True),
    }
    for name, kwargs in cases.items():
        fn = jax.jit(
            lambda *ps, kw=kwargs: lsp.merge_sparse(
                list(ps), out_cap, sr.PLUS_TIMES, **kw
            )[0].vals
        )
        times[name] = time_jit(fn, *parts)
        _note(rows_out, **dict(
            op="merge_fiber", variant=name, wall_ms=times[name] / 1e3,
            gflops=total / times[name] / 1e3, entries=total, layers=layers,
        ))
        emit(f"tableVII/merge_fiber_{name}", times[name], f"l={layers}")
    speed = times["lexsort"] / max(times["auto"], 1)
    _note(rows_out, **dict(
        op="merge_fiber", variant="speedup_packed_vs_lexsort",
        wall_ms=0.0, gflops=0.0, speedup=speed,
    ))
    emit("tableVII/merge_fiber_speedup", 0.0, f"{speed:.2f}x")
    return speed


def bench_hash_vs_esc(rows_out=None, n=256, nnz_per_row=16):
    """Local multiply: ESC expansion vs hash accumulator on a dense-ish
    (high compression factor) workload — the regime where the table's
    O(nnz(C)·load-factor) scratch beats the O(flops) expansion."""
    a = gen.erdos_renyi(n, nnz_per_row, seed=5)
    b = gen.erdos_renyi(n, nnz_per_row, seed=6)
    flops = int(np.asarray(a.col_counts(), np.int64)
                @ np.asarray(b.row_counts(), np.int64))
    flops_cap = sym.rup8(flops)
    out_cap = 1 << 16
    c_probe, ovf = jax.jit(
        lambda x, y: lsp.spgemm_esc(x, y, out_cap, flops_cap)
    )(a, b)
    nnz_out = int(c_probe.nnz)
    assert int(ovf) == 0, int(ovf)
    cf = flops / max(nnz_out, 1)
    table_cap = sym.rup_pow2(max(int(nnz_out * sym.HASH_LOAD_FACTOR), 64))
    chunk_cap = 4096
    num_chunks = -(-flops_cap // chunk_cap)

    t_esc = time_jit(
        jax.jit(lambda x, y: lsp.spgemm_esc(x, y, out_cap, flops_cap)[0].vals),
        a, b,
    )
    t_hash = time_jit(
        jax.jit(lambda x, y: lsp.spgemm_hash(
            x, y, out_cap, table_cap, chunk_cap, num_chunks)[0].vals),
        a, b,
    )
    # resident scratch: the expansion's 3 arrays vs the table's 2
    scratch_esc = flops_cap * 12
    scratch_hash = table_cap * sym.HASH_SLOT_BYTES
    _note(rows_out, **dict(
        op="local_multiply", variant="esc", wall_ms=t_esc / 1e3,
        gflops=2 * flops / t_esc / 1e3, flops=flops, nnz_out=nnz_out,
        compression_factor=cf, scratch_bytes=scratch_esc,
    ))
    _note(rows_out, **dict(
        op="local_multiply", variant="hash", wall_ms=t_hash / 1e3,
        gflops=2 * flops / t_hash / 1e3, flops=flops, nnz_out=nnz_out,
        compression_factor=cf, scratch_bytes=scratch_hash,
        table_cap=table_cap,
    ))
    emit("tableVII/local_multiply_esc", t_esc, f"cf={cf:.2f}")
    emit("tableVII/local_multiply_hash", t_hash,
         f"cf={cf:.2f} scratch {scratch_hash}/{scratch_esc}B")
    return scratch_esc / max(scratch_hash, 1)


def run(n: int = 256, nnz_per_row: int = 8, layers: int = 4) -> None:
    """CSV suite (paper Table VII shape) — kept for ``benchmarks.run`` all."""
    a = gen.erdos_renyi(n, nnz_per_row, seed=1)
    b = gen.erdos_renyi(n, nnz_per_row, seed=2)
    flops_cap = 1 << 17
    out_cap = 1 << 16

    # --- local multiply: ESC (sort-free) vs dense-accumulator
    esc = jax.jit(lambda x, y: lsp.spgemm_esc(x, y, out_cap, flops_cap)[0].vals)
    t_esc = time_jit(esc, a, b)
    emit("tableVII/local_multiply_esc_sortfree", t_esc, f"n={n}")

    acc = jax.jit(lambda x, y: lsp.spgemm_dense_acc(x, y))
    t_acc = time_jit(acc, a, b)
    emit("tableVII/local_multiply_dense_acc", t_acc, f"n={n}")

    # --- merge: sorted-maintained baseline vs sort-free hash-merge
    parts = [gen.erdos_renyi(n, nnz_per_row, seed=10 + i) for i in range(layers)]

    def merge_sorted_baseline(ps):
        # 'heap-like': sort every input first, then pairwise coalesce —
        # sortedness maintained at every step (the paper's 'previous')
        cur = ps[0].sort_rowmajor()
        for nxt in ps[1:]:
            stacked, _ = sp.concat([cur, nxt.sort_rowmajor()], new_cap=out_cap)
            cur, _ = sp.coalesce(stacked, new_cap=out_cap)
        return cur.vals

    def merge_sortfree(ps):
        m, _ = lsp.merge_sparse(ps, out_cap)
        return m.vals

    t_sorted = time_jit(jax.jit(merge_sorted_baseline), parts)
    t_free = time_jit(jax.jit(merge_sortfree), parts)
    emit("tableVII/merge_sorted_baseline", t_sorted, f"l={layers}")
    emit("tableVII/merge_sortfree", t_free,
         f"l={layers} speedup={t_sorted / max(t_free, 1):.2f}x")

    bench_coalesce()
    bench_merge()
    bench_hash_vs_esc()


def run_local_suite() -> list:
    """The ``--suite local`` entry: returns JSON-ready rows (op, variant,
    wall_ms, gflops, extras)."""
    rows = []
    coal = bench_coalesce(rows)
    merg = bench_merge(rows)
    scratch = bench_hash_vs_esc(rows)
    rows.append(dict(
        op="summary", variant="acceptance",
        wall_ms=0.0, gflops=0.0,
        coalesce_speedup=coal, merge_speedup=merg,
        hash_scratch_reduction=scratch,
    ))
    return rows
