"""Cost-model calibration + autotuner acceptance suite (BENCH_tune.json).

Two claims, both host math (no devices, no timed multiplies):

  * model rows — for every PIPELINED driver row of the checked-in
    ``BENCH_summa3d.json``, the analytical cost model's prediction for the
    exact same workload (R-MAT seeds, grid, forced batch count — replanned
    through the host symbolic oracle) divided by the measured wall-ms lands
    inside the fixed ``ACCEPT_BAND`` after the single-scalar overhead fit.
    The ratio per row is the artifact later PRs assert against: the model
    stays calibrated as the kernels evolve or it fails the schema check.
  * autotune rows — across memory budgets, the tuner's pick is NEVER priced
    worse than the untouched defaults (the default config is in its
    candidate set), and on the constrained R-MAT skew budget it picks a
    config with strictly fewer transfer bytes or batches than the fixed
    heuristics (it drops the fiber exchange by choosing fewer layers).

``--smoke`` shrinks the budget sweep, same rows/schema.
"""
import json
import pathlib
import time

from repro.tune import (
    ACCEPT_BAND,
    autotune,
    fit_overhead,
    predict_cost,
)

from .common import emit

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

# the exact workload run_summa3d_suite times, replanned via the host oracle
BENCH_SCALE, BENCH_EF, BENCH_NB = 8, 8, 32
BENCH_GRID = (2, 2, 2)
BENCH_PPM = 1 << 30
PIPELINED_VARIANTS = {
    "pipelined": "auto",
    "pipelined_esc": "esc",
    "pipelined_hash": "hash",
}

SKEW_BUDGET = 80_000  # forces batching; layer choice moves real bytes


def _bench_pair():
    from repro.core import gen

    return (gen.rmat(scale=BENCH_SCALE, edge_factor=BENCH_EF, seed=3),
            gen.rmat(scale=BENCH_SCALE, edge_factor=BENCH_EF, seed=4))


def _model_rows(a, b) -> list:
    """Predicted-vs-measured ratio per checked-in pipelined driver row."""
    from repro.core.batched import PlanInputs, plan_from_symbolic
    from repro.core.specs import PlanFloors, PlanSpec
    from repro.core.symbolic import host_symbolic_counts

    artifact = REPO_ROOT / "BENCH_summa3d.json"
    if not artifact.exists():
        raise FileNotFoundError(
            f"{artifact} not found — run `benchmarks.run --suite summa3d` "
            f"first (the tune suite calibrates against its driver rows)"
        )
    measured = {
        r["variant"]: r["wall_ms"]
        for r in json.loads(artifact.read_text())["rows"]
        if r.get("op") == "driver_e2e" and r["variant"] in PIPELINED_VARIANTS
    }
    counts = host_symbolic_counts(a, b, BENCH_GRID)
    inputs = PlanInputs.from_host(a, b, BENCH_GRID)
    raw = {}
    for variant, path in PIPELINED_VARIANTS.items():
        plan = plan_from_symbolic(
            counts, inputs, BENCH_PPM,
            PlanSpec(local_path=path, force_num_batches=BENCH_NB),
            PlanFloors(),
        )
        raw[variant] = predict_cost(plan, BENCH_GRID, inputs.nnz_a,
                                    inputs.nnz_b)
    coeffs = fit_overhead(
        [(raw[v].total_ms, measured[v]) for v in measured]
    )
    lo, hi = ACCEPT_BAND
    rows = []
    all_ok = True
    for variant in PIPELINED_VARIANTS:
        pred = coeffs.overhead * raw[variant].total_ms
        ratio = pred / measured[variant]
        ok = lo <= ratio <= hi
        all_ok = all_ok and ok
        rows.append(dict(
            op="model", variant=variant, wall_ms=measured[variant],
            raw_predicted_ms=raw[variant].total_ms, predicted_ms=pred,
            ratio=ratio, band_lo=lo, band_hi=hi, within_band=ok,
            num_batches=raw[variant].num_batches, path=raw[variant].path,
        ))
        emit(f"tune/model_{variant}", 0.0, f"ratio={ratio:.2f}")
    rows.append(dict(
        op="summary", variant="model_acceptance", wall_ms=0.0,
        overhead=coeffs.overhead, all_within_band=all_ok,
        band_lo=lo, band_hi=hi,
    ))
    emit("tune/model_acceptance", 0.0,
         f"overhead={coeffs.overhead:.2f} all_within_band={all_ok}")
    return rows


def _autotune_row(a, b, budget, variant) -> dict:
    t0 = time.perf_counter()
    t = autotune(a, b, budget, num_devices=8)
    wall = (time.perf_counter() - t0) * 1e3
    never_worse = t.predicted.total_ms <= t.baseline_predicted.total_ms
    row = dict(
        op="autotune", variant=variant, wall_ms=wall, budget=budget,
        tuned_grid=list(t.grid_shape), tuned_path=t.spec.local_path,
        tuned_batches=t.num_batches,
        tuned_pred_ms=round(t.predicted.total_ms, 3),
        tuned_comm_bytes=t.predicted.comm_bytes,
        base_grid=list(t.baseline_grid_shape),
        base_batches=t.baseline_num_batches,
        base_pred_ms=round(t.baseline_predicted.total_ms, 3),
        base_comm_bytes=t.baseline_predicted.comm_bytes,
        never_worse=never_worse,
    )
    emit(f"tune/{variant}", wall * 1e3,
         f"grid={t.grid_shape} path={t.spec.local_path} "
         f"b={t.num_batches} vs default b={t.baseline_num_batches}")
    return row


def run_tune_suite(smoke: bool = False) -> list:
    """The ``--suite tune`` entry: returns JSON-ready rows."""
    a, b = _bench_pair()
    rows = _model_rows(a, b)

    budgets = ((200_000, 40_000) if smoke
               else (1 << 30, 200_000, 120_000, 80_000, 40_000))
    never_worse_all = True
    for budget in budgets:
        row = _autotune_row(a, b, budget, f"budget_{budget}")
        never_worse_all = never_worse_all and row["never_worse"]
        rows.append(row)

    # the R-MAT skew acceptance row: constrained budget, tuned must beat the
    # fixed heuristics on a MEASURABLE axis (bytes or batches), not just ms
    skew = _autotune_row(a, b, SKEW_BUDGET, "skew")
    skew["cheaper_comm_bytes"] = (
        skew["tuned_comm_bytes"] < skew["base_comm_bytes"])
    skew["cheaper_batches"] = skew["tuned_batches"] < skew["base_batches"]
    skew_cheaper = skew["cheaper_comm_bytes"] or skew["cheaper_batches"]
    never_worse_all = never_worse_all and skew["never_worse"]
    rows.append(skew)

    rows.append(dict(
        op="summary", variant="autotune_acceptance", wall_ms=0.0,
        never_worse_all=never_worse_all, skew_cheaper=skew_cheaper,
        skew_budget=SKEW_BUDGET,
    ))
    emit("tune/autotune_acceptance", 0.0,
         f"never_worse_all={never_worse_all} skew_cheaper={skew_cheaper}")
    return rows


def run() -> None:
    run_tune_suite(smoke=True)
