"""Validate the checked-in BENCH_*.json artifacts against the shared schema.

Every perf suite (``benchmarks.run --suite local|summa3d|mcl``) writes a JSON
payload with the same envelope, so stale or hand-edited artifacts are caught
mechanically (a CI step runs this after the bench smoke):

    top level: {"suite": str, "backend": str, "platform": str, "rows": [...]}
    every row: {"op": str, "variant": str, "wall_ms": int|float, ...}

Usage::

    python -m benchmarks.check_bench_json [paths...]

With no arguments, validates every BENCH_*.json at the repo root. Exits
nonzero (listing every violation) if any artifact is malformed.
"""
from __future__ import annotations

import json
import pathlib
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

TOP_KEYS = ("suite", "backend", "platform", "rows")
ROW_KEYS = ("op", "variant", "wall_ms")

# Per-suite required (op, variant) -> extra row keys. Suites grow rows over
# time; the pairs here are the acceptance artifacts later PRs assert against,
# so dropping one is a schema error, not a silent regression.
SUITE_ROWS = {
    "summa3d_driver": {
        ("plan", "fixed_mem_batches"): (
            "num_batches_esc", "num_batches_hash", "per_process_memory",
            "compression_factor",
        ),
        ("driver_e2e", "pipelined_hash"): (),
        ("summary", "acceptance"): (
            "num_batches_esc", "num_batches_hash", "hash_batches_fewer",
            "local_path_used",
        ),
    },
    "local_kernels": {
        ("local_multiply", "esc"): ("compression_factor", "scratch_bytes"),
        ("local_multiply", "hash"): ("compression_factor", "scratch_bytes"),
        ("summary", "acceptance"): ("hash_scratch_reduction",),
    },
    "mcl_pipeline": {
        ("mcl_e2e", "device"): ("iters", "host_bytes"),
        ("mcl_e2e", "host"): ("iters", "host_bytes"),
        ("summary", "device_vs_host"): (
            "speedup_device_vs_host", "host_transfer_reduction",
        ),
        # durability lane: per-iteration checkpoint overhead, async vs sync
        ("checkpoint", "async"): (
            "overhead_ms_per_iter", "bytes_per_save", "checkpoint_stalls",
        ),
        ("checkpoint", "sync"): (
            "overhead_ms_per_iter", "bytes_per_save", "checkpoint_stalls",
        ),
    },
    "tune": {
        # one calibration row per pipelined summa3d variant; the summaries
        # carry the two autotuner acceptance criteria
        ("model", "pipelined"): ("ratio", "within_band"),
        ("model", "pipelined_esc"): ("ratio", "within_band"),
        ("model", "pipelined_hash"): ("ratio", "within_band"),
        ("summary", "model_acceptance"): ("overhead", "all_within_band"),
        ("autotune", "skew"): (
            "never_worse", "cheaper_comm_bytes", "cheaper_batches",
        ),
        ("summary", "autotune_acceptance"): (
            "never_worse_all", "skew_cheaper",
        ),
    },
    "graph_masked": {
        ("summary", "masked_vs_unmasked"): (
            "batches_masked", "batches_unmasked", "d_cap_masked",
            "d_cap_unmasked", "masked_below_unmasked",
        ),
        # structure-aware placement acceptance: degree-spread must plan
        # strictly fewer capacity-padded transfer bytes than block-cyclic
        ("placement", "block_cyclic"): (
            "batches", "sel_cap", "piece_cap", "all_to_all_bytes",
            "gather_bytes", "padded_bytes",
        ),
        ("placement", "degree"): (
            "batches", "sel_cap", "piece_cap", "all_to_all_bytes",
            "gather_bytes", "padded_bytes",
        ),
        ("summary", "placement_volume"): (
            "batches_block_cyclic", "batches_degree",
            "padded_bytes_block_cyclic", "padded_bytes_degree",
            "volume_reduction", "degree_below_block_cyclic",
        ),
    },
    "serve_engine": {
        ("serve_e2e", "open_loop"): (
            "p50_ms", "p99_ms", "multiplies_per_s", "requests",
        ),
        ("plan_cache", "hit_rate"): ("hit_rate", "hits", "misses"),
        ("summary", "acceptance"): (
            "plan_cache_hit_rate", "retraces_repeat", "p50_ms", "p99_ms",
        ),
    },
}


def check_payload(payload: object, name: str = "<payload>") -> list:
    """Schema errors for one parsed artifact (empty list = valid)."""
    errors = []
    if not isinstance(payload, dict):
        return [f"{name}: top level must be an object, got {type(payload).__name__}"]
    for key in TOP_KEYS:
        if key not in payload:
            errors.append(f"{name}: missing top-level key '{key}'")
    rows = payload.get("rows")
    if not isinstance(rows, list) or not rows:
        errors.append(f"{name}: 'rows' must be a non-empty list")
        return errors
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            errors.append(f"{name}: rows[{i}] is not an object")
            continue
        for key in ROW_KEYS:
            if key not in row:
                errors.append(f"{name}: rows[{i}] missing '{key}' (op={row.get('op')!r})")
        wall = row.get("wall_ms")
        if wall is not None and not isinstance(wall, (int, float)):
            errors.append(f"{name}: rows[{i}].wall_ms not a number: {wall!r}")
        elif isinstance(wall, (int, float)) and wall < 0:
            errors.append(f"{name}: rows[{i}].wall_ms negative: {wall!r}")
    by_key = {
        (row.get("op"), row.get("variant")): row
        for row in rows if isinstance(row, dict)
    }
    for (op, variant), extras in SUITE_ROWS.get(payload.get("suite"), {}).items():
        row = by_key.get((op, variant))
        if row is None:
            errors.append(f"{name}: missing required row op={op!r} variant={variant!r}")
            continue
        for key in extras:
            if key not in row:
                errors.append(
                    f"{name}: row op={op!r} variant={variant!r} missing '{key}'"
                )
    return errors


def check_file(path: pathlib.Path) -> list:
    try:
        payload = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path.name}: unreadable/unparsable ({e})"]
    return check_payload(payload, path.name)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    paths = [pathlib.Path(p) for p in argv] or sorted(
        REPO_ROOT.glob("BENCH_*.json")
    )
    if not paths:
        print("no BENCH_*.json artifacts found", file=sys.stderr)
        return 1
    errors = []
    for p in paths:
        errors.extend(check_file(p))
    for e in errors:
        print(f"SCHEMA ERROR: {e}", file=sys.stderr)
    if not errors:
        print(f"ok: {len(paths)} artifact(s) valid "
              f"({', '.join(p.name for p in paths)})")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
