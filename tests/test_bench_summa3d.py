"""Smoke test for ``benchmarks.run --suite summa3d`` — the driver benchmark
must produce the acceptance rows (the fixed-memory plan, per-batch and
end-to-end driver timings, summary). Runs in a subprocess with 8 host devices; excluded
from the CI fast lane (-m 'not slow')."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SNIPPET = """
import json, sys
from benchmarks.bench_summa3d import run_summa3d_suite
rows = run_summa3d_suite(scale=6, edge_factor=6, nb=4, iters=1)
json.dump(rows, open(sys.argv[1], "w"))
"""


def test_summa3d_suite_rows(tmp_path):
    out = tmp_path / "rows.json"
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, "-c", _SNIPPET, str(out)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900,
    )
    assert r.returncode == 0, f"suite failed:\n{r.stdout}\n{r.stderr}"
    rows = json.loads(out.read_text())
    by_op = {}
    for row in rows:
        by_op.setdefault(row["op"], []).append(row)

    plans = {row["variant"]: row for row in by_op["plan"]}
    assert set(plans) == {"fixed_mem_batches"}
    # the hash memory model's acceptance row: fewer batches at fixed memory
    fixed = plans["fixed_mem_batches"]
    assert fixed["num_batches_esc"] > 1, fixed
    assert fixed["num_batches_hash"] < fixed["num_batches_esc"], fixed

    e2e = {row["variant"]: row["wall_ms"] for row in by_op["driver_e2e"]}
    assert set(e2e) == {"serial", "pipelined", "pipelined_esc",
                        "pipelined_hash"}
    assert all(ms > 0 for ms in e2e.values()), e2e
    assert len(by_op["driver_batch"]) == 4  # one wall-ms row per batch

    (summary,) = by_op["summary"]
    assert summary["speedup_pipelined_vs_serial"] > 0
    assert summary["hash_batches_fewer"] is True, summary
    assert summary["local_path_used"] in ("esc", "hash")
