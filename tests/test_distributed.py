"""Distributed SUMMA tests — each case runs in a subprocess with 8 host
devices (XLA device count is locked at first jax init, so the main pytest
process must keep seeing 1 device)."""
import os
import subprocess
import sys

import pytest

# subprocess-per-case with an 8-device host platform — excluded from the CI fast lane (-m 'not slow')
pytestmark = pytest.mark.slow

CASES = [
    "scatter_gather_roundtrip",
    "dense_path_full_multiply",
    "sparse_path_full_multiply",
    "symbolic_flops_exact",
    "plan_batches_bounds",
    "batched_dense_invariance",
    "batched_sparse_invariance",
    "layer1_grid",
    "symbolic_driven_batching",
    "semiring_or_and",
    "overflow_retry",
    "pipelined_serial_parity",
    "pipelined_overflow_retry",
    "rectangular_aat",
    "ring_schedule_matches",
    "tune_oracle_parity",
    "rect_grid_oracle_parity",
    "fused_step_phase_scopes",
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("case", CASES)
def test_distributed_case(case):
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_PLATFORMS="cpu",
    )
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "distributed_cases.py"), case],
        env=env,
        capture_output=True,
        text=True,
        timeout=900,
    )
    assert r.returncode == 0, f"case {case} failed:\n{r.stdout}\n{r.stderr}"
    assert f"OK {case}" in r.stdout
