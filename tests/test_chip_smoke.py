"""``chip_smoke.py``'s phases at tiny sizes on the CPU, against scipy.

The phase functions are the ones the chip run calls; here the budget is
given explicitly (the CPU keeps no memory stats) and n is 1024 instead of
2^20 / 2^16. The ``--chips 4`` grids run in a subprocess on four virtual
CPU devices, since the device count is fixed when JAX starts.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core.grid import make_grid

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
import chip_smoke as cs  # noqa: E402

N = 1024
FLOPS_PER_COL = 1028  # A·A partial products per column of this generator


def _budget(a, batches):
    """Per-process bytes that make the planner split A·A into ~``batches``:
    the operands plus 1/(``batches`` - 1) of the ESC step at b = 1."""
    from repro.core.specs import PlanSpec
    from repro.core.symbolic import R_BYTES_DEFAULT, esc_step_bytes

    step = esc_step_bytes(PlanSpec().slack * FLOPS_PER_COL * N)
    return R_BYTES_DEFAULT * 2 * int(a.nnz) + step // (batches - 1)


@pytest.fixture(scope="module")
def problem():
    a, a64 = cs.make_problem(N, seed=0)
    return a, a64, cs.reference_product(a64)


@pytest.fixture(scope="module")
def grid():
    return make_grid(1, 1, 1)


@pytest.fixture(scope="module")
def counter():
    return cs.CompileCounter()


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO / "src"), str(REPO)]))
    env.update(extra)
    return env


def test_problem_has_protein_network_shape():
    a, a64 = cs.make_problem(4096, seed=1)
    per_col = int(a.nnz) / 4096
    assert 28 <= per_col <= 36, per_col
    # column-normalized, as HipMCL's input
    np.testing.assert_allclose(np.asarray(a64.sum(axis=0)).ravel(), 1.0,
                               rtol=1e-6)


@pytest.mark.parametrize("local_path", ["auto", "esc", "hash"])
def test_multiply_phase_matches_scipy(problem, grid, counter, local_path):
    a, _, ref = problem
    out = cs.run_multiply(local_path, a, ref, grid, local_path, counter,
                          budget=_budget(a, 4))
    assert out["local_path"] == ("esc" if local_path == "auto" else local_path)
    assert out["max_rel_err"] <= cs.RTOL
    assert out["warm_compiles"] == 0
    assert out["nnz_c"] == ref.nnz
    if local_path != "hash":  # the hash budget counts the smaller table
        assert out["b"] > 1, out


def test_warm_rerun_skipped_past_deadline(problem, grid, counter):
    import time

    a, _, ref = problem
    out = cs.run_multiply("late", a, ref, grid, "esc", counter,
                          budget=_budget(a, 4), deadline=time.perf_counter())
    assert out["warm_s"] is None and out["warm_skipped"] == "time limit"
    assert out["max_rel_err"] <= cs.RTOL and out["steady_batch_s"] > 0


@pytest.mark.parametrize("corrupt", ["value", "structure"])
def test_checker_rejects_a_wrong_product(problem, grid, counter, corrupt):
    a, _, ref = problem
    bad = ref.copy()
    if corrupt == "value":
        bad.data[bad.nnz // 2] *= 1.001
        match = "relative error"
    else:
        bad.data[bad.nnz // 2] = 0.0
        bad.eliminate_zeros()
        match = "structure differs"
    with pytest.raises(AssertionError, match=match):
        cs.run_multiply("bad", a, bad, grid, "esc", counter,
                        budget=_budget(a, 4))


def test_mcl_phase_matches_host_loop(problem, grid, counter):
    a, a64, _ = problem
    # small enough that the dense step's (nnz x width) gathers need batches
    out = cs.run_mcl("mcl", a, a64, grid, counter, budget=1 << 27)
    assert out["iters"] == cs.MCL_ITERS and out["b"] > 1
    assert out["nnz_per_iter"] == out["ref_nnz_per_iter"]
    assert out["pattern_diff"] == 0 and out["max_rel_err"] < 1e-5
    assert out["same_as_warm"] and out["warm_compiles"] == 0


def test_four_chip_grids_on_virtual_devices(problem):
    """``four_chips`` on the 2x2x1 and 1x1x4 grids, four CPU devices."""
    a, _, _ = problem
    snippet = (
        "import json, jax, chip_smoke as cs\n"
        f"outs = cs.four_chips(0, jax.devices(), cs.CompileCounter(), n={N},"
        f" budget={_budget(a, 4)})\n"
        "print(json.dumps(outs))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", snippet], cwd=REPO, capture_output=True,
        text=True, timeout=900,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"),
    )
    assert r.returncode == 0, r.stderr[-4000:]
    outs = json.loads(r.stdout.strip().splitlines()[-1])
    assert [o["grid"] for o in outs] == ["2x2x1", "1x1x4"]
    for o in outs:
        assert o["tile_devices"] == 4 and o["max_rel_err"] <= cs.RTOL


def test_planner_batches_the_smoke_at_chip_memory():
    """The host oracle of the one-chip smoke plan: A·A at n = 2^20 against
    a v5e's 16.9 GB ``bytes_limit``, with no override, plans the ESC step's
    footprint and needs at least 8 batches (4 would need ~16.3 GB, by the
    v5e ahead-of-time compile of that step)."""
    from repro.core import gen
    from repro.core.batched import PlanInputs, plan_from_symbolic
    from repro.core.specs import PlanFloors, PlanSpec
    from repro.core.symbolic import host_symbolic_counts

    n = cs.N_MAIN
    a = gen.protein_similarity_like(
        n, blocks=n // cs.FAMILY, intra_p=cs.INTRA_P, seed=0
    )
    plan = plan_from_symbolic(
        host_symbolic_counts(a, a, (1, 1, 1)),
        PlanInputs.from_host(a, a, (1, 1, 1)),
        16_909_336_064, PlanSpec(), PlanFloors(),
    )
    assert plan.local_path == "esc", plan.path_reason
    assert plan.num_batches >= 8, plan


def test_script_without_tpu_exits_nonzero():
    r = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")], cwd=REPO,
        capture_output=True, text=True, timeout=300, env=_env(),
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no TPU" in r.stderr


def test_script_alone_exits_nonzero(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    env = _env()
    env.pop("PYTHONPATH")
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode != 0 and '"ok"' not in r.stdout


_CACHE_SNIPPET = """
import json, jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
where = enable_compile_cache()
jax.jit(lambda x: jnp.sort(x) * 2)(jnp.arange(8.0)).block_until_ready()
print(json.dumps([where, jax.config.jax_compilation_cache_dir]))
"""


def test_compile_cache_follows_env_var(tmp_path):
    cache = tmp_path / "cache"
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_SNIPPET], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"),
    )
    assert r.returncode == 0, r.stderr[-4000:]
    where, configured = json.loads(r.stdout.strip().splitlines()[-1])
    assert where == configured == str(cache)
    assert any(cache.iterdir())  # the compile landed there


def test_compile_cache_defaults_to_repo_dir(tmp_path):
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    snippet = (
        "import json, jax\n"
        "from repro.compile_cache import enable_compile_cache\n"
        "print(json.dumps([enable_compile_cache(),"
        " jax.config.jax_compilation_cache_dir]))\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", snippet], cwd=tmp_path,
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    where, configured = json.loads(r.stdout.strip().splitlines()[-1])
    assert where == configured == str(REPO / ".jax_cache")
