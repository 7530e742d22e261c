"""The program's own trace names: the fused step's phase scopes and the
driver's host spans.

The device phases are ``jax.named_scope`` scopes, so every op of the compiled
step carries its phase in its ``op_name`` (the l = 2 grid, with its split and
fiber exchange, is ``distributed_cases.case_fused_step_phase_scopes``); the host spans are
``jax.profiler.TraceAnnotation`` events, recorded only under
``jax.profiler.trace``, with ints and strings as args. Recording changes no
result.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from phase_scopes import (
    PHASES,
    checked_ops,
    hlo_text,
    outside_one_phase,
    scopes,
)
from repro.core import gen
from repro.core import semiring as sr
from repro.core import sparse as sp
from repro.core.batched import _fused_jit, batched_summa3d, plan_batches
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.spans import span
from repro.core.specs import ExecSpec, PlanSpec

N = 64
NB = 2


@pytest.fixture(scope="module")
def grid1():
    return make_grid(1, 1, 1)


@pytest.fixture(scope="module")
def operands(grid1):
    a = gen.erdos_renyi(N, avg_nnz_per_row=6, seed=1)
    return scatter_to_grid(a, grid1, "A"), scatter_to_grid(a, grid1, "B")


def _mask(grid, seed=3):
    rng = np.random.default_rng(seed)
    mr, mc = np.nonzero(rng.random((N, N)) < 0.2)
    coo = sp.from_numpy_coo(mr, mc, np.ones(len(mr), np.float32), (N, N),
                            cap=len(mr) + 8)
    return scatter_to_grid(coo, grid, "C")


def _lower(A, B, grid, local_path, mask=None):
    plan = plan_batches(A, B, grid, 1 << 26, spec=PlanSpec(
        local_path=local_path, force_num_batches=NB, mask=mask))
    assert plan.local_path == local_path
    return _fused_jit.lower(
        A, B, jnp.int32(0), mask, grid=grid, num_batches=NB,
        sel_cap=plan.sel_cap, caps=plan.caps, semiring=sr.PLUS_TIMES,
        sorted_merge=True, path="sparse", hashc=plan.hash_caps,
        mask_cap=plan.mask_sel_cap, mask_complement=False,
    )


@pytest.mark.parametrize("local_path,masked", [
    ("esc", False), ("hash", False), ("esc", True)])
def test_fused_step_ops_lie_in_one_phase_scope(grid1, operands, local_path,
                                               masked):
    A, B = operands
    hlo = hlo_text(_lower(A, B, grid1, local_path,
                          _mask(grid1) if masked else None))
    ops = checked_ops(hlo)
    assert {op for op, _ in ops} >= {"gather", "scatter"}
    assert outside_one_phase(hlo) == []
    found = {scopes(name)[0] for _, name in ops}
    if local_path == "esc":
        assert {"spgemm.select", "spgemm.esc.sort_a", "spgemm.esc.expand",
                "spgemm.esc.compress"} <= found
    else:
        assert "spgemm.hash" in found and not any(
            s.startswith("spgemm.esc.") for s in found)
    assert found <= PHASES


def _record(fn, log_dir):
    """Run ``fn`` under the profiler; return its result and the program's
    spans as (name, start_ns, end_ns, args) in start order."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(log_dir)):
        out = fn()
    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("spgemm.")
    ]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _multiply(A, B, grid, exec_spec, slack=None, consumer=None):
    spec = PlanSpec(local_path="esc", force_num_batches=NB)
    if slack is not None:
        spec = spec.replace(slack=slack)
    batches = []

    def keep(bi, c, col_map):
        batches.append(jax.device_get((c.rows, c.cols, c.vals, c.nnz)))
        if consumer is not None:
            consumer()

    res = batched_summa3d(A, B, grid, 1 << 26, consumer=keep, spec=spec,
                          exec_spec=exec_spec)
    return res, batches


@pytest.mark.parametrize("pipelined", [False, True])
def test_spans_nest_and_name_each_batch(grid1, operands, tmp_path,
                                        pipelined):
    A, B = operands

    def consumer():  # a caller's own span nests inside spgemm.consume
        with span("spgemm.test_consumer", batch=7):
            pass

    (res, _), spans = _record(
        lambda: _multiply(A, B, grid1, ExecSpec(pipelined=pipelined),
                          consumer=consumer), tmp_path)
    assert res.report.retries == 0
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    (call,) = by["spgemm.call"]
    (plan,) = by["spgemm.plan"]
    (symbolic,) = by["spgemm.symbolic"]
    (plan_host,) = by["spgemm.plan_host"]
    assert _inside(plan, call)
    assert _inside(symbolic, plan) and _inside(plan_host, plan)
    assert symbolic[2] <= plan_host[1]
    assert all(_inside(s, call) for s in spans)
    for name in ("spgemm.dispatch", "spgemm.wait", "spgemm.consume"):
        assert [s[3]["batch"] for s in by[name]] == list(range(NB)), name
    assert "spgemm.retry" not in by and "spgemm.replan" not in by
    for inner, outer in zip(by["spgemm.test_consumer"], by["spgemm.consume"]):
        assert _inside(inner, outer)
    for d, w in zip(by["spgemm.dispatch"], by["spgemm.wait"]):
        assert d[2] <= w[1]  # a batch is dispatched before it is waited on
    assert not any(_inside(s, plan) for s in by["spgemm.dispatch"])


@pytest.mark.parametrize("pipelined", [False, True])
def test_one_retry_span_per_counted_retry(grid1, operands, tmp_path,
                                          pipelined):
    A, B = operands
    (res, _), spans = _record(
        lambda: _multiply(A, B, grid1, ExecSpec(pipelined=pipelined,
                                                max_retries=8),
                          slack=0.05), tmp_path)
    retries = [s for s in spans if s[0] == "spgemm.retry"]
    assert res.report.retries > 0
    assert len(retries) == res.report.retries
    assert all(s[3]["kind"] in ("sel", "mul") for s in retries)
    assert all(s[3]["batch"] in range(NB) for s in retries)
    kinds = [s[3]["kind"] for s in retries]
    assert kinds.count("sel") == res.report.sel_retries
    dispatches = [s for s in spans if s[0] == "spgemm.dispatch"]
    assert len(dispatches) == NB + res.report.retries


def test_replan_spans_name_the_degraded_batches(grid1, tmp_path):
    """A budget a fraction of the output's footprint and a slack-starved
    plan: the ladder is refused and the failing batches rerun at finer
    batching, one ``spgemm.replan`` per attempt (``tests/test_resilient.py``
    checks the product)."""
    rng = np.random.default_rng(0)
    dense = (rng.random((N, N)) < 0.3) * rng.random((N, N))
    r, c = np.nonzero(dense)
    coo = sp.from_numpy_coo(r.astype(np.int32), c.astype(np.int32),
                            dense[r, c].astype(np.float32), (N, N))
    A, B = scatter_to_grid(coo, grid1, "A"), scatter_to_grid(coo, grid1, "B")
    ref = plan_batches(A, B, grid1, 1 << 30,
                       spec=PlanSpec(slack=1.0, local_path="esc"))
    budget = 12 * (int(np.asarray(A.nnz).max()) + int(np.asarray(B.nnz).max())
                   + ref.caps.flops_cap // 4)
    res, spans = _record(lambda: batched_summa3d(
        A, B, grid1, budget, consumer=lambda *_: None,
        spec=PlanSpec(slack=0.05), exec_spec=ExecSpec(max_retries=12),
    ), tmp_path)
    replans = [s for s in spans if s[0] == "spgemm.replan"]
    assert res.report.replans > 0
    assert len(replans) >= res.report.replans
    done = {(s[3]["batch"], s[3]["split"]) for s in replans}
    assert set(res.report.degraded_batches) <= done
    assert len([s for s in spans if s[0] == "spgemm.retry"]) == \
        res.report.retries


def test_profiler_changes_no_result(grid1, operands, tmp_path):
    A, B = operands
    ex = ExecSpec(pipelined=False, max_retries=8)
    res0, plain = _multiply(A, B, grid1, ex, slack=0.05)
    (res1, traced), _ = _record(
        lambda: _multiply(A, B, grid1, ex, slack=0.05), tmp_path)
    assert res0.report == res1.report
    assert len(plain) == len(traced) == NB
    for x, y in zip(plain, traced):
        for u, v in zip(x, y):
            assert u.dtype == v.dtype and np.array_equal(u, v)


def test_span_args_are_ints_and_strings():
    with span("spgemm.test", batch=1, kind="sel"):
        pass
    with pytest.raises(TypeError):
        span("spgemm.test", share=0.5)
