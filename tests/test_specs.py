"""Fast-lane tests for the unified PlanSpec / PlanFloors / ExecSpec API.

Single-device grid: the legacy-kwarg shim produces the IDENTICAL ``BatchPlan``
and fused-step static signature (zero extra traces via
``summa3d.TRACE_COUNTS``) as the spec path, under exactly one
``DeprecationWarning``; unknown kwargs still raise ``TypeError``;
``PlanFloors.merged`` is a monotonic fold that JSON round-trips; and
``LookaheadWindow.from_exec`` is the one place exec policy becomes schedule
depth.
"""
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import sparse as sp
from repro.core import summa3d
from repro.core.batched import batched_summa3d, plan_batches
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.specs import (
    ExecSpec,
    PlanFloors,
    PlanSpec,
    resolve_specs,
)
from repro.core.summa3d import BatchCaps, HashCaps
from repro.runtime.driver import LookaheadWindow


@pytest.fixture(scope="module")
def grid1():
    return make_grid(1, 1, 1)


def _rand_sparse(n, density, seed):
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.5, 1.0, (n, n)).astype(np.float32)
    return np.where(rng.random((n, n)) < density, x, 0.0).astype(np.float32)


def _operands(grid, n=32, seed=0):
    xa = _rand_sparse(n, 0.3, seed)
    xb = _rand_sparse(n, 0.3, seed + 1)
    A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024), grid, "A")
    B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024), grid, "B")
    return xa, xb, A, B


class TestKwargShim:
    def test_plan_batches_legacy_equals_spec(self, grid1):
        """Old kwargs and the spec objects produce the IDENTICAL plan."""
        _, _, A, B = _operands(grid1, seed=1)
        with pytest.warns(DeprecationWarning, match="plan_batches"):
            legacy = plan_batches(
                A, B, grid1, per_process_memory=1 << 24,
                force_num_batches=2, local_path="esc", slack=1.5,
            )
        new = plan_batches(
            A, B, grid1, per_process_memory=1 << 24,
            spec=PlanSpec(force_num_batches=2, local_path="esc", slack=1.5),
        )
        assert legacy.num_batches == new.num_batches
        assert legacy.caps == new.caps
        assert legacy.sel_cap == new.sel_cap
        assert legacy.local_path == new.local_path
        np.testing.assert_array_equal(legacy.per_batch_flops,
                                      new.per_batch_flops)

    def test_bare_plan_keeps_esc_default(self, grid1):
        """No spec, no kwargs → historical local_path="esc" default; a
        passed spec opts into the "auto" plan-driven dispatch."""
        _, _, A, B = _operands(grid1, seed=2)
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # bare call must NOT warn
            bare = plan_batches(A, B, grid1, per_process_memory=1 << 24)
        assert bare.local_path == "esc"

    def test_batched_legacy_same_signature_zero_retrace(self, grid1):
        """The shim maps onto the same static signature: running the legacy
        spelling after the spec spelling compiles NOTHING new."""
        xa, xb, A, B = _operands(grid1, seed=3)
        kw = dict(per_process_memory=1 << 24, path="sparse",
                  consumer=lambda bi, c, cm: None)
        res_new = batched_summa3d(
            A, B, grid1, spec=PlanSpec(force_num_batches=2, local_path="esc"),
            exec_spec=ExecSpec(lookahead=1), **kw)
        t0 = summa3d.TRACE_COUNTS["fused_step"]
        with pytest.warns(DeprecationWarning, match="batched_summa3d"):
            res_old = batched_summa3d(
                A, B, grid1, force_num_batches=2, local_path="esc",
                lookahead=1, **kw)
        assert summa3d.TRACE_COUNTS["fused_step"] - t0 == 0
        assert res_old.plan.caps == res_new.plan.caps
        assert res_old.plan.num_batches == res_new.plan.num_batches
        assert res_old.plan.sel_cap == res_new.plan.sel_cap
        assert res_old.local_path == res_new.local_path

    def test_legacy_floor_kwargs_map_to_floors(self, grid1):
        _, _, A, B = _operands(grid1, seed=4)
        caps = BatchCaps(4096, 4096, 4096, 4096)
        with pytest.warns(DeprecationWarning):
            legacy = plan_batches(
                A, B, grid1, per_process_memory=1 << 24,
                caps_floor=caps, sel_cap_floor=512, num_batches_floor=4,
            )
        new = plan_batches(
            A, B, grid1, per_process_memory=1 << 24,
            floors=PlanFloors(caps=caps, sel_cap=512, num_batches=4),
        )
        assert legacy.caps == new.caps
        assert legacy.sel_cap == new.sel_cap == 512
        assert legacy.num_batches == new.num_batches == 4

    def test_unknown_kwarg_raises_typeerror(self, grid1):
        _, _, A, B = _operands(grid1, seed=5)
        with pytest.raises(TypeError, match="nonsense"):
            plan_batches(A, B, grid1, per_process_memory=1 << 24,
                         nonsense=1)
        # exec-only kwargs are not part of plan_batches' surface
        with pytest.raises(TypeError, match="lookahead"):
            plan_batches(A, B, grid1, per_process_memory=1 << 24,
                         lookahead=3)

    def test_single_warning_lists_all_legacy_keys(self):
        with pytest.warns(DeprecationWarning) as rec:
            resolve_specs(None, None, None,
                          {"slack": 1.1, "lookahead": 3},
                          where="batched_summa3d")
        assert len(rec) == 1
        msg = str(rec[0].message)
        assert "slack" in msg and "lookahead" in msg


class TestPlanFloors:
    def test_merged_monotone(self):
        a = PlanFloors(caps=BatchCaps(8, 16, 32, 64), sel_cap=10,
                       num_batches=2,
                       hash_caps=HashCaps(128, 64, 8), caps_pow2=False)
        b = PlanFloors(caps=BatchCaps(16, 8, 64, 32), sel_cap=5,
                       num_batches=4,
                       hash_caps=HashCaps(64, 128, 16), caps_pow2=True)
        m = a.merged(b)
        assert m.caps == BatchCaps(16, 16, 64, 64)
        assert m.sel_cap == 10 and m.num_batches == 4
        assert m.hash_caps == HashCaps(128, 128, 16)
        assert m.caps_pow2 is True
        # dominance: merging the fold back in is a no-op (idempotent max)
        assert m.merged(a) == m and m.merged(b) == m
        # commutative
        assert b.merged(a) == m

    def test_merged_none_fields(self):
        a = PlanFloors(sel_cap=3)
        b = PlanFloors(caps=BatchCaps(1, 2, 3, 4))
        m = a.merged(b)
        assert m.caps == BatchCaps(1, 2, 3, 4) and m.sel_cap == 3
        assert m.hash_caps is None

    def test_meta_round_trip(self):
        f = PlanFloors(caps=BatchCaps(8, 16, 32, 64), sel_cap=7,
                       num_batches=3, hash_caps=HashCaps(32, 16, 4), caps_pow2=True)
        assert PlanFloors.from_meta(f.to_meta()) == f
        assert PlanFloors.from_meta(None) == PlanFloors()
        assert PlanFloors.from_meta({}) == PlanFloors()


class TestExecWindow:
    def test_from_exec_depth(self):
        done = []
        w = LookaheadWindow.from_exec(ExecSpec(lookahead=3), done.append)
        assert w.depth == 3
        w = LookaheadWindow.from_exec(
            ExecSpec(pipelined=False, lookahead=3), done.append)
        assert w.depth == 0
        w.push(1)
        assert done == [1]  # synchronous: completes on push
