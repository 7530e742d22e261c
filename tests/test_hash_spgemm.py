"""Hash-accumulator local multiply: kernel, local, plan and driver tests.

The hash path (``kernels/spgemm_hash`` + ``local_spgemm.spgemm_hash``) must
be a drop-in third local multiply: identical (row-major-sorted C, overflow)
contract to ESC across semirings / masks / batch counts, the same
count-and-retry overflow behavior, and — the point of the exercise — a
strictly smaller memory footprint on compressing workloads, surfaced as
fewer planned batches at a fixed ``per_process_memory``.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import gen, semiring as sr, sortkeys, sparse as sp
from repro.core.batched import (
    HASH_CF_THRESHOLD,
    batched_summa3d,
    plan_batches,
    probe_memory_budget,
    symbolic3d_counts,
)
from repro.core.distsparse import scatter_to_grid
from repro.core.grid import make_grid
from repro.core.local_spgemm import spgemm_esc, spgemm_hash
from repro.core.symbolic import (
    HASH_LOAD_FACTOR,
    estimate_mem_c_bytes,
    rup_pow2,
)
from repro.kernels import spgemm_hash as hashkern
from repro.sparse_apps.mcl import _sparse_batch_to_global


@pytest.fixture(scope="module")
def grid1():
    return make_grid(1, 1, 1)


def _dense(m, n, density, seed, lo=0.5, hi=1.0):
    rng = np.random.default_rng(seed)
    x = rng.uniform(lo, hi, (m, n)).astype(np.float32)
    return np.where(rng.random((m, n)) < density, x, 0.0).astype(np.float32)


def _pair(seed=0, m=24, k=20, n=22, da=0.35, db=0.35):
    xa = _dense(m, k, da, seed)
    xb = _dense(k, n, db, seed + 1)
    a = sp.from_dense(jnp.asarray(xa), cap=max(int((xa != 0).sum()), 8))
    b = sp.from_dense(jnp.asarray(xb), cap=max(int((xb != 0).sum()), 8))
    return xa, xb, a, b


def _as_sets(c: sp.SparseCOO):
    nnz = int(c.nnz)
    return (
        np.asarray(c.rows[:nnz]),
        np.asarray(c.cols[:nnz]),
        np.asarray(c.vals[:nnz]),
    )


def _assert_same_output(ch, ce, rtol=1e-5):
    rh, colh, vh = _as_sets(ch)
    re_, cole, ve = _as_sets(ce)
    assert len(rh) == len(re_), (len(rh), len(re_))
    np.testing.assert_array_equal(rh, re_)
    np.testing.assert_array_equal(colh, cole)
    np.testing.assert_allclose(vh, ve, rtol=rtol, atol=1e-6)


def _hash_kwargs(a, b, table_slack=4.0):
    """Generous static caps for parity tests (overflow exercised separately)."""
    flops = 4096
    nnz_hint = int(a.shape[0]) * int(b.shape[1])
    return dict(
        out_cap=max(nnz_hint, 8),
        table_cap=rup_pow2(max(int(nnz_hint * table_slack), 64)),
        chunk_cap=256,
        num_chunks=-(-flops // 256),
    )


SEMIRINGS = [sr.PLUS_TIMES, sr.MIN_PLUS, sr.MAX_TIMES]


# ---------------------------------------------------------------------------
# Kernel level: insert rounds
# ---------------------------------------------------------------------------
class TestHashInsert:
    def test_insert_matches_dict_reference(self):
        """With probe rounds to spare, the table holds exactly the distinct
        valid keys, each with the sum of its values."""
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 500, 128).astype(np.int32)
        vals = rng.uniform(0.5, 1, 128).astype(np.float32)
        valid = rng.random(128) < 0.8
        T = 256
        tk, tv, dropped = hashkern.hash_insert(
            jnp.full((T,), hashkern.EMPTY, jnp.int32),
            jnp.zeros((T,), jnp.float32), jnp.asarray(keys), jnp.asarray(vals),
            jnp.asarray(valid), add_kind="sum", max_probes=T)
        assert int(dropped) == 0
        want = {}
        for k, v in zip(keys[valid], vals[valid]):
            want[int(k)] = want.get(int(k), 0.0) + float(v)
        tk_np, tv_np = np.asarray(tk), np.asarray(tv)
        occupied = tk_np != hashkern.EMPTY
        got = dict(zip(tk_np[occupied].tolist(), tv_np[occupied].tolist()))
        assert got.keys() == want.keys()
        for k, v in want.items():
            np.testing.assert_allclose(got[k], v, rtol=1e-5)

    @pytest.mark.parametrize("add_kind", ["sum", "min", "max"])
    def test_duplicate_keys_accumulate_in_one_slot(self, add_kind):
        """Every copy of a key resolves to a single slot regardless of which
        probe round placed it (the linear-probing invariant the vectorized
        rounds must preserve)."""
        rng = np.random.default_rng(1)
        # 64 distinct keys, each repeated 8 times, shuffled
        base = rng.choice(10_000, 64, replace=False).astype(np.int32)
        keys_np = np.repeat(base, 8)
        rng.shuffle(keys_np)
        vals_np = rng.uniform(0.5, 1, keys_np.shape[0]).astype(np.float32)
        T = 128  # load factor 0.5 over distinct keys
        tk = jnp.full((T,), hashkern.EMPTY, jnp.int32)
        tv = jnp.full((T,), hashkern.table_init_val(add_kind), jnp.float32)
        tk, tv, dropped = hashkern.hash_insert(
            tk, tv, jnp.asarray(keys_np), jnp.asarray(vals_np),
            jnp.ones(keys_np.shape[0], bool), add_kind=add_kind,
            max_probes=T)
        assert int(dropped) == 0
        tk_np, tv_np = np.asarray(tk), np.asarray(tv)
        occupied = tk_np != hashkern.EMPTY
        assert occupied.sum() == len(base)  # one slot per distinct key
        reduce = {"sum": np.sum, "min": np.min, "max": np.max}[add_kind]
        for k in base:
            slots = np.nonzero(tk_np == k)[0]
            assert len(slots) == 1, (k, slots)
            np.testing.assert_allclose(
                tv_np[slots[0]], reduce(vals_np[keys_np == k]), rtol=1e-6)

    def test_probe_exhaustion_drops_and_counts(self):
        """A full table (or too few probe rounds) drops entries and REPORTS
        them — the driver's retry signal, never a crash or silent loss."""
        keys = jnp.arange(64, dtype=jnp.int32)
        vals = jnp.ones(64, jnp.float32)
        valid = jnp.ones(64, bool)
        T = 16
        tk = jnp.full((T,), hashkern.EMPTY, jnp.int32)
        tv = jnp.zeros((T,), jnp.float32)
        tk, tv, dropped = hashkern.hash_insert(
            tk, tv, keys, vals, valid, add_kind="sum", max_probes=T)
        assert int(dropped) == 64 - T  # every slot claimed, rest counted
        assert int(np.sum(np.asarray(tk) != hashkern.EMPTY)) == T


# ---------------------------------------------------------------------------
# Local multiply: spgemm_hash vs spgemm_esc
# ---------------------------------------------------------------------------
class TestSpgemmHashParity:
    @pytest.mark.parametrize("semiring", SEMIRINGS, ids=lambda s: s.name)
    @pytest.mark.parametrize("mask_mode", ["none", "strict", "complement"])
    def test_matches_esc(self, semiring, mask_mode):
        xa, xb, a, b = _pair(seed=3)
        m, n = xa.shape[0], xb.shape[1]
        mask_keys = None
        complement = False
        if mask_mode != "none":
            md = np.random.default_rng(5).random((m, n)) < 0.3
            mr, mc = np.nonzero(md)
            mask_keys = sortkeys.sorted_mask_keys(
                jnp.asarray(mr, jnp.int32), jnp.asarray(mc, jnp.int32),
                jnp.ones(len(mr), bool), (m, n))
            complement = mask_mode == "complement"
        ce, ovf_e = spgemm_esc(
            a, b, out_cap=m * n, flops_cap=4096, semiring=semiring,
            mask_keys=mask_keys, mask_complement=complement)
        ch, ovf_h = spgemm_hash(
            a, b, semiring=semiring, mask_keys=mask_keys,
            mask_complement=complement, **_hash_kwargs(a, b))
        assert int(ovf_e) == 0 and int(ovf_h) == 0
        _assert_same_output(ch, ce)

    def test_collision_heavy_table_still_exact(self):
        """Table sized at load factor ~1.0 (every slot needed): parity holds
        with enough probe rounds — correctness never depends on a low load
        factor, only speed does."""
        xa, xb, a, b = _pair(seed=7, m=16, k=16, n=16, da=0.5, db=0.5)
        ce, _ = spgemm_esc(a, b, out_cap=256, flops_cap=4096)
        exact_nnz = int(ce.nnz)
        table_cap = rup_pow2(exact_nnz)
        ch, ovf = spgemm_hash(
            a, b, out_cap=256, table_cap=table_cap, chunk_cap=256,
            num_chunks=16, max_probes=table_cap)
        assert int(ovf) == 0
        _assert_same_output(ch, ce)
        # with one probe round the same table MUST drop entries (and say so)
        _, ovf1 = spgemm_hash(
            a, b, out_cap=256, table_cap=table_cap, chunk_cap=256,
            num_chunks=16, max_probes=1)
        assert int(ovf1) > 0

    def test_table_overflow_reported_then_doubling_clears(self):
        """ESC's overflow contract: a too-small table reports a positive
        count; doubling caps (the driver's retry ladder) converges to the
        exact result."""
        xa, xb, a, b = _pair(seed=9)
        ce, _ = spgemm_esc(a, b, out_cap=2048, flops_cap=4096)
        table_cap, probes = 8, 8
        ovf = 1
        for _ in range(10):
            ch, ovf = spgemm_hash(
                a, b, out_cap=2048, table_cap=table_cap, chunk_cap=256,
                num_chunks=16, max_probes=probes)
            if int(ovf) == 0:
                break
            table_cap *= 2
            probes = min(probes * 2, 256)
        assert int(ovf) == 0
        _assert_same_output(ch, ce)

    def test_flop_overflow_reported(self):
        xa, xb, a, b = _pair(seed=11)
        total_flops = int(
            ((xa != 0).sum(axis=0) * (xb != 0).sum(axis=1)).sum())
        _, ovf = spgemm_hash(
            a, b, out_cap=2048, table_cap=4096, chunk_cap=8, num_chunks=1)
        assert int(ovf) >= total_flops - 8


# ---------------------------------------------------------------------------
# Plan: hash memory model and ESC/hash dispatch
# ---------------------------------------------------------------------------
class TestHashPlanning:
    def test_hash_mem_model_beats_esc_on_compression(self):
        """mem(C) under the hash model scales with merged output, so it
        drops below the ESC expansion exactly when cf > lf·slot/r."""
        flops = 1_000_000
        esc = estimate_mem_c_bytes(flops, 1.0, r=12)
        for cf in (2.0, 4.0, 8.0):
            h = estimate_mem_c_bytes(flops, cf, r=12, local_path="hash")
            assert h == int(np.ceil(flops / cf * HASH_LOAD_FACTOR * 8))
            assert h < esc
        # load_factor override is honored
        assert estimate_mem_c_bytes(
            flops, 4.0, r=12, local_path="hash", load_factor=1.0
        ) < estimate_mem_c_bytes(flops, 4.0, r=12, local_path="hash")

    def test_auto_dispatch_uses_compression_threshold(self, grid1):
        xa, xb, a, b = _pair(seed=15, m=32, k=32, n=32)
        A = scatter_to_grid(a, grid1, "A")
        B = scatter_to_grid(b, grid1, "B")
        p = plan_batches(A, B, grid1, per_process_memory=1 << 26,
                         local_path="auto")
        expect = "hash" if p.compression_est >= HASH_CF_THRESHOLD else "esc"
        assert p.local_path == expect
        assert (p.hash_caps is not None) == (p.local_path == "hash")
        # explicit paths are respected verbatim
        for forced in ("esc", "hash"):
            pf = plan_batches(A, B, grid1, per_process_memory=1 << 26,
                              local_path=forced)
            assert pf.local_path == forced

    def test_fixed_memory_hash_needs_fewer_batches(self, grid1):
        """THE acceptance property: on R-MAT A·Aᵀ (high compression factor)
        at a fixed per-process memory, the hash plan runs in strictly fewer
        batches than the ESC plan — the paper's b = ceil(mem(C)/M) with a
        smaller mem(C)."""
        a = gen.rmat(7, edge_factor=16, seed=3)
        A = scatter_to_grid(a, grid1, "A")
        B = scatter_to_grid(a.transpose().sort_rowmajor(), grid1, "B")
        ppm = probe_memory_budget(A, B, grid1)
        pe = plan_batches(A, B, grid1, per_process_memory=ppm,
                          local_path="esc")
        ph = plan_batches(A, B, grid1, per_process_memory=ppm,
                          local_path="hash")
        pa = plan_batches(A, B, grid1, per_process_memory=ppm,
                          local_path="auto")
        assert pe.num_batches > 1, pe.num_batches
        assert ph.num_batches < pe.num_batches, (
            ph.num_batches, pe.num_batches)
        assert pa.local_path == "hash" and pa.num_batches == ph.num_batches
        assert ph.compression_est >= HASH_CF_THRESHOLD


# ---------------------------------------------------------------------------
# Driver: batched_summa3d with the hash local multiply
# ---------------------------------------------------------------------------
def _multiply(A, B, grid, nb, semiring=sr.PLUS_TIMES, mask=None,
              complement=False, **kw):
    n = B.shape[1]
    got = np.full((A.shape[0], n), np.inf if semiring.add_kind == "min"
                  else (-np.inf if semiring.add_kind == "max" else 0.0),
                  np.float32)

    def consumer(bi, c, cm):
        rr, cc, vv = _sparse_batch_to_global(c, cm)
        if semiring.add_kind == "min":
            np.minimum.at(got, (rr, cc), vv)
        elif semiring.add_kind == "max":
            np.maximum.at(got, (rr, cc), vv)
        else:
            got[rr, cc] += vv
    res = batched_summa3d(
        A, B, grid, per_process_memory=1 << 26, consumer=consumer,
        path="sparse", force_num_batches=nb, semiring=semiring,
        mask=mask, mask_complement=complement, **kw)
    return got, res


def _reference(xa, xb, semiring):
    m, n = xa.shape[0], xb.shape[1]
    if semiring is sr.PLUS_TIMES:
        return xa @ xb, 0.0
    acc = np.full((m, n), np.inf if semiring.add_kind == "min" else -np.inf,
                  np.float32)
    for kk in range(xa.shape[1]):
        av, bv = xa[:, kk], xb[kk, :]
        hit = np.outer(av != 0, bv != 0)
        prod = (np.add if semiring is sr.MIN_PLUS else np.multiply).outer(
            av, bv)
        red = np.minimum if semiring.add_kind == "min" else np.maximum
        acc = np.where(hit, red(acc, prod), acc)
    return acc, (np.inf if semiring.add_kind == "min" else -np.inf)


class TestHashKeyFit:
    """The hash table packs a D tile's (row, col) into one i32 key, so the
    planner must never hand the hash path a tile whose key overflows."""

    TM = 1 << 20  # a protein network's tile height on one chip

    def _plan(self, local_path, force=None):
        from repro.core.batched import PlanInputs, plan_from_symbolic
        from repro.core.specs import PlanFloors, PlanSpec
        from repro.core.symbolic import SymbolicCounts

        tn = self.TM
        # a strict mask keeps 10 of ~100 partial products per column: the
        # compression estimate (10) clears HASH_CF_THRESHOLD
        counts = SymbolicCounts(
            percol=np.full((1, 1, 1, tn), 100, np.int64),
            b_colcounts=np.full((1, 1, 1, tn), 10, np.int64),
            mask_colcounts=np.full((1, 1, 1, tn), 10, np.int64),
        )
        inputs = PlanInputs(
            tm_a=self.TM, max_nnz_a=10 * tn, max_nnz_b=10 * tn,
            nnz_a=10 * tn, nnz_b=10 * tn, cap_a=10 * tn, cap_b=10 * tn,
            p=1, cap_mask=10 * tn,
        )
        return plan_from_symbolic(
            counts, inputs, 1 << 40,
            PlanSpec(local_path=local_path, force_num_batches=force),
            PlanFloors(),
        )

    def test_auto_takes_esc_when_key_overflows(self):
        plan = self._plan("auto")
        assert plan.compression_est >= HASH_CF_THRESHOLD
        assert plan.local_path == "esc" and plan.hash_caps is None
        assert "overflows i32" in plan.path_reason, plan.path_reason

    def test_forced_hash_raises_batches_until_key_fits(self):
        plan = self._plan("hash")
        wb = self.TM // plan.num_batches
        assert plan.local_path == "hash"
        assert sortkeys.fits_i32(self.TM, wb)
        assert not sortkeys.fits_i32(self.TM, 2 * wb)  # the smallest such b
        assert "raised" in plan.path_reason, plan.path_reason

    def test_forced_hash_with_forced_batches_refuses_overflow(self):
        with pytest.raises(ValueError, match="overflows i32"):
            self._plan("hash", force=2)


class TestBatchedHashDriver:
    @pytest.mark.parametrize("semiring", SEMIRINGS, ids=lambda s: s.name)
    @pytest.mark.parametrize("nb", [1, 3])
    def test_forced_hash_matches_reference(self, grid1, semiring, nb):
        xa = _dense(48, 48, 0.25, seed=21)
        xb = _dense(48, 48, 0.25, seed=22)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024),
                            grid1, "B")
        got, res = _multiply(A, B, grid1, nb, semiring=semiring,
                             local_path="hash")
        assert res.local_path == "hash" and res.num_retries == 0
        want, empty = _reference(xa, xb, semiring)
        got = np.where(np.isinf(got), empty, got) if empty else got
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("complement", [False, True])
    @pytest.mark.parametrize("nb", [1, 2])
    def test_masked_hash_matches_dense(self, grid1, complement, nb, n=32):
        xa = _dense(n, n, 0.3, seed=23)
        xb = _dense(n, n, 0.3, seed=24)
        md = np.random.default_rng(25).random((n, n)) < 0.2
        mr, mc = np.nonzero(md)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024),
                            grid1, "B")
        M = scatter_to_grid(
            sp.from_numpy_coo(mr, mc, np.ones(len(mr), np.float32), (n, n)),
            grid1, "C")
        got, res = _multiply(A, B, grid1, nb, mask=M, complement=complement,
                             local_path="hash")
        assert res.local_path == "hash" and res.num_retries == 0
        keep = ~md if complement else md
        np.testing.assert_allclose(got, (xa @ xb) * keep,
                                   rtol=1e-4, atol=1e-5)

    def test_undersized_hash_caps_retry_to_parity(self, grid1):
        """A deliberately starved HashCaps floor trips the device overflow
        flag; the driver's doubling retry ladder converges to the exact
        product (same machinery as ESC cap overflow)."""
        from repro.core.summa3d import HashCaps

        xa = _dense(32, 32, 0.3, seed=27)
        xb = _dense(32, 32, 0.3, seed=28)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024),
                            grid1, "B")
        import repro.core.batched as batched_mod
        real_plan = batched_mod.plan_batches

        def starved_plan(*args, **kwargs):
            p = real_plan(*args, **kwargs)
            if p.hash_caps is None:
                return p
            import dataclasses
            return dataclasses.replace(
                p, hash_caps=HashCaps(table_cap=16, chunk_cap=p.hash_caps.
                                      chunk_cap, num_chunks=p.hash_caps.
                                      num_chunks, max_probes=4))
        batched_mod.plan_batches = starved_plan
        try:
            got, res = _multiply(A, B, grid1, 2, local_path="hash")
        finally:
            batched_mod.plan_batches = real_plan
        assert res.num_retries > 0
        assert res.hash_caps.table_cap > 16  # the grown caps are recorded
        np.testing.assert_allclose(got, xa @ xb, rtol=1e-4, atol=1e-5)

    def test_auto_path_does_not_retrace_across_runs(self, grid1):
        """Repeated auto-dispatch runs (the MCL regime: pinned path + caps
        floor) hit the jit cache — one fused-step trace total."""
        from repro.core import summa3d

        xa = _dense(32, 32, 0.3, seed=29)
        xb = _dense(32, 32, 0.3, seed=30)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xb), cap=1024),
                            grid1, "B")
        _, first_res = _multiply(A, B, grid1, 2)  # local_path defaults auto
        first = summa3d.TRACE_COUNTS["fused_step"]
        for _ in range(3):
            _, res = _multiply(
                A, B, grid1, 2, local_path=first_res.local_path,
                hash_caps_floor=first_res.hash_caps)
            assert res.num_retries == 0
        repeat = summa3d.TRACE_COUNTS["fused_step"] - first
        assert repeat == 0, repeat


# ---------------------------------------------------------------------------
# Satellite: device-resident mask counts (planner no longer pulls the mask)
# ---------------------------------------------------------------------------
class TestDeviceMaskCounts:
    def test_device_counts_match_host_oracle(self, grid1, n=32):
        from repro.core.batched import _mask_tile_colcounts

        md = np.random.default_rng(31).random((n, n)) < 0.2
        mr, mc = np.nonzero(md)
        xa = _dense(n, n, 0.3, seed=33)
        A = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "A")
        B = scatter_to_grid(sp.from_dense(jnp.asarray(xa), cap=1024),
                            grid1, "B")
        M = scatter_to_grid(
            sp.from_numpy_coo(mr, mc, np.ones(len(mr), np.float32), (n, n)),
            grid1, "C")
        counts = symbolic3d_counts(A, B, grid1, mask=M)
        np.testing.assert_array_equal(
            counts.mask_colcounts, _mask_tile_colcounts(M))
