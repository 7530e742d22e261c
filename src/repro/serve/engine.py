"""Multiply-as-a-service: a plan-cached SpGEMM serving engine.

Requests (pairs of host COO operands + semiring, optionally masked) enter a
FIFO queue. Admission control prices each request with the SAME memory model
the batched driver enforces (``batched.plan_footprint`` over the Alg. 3
plan): a request whose planned footprint does not fit alongside the
in-flight work is DEFERRED (FIFO, no overtaking); one that cannot fit the
``per_process_memory`` budget even alone is re-planned at finer batching
(``force_num_batches`` doublings, up to ``max_splits``) and REFUSED only
when no split fits.

The plan cache is keyed by the matrix signature — global shape, pow2 nnz
profile, pow2 scatter capacities, pow2 max per-column count, semiring,
local-path policy, mask id — and stores the pow2/floor capacities of the
last plan with that signature as one ``PlanFloors``.
Repeat traffic re-plans through ``plan_batches(spec=..., floors=...)`` with
the cached floors, landing on the IDENTICAL fused-step static signature: the
dispatch
goes through the driver's shared ``batched._fused_jit``, so a cache hit
costs zero retraces (asserted via ``summa3d.TRACE_COUNTS`` in the tests).

Concurrent in-flight requests interleave round-robin, one batch per engine
tick, through a shared ``runtime.driver.LookaheadWindow`` — batch overflow
flags are read ``lookahead`` dispatches late, so one request's host-side
assembly overlaps another's device compute. Per-request accounting lands in
a ``RunReport`` (retries / selection retries), plus latency and the price
the admission controller charged.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import semiring as sr
from ..core.batched import (
    BatchPlan,
    RunReport,
    _fused_jit,
    batch_column_map,
    plan_batches,
    plan_footprint,
)
from ..core.distsparse import DistSparse, scatter_to_grid, tile_nnz_counts
from ..core.grid import Grid
from ..core.sparse import SparseCOO, from_numpy_coo
from ..core.specs import PlanFloors, PlanSpec
from ..core.summa3d import BatchCaps, HashCaps
from ..core.symbolic import rup8 as _rup8, rup_pow2 as _rup_pow2
from ..runtime.driver import LookaheadWindow


@dataclasses.dataclass
class MultiplyRequest:
    """One SpGEMM to serve: C = A·B under ``semiring`` (optionally ⊙ mask).

    ``mask`` (C-layout structure gating the output, §V-B) requires a caller
    ``mask_id``: mask VALUES never matter, so the id stands in for the mask's
    structure in the plan-cache key.
    """

    rid: int
    a: SparseCOO
    b: SparseCOO
    semiring: sr.Semiring = sr.PLUS_TIMES
    mask: Optional[SparseCOO] = None
    mask_id: Optional[str] = None


@dataclasses.dataclass
class ServeConfig:
    per_process_memory: int = 1 << 26
    r_bytes: int = 12
    slack: float = 1.3
    lookahead: int = 2  # in-flight window depth (shared across requests)
    max_retries: int = 4  # per-batch overflow retry bound
    max_splits: int = 3  # admission force_num_batches doublings before refusal
    local_path: str = "auto"  # local-multiply policy (part of the key)
    # base capacity floors applied to every FIRST plan of a signature (an
    # autotuner warm-start: repeat traffic still folds its own floors on top)
    seed_floors: Optional[PlanFloors] = None

    @classmethod
    def from_tuned(cls, tuned, **overrides) -> "ServeConfig":
        """Admission config from an autotuner ``TunedConfig`` (duck-typed:
        anything with per_process_memory / spec / floors / exec_spec) — the
        tuned local path, slack, lookahead, and batch-count floor flow
        straight into the pricing path, no kwarg threading."""
        kw = dict(
            per_process_memory=tuned.per_process_memory,
            r_bytes=tuned.spec.r_bytes,
            slack=tuned.spec.slack,
            lookahead=tuned.exec_spec.lookahead,
            max_retries=tuned.exec_spec.max_retries,
            local_path=tuned.spec.local_path,
            seed_floors=tuned.floors,
        )
        kw.update(overrides)
        return cls(**kw)


@dataclasses.dataclass
class MultiplyResult:
    rid: int
    status: str  # "ok" | "refused"
    c: Optional[SparseCOO]
    report: RunReport
    plan_cached: bool = False
    was_deferred: bool = False
    splits: int = 0
    latency_ms: float = 0.0
    price_bytes: int = 0
    num_batches: int = 0
    reason: str = ""


@dataclasses.dataclass
class PlanCacheEntry:
    """Floors for replanning repeat traffic onto one executable: ONE
    `PlanFloors` holding the pow2 capacities a previous same-signature
    request actually USED (monotone — retry growth folds back via
    ``merged()``), plus the decided local path and admission price."""

    floors: PlanFloors
    local_path: str
    price_bytes: int
    splits: int
    hits: int = 0


@dataclasses.dataclass
class _Active:
    """In-flight request state: scattered operands + the static dispatch
    capacities (grown in place by the per-batch retry ladder)."""

    req: MultiplyRequest
    key: tuple
    plan: BatchPlan
    A: DistSparse
    B: DistSparse
    M: Optional[DistSparse]
    nb: int
    caps: BatchCaps
    sel_cap: int
    hc: Optional[HashCaps]
    mask_cap: int
    price: int
    splits: int
    plan_cached: bool
    was_deferred: bool
    t_submit: float
    bi: int = 0  # next batch to dispatch
    done_batches: int = 0
    retries: int = 0
    sel_retries: int = 0
    pieces: List[tuple] = dataclasses.field(default_factory=list)


def matrix_signature(req: MultiplyRequest, grid: Grid, cfg: ServeConfig) -> tuple:
    """Pow2-quantized request signature = the plan-cache key.

    Everything that feeds the fused step's STATIC signature is quantized to
    a power of two here (nnz profile, scatter capacities, max per-column
    counts), so near-identical repeat traffic maps to one key — and the
    scatter capacities are taken FROM the signature, which is what makes two
    same-key requests produce identical operand array shapes.
    """

    def prof(x: SparseCOO, kind: str):
        nnz = int(x.nnz)
        cols = np.asarray(x.cols)[:nnz]
        maxcol = int(np.bincount(cols).max()) if nnz else 0
        counts = tile_nnz_counts(x, grid, kind)
        cap = _rup_pow2(max(int(counts.max() * cfg.slack), 8))
        return (_rup_pow2(max(nnz, 1)), _rup_pow2(max(maxcol, 1)), cap)

    return (
        req.a.shape, req.b.shape,
        prof(req.a, "A"), prof(req.b, "B"),
        req.semiring.name, cfg.local_path, req.mask_id,
    )


def _batch_triplets(c: DistSparse, col_map: np.ndarray):
    """Host triplets of one sparse C batch in global coordinates."""
    pr, pc, l = c.grid_shape
    tm = c.tile_shape[0]
    R, C, V, N = (np.asarray(x) for x in (c.rows, c.cols, c.vals, c.nnz))
    valid = np.arange(R.shape[-1])[None, None, None, :] < N[..., None]
    i, j, kk, s = np.nonzero(valid)
    return i * tm + R[i, j, kk, s], col_map[j, kk, C[i, j, kk, s]], V[i, j, kk, s]


class SpgemmEngine:
    """Plan-cached SpGEMM serving engine on one device grid.

    ``submit`` enqueues; ``step`` runs one tick (admit → dispatch one batch
    per active request → reap); ``run_to_completion`` drains everything and
    returns the results in completion order.
    """

    def __init__(self, grid: Grid, cfg: Optional[ServeConfig] = None):
        self.grid = grid
        self.cfg = cfg or ServeConfig()
        self.queue: Deque[MultiplyRequest] = deque()
        self.active: List[_Active] = []
        self.done: List[MultiplyResult] = []
        self.plan_cache: Dict[tuple, PlanCacheEntry] = {}
        self.in_use = 0  # admitted bytes currently in flight
        self.stats = {"hits": 0, "misses": 0, "deferred": 0, "refused": 0,
                      "splits": 0, "served": 0}
        self._t_submit: Dict[int, float] = {}
        self._deferred_rids: set = set()
        self._head: Optional[_Active] = None  # priced-but-not-admitted head
        self._window = LookaheadWindow(self.cfg.lookahead, self._finish)

    # -- admission ---------------------------------------------------------
    def submit(self, req: MultiplyRequest) -> None:
        if req.mask is not None:
            assert req.mask_id is not None, "masked requests need a mask_id"
        self._t_submit[req.rid] = time.perf_counter()
        self.queue.append(req)

    def _price(self, req: MultiplyRequest) -> Tuple[Optional[_Active], str]:
        """Scatter + plan + price one request (the head of the queue).

        Splits the plan (force_num_batches doublings) while its footprint
        exceeds the budget; returns ``(None, reason)`` when no allowed split
        fits — the request is refused without dispatching anything.
        """
        cfg = self.cfg
        key = matrix_signature(req, self.grid, cfg)
        entry = self.plan_cache.get(key)
        cap_a, cap_b = key[2][2], key[3][2]  # pow2 scatter caps from the key
        A = scatter_to_grid(req.a, self.grid, "A", cap=cap_a)
        B = scatter_to_grid(req.b, self.grid, "B", cap=cap_b)
        M = (scatter_to_grid(req.mask, self.grid, "A")
             if req.mask is not None else None)
        if entry is not None:
            floors = entry.floors
        else:
            floors = cfg.seed_floors or PlanFloors()
        floors = floors.replace(caps_pow2=True)
        local_path = entry.local_path if entry is not None else cfg.local_path
        max_nnz_a = int(np.asarray(A.nnz).max())
        max_nnz_b = int(np.asarray(B.nnz).max())
        splits = entry.splits if entry is not None else 0
        force = None
        while True:
            try:
                plan = plan_batches(
                    A, B, self.grid, per_process_memory=cfg.per_process_memory,
                    spec=PlanSpec(
                        mask=M, local_path=local_path, slack=cfg.slack,
                        r_bytes=cfg.r_bytes, force_num_batches=force,
                    ),
                    floors=floors,
                )
            except MemoryError as e:
                return None, str(e)
            price = plan_footprint(
                plan.caps, plan.sel_cap, plan.hash_caps,
                r_bytes=cfg.r_bytes, max_nnz_a=max_nnz_a, max_nnz_b=max_nnz_b,
            )
            if price <= cfg.per_process_memory or splits >= cfg.max_splits:
                break
            splits += 1
            self.stats["splits"] += 1
            force = plan.num_batches * 2
        if price > cfg.per_process_memory:
            return None, (
                f"footprint {price} exceeds budget {cfg.per_process_memory} "
                f"after {splits} splits"
            )
        use_hash = plan.local_path == "hash"
        # the cache entry is written at PLAN time (not completion) so repeat
        # traffic hits even while the first request with this signature is
        # still in flight; completion folds any retry growth back in.
        if entry is not None:
            entry.hits += 1
            self.stats["hits"] += 1
        else:
            self.stats["misses"] += 1
            self.plan_cache[key] = PlanCacheEntry(
                floors=PlanFloors(
                    caps=plan.caps, sel_cap=plan.sel_cap,
                    num_batches=plan.num_batches,
                    hash_caps=(plan.hash_caps if use_hash else None),
                    caps_pow2=True,
                ),
                local_path=plan.local_path,
                price_bytes=price, splits=splits,
            )
        return _Active(
            req=req, key=key, plan=plan, A=A, B=B, M=M,
            nb=plan.num_batches, caps=plan.caps, sel_cap=plan.sel_cap,
            hc=(plan.hash_caps if use_hash else None),
            mask_cap=plan.mask_sel_cap, price=price, splits=splits,
            plan_cached=entry is not None,
            was_deferred=req.rid in self._deferred_rids,
            t_submit=self._t_submit.pop(req.rid, time.perf_counter()),
        ), ""

    def _admit(self) -> None:
        """FIFO admission: price the head, defer it while the in-flight work
        leaves no room (never overtaken), refuse what no split can fit."""
        while self.queue:
            req = self.queue[0]
            if self._head is None or self._head.req.rid != req.rid:
                act, reason = self._price(req)
                if act is None:
                    self.queue.popleft()
                    self.stats["refused"] += 1
                    self.done.append(MultiplyResult(
                        rid=req.rid, status="refused", c=None,
                        report=RunReport(), reason=reason,
                        was_deferred=req.rid in self._deferred_rids,
                    ))
                    self._deferred_rids.discard(req.rid)
                    continue
                self._head = act
            act = self._head
            if self.in_use > 0 and (
                self.in_use + act.price > self.cfg.per_process_memory
            ):
                if req.rid not in self._deferred_rids:
                    self._deferred_rids.add(req.rid)
                    self.stats["deferred"] += 1
                    act.was_deferred = True
                return  # FIFO: nothing behind the head may overtake it
            self.queue.popleft()
            self._deferred_rids.discard(req.rid)
            self._head = None
            self.in_use += act.price
            self.active.append(act)

    # -- dispatch / finish -------------------------------------------------
    def _dispatch(self, act: _Active, bi: int):
        return _fused_jit(
            act.A, act.B, jnp.int32(bi), act.M,
            grid=self.grid, num_batches=act.nb, sel_cap=act.sel_cap,
            caps=act.caps, semiring=act.req.semiring, sorted_merge=True,
            path="sparse", hashc=act.hc, mask_cap=act.mask_cap,
            mask_complement=False,
        )

    def _finish(self, act: _Active, bi: int, c_batch, ovf) -> None:
        """Window sync point: read batch bi's flags, retry if beaten, then
        assemble the batch's triplets on the host."""
        o = np.asarray(ovf)
        for _ in range(self.cfg.max_retries):
            if not o.any():
                break
            act.retries += 1
            if o[0] > 0:
                act.sel_retries += 1
                act.sel_cap = min(
                    _rup8(max(act.sel_cap * 2, 8)), act.B.cap
                )
            elif o[1] > 0:
                act.caps = act.caps.doubled()
                act.hc = act.hc.doubled() if act.hc is not None else None
                if act.M is not None:
                    act.mask_cap = min(act.mask_cap * 2, act.M.cap)
            c_batch, ovf = self._dispatch(act, bi)
            o = np.asarray(ovf)
        assert not o.any(), (
            f"rid {act.req.rid} batch {bi}: overflow persisted after "
            f"{self.cfg.max_retries} retries"
        )
        col_map = batch_column_map(
            act.B.shape[1], self.grid, act.nb, bi
        )
        act.pieces.append(_batch_triplets(c_batch, col_map))
        act.done_batches += 1

    def _reap(self) -> None:
        for act in [a for a in self.active if a.done_batches == a.nb]:
            self.active.remove(act)
            self.in_use -= act.price
            rows = np.concatenate([p[0] for p in act.pieces])
            cols = np.concatenate([p[1] for p in act.pieces])
            vals = np.concatenate([p[2] for p in act.pieces])
            shape = (act.A.shape[0], act.B.shape[1])
            c = from_numpy_coo(rows, cols, vals, shape, cap=max(len(rows), 8))
            # fold retry growth back into the entry (monotone floors)
            entry = self.plan_cache[act.key]
            entry.floors = entry.floors.merged(PlanFloors(
                caps=act.caps, sel_cap=act.sel_cap, num_batches=act.nb,
                hash_caps=act.hc, caps_pow2=True,
            ))
            entry.price_bytes = max(entry.price_bytes, act.price)
            self.stats["served"] += 1
            self.done.append(MultiplyResult(
                rid=act.req.rid, status="ok", c=c,
                report=RunReport(retries=act.retries,
                                 sel_retries=act.sel_retries),
                plan_cached=act.plan_cached, was_deferred=act.was_deferred,
                splits=act.splits,
                latency_ms=(time.perf_counter() - act.t_submit) * 1e3,
                price_bytes=act.price, num_batches=act.nb,
            ))

    # -- scheduling --------------------------------------------------------
    def step(self) -> int:
        """One engine tick. Returns the number of requests still in the
        system (queued + in flight)."""
        self._admit()
        progressed = False
        for act in list(self.active):
            if act.bi < act.nb:
                c_batch, ovf = self._dispatch(act, act.bi)
                self._window.push(act, act.bi, c_batch, ovf)
                act.bi += 1
                progressed = True
        if not progressed:
            self._window.drain()
        self._reap()
        return len(self.active) + len(self.queue)

    def run_to_completion(self, max_ticks: int = 100_000) -> List[MultiplyResult]:
        ticks = 0
        while (self.queue or self.active) and ticks < max_ticks:
            self.step()
            ticks += 1
        assert not (self.queue or self.active), "engine did not drain"
        return self.done

    def cache_hit_rate(self) -> float:
        total = self.stats["hits"] + self.stats["misses"]
        return self.stats["hits"] / total if total else 0.0
