"""Fault-tolerant harness for iterated SpGEMM: checkpoint/resume + injection.

The training driver's restartable-loop machinery (`runtime/driver.py`)
retargeted at the real stack — MCL expansion/inflation and APSP iterated
squaring run for hours on real inputs (HipMCL at 262K cores, §V-C), where
preemption, checkpoint corruption, and under-predicted output memory are
routine. `run_iterated` is the generic loop:

  * **checkpoint every N iterations** through `store.AsyncCheckpointer`
    (host snapshot + off-thread write, overlapped with the next multiply;
    stall time and bytes land in the `RunReport`);
  * **cold-or-warm start**: `restore_arrays_latest` walks `steps_available`
    newest-first, *refusing* any corrupt/truncated checkpoint (content-hash
    or unreadable-archive failure) and falling back to the previous step —
    a refused restore is counted, never fatal, and an empty/corrupt store
    degrades to a cold start;
  * **plan-signature meta** rides in the checkpoint manifest (`store.save
    (meta=...)`): the workload's encode/decode callbacks snapshot the pow2/
    floor caps, hash caps, local path and
    batch-count floor next to the iterate, so the restored loop rebuilds the
    IDENTICAL fused-step static signature — zero extra retraces after a
    resume (asserted via ``summa3d.TRACE_COUNTS`` in the tests);
  * **straggler watchdog**: the driver's warm-up-fixed `StragglerEwma`
    observes per-iteration wall time; events are logged through the
    verbose/logging path and counted in the report.

`SpgemmFailureInjector` grows the deterministic `FailureInjector` to the
SpGEMM failure modes: preemption mid-iteration (at a chosen batch inside
the pipelined lookahead window), checkpoint truncation after a completed
save, overflow storms (forced capacity under-prediction via a slack
override), and per-batch straggler delays.
"""
from __future__ import annotations

import dataclasses
import logging
import os
import re
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..checkpoint import store
from ..core.batched import RunReport
from .driver import FailureInjector, StragglerEwma

log = logging.getLogger("repro.runtime.resilient")


class PreemptionError(RuntimeError):
    """Injected (or real) preemption: the loop restores and continues."""


# --- real-preemption translation (SIGTERM → PreemptionError) ---------------
# Schedulers deliver preemption as a signal, not an exception; the handler
# only flips this flag (signal-safe), and `check_preemption` — called at the
# iteration boundary inside `run_iterated` — turns it into the same
# `PreemptionError` the injector raises, so the restore path covers real
# kills identically to injected ones.
_PREEMPTION = {"requested": False}


def _sigterm_handler(signum, frame):
    _PREEMPTION["requested"] = True
    log.warning("signal %d received — requesting preemption", signum)


def install_preemption_handler(signals=None) -> None:
    """Install the SIGTERM→`PreemptionError` translation for this process.

    Launchers call this once before entering a resilient loop; subsequent
    SIGTERMs set a flag that `run_iterated` converts into the restore path
    at the next iteration boundary (a mid-step signal never corrupts an
    in-flight checkpoint write).
    """
    import signal as _signal

    for s in signals if signals is not None else (_signal.SIGTERM,):
        _signal.signal(s, _sigterm_handler)


def preemption_requested() -> bool:
    return _PREEMPTION["requested"]


def clear_preemption() -> None:
    _PREEMPTION["requested"] = False


def check_preemption() -> None:
    """Raise (and clear — one restore per signal, not a restart storm) when
    a translated signal is pending."""
    if _PREEMPTION["requested"]:
        _PREEMPTION["requested"] = False
        raise PreemptionError("preemption signal received (SIGTERM)")


@dataclasses.dataclass
class ResilientConfig:
    """Knobs of the resilient iterated loop (checkpoint cadence + watchdog)."""

    ckpt_dir: str
    ckpt_every: int = 1  # iterations between checkpoints
    keep: int = 3  # keep-N garbage collection
    max_restarts: int = 3  # bounded preemption recoveries
    async_save: bool = True  # off-thread writes (False: synchronous)
    resume: bool = True  # warm-start from latest_step when available
    straggler_factor: float = 3.0
    ewma_alpha: float = 0.2
    ewma_warmup: int = 1  # iterations before the watchdog arms


class SpgemmFailureInjector(FailureInjector):
    """Deterministic SpGEMM fault injection (tests + the durability CI lane).

    All sites fire once (like `FailureInjector.maybe_fail`) so a recovered
    run proceeds past the fault:

      * ``preempt_iters`` — `PreemptionError` at the start of those
        iterations; with ``preempt_batch`` set, the preemption instead fires
        *mid-iteration*, when the workload's consumer reaches that batch
        index (inside the pipelined lookahead window).
      * ``corrupt_steps`` — after the checkpoint for step s is on disk,
        truncate its ``arrays.npz`` (the restore must refuse it and fall
        back to the previous step).
      * ``overflow_iters`` — force capacity under-prediction: the workload
        plans those iterations with ``overflow_slack`` instead of its normal
        slack, driving the §IV-A retry ladder (and, under a tight budget,
        the degradation replans).
      * ``straggle_batches`` — sleep ``batch_straggle_s`` inside the
        consumer at the given (iteration, batch) pairs.
    """

    def __init__(
        self, fail_steps=(), straggle_steps=(), straggle_s: float = 0.0,
        preempt_iters=(), preempt_batch: Optional[int] = None,
        corrupt_steps=(), overflow_iters=(), overflow_slack: float = 0.05,
        straggle_batches=(), batch_straggle_s: float = 0.0,
    ):
        super().__init__(fail_steps, straggle_steps, straggle_s)
        self.preempt_iters = set(preempt_iters)
        self.preempt_batch = preempt_batch
        self.corrupt_steps = set(corrupt_steps)
        self.overflow_iters = set(overflow_iters)
        self.overflow_slack = overflow_slack
        self.straggle_batches = set(straggle_batches)
        self.batch_straggle_s = batch_straggle_s

    def maybe_preempt(self, it: int, batch: Optional[int] = None) -> None:
        """Iteration-start check (``batch=None``) or mid-iteration check
        from the workload's consumer (``batch`` = batch index)."""
        if it not in self.preempt_iters:
            return
        at_batch = self.preempt_batch is not None
        if (batch is None) == at_batch:
            return  # armed for the other site
        if at_batch and batch != self.preempt_batch:
            return
        self.preempt_iters.discard(it)  # fire once
        where = f"batch {batch} of " if batch is not None else ""
        raise PreemptionError(f"injected preemption at {where}iteration {it}")

    def maybe_corrupt(self, ckpt_dir: str, step: int) -> bool:
        """Truncate step's on-disk payload (call after the save landed)."""
        if step not in self.corrupt_steps:
            return False
        self.corrupt_steps.discard(step)
        path = os.path.join(ckpt_dir, f"step_{step:08d}", "arrays.npz")
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
        log.warning("injected corruption: truncated %s", path)
        return True

    def capacity_slack(self, it: int) -> Optional[float]:
        """Slack override for iteration ``it`` (None = no storm)."""
        return self.overflow_slack if it in self.overflow_iters else None

    def maybe_straggle_batch(self, it: int, batch: int) -> None:
        if (it, batch) in self.straggle_batches:
            self.straggle_batches.discard((it, batch))
            time.sleep(self.batch_straggle_s)


_KEYSTR_RE = re.compile(r"^\['(.*)'\]$")


def _plain_key(k: str) -> str:
    """Undo `jax.tree_util.keystr` on a single-level dict key.

    `store.save` flattens state with keystr, so a top-level leaf ``A_rows``
    lands in the archive as ``['A_rows']``; workloads' decode callbacks see
    the plain name again.
    """
    m = _KEYSTR_RE.match(k)
    return m.group(1) if m else k


def restore_arrays_latest(
    ckpt_dir: str,
) -> Tuple[Optional[Dict[str, np.ndarray]], Optional[dict], Optional[int], int]:
    """Newest-valid restore: ``(arrays, meta, step, refused)``.

    Walks complete checkpoints newest-first; a corrupt/truncated/unreadable
    one is REFUSED (logged + counted) and the previous step is tried. With
    no valid checkpoint, returns ``(None, None, None, refused)`` — the
    caller cold-starts.
    """
    refused = 0
    for s in reversed(store.steps_available(ckpt_dir)):
        try:
            arrays = store.restore_arrays(ckpt_dir, s)
            meta = store.load_meta(ckpt_dir, s)
            return {_plain_key(k): v for k, v in arrays.items()}, meta, s, refused
        except IOError as e:
            refused += 1
            log.warning("refusing checkpoint step %d: %s", s, e)
    return None, None, None, refused


@dataclasses.dataclass
class IteratedResult:
    """What `run_iterated` hands back: final state + durability accounting."""

    state: Any
    it: int  # iterations completed
    report: RunReport


def run_iterated(
    *,
    rc: ResilientConfig,
    max_iters: int,
    cold_start: Callable[[], Any],
    step_fn: Callable[[Any, int, "SpgemmFailureInjector"], Tuple[Any, Optional[RunReport], bool]],
    encode: Callable[[Any], Tuple[Dict[str, np.ndarray], dict]],
    decode: Callable[[Dict[str, np.ndarray], dict], Any],
    injector: Optional[SpgemmFailureInjector] = None,
    verbose: bool = False,
) -> IteratedResult:
    """The restartable iterated-SpGEMM loop (MCL, APSP, …).

    Contract with the workload:
      * ``cold_start() -> state`` builds iteration-0 state from the input;
      * ``step_fn(state, it, injector) -> (state', report_i, done)`` runs ONE
        iteration; ``report_i`` (per-iteration `RunReport` or None) is merged
        into the loop's report; ``done`` stops the loop after a checkpoint;
      * ``encode(state) -> (arrays, meta)`` flattens state into checkpoint
        leaves (exact-dtype host arrays) + a JSON-safe meta dict carrying the
        plan signature; ``decode(arrays, meta) -> state`` inverts it,
        re-device_put with the *current* mesh's shardings (elastic restore).

    A `PreemptionError` from ``step_fn`` (injected, or a real SIGTERM
    handler translated by the caller) triggers the restore path: wait out
    the in-flight write, restore the newest VALID checkpoint (refusing
    corrupt ones), and continue from its iteration — bounded by
    ``rc.max_restarts``. Encode/decode round-trip bitwise-identical arrays
    and an identical plan signature, so the trajectory matches the
    uninterrupted run and the resumed fused step hits the jit cache (zero
    retraces).
    """
    injector = injector or SpgemmFailureInjector()
    ckpt = store.AsyncCheckpointer(rc.ckpt_dir, keep=rc.keep)
    report = RunReport()
    ewma = StragglerEwma(rc.straggler_factor, rc.ewma_alpha, rc.ewma_warmup)

    def warm_or_cold(first: bool = False) -> Tuple[Any, int]:
        # rc.resume=False only opts the INITIAL start out of warm-starting
        # (a deliberately fresh run); mid-run preemption recovery always
        # reads the store — that is the point of the checkpoints.
        nonlocal report
        if rc.resume or not first:
            arrays, meta, s, refused = restore_arrays_latest(rc.ckpt_dir)
            report = report.merged(RunReport(refused_restores=refused))
            if arrays is not None:
                log.info("restored checkpoint at iteration %d", s)
                if verbose:
                    print(f"[resilient] resume from iteration {s}")
                return decode(arrays, meta), s
        return cold_start(), 0

    state, it = warm_or_cold(first=True)
    restarts = 0
    done = False
    while it < max_iters and not done:
        try:
            injector.maybe_preempt(it)
            check_preemption()  # real SIGTERM, translated at the boundary
            t0 = time.perf_counter()
            state, rep_i, done = step_fn(state, it, injector)
            dt = time.perf_counter() - t0
            if rep_i is not None:
                report = report.merged(rep_i)
            if ewma.observe(dt):
                report = report.merged(RunReport(straggler_events=1))
                log.warning("straggler: iteration %d took %.3fs (ewma %.3fs)",
                            it, dt, ewma.ewma)
            if verbose:
                ew = f"{ewma.ewma:.3f}" if ewma.ewma is not None else "warmup"
                print(f"[resilient] iter={it} wall={dt:.3f}s ewma={ew}s")
            it += 1
            if it % rc.ckpt_every == 0 or done or it == max_iters:
                arrays, meta = encode(state)
                if rc.async_save:
                    ckpt.save(it, arrays, meta=meta)
                else:
                    ckpt.save_sync(it, arrays, meta=meta)
                if it in injector.corrupt_steps:
                    ckpt.wait()  # the file must be on disk to truncate
                    injector.maybe_corrupt(rc.ckpt_dir, it)
        except PreemptionError as e:
            restarts += 1
            report = report.merged(RunReport(restarts=1))
            if restarts > rc.max_restarts:
                raise
            log.warning("%s — restoring", e)
            ckpt.wait()  # drain the in-flight write before reading the store
            state, it = warm_or_cold()
            done = False
    ckpt.wait()
    report = report.merged(RunReport(
        checkpoint_stalls=ckpt.stalls,
        checkpoint_stall_s=ckpt.stall_s,
        checkpoint_bytes=ckpt.bytes_written,
    ))
    return IteratedResult(state=state, it=it, report=report)
