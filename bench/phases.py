"""Split a traced window by the program's own names: the fused step's device
time by phase scope, the driver's host spans, and idle gaps named by them.

The program names its device phases with ``jax.named_scope`` (``spgemm.*``
in ``core/summa3d.py`` and ``core/local_spgemm.py``), which a TPU trace
keeps in each device op's ``tf_op`` (``bench/xspace.py`` reads it), and its
host work with ``jax.profiler.TraceAnnotation`` spans (``spgemm.*`` in
``core/batched.py``), whose args are event stats. From a ``--trace 1`` run's
trace (``bench.run --trace-dir``) this module computes:

* per run of the fused step, the device seconds of each scope: an op is
  charged to the innermost ``spgemm.*`` segment of its ``tf_op``, or to
  ``(unscoped)``; an op's time excludes the ops nested in it on its line,
  so the scopes of a run add up to its busy time;
* the program's spans inside the window, with their args;
* the device's idle gaps, each named by the innermost ``bench.*`` or
  ``spgemm.*`` span the host was in at the gap's middle;
* the eight per-layer numbers of ``METRICS``, each None where the program
  records nothing to read it from (a program without scopes or spans).

Phase numbers are mean device seconds per counted batch, over the first
``batches`` runs of the fused step in the window, as ``step_device_s``
reads them; ``batches`` defaults to the ``bench.consumer`` spans in the
window, one per counted batch.

    python3 -m bench.phases <trace dir or .xplane.pb[.gz]> [--batches N]

prints the numbers as one JSON object.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from collections import defaultdict
from typing import List, Optional

from bench import trace as tracing
from bench import xspace

SCOPE_PREFIX = "spgemm."
SPAN_PREFIXES = ("bench.", "spgemm.")
UNSCOPED = "(unscoped)"
CONSUMER_SPAN = "bench.consumer"
CALL_SPAN = "spgemm.call"
FUSED_STEP = "summa3d_fused_step"

# per-layer metric -> the scopes whose device seconds it adds up
PHASE_METRICS = {
    "select_device_s": ("spgemm.select", "spgemm.gather"),
    "sort_a_device_s": ("spgemm.esc.sort_a",),
    "expand_device_s": ("spgemm.esc.expand",),
    "compress_device_s": ("spgemm.esc.compress",),
    "merge_device_s": ("spgemm.merge",),
}
# per-layer metric -> the planner span whose seconds, in the window's first
# call, it reads
SPAN_METRICS = {
    "symbolic_s": "spgemm.symbolic",
    "plan_host_s": "spgemm.plan_host",
}
RETRY_SPAN = "spgemm.retry"
METRICS = tuple(PHASE_METRICS) + tuple(SPAN_METRICS) + ("overflow_retries",)


def scope_of(tf_op: str) -> str:
    """The innermost ``spgemm.*`` segment of ``tf_op``, or ``""``:
    ``jit(f)/spgemm.esc.compress/jit(lexsort)/sort:`` ->
    ``spgemm.esc.compress``."""
    for seg in reversed(tf_op.split("/")):
        if seg.startswith(SCOPE_PREFIX):
            return seg.rstrip(":")
    return ""


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float  # ns
    dur: float  # ns
    args: tuple = ()  # ((key, value), ...)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Op:
    plane: str
    start: float  # ns
    dur: float  # ns
    tf_op: str  # "" where the op's metadata has none

    @property
    def scope(self) -> str:
        return scope_of(self.tf_op)


@dataclasses.dataclass
class Trace:
    spans: List[Span]  # host spans named bench.* or spgemm.*
    ops: List[Op]  # device ops (the ops line)
    modules: List[tracing.Event]  # runs of device executables


def load(path: str) -> Trace:
    """Spans, device ops and executable runs of a ``.xplane.pb`` file, of
    its gzip, or of the newest trace under a ``jax.profiler`` log dir."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tracing.find_xplane(path)
    data = xspace.read_bytes(path)
    tf_ops = xspace.tf_ops(data)
    spans, ops, modules = [], [], []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        device = plane.name.startswith(tracing.DEVICE_PLANE_PREFIX)
        names = tf_ops.get(plane.name, {})
        for line in plane.lines:
            for ev in line.events:
                start, dur = float(ev.start_ns), float(ev.duration_ns)
                if device and line.name == tracing.OPS_LINE:
                    ops.append(Op(plane.name, start, dur,
                                  names.get(ev.name, "")))
                elif device and line.name == tracing.MODULES_LINE:
                    modules.append(tracing.Event(plane.name, line.name,
                                                 ev.name, start, dur))
                elif not device and ev.name.startswith(SPAN_PREFIXES):
                    spans.append(Span(ev.name, start, dur, tuple(
                        (k, v) for k, v in ev.stats)))
    return Trace(spans, ops, modules)


def _self_times(ops: List[Op]) -> List[float]:
    """Each op's duration less the ops nested in it (same device)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].plane,
                                                   ops[i].start, -ops[i].dur))
    own = [op.dur for op in ops]
    stack: list = []
    for i in order:
        op = ops[i]
        while stack and (ops[stack[-1]].plane != op.plane
                         or ops[stack[-1]].start + ops[stack[-1]].dur
                         <= op.start):
            stack.pop()
        if stack:
            parent = stack[-1]
            own[parent] -= min(op.dur, ops[parent].start + ops[parent].dur
                               - op.start)
        stack.append(i)
    return own


@dataclasses.dataclass
class PhaseSummary:
    window: Span
    step_runs: list  # per fused-step run, in start order: {scope: s}
    spans: List[Span]  # bench.* and spgemm.* spans inside the window
    idle_gaps: list  # [(span name, s)], longest first

    def counted_batches(self) -> int:
        return sum(1 for s in self.spans if s.name == CONSUMER_SPAN)

    @property
    def scoped(self) -> bool:
        """Whether any op of the step ran under a phase scope."""
        return any(k for run in self.step_runs for k in run)

    def step_phases(self, batches: Optional[int] = None) -> list:
        """[[scope, s per batch], ...], most time first; ``(unscoped)``
        holds the ops without a phase scope."""
        runs = self._runs(batches)
        if not runs:
            return []
        total = defaultdict(float)
        for run in runs:
            for scope, s in run.items():
                total[scope or UNSCOPED] += s
        return sorted(([k, v / len(runs)] for k, v in total.items()),
                      key=lambda kv: -kv[1])

    def phase_s(self, scopes, batches: Optional[int] = None):
        """Mean device seconds a batch spends in ``scopes``; None where the
        step carries no scope at all."""
        runs = self._runs(batches)
        if not runs or not self.scoped:
            return None
        return sum(run.get(s, 0.0) for run in runs for s in scopes) / len(runs)

    def first_call_span_s(self, name: str):
        """Seconds of the first ``name`` span inside the window's first
        ``spgemm.call``; None where there is none."""
        calls = [s for s in self.spans if s.name == CALL_SPAN]
        if not calls:
            return None
        call = min(calls, key=lambda s: s.start)
        inside = [s for s in self.spans if s.name == name
                  and call.start <= s.start and s.end <= call.end]
        return min(inside, key=lambda s: s.start).dur / 1e9 if inside else None

    def span_count(self, name: str):
        """Spans named ``name`` in the window; None where the program
        recorded no ``spgemm.call`` there."""
        if not any(s.name == CALL_SPAN for s in self.spans):
            return None
        return sum(1 for s in self.spans if s.name == name)

    def metrics(self, batches: Optional[int] = None) -> dict:
        out = {m: self.phase_s(scopes, batches)
               for m, scopes in PHASE_METRICS.items()}
        out.update({m: self.first_call_span_s(span)
                    for m, span in SPAN_METRICS.items()})
        out["overflow_retries"] = self.span_count(RETRY_SPAN)
        return out

    def _runs(self, batches):
        n = self.counted_batches() if batches is None else batches
        return self.step_runs[:n]


def summarize(tr: Trace, fused_step: str = FUSED_STEP,
              top: int = 10) -> PhaseSummary:
    windows = [s for s in tr.spans if s.name == tracing.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {tracing.WINDOW_SPAN!r} span")
    w = max(windows, key=lambda s: s.dur)
    w0, w1 = w.start, w.end

    ops = [op for op in tr.ops if op.start < w1 and op.start + op.dur > w0]
    if not ops:
        raise ValueError("no device operation ran inside the traced window")
    own = _self_times(ops)
    runs = sorted((m for m in tr.modules if fused_step in m.name
                   and m.start >= w0 and m.end <= w1),
                  key=lambda m: m.start)
    step_runs = []
    for run in runs:
        by_scope = defaultdict(float)
        for op, t in zip(ops, own):
            if (op.plane == run.plane and op.start >= run.start
                    and op.start + op.dur <= run.end):
                by_scope[op.scope] += t / 1e9
        step_runs.append(dict(by_scope))

    first = min(op.plane for op in ops)  # gaps of the first device
    busy = tracing._union((max(op.start, w0), min(op.start + op.dur, w1))
                          for op in ops if op.plane == first)
    spans = [s for s in tr.spans if s is not w and s.start < w1
             and s.end > w0]
    gaps, prev = [], w0
    for s, t in busy + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    named = []
    for s, t in gaps:
        mid = (s + t) / 2
        inside = [x for x in spans if x.start <= mid <= x.end]
        label = (min(inside, key=lambda x: x.dur).name if inside
                 else tracing.WINDOW_SPAN)
        named.append((label, (t - s) / 1e9))
    named.sort(key=lambda x: -x[1])
    return PhaseSummary(window=w, step_runs=step_runs, spans=spans,
                        idle_gaps=named[:top])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a jax.profiler log dir or .xplane.pb[.gz]")
    ap.add_argument("--batches", type=int, default=None,
                    help="counted batches (default: bench.consumer spans)")
    args = ap.parse_args(argv)
    s = summarize(load(args.trace))
    n = s.counted_batches() if args.batches is None else args.batches
    spans = defaultdict(int)
    for x in s.spans:
        spans[x.name] += 1
    print(json.dumps({
        "window_s": s.window.dur / 1e9,
        "batches": n,
        "step_runs_s": [sum(r.values()) for r in s.step_runs],
        "step_phases": s.step_phases(n),
        "metrics": s.metrics(n),
        "idle_gaps": [[k, v] for k, v in s.idle_gaps],
        "spans": dict(sorted(spans.items())),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
