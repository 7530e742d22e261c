"""Reduce a profiler trace to the benchmark's device numbers.

A trace is read into plain events (plane, line, name, start, duration; ns
on the profiler's one clock). From them:

* the traced window: the host span ``bench.window`` the harness records;
* device busy time: the union of the intervals in which an operation ran
  on a device, clipped to the window, averaged over the devices;
* per-executable device time: the events of an executable (the device's
  ``XLA Modules`` line) that ran wholly inside the window;
* the operations that took most device time;
* the longest idle gaps of the device, each named by the innermost
  benchmark span (``bench.*``) the host was in at the gap's middle.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import os
from collections import defaultdict
from typing import List

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float  # ns
    dur: float  # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


def find_xplane(log_dir: str) -> str:
    """The ``.xplane.pb`` file ``jax.profiler`` wrote under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> List[Event]:
    """Events of a ``.xplane.pb`` file (or of its gzip, ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    return [
        Event(plane.name, line.name, ev.name, float(ev.start_ns),
              float(ev.duration_ns))
        for plane in data.planes for line in plane.lines
        for ev in line.events
    ]


def short_op_name(name: str) -> str:
    """``%fusion.4 = s32[87654656]{0:T(1024)} fusion(...), kind=...`` ->
    ``%fusion.4 s32[87654656]``: the operation and its output shape."""
    left, _, right = name.partition(" = ")
    shape = right.split(" ")[0].split("{")[0] if right else ""
    return f"{left} {shape}".strip()


def _union(intervals):
    """Merged, sorted, disjoint intervals of ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: float  # averaged over the devices
    devices: int
    modules: dict  # executable name -> [(start, duration) ns] in window
    top_ops: list  # [(name, seconds)], most device time first
    idle_gaps: list  # [(span name, seconds)], longest first

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_ns / self.window_ns

    def module_durations(self, pattern: str) -> list:
        """Durations (s), in start order, of every run of the executables
        whose name contains ``pattern``."""
        runs = sorted(run for name, items in self.modules.items()
                      if pattern in name for run in items)
        return [d / 1e9 for _, d in runs]


def summarize(events: List[Event], top: int = 10) -> TraceSummary:
    windows = [e for e in events if e.name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    w = max(windows, key=lambda e: e.dur)
    w0, w1 = w.start, w.end

    per_device = defaultdict(list)
    op_time = defaultdict(float)
    modules = defaultdict(list)
    for e in events:
        if not e.plane.startswith(DEVICE_PLANE_PREFIX):
            continue
        if e.line == OPS_LINE:
            s, t = max(e.start, w0), min(e.end, w1)
            if t > s:
                per_device[e.plane].append((s, t))
                op_time[short_op_name(e.name)] += t - s
        elif e.line == MODULES_LINE and e.start >= w0 and e.end <= w1:
            modules[e.name].append((e.start, e.dur))
    if not per_device:
        raise ValueError("no device operation ran inside the traced window")

    busy = {d: _union(iv) for d, iv in per_device.items()}
    busy_ns = sum(sum(t - s for s, t in u) for u in busy.values()) / len(busy)

    spans = [e for e in events
             if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW_SPAN]
    first = sorted(busy)[0]
    gaps, prev = [], w0
    for s, t in busy[first] + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    named = []
    for s, t in gaps:
        mid = (s + t) / 2
        inside = [e for e in spans if e.start <= mid <= e.end]
        label = min(inside, key=lambda e: e.dur).name if inside else WINDOW_SPAN
        named.append((label, (t - s) / 1e9))
    named.sort(key=lambda x: -x[1])
    ops = sorted(((n, v / 1e9) for n, v in op_time.items()),
                 key=lambda x: -x[1])
    return TraceSummary(
        window_ns=w1 - w0, busy_ns=busy_ns, devices=len(busy),
        modules=dict(modules), top_ops=ops[:top], idle_gaps=named[:top],
    )


def describe(events: List[Event]) -> str:
    """One line per (plane, line): its event count and a few names, to read
    a trace by hand."""
    groups = defaultdict(list)
    for e in events:
        groups[(e.plane, e.line)].append(e.name)
    out = []
    for (plane, line), names in sorted(groups.items()):
        uniq = sorted(set(names))
        out.append(f"{plane} | {line} | {len(names)} events | "
                   f"{uniq[:6]}{' ...' if len(uniq) > 6 else ''}")
    return "\n".join(out)

