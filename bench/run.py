"""Run one benchmark cell of batched SUMMA3D on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, its traffic mix and
its metrics are found by name through ``BENCHMARK.json`` (``bench/spec.py``).

A run is a closed loop with one caller, as HipMCL's driver waits for each
expansion:

1. set-up: the compile cache on, the operand made from ``--seed`` by the
   configuration's generator, the operation's operands scattered onto the
   cell's grid, the per-process budget set to the device's free memory,
   and one synchronous call stopped after its first batch, which compiles
   (or loads) every program the window runs;
2. the window: calls of the operation's program (``batched_summa3d``) back
   to back on the same operands, with the program's default specs or the
   driver the traffic mix names under ``"exec"``, counted batch by batch as
   they reach the consumer (``bench/window.py``), for ``--seconds``;
3. the check: every counted batch against a float64 scipy product of the
   same columns (``bench/check.py``).

``--trace 1`` makes the same run with the profiler on over the window, and
reports the per-layer metrics instead of the end-to-end ones. The last line
of standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced) and, last,
``checks``: each number compared with its limit. Without a TPU, or with
fewer chips than the cell asks for, the command exits with 2 and prints no
result.
"""
from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is timed from process start

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))
# the TPU runtime would otherwise write its logs under /tmp, outside the
# checkout and the run's own directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from bench import check, counting, operand, trace as tracing  # noqa: E402
from bench.spec import Benchmark, Cell  # noqa: E402
from bench.window import Window  # noqa: E402

PEAKS_FILE = ROOT / "bench" / "peaks.json"
# the executable of one batch: the fused select + multiply + merge step
FUSED_STEP = "summa3d_fused_step"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts the programs JAX builds: every build fires the backend-compile
    event, and a build served by the persistent cache also fires a cache
    hit. ``compiles`` is the builds the cache did not serve."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.builds = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_build)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_build(self, event, duration, **kwargs):
        if event == self.BUILD:
            self.builds += 1

    def _on_event(self, event, **kwargs):
        if event == self.CACHE_HIT:
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self.builds - self.cache_hits


def peaks_for(kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS_FILE} "
                       f"(have {sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class RunContext:
    """Everything a metric reader (``bench/metrics/<name>.py``) may read."""

    cell: Cell
    seed: int
    setup_s: float
    window: Window
    counts: counting.ProductCounts
    checks: list  # check.BatchCheck per counted batch
    peaks: Optional[dict]
    memory: Optional[dict]  # {"peak_bytes_in_use", "bytes_limit"}
    plan: object = None  # BatchPlan of the traced run's plan_s call
    plan_s: Optional[float] = None
    trace: Optional[tracing.TraceSummary] = None
    fused_step: str = FUSED_STEP


def device_memory(devices) -> Optional[dict]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return {
        "bytes_limit": min(s["bytes_limit"] for s in stats),
        "bytes_in_use": max(s["bytes_in_use"] for s in stats),
        "peak_bytes_in_use": max(s["peak_bytes_in_use"] for s in stats),
    }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peaks: Optional[dict], *, t_process: float,
             budget: Optional[int] = None, clock: Callable = None,
             trace_dir: Optional[str] = None,
             make_multiply: Optional[Callable] = None):
    """One run of ``cell``; returns (result dict, RunContext). ``budget``
    replaces the device's free memory and ``clock`` the host clock (tests
    on the CPU, which reports no memory); ``make_multiply`` puts another
    system in the program's place (the control, ``bench/control.py``)."""
    import jax

    from repro.core.batched import plan_batches
    from repro.core.distsparse import scatter_to_grid
    from repro.core.grid import make_grid
    from repro.core.sparse import SparseCOO
    from repro.core.specs import ExecSpec, PlanSpec

    traffic, config = cell.traffic, cell.config
    # the window is a closed loop of one caller (bench/window.py)
    loop = (traffic["loop"], traffic["callers"])
    if loop != ("closed", 1):
        raise ValueError(f"traffic {traffic['name']!r}: unsupported "
                         f"(loop, callers) {loop}")
    grid_shape = tuple(traffic["grid"])
    if int(np.prod(grid_shape)) != cell.chips:
        raise ValueError(f"cell {cell.name}: grid {grid_shape} does not use "
                         f"its {cell.chips} chip(s)")
    counter = CompileCounter()

    t0 = time.perf_counter()
    op = operand.generate(cell.generator, config, seed)
    left, right = cell.operation.operands(op)
    a64 = left.to_scipy()
    b64 = a64 if right is left else right.to_scipy()
    gen_s = time.perf_counter() - t0
    counts = counting.ProductCounts(a64, b64)
    ref = check.Reference(a64, b64)

    t0 = time.perf_counter()
    grid = make_grid(*grid_shape, devices=devices[:cell.chips])

    def scatter(m, which):
        coo = SparseCOO(m.rows, m.cols, m.vals, np.int32(m.nnz), (m.n, m.n))
        return scatter_to_grid(coo, grid, which)

    A, B = scatter(left, "A"), scatter(right, "B")
    jax.block_until_ready((A, B))
    scatter_s = time.perf_counter() - t0
    grid_devices = list(grid.mesh.devices.flat)
    if budget is None:
        mem = device_memory(grid_devices)
        if mem is None:
            raise RuntimeError("the devices report no memory statistics")
        budget = mem["bytes_limit"] - mem["bytes_in_use"]
    log(f"[setup] {config['name']} seed={seed} n={op.n} nnz={op.nnz} "
        f"products={int(counts.col_products.sum())} grid={grid_shape} "
        f"per_process_memory={budget} generate_s={gen_s:.3f} "
        f"scatter_s={scatter_s:.3f}")

    spec = (None if traffic["local_path"] == "auto"
            else PlanSpec(local_path=traffic["local_path"]))
    # the window's driver: the program's default unless the mix names one
    window_exec = ExecSpec(**traffic["exec"]) if "exec" in traffic else None

    if make_multiply is None:
        multiply = cell.operation.program(A, B, grid, budget, spec)
    else:
        multiply = make_multiply(A, B, grid, budget, spec, left, right)
    # warm-up: a synchronous call stops clean after its first batch, with
    # nothing left queued on the device
    t0 = time.perf_counter()
    warm = Window(0.0)
    warm.run(lambda consumer: multiply(consumer, ExecSpec(pipelined=False)))
    log(f"[setup] warm-up: first batch in {time.perf_counter() - t0:.3f} s; "
        f"programs built {counter.builds}: {counter.compiles} compiled, "
        f"{counter.cache_hits} from the cache")

    plan = plan_s = None
    profile_dir = None
    if trace:
        t0 = time.perf_counter()
        plan = plan_batches(A, B, grid, budget, spec=spec or PlanSpec())
        plan_s = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_process

    win = Window(seconds, clock=clock,
                 span=jax.profiler.TraceAnnotation if trace else None)
    builds0 = counter.builds
    if trace:
        profile_dir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans and runtime events only
        options.enable_hlo_proto = False
        jax.profiler.start_trace(profile_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                win.run(lambda consumer: multiply(consumer, window_exec))
        finally:
            jax.profiler.stop_trace()
    else:
        win.run(lambda consumer: multiply(consumer, window_exec))
    window_builds = counter.builds - builds0
    memory = device_memory(grid_devices)
    log(f"[window] calls={win.calls} batches={len(win.batches)} "
        f"seconds={win.elapsed:.6f} programs_built_in_window={window_builds}")
    log("[window] arrivals_s=" + json.dumps(
        [[b.call, b.index, round(t, 6)]
         for b, t in zip(win.batches, win.arrivals())]))

    summary = None
    if trace:
        events = tracing.load_xplane(tracing.find_xplane(profile_dir))
        log("[trace] planes and lines:\n" + tracing.describe(events))
        summary = tracing.summarize(events)
        if trace_dir is None:
            shutil.rmtree(profile_dir, ignore_errors=True)

    t0 = time.perf_counter()
    correct, failed, results, numbers = check.check_window(
        ref, win.batches, config["check"])
    log(f"[check] {len(win.batches)} batches in "
        f"{time.perf_counter() - t0:.3f} s")

    ctx = RunContext(
        cell=cell, seed=seed, setup_s=setup_s, window=win, counts=counts,
        checks=results, peaks=peaks, memory=memory, plan=plan, plan_s=plan_s,
        trace=summary,
    )
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    dev = grid_devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(grid_devices),
              "memory_peak_bytes": memory["peak_bytes_in_use"] if memory
              else None}
    result = {"correct": bool(correct), "attempted": len(win.batches),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps],
        }
    result["checks"] = numbers
    return result, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace in this directory")
    args = ap.parse_args(argv)

    cell = Benchmark(ROOT).cell(args.workload)
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # every program goes to the cache, however short its compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: no TPU (JAX platform {devices[0].platform!r})")
        return 2
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX sees "
            f"{len(devices)}")
        return 2
    peaks = peaks_for(devices[0].device_kind)
    log(f"[device] {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; jax {jax.__version__}; compile cache {cache_dir}")
    result, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         devices, peaks, t_process=_T_PROCESS,
                         trace_dir=args.trace_dir)
    for name, num in result["checks"].items():
        log(f"check {name} = {num['value']} (limit {num['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Batches the driver dispatched past the window's end may still be
    # running on the device; nothing reads them, so the process ends
    # without waiting for them.
    os._exit(code)
