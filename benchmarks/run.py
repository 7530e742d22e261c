import os

# The paper's figures measure COLLECTIVES (broadcast/fiber-a2a volumes), so
# this entrypoint provisions 8 host devices for itself — deliberately scoped
# here, not in conftest/pyproject (tests must keep seeing 1 device).
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

"""Benchmark harness — one module per paper table/figure.

``--suite all`` (default) prints ``name,us_per_call,derived`` CSV across every
table/figure module. ``--suite local`` runs the local-kernel hot-path suite
(packed-key sort engine + hash-vs-ESC scratch) and writes
``BENCH_local_kernels.json`` at the repo root — op, variant, wall-ms, achieved
GFLOP/s per row — so the perf trajectory is tracked from PR to PR.
``--suite summa3d`` runs the end-to-end batched driver suite (pipelined vs
serial schedule, ESC vs hash-accumulator local multiply, plus the
fixed-memory hash-vs-ESC batch-count row) and writes ``BENCH_summa3d.json``,
refreshing ``BENCH_local_kernels.json`` in the same run so both perf files
stay in lockstep; ``--smoke`` shrinks it to CI-sized shapes with the same
row schema. ``--suite mcl`` runs the
device-resident vs host-loop MCL comparison (per-iteration wall-ms and
host-transfer bytes) and writes ``BENCH_mcl.json``. ``--suite graph`` runs
the §V-B masked-SpGEMM workloads (masked vs unmasked triangle counting on
R-MAT, on-grid vs host-filtered overlap detection) and writes
``BENCH_graph.json``. ``--suite serve`` runs the plan-cached serving-engine
suite (open-loop mixed repeat/novel traffic: p50/p99 latency,
multiplies/sec, plan-cache hit rate, zero-retrace repeat probe) and writes
``BENCH_serve.json``; ``--smoke`` shrinks it to CI size. ``--suite tune``
runs the cost-model calibration + autotuner acceptance suite (predicted /
measured ratio per checked-in summa3d pipelined row, never-worse-than-default
and R-MAT-skew autotuner rows — pure host math) and writes
``BENCH_tune.json``. Every BENCH_*.json artifact validates against the
shared row schema via ``python -m benchmarks.check_bench_json`` (enforced
in CI).
"""
import argparse
import json
import pathlib
import platform
import sys

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def run_all() -> None:
    from . import (
        bench_comm_model,
        bench_graph,
        bench_layers_batches,
        bench_local_kernels,
        bench_mcl,
        bench_scaling,
        bench_serve,
        bench_summa3d,
        bench_symbolic,
    )

    print("name,us_per_call,derived")
    bench_local_kernels.run()   # Table VII / Fig. 15
    bench_comm_model.run()      # Table II
    bench_layers_batches.run()  # Fig. 4/5 (+ Table VI trends)
    bench_summa3d.run()         # Alg. 4 pipelined driver
    bench_symbolic.run()        # Fig. 8
    bench_scaling.run()         # Fig. 6/7/9 (alpha-beta projection)
    bench_mcl.run()             # Fig. 3 (HipMCL end-to-end)
    bench_graph.run()           # §V-B masked graph workloads
    bench_serve.run()           # plan-cached serving engine


def _write_suite(suite: str, rows_fn, json_path: pathlib.Path) -> None:
    """Shared single-suite runner: one payload schema for every artifact
    (``check_bench_json`` validates exactly this envelope)."""
    import jax

    print("name,us_per_call,derived")
    payload = {
        "suite": suite,
        "backend": jax.default_backend(),
        "platform": platform.platform(),
        "rows": rows_fn(),
    }
    json_path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {json_path}", file=sys.stderr)


def run_local(json_path: pathlib.Path) -> None:
    from . import bench_local_kernels

    _write_suite("local_kernels", bench_local_kernels.run_local_suite, json_path)


def run_summa3d(json_path: pathlib.Path, smoke: bool = False) -> None:
    from . import bench_summa3d

    if smoke:
        # CI-sized shapes: same rows/schema (check_bench_json validates the
        # full summa3d row set), minutes -> seconds
        _write_suite(
            "summa3d_driver",
            lambda: bench_summa3d.run_summa3d_suite(
                scale=6, edge_factor=6, nb=4, iters=1
            ),
            json_path,
        )
        return
    _write_suite("summa3d_driver", bench_summa3d.run_summa3d_suite, json_path)
    # keep the local-kernel numbers in lockstep with the driver numbers
    run_local(REPO_ROOT / "BENCH_local_kernels.json")


def run_mcl(json_path: pathlib.Path) -> None:
    from . import bench_mcl

    _write_suite("mcl_pipeline", bench_mcl.run_mcl_suite, json_path)


def run_graph(json_path: pathlib.Path) -> None:
    from . import bench_graph

    _write_suite("graph_masked", bench_graph.run_graph_suite, json_path)


def run_tune(json_path: pathlib.Path, smoke: bool = False) -> None:
    from . import bench_tune

    _write_suite(
        "tune",
        lambda: bench_tune.run_tune_suite(smoke=smoke),
        json_path,
    )


def run_serve(json_path: pathlib.Path, smoke: bool = False) -> None:
    from . import bench_serve

    _write_suite(
        "serve_engine",
        lambda: bench_serve.run_serve_suite(smoke=smoke),
        json_path,
    )


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--suite",
        choices=("all", "local", "summa3d", "mcl", "graph", "serve", "tune"),
        default="all",
    )
    ap.add_argument(
        "--json-out",
        default=None,
        help="output path for the single-suite modes",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized shapes (summa3d/serve suites): same rows, tiny scale",
    )
    args = ap.parse_args()
    if args.suite == "local":
        run_local(pathlib.Path(
            args.json_out or REPO_ROOT / "BENCH_local_kernels.json"
        ))
    elif args.suite == "summa3d":
        run_summa3d(pathlib.Path(
            args.json_out or REPO_ROOT / "BENCH_summa3d.json"
        ), smoke=args.smoke)
    elif args.suite == "mcl":
        run_mcl(pathlib.Path(args.json_out or REPO_ROOT / "BENCH_mcl.json"))
    elif args.suite == "graph":
        run_graph(pathlib.Path(
            args.json_out or REPO_ROOT / "BENCH_graph.json"
        ))
    elif args.suite == "serve":
        run_serve(pathlib.Path(
            args.json_out or REPO_ROOT / "BENCH_serve.json"
        ), smoke=args.smoke)
    elif args.suite == "tune":
        run_tune(pathlib.Path(
            args.json_out or REPO_ROOT / "BENCH_tune.json"
        ), smoke=args.smoke)
    else:
        run_all()


if __name__ == "__main__":
    main()
