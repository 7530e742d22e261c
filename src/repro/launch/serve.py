"""SpGEMM serving launcher: plan-cached multiply-as-a-service on a grid.

  python -m repro.launch.serve --requests 16 --repeat-frac 0.5

Generates a mixed repeat/novel request stream, runs it through the
``SpgemmEngine`` admission queue + plan cache, and reports per-request
latency percentiles, throughput, and the plan-cache hit rate. A real
SIGTERM is translated into ``PreemptionError`` at the loop boundary
(``runtime.resilient.install_preemption_handler``), so an orchestrator's
stop signal drains as a clean preemption instead of a hard kill.
"""
from __future__ import annotations

import argparse


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--n", type=int, default=128, help="matrix dimension")
    ap.add_argument("--deg", type=float, default=4.0,
                    help="average nonzeros per row")
    ap.add_argument("--repeat-frac", type=float, default=0.5,
                    help="fraction of requests repeating one signature")
    ap.add_argument("--memory", type=int, default=1 << 26,
                    help="per-process admission budget (bytes)")
    ap.add_argument("--pr", type=int, default=1)
    ap.add_argument("--layers", type=int, default=1)
    args = ap.parse_args()

    import numpy as np

    from ..compile_cache import enable_compile_cache
    from ..core.gen import erdos_renyi
    from ..core.grid import make_grid
    from ..runtime.resilient import (
        PreemptionError,
        install_preemption_handler,
    )
    from ..serve import MultiplyRequest, ServeConfig, SpgemmEngine

    enable_compile_cache()
    install_preemption_handler()
    grid = make_grid(args.pr, args.pr, args.layers)
    eng = SpgemmEngine(grid, ServeConfig(per_process_memory=args.memory))

    a0 = erdos_renyi(args.n, args.deg, seed=7)
    b0 = erdos_renyi(args.n, args.deg, seed=8)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        if rng.random() < args.repeat_frac:
            eng.submit(MultiplyRequest(rid=rid, a=a0, b=b0))
        else:
            eng.submit(MultiplyRequest(
                rid=rid,
                a=erdos_renyi(args.n, args.deg, seed=100 + 2 * rid),
                b=erdos_renyi(args.n, args.deg, seed=101 + 2 * rid),
            ))
    try:
        results = eng.run_to_completion()
    except PreemptionError as e:
        print(f"preempted: {e} — served {len(eng.done)} of {args.requests}")
        return 0
    ok = [r for r in results if r.status == "ok"]
    lat = sorted(r.latency_ms for r in ok)
    p = lambda q: lat[min(int(q * len(lat)), len(lat) - 1)] if lat else 0.0  # noqa: E731
    print(
        f"served {len(ok)}/{args.requests} "
        f"(refused {eng.stats['refused']}, deferred {eng.stats['deferred']}) "
        f"p50 {p(0.5):.1f}ms p99 {p(0.99):.1f}ms "
        f"plan-cache hit rate {eng.cache_hit_rate():.2f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
