"""Batched serving with continuous batching (deliverable (b), serving kind).

Spins up the ServeEngine on a smoke-size model, submits a burst of requests
with varying prompt lengths, and drives prefill + lock-step decode to
completion.

Run:  PYTHONPATH=src python examples/serve_lm.py --arch granite-20b
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-20b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    import numpy as np

    import jax
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.models import transformer as tfm
    from repro.serve import EngineConfig, Request, ServeEngine

    cfg = get_config(args.arch, smoke=True)
    ndev = len(jax.devices())
    mesh = jax.make_mesh((1, min(2, ndev)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        params = tfm.init_params(cfg, jax.random.PRNGKey(3))
        eng = ServeEngine(cfg, params, mesh, EngineConfig(max_batch=3, s_max=64))
        rng = np.random.default_rng(0)
        for rid in range(args.requests):
            plen = int(rng.integers(4, 12))
            eng.submit(Request(
                rid=rid,
                prompt=rng.integers(0, cfg.vocab, plen).astype(np.int32),
                max_new_tokens=args.max_new,
            ))
        print(f"submitted {args.requests} requests (max_batch=3 -> continuous "
              f"batching refills slots)")
        done = eng.run_to_completion()
    for req in sorted(done, key=lambda r: r.rid):
        print(f"  req{req.rid}: prompt_len={len(req.prompt)} "
              f"generated={req.out_tokens}")
    assert len(done) == args.requests
    print("OK — all requests completed")


if __name__ == "__main__":
    main()
