"""Production training launcher.

On a real pod this is the per-host entrypoint (jax.distributed.initialize
picks up the TPU topology); on this container it runs the same code path on
host devices. Wires together: config registry → production mesh → sharded
train step → synthetic/real data pipeline → fault-tolerant driver with async
checkpointing.

  python -m repro.launch.train --arch granite-20b --steps 100 \
      --ckpt /tmp/ckpt [--smoke] [--microbatches 2] [--seq 4096 --batch 256]
"""
from __future__ import annotations

import argparse


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-size); full config otherwise")
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="auto",
                    help="auto | single | multi | dxm (e.g. 2x2)")
    ap.add_argument("--distributed-init", action="store_true",
                    help="call jax.distributed.initialize() (real pods)")
    args = ap.parse_args()

    if args.distributed_init:
        import jax

        jax.distributed.initialize()

    import jax
    import numpy as np
    from jax.sharding import AxisType

    from ..configs import get_config
    from ..data import DataConfig, synthetic_batch
    from ..models import transformer as tfm
    from ..optim import adamw
    from ..runtime import RuntimeConfig, run_training
    from ..train import TrainConfig, build_train_step
    from .mesh import make_production_mesh

    cfg = get_config(args.arch, smoke=args.smoke)
    ndev = len(jax.devices())
    if args.mesh == "single":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "multi":
        mesh = make_production_mesh(multi_pod=True)
    elif args.mesh == "auto":
        model = 2 if ndev >= 4 else 1
        mesh = jax.make_mesh((ndev // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    else:
        d, m = (int(v) for v in args.mesh.split("x"))
        mesh = jax.make_mesh((d, m), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    print(f"arch={cfg.arch_id} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    seq = args.seq or (128 if args.smoke else 4096)
    batch = args.batch or (8 if args.smoke else 256)
    tc = TrainConfig(
        optimizer=adamw.AdamWConfig(lr=args.lr),
        microbatches=args.microbatches,
    )
    step_fn, shardings, _ = build_train_step(cfg, mesh, tc)
    dcfg = DataConfig(seq_len=seq, global_batch=batch, vocab=cfg.vocab,
                      input_mode=cfg.input_mode, d_model=cfg.d_model)

    def make_state():
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": adamw.init_opt_state(params)}

    def wrapped_step(state, batch_):
        with jax.set_mesh(mesh):
            p, o, m = step_fn(state["params"], state["opt"], batch_)
        return {"params": p, "opt": o}, m

    rc = RuntimeConfig(ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every)
    res = run_training(
        steps=args.steps, make_state=make_state, step_fn=wrapped_step,
        batch_fn=lambda s: synthetic_batch(dcfg, s), rc=rc,
    )
    print(f"done: step={res.final_step} loss[last5]={np.mean(res.losses[-5:]):.4f} "
          f"rollbacks={res.rollbacks} restarts={res.restarts} "
          f"stragglers={res.straggler_events}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
