"""End-to-end LM training driver (deliverable (b)): trains a ~100M-class
model for a few hundred steps with the full substrate — sharded train step,
AdamW+ZeRO, synthetic pipeline, async checkpointing, fault-tolerant loop.

Default runs a reduced-width model sized for this CPU container; pass
--full-100m for the 100M-parameter configuration (same code path, slower).

Run:  PYTHONPATH=src python examples/train_lm.py --steps 300
      PYTHONPATH=src python examples/train_lm.py --arch deepseek-moe-16b --smoke
"""
import argparse
import os
import tempfile

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--arch", default=None, help="train a smoke config of an arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--full-100m", action="store_true")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    import jax
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.data import DataConfig, synthetic_batch
    from repro.models import transformer as tfm
    from repro.models.transformer import ModelConfig
    from repro.optim import adamw
    from repro.runtime import RuntimeConfig, run_training
    from repro.train import TrainConfig, build_train_step

    ndev = len(jax.devices())
    mesh = jax.make_mesh((max(ndev // 2, 1), min(2, ndev)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    if args.arch:
        cfg = get_config(args.arch, smoke=True)
    elif args.full_100m:
        cfg = ModelConfig(
            arch_id="lm-100m", n_layers=12, d_model=768, n_heads=12,
            kv_heads=12, head_dim=64, d_ff=3072, vocab=32000, act="swiglu",
            family="attn", dtype="float32",
        )
    else:  # 100M-class structure, reduced width for CPU throughput
        cfg = ModelConfig(
            arch_id="lm-mini", n_layers=4, d_model=256, n_heads=8,
            kv_heads=4, head_dim=32, d_ff=1024, vocab=4096, act="swiglu",
            family="attn", dtype="float32",
        )
    n_params = None

    tc = TrainConfig(optimizer=adamw.AdamWConfig(lr=1e-3, warmup_steps=20))
    step_fn, shardings, _ = build_train_step(cfg, mesh, tc)
    dcfg = DataConfig(seq_len=args.seq, global_batch=args.batch, vocab=cfg.vocab,
                      input_mode=cfg.input_mode, d_model=cfg.d_model)

    def make_state():
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        nonlocal n_params
        from repro.models.common import param_count
        n_params = param_count(params)
        return {"params": params, "opt": adamw.init_opt_state(params)}

    def wrapped_step(state, batch):
        with jax.set_mesh(mesh):
            p, o, m = step_fn(state["params"], state["opt"], batch)
        return {"params": p, "opt": o}, m

    ckpt_dir = args.ckpt or tempfile.mkdtemp(prefix="lm_ckpt_")
    rc = RuntimeConfig(ckpt_dir=ckpt_dir, ckpt_every=max(args.steps // 4, 10))
    res = run_training(
        steps=args.steps, make_state=make_state, step_fn=wrapped_step,
        batch_fn=lambda s: synthetic_batch(dcfg, s), rc=rc,
    )
    print(f"arch={cfg.arch_id} params={n_params/1e6:.1f}M steps={res.final_step}")
    k = max(args.steps // 10, 1)
    print(f"loss: first {np.mean(res.losses[:k]):.3f} -> last "
          f"{np.mean(res.losses[-k:]):.3f}")
    assert np.mean(res.losses[-k:]) < np.mean(res.losses[:k]), "loss must drop"
    print(f"checkpoints in {ckpt_dir}; OK")


if __name__ == "__main__":
    main()
