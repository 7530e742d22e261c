"""Correctness of the §Perf sharding variants: they must be function-exact
(padding) or training-equivalent (strategies) vs the baseline."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh

from repro.configs import get_config
from repro.models import transformer as tfm
from repro.models.attention import attend, init_attention
from repro.optim import adamw
from repro.train import TrainConfig, build_train_step

# compiles model variants — excluded from the CI fast lane (-m 'not slow')
pytestmark = pytest.mark.slow


def tiny_mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"), axis_types=(AxisType.Auto,) * 2)


class TestHeadPadding:
    def test_padded_attention_exact(self):
        """48-head padded attention == 36-head original, bit-for-bit structure:
        zero wq rows -> garbage in pad heads, zero wo rows -> never surfaces,
        per-group layout preserves the q->kv mapping."""
        key = jax.random.PRNGKey(0)
        D, H, KV, hd = 64, 6, 2, 16
        base = init_attention(key, D, H, KV, hd)
        padded = init_attention(key, D, H, KV, hd, pad_heads_to=8)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, D))
        pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
        y0, _ = attend(base, x, pos)
        y1, _ = attend(padded, x, pos)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   rtol=1e-5, atol=1e-5)

    def test_padded_model_forward_exact(self):
        cfg = get_config("starcoder2-7b", smoke=True)  # 4 heads kv 2
        cfg_pad = dataclasses.replace(cfg, pad_heads_to=8)
        params = tfm.init_params(cfg, jax.random.PRNGKey(3))
        params_pad = tfm.init_params(cfg_pad, jax.random.PRNGKey(3))
        tok = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, cfg.vocab)
        mesh = tiny_mesh()
        with jax.set_mesh(mesh):
            l0, _ = tfm.forward(cfg, params, tok, mesh)
            l1, _ = tfm.forward(cfg_pad, params_pad, tok, mesh)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=1e-4, atol=1e-4)

    def test_pad_specs_shardable(self):
        cfg = get_config("starcoder2-7b")
        cfg_pad = dataclasses.replace(cfg, pad_heads_to=48)
        specs = tfm.param_specs(cfg_pad, tp=16)
        assert specs["layers"]["attn"]["wq"][2] == "model"
        # unpadded 36 heads cannot shard over 16
        specs0 = tfm.param_specs(cfg, tp=16)
        assert specs0["layers"]["attn"]["wq"][2] is None


class TestStrategies:
    @pytest.mark.parametrize("strategy,master", [("tp", False), ("dp", True)])
    def test_one_step_finite(self, strategy, master):
        cfg = get_config("minitron-8b", smoke=True)
        mesh = tiny_mesh()
        tc = TrainConfig(
            optimizer=adamw.AdamWConfig(lr=1e-3, master_in_opt=master),
            strategy=strategy,
        )
        from repro.data import DataConfig, synthetic_batch

        with jax.set_mesh(mesh):
            step_fn, _, _ = build_train_step(cfg, mesh, tc, global_batch=2)
            params = tfm.init_params(cfg, jax.random.PRNGKey(0))
            if master:
                params = jax.tree.map(
                    lambda p: p.astype(jnp.bfloat16)
                    if p.dtype == jnp.float32 else p, params
                )
            opt = adamw.init_opt_state(params, master_in_opt=master)
            batch = synthetic_batch(DataConfig(seq_len=8, global_batch=2,
                                               vocab=cfg.vocab), 0)
            p, o, m = step_fn(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        if master:
            assert "master" in o
            # master stays f32, params stay bf16
            assert jax.tree.leaves(o["master"])[0].dtype == jnp.float32

    def test_dp_tp_losses_match(self):
        """Strategy changes sharding, never math: first-step loss identical."""
        cfg = get_config("musicgen-large", smoke=True)
        mesh = tiny_mesh()
        from repro.data import DataConfig, synthetic_batch

        dcfg = DataConfig(seq_len=8, global_batch=2, vocab=cfg.vocab,
                          input_mode=cfg.input_mode, d_model=cfg.d_model)
        batch = synthetic_batch(dcfg, 0)
        losses = {}
        with jax.set_mesh(mesh):
            for strat in ("tp", "dp"):
                step_fn, _, _ = build_train_step(
                    cfg, mesh, TrainConfig(strategy=strat), global_batch=2
                )
                params = tfm.init_params(cfg, jax.random.PRNGKey(0))
                opt = adamw.init_opt_state(params)
                _, _, m = step_fn(params, opt, batch)
                losses[strat] = float(m["loss"])
        np.testing.assert_allclose(losses["tp"], losses["dp"], rtol=1e-4)
