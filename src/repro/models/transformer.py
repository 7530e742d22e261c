"""Decoder LM covering all assigned architecture families.

One ``ModelConfig`` describes dense (llama/starcoder/granite/minitron),
gemma2 (alternating local/global + soft-caps + post-norms), MoE
(deepseek-moe/olmoe with SpGEMM dispatch), SSM (mamba2), hybrid (zamba2:
mamba backbone + a weight-shared attention block every k layers), and
embeds-input stubs (pixtral vision / musicgen audio frontends).

Params are nested dicts; layers are stacked along a leading L axis and
iterated with ``lax.scan`` (compile time ~ one layer). ``param_specs``
returns the parallel PartitionSpec tree (TP over "model", see DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as attn_mod
from . import mlp as mlp_mod
from . import moe as moe_mod
from . import ssm as ssm_mod
from .common import MODEL_AX, dense_init, embed_init, rms_norm, soft_cap

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    act: str = "swiglu"
    rope_theta: float = 10_000.0
    family: str = "attn"  # "attn" | "ssm" | "hybrid"
    # gemma2-style features
    local_global_alt: bool = False
    window: Optional[int] = None
    attn_softcap: Optional[float] = None
    final_softcap: Optional[float] = None
    query_scale: Optional[float] = None
    use_post_norms: bool = False
    embed_scale: bool = False
    # MoE / SSM / hybrid
    moe: Optional[moe_mod.MoEConfig] = None
    ssm: Optional[ssm_mod.SSMConfig] = None
    hybrid_every: int = 6
    # IO
    input_mode: str = "tokens"  # "tokens" | "embeds" (modality-frontend stub)
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    remat: bool = True
    # which serve shapes apply (encoder-only would disable decode; all ours decode)
    supports_long_context: bool = False  # sub-quadratic decode state
    # Megatron-style vocab padding: keeps the unembed shardable over "model"
    # for any tokenizer size (e.g. mamba2's 50280). Padded logits are masked
    # to -inf, so loss/sampling are exact.
    vocab_pad_multiple: int = 128
    # --- beyond-baseline sharding knobs (EXPERIMENTS.md section Perf) ---
    # pad attention heads per GQA group with zero heads so the head dim
    # divides the model axis (e.g. starcoder2 36 -> 48); function-exact.
    pad_heads_to: int = 0
    # activation sharding constraint between layers: None = GSPMD choice,
    # "seq" = sequence parallelism (residual sharded over "model" on S).
    act_sharding: Optional[str] = None

    @property
    def padded_vocab(self) -> int:
        m = self.vocab_pad_multiple
        return (self.vocab + m - 1) // m * m

    @property
    def eff_heads(self) -> int:
        return self.pad_heads_to or self.n_heads

    @property
    def hdim(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def param_dtype(self):
        return jnp.float32  # master weights; compute casts per-step

    @property
    def compute_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    def active_param_count(self) -> int:
        """Approximate activated params per token (for 6·N·D MODEL_FLOPS)."""
        D, F, V, L = self.d_model, self.d_ff, self.vocab, self.n_layers
        if self.family == "ssm":
            cfg = self.ssm
            di = cfg.d_inner(D)
            per_layer = D * di * 2 + D * (2 * cfg.n_groups * cfg.d_state) + di * D
        elif self.family == "hybrid":
            cfg = self.ssm
            di = cfg.d_inner(D)
            per_layer = D * di * 2 + D * (2 * cfg.n_groups * cfg.d_state) + di * D
            # shared attention block amortized over hybrid_every layers
            shared = (
                D * self.n_heads * self.hdim * 2
                + D * self.kv_heads * self.hdim * 2
                + 3 * D * F
            )
            per_layer += shared // self.hybrid_every
        else:
            attn = D * self.n_heads * self.hdim * 2 + D * self.kv_heads * self.hdim * 2
            if self.moe:
                m = self.moe
                ffn = m.top_k * 3 * D * m.d_expert + m.n_shared * 3 * D * m.d_expert
            else:
                ffn = (3 if self.act == "swiglu" else 2) * D * F
            per_layer = attn + ffn
        return L * per_layer + V * D  # + unembed


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _stack_init(key, n: int, fn):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = cfg.param_dtype
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = embed_init(keys[0], (cfg.padded_vocab, cfg.d_model), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[1], (cfg.d_model, cfg.padded_vocab), dtype=dtype
        )
    params["final_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)

    if cfg.family == "attn":
        def layer_init(k):
            k1, k2 = jax.random.split(k)
            lp = {
                "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
                "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
                "attn": attn_mod.init_attention(
                    k1, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hdim, dtype,
                    pad_heads_to=cfg.pad_heads_to,
                ),
            }
            if cfg.use_post_norms:
                lp["ln1_post"] = jnp.zeros((cfg.d_model,), jnp.float32)
                lp["ln2_post"] = jnp.zeros((cfg.d_model,), jnp.float32)
            if cfg.moe:
                lp["moe"] = moe_mod.init_moe(k2, cfg.d_model, cfg.moe, dtype)
            else:
                lp["mlp"] = mlp_mod.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)
            return lp

        params["layers"] = _stack_init(keys[2], cfg.n_layers, layer_init)
    elif cfg.family == "ssm":
        def layer_init(k):
            return {
                "ln": jnp.zeros((cfg.d_model,), jnp.float32),
                "mamba": ssm_mod.init_mamba2(k, cfg.d_model, cfg.ssm, dtype),
            }

        params["layers"] = _stack_init(keys[2], cfg.n_layers, layer_init)
    elif cfg.family == "hybrid":
        def layer_init(k):
            return {
                "ln": jnp.zeros((cfg.d_model,), jnp.float32),
                "mamba": ssm_mod.init_mamba2(k, cfg.d_model, cfg.ssm, dtype),
            }

        params["layers"] = _stack_init(keys[2], cfg.n_layers, layer_init)
        k1, k2 = jax.random.split(keys[3])
        params["shared_block"] = {
            "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
            "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
            "attn": attn_mod.init_attention(
                k1, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.hdim, dtype
            ),
            "mlp": mlp_mod.init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype),
        }
    else:
        raise ValueError(cfg.family)
    return params


def param_specs(cfg: ModelConfig, tp: int = 1) -> Dict[str, Any]:
    """PartitionSpec tree; tp = size of the "model" axis for divisibility
    fallbacks (dims that don't divide tp are replicated)."""
    d_ax = MODEL_AX if tp > 1 and cfg.d_model % tp == 0 else None
    v_ax = MODEL_AX if tp > 1 and cfg.padded_vocab % tp == 0 else None
    specs: Dict[str, Any] = {"final_norm": P(None)}
    if cfg.input_mode == "tokens":
        # untied: shard the hidden dim (lookup needs no collective).
        # tied: shard the VOCAB dim so the unembed contraction keeps logits
        # vocab-sharded (otherwise (B,S,V) materializes replicated — 13 GB/dev
        # for mamba2 train_4k); the lookup costs one table all-gather, orders
        # of magnitude smaller.
        specs["embed"] = P(v_ax, None) if cfg.tie_embeddings else P(None, d_ax)
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, v_ax)

    def _stack(d):  # prepend the layer axis (unsharded)
        return jax.tree.map(lambda s: P(*((None,) + tuple(s))), d,
                            is_leaf=lambda x: isinstance(x, P))

    if cfg.family == "attn":
        lp = {"ln1": P(None), "ln2": P(None),
              "attn": attn_mod.attention_specs(cfg.eff_heads, cfg.kv_heads, tp)}
        if cfg.use_post_norms:
            lp["ln1_post"] = P(None)
            lp["ln2_post"] = P(None)
        if cfg.moe:
            lp["moe"] = moe_mod.moe_specs(cfg.moe, tp)
        else:
            lp["mlp"] = mlp_mod.mlp_specs(cfg.act, cfg.d_ff, tp)
        specs["layers"] = _stack(lp)
    else:
        lp = {"ln": P(None),
              "mamba": ssm_mod.mamba2_specs(cfg.ssm, cfg.d_model, tp)}
        specs["layers"] = _stack(lp)
        if cfg.family == "hybrid":
            specs["shared_block"] = {
                "ln1": P(None),
                "ln2": P(None),
                "attn": attn_mod.attention_specs(cfg.n_heads, cfg.kv_heads, tp),
                "mlp": mlp_mod.mlp_specs(cfg.act, cfg.d_ff, tp),
            }
    return specs


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------
def _act_constraint(cfg: ModelConfig, mesh, h: Array) -> Array:
    """Sequence-parallel residual constraint (cfg.act_sharding == "seq"):
    between layers the residual stream is sharded over "model" along S —
    GSPMD converts the per-layer collectives to the AG/RS pattern of
    Megatron-SP and divides residual memory by tp."""
    if mesh is None or cfg.act_sharding != "seq":
        return h
    if h.shape[1] % mesh.shape.get(MODEL_AX, 1) != 0:
        return h
    from .common import batch_axes

    dp = batch_axes(mesh)
    dspec = dp if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(
        h, jax.sharding.NamedSharding(mesh, P(dspec, MODEL_AX, None))
    )


def _layer_windows(cfg: ModelConfig, s_ref: int) -> Array:
    """Per-layer attention window as a traced scan input (gemma2 alternation:
    even layers local, odd global). A huge window == unconstrained."""
    big = jnp.int32(2**30)
    if cfg.local_global_alt:
        loc = jnp.int32(cfg.window)
        return jnp.where(jnp.arange(cfg.n_layers) % 2 == 0, loc, big)
    if cfg.window is not None:
        return jnp.full((cfg.n_layers,), cfg.window, jnp.int32)
    return jnp.full((cfg.n_layers,), big, jnp.int32)


def _gather_for_attn(cfg: ModelConfig, mesh, x: Array) -> Array:
    """Megatron-SP: one explicit sequence all-gather of the normed residual
    before the qkv projections (instead of GSPMD gathering each of q/k/v
    post-projection — 2-3× the volume)."""
    if mesh is None or cfg.act_sharding != "seq":
        return x
    from .common import batch_axes

    dp = batch_axes(mesh)
    dspec = dp if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, P(dspec, None, None))
    )


def _attn_layer_fwd(cfg: ModelConfig, mesh, lp, h, positions, window,
                    kv_cache=None, cache_index=None, moe_mode="a2a"):
    att, new_cache = attn_mod.attend(
        lp["attn"], _gather_for_attn(cfg, mesh, rms_norm(h, lp["ln1"])),
        positions,
        rope_theta=cfg.rope_theta, window=window,
        attn_softcap=cfg.attn_softcap, query_scale=cfg.query_scale,
        kv_cache=kv_cache, cache_index=cache_index,
    )
    if cfg.use_post_norms:
        att = rms_norm(att, lp["ln1_post"])
    h = h + att
    ff_in = rms_norm(h, lp["ln2"])
    if cfg.moe:
        ff, aux = moe_mod.moe_layer(lp["moe"], ff_in, cfg.moe, mesh, mode=moe_mode)
    else:
        ff, aux = mlp_mod.mlp(lp["mlp"], ff_in, cfg.act), jnp.float32(0)
    if cfg.use_post_norms:
        ff = rms_norm(ff, lp["ln2_post"])
    return h + ff, aux, new_cache


def _shared_block_fwd(cfg: ModelConfig, sp, h, positions):
    att, _ = attn_mod.attend(
        sp["attn"], rms_norm(h, sp["ln1"]), positions,
        rope_theta=cfg.rope_theta, query_scale=cfg.query_scale,
    )
    h = h + att
    h = h + mlp_mod.mlp(sp["mlp"], rms_norm(h, sp["ln2"]), cfg.act)
    return h


def forward(
    cfg: ModelConfig,
    params: Dict[str, Any],
    inputs: Array,  # (B,S) tokens or (B,S,D) embeds
    mesh=None,
) -> Tuple[Array, Array]:
    """Returns (logits (B,S,V), aux loss scalar)."""
    cd = cfg.compute_dtype
    if cfg.input_mode == "tokens":
        h = params["embed"].astype(cd)[inputs]
    else:
        h = inputs.astype(cd)
    if cfg.embed_scale:
        h = h * jnp.sqrt(jnp.float32(cfg.d_model)).astype(cd)
    B, S = h.shape[0], h.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    aux_total = jnp.float32(0)
    if cfg.family == "attn":
        windows = _layer_windows(cfg, S)

        def body(h, xs):
            lp, window = xs
            lp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                              and p.ndim > 1 else p, lp)
            h2, aux, _ = _attn_layer_fwd(cfg, mesh, lp, h, positions, window)
            return _act_constraint(cfg, mesh, h2), aux

        if cfg.remat:
            body = jax.checkpoint(body)
        h, auxs = jax.lax.scan(body, h, (params["layers"], windows))
        aux_total = jnp.sum(auxs)
    elif cfg.family == "ssm":
        def body(h, lp):
            lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                and p.ndim > 1 else p, lp)
            h = h + ssm_mod.mamba2_block(
                lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm
            )
            return _act_constraint(cfg, mesh, h), jnp.float32(0)

        if cfg.remat:
            body = jax.checkpoint(body)
        h, _ = jax.lax.scan(body, h, params["layers"])
    else:  # hybrid
        k = cfg.hybrid_every
        ngroups = cfg.n_layers // k
        stacked = jax.tree.map(
            lambda p: p.reshape((ngroups, k) + p.shape[1:]), params["layers"]
        )
        sp = params["shared_block"]
        sp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                          and p.ndim > 1 else p, sp)

        def group_body(h, lp_group):
            def inner(h, lp):
                lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                    and p.ndim > 1 else p, lp)
                h = h + ssm_mod.mamba2_block(
                    lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm
                )
                return h, None

            h, _ = jax.lax.scan(inner, h, lp_group)
            h = _shared_block_fwd(cfg, sp, h, positions)
            return h, None

        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        h, _ = jax.lax.scan(group_body, h, stacked)

    h = rms_norm(h, params["final_norm"])
    w_out = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cd)
    logits = jnp.einsum("bsd,dv->bsv", h, w_out)
    logits = soft_cap(logits.astype(jnp.float32), cfg.final_softcap)
    return _mask_pad_vocab(cfg, logits), aux_total


def _mask_pad_vocab(cfg: ModelConfig, logits: Array) -> Array:
    """Padded vocab entries get -inf so softmax/argmax are exact."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    live = jnp.arange(cfg.padded_vocab) < cfg.vocab
    return jnp.where(live, logits, -1e30)


@jax.custom_vjp
def _vp_xent_local(lg: Array, tg: Array) -> Array:
    nll, _ = _vp_xent_fwd(lg, tg)
    return nll


def _vp_xent_fwd(lg, tg):
    """Runs inside shard_map over the model axis. lg (b,s,v_loc) f32."""
    v_loc = lg.shape[-1]
    off = jax.lax.axis_index(MODEL_AX) * v_loc
    m = jax.lax.pmax(jnp.max(lg, axis=-1), MODEL_AX)  # (b,s)
    s = jax.lax.psum(jnp.sum(jnp.exp(lg - m[..., None]), axis=-1), MODEL_AX)
    lse = m + jnp.log(s)
    t_loc = tg - off
    in_range = (t_loc >= 0) & (t_loc < v_loc)
    tl = jnp.take_along_axis(
        lg, jnp.clip(t_loc, 0, v_loc - 1)[..., None], axis=-1
    )[..., 0]
    tl = jax.lax.psum(jnp.where(in_range, tl, 0.0), MODEL_AX)
    nll = lse - tl
    return nll, (lg, lse, t_loc, in_range)


def _vp_xent_bwd(res, g):
    """d nll / d lg = softmax(lg) - onehot(target) — purely local."""
    lg, lse, t_loc, in_range = res
    v_loc = lg.shape[-1]
    softmax = jnp.exp(lg - lse[..., None])
    iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, len(lg.shape) - 1)
    onehot = (iota == t_loc[..., None]) & in_range[..., None]
    dlg = (softmax - onehot.astype(jnp.float32)) * g[..., None]
    return dlg, None


_vp_xent_local.defvjp(_vp_xent_fwd, _vp_xent_bwd)


def _sharded_xent(cfg: ModelConfig, mesh, logits: Array, targets: Array) -> Array:
    """Megatron-style vocab-parallel cross entropy: each model shard extracts
    its local target logits (masked gather) and computes a partial logsumexp;
    both reduce with one tiny psum. Never materializes a replicated (B,S,V)
    or a one-hot tensor. custom_vjp because pmax has no autodiff rule."""
    from .common import batch_axes

    dp = batch_axes(mesh)
    dspec = dp if len(dp) > 1 else dp[0]
    lspec = P(dspec, None, MODEL_AX)
    tspec = P(dspec, None)

    def local(lg, tg):
        return _vp_xent_local(lg.astype(jnp.float32), tg)

    from jax import shard_map as _shard_map

    nll = _shard_map(
        local, mesh=mesh, in_specs=(lspec, tspec), out_specs=tspec,
        check_vma=False,
    )(logits, targets)
    return nll.mean()


def lm_loss(cfg: ModelConfig, params, inputs, targets, mesh=None,
            aux_weight: float = 0.01):
    logits, aux = forward(cfg, params, inputs, mesh)
    if mesh is not None and "model" in mesh.axis_names:
        nll_mean = _sharded_xent(cfg, mesh, logits, targets)
        return nll_mean + aux_weight * aux
    # single-device fallback (smoke tests)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)  # (B,S)
    onehot = jax.nn.one_hot(targets, cfg.padded_vocab, dtype=jnp.float32)
    target_logit = jnp.sum(logits * onehot, axis=-1)  # (B,S)
    nll = lse - target_logit
    return nll.mean() + aux_weight * aux


# ---------------------------------------------------------------------------
# serving: KV / SSM state caches + single-token decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, s_max: int) -> Dict[str, Any]:
    """Decode-state pytree. Attention: (L,B,S_max,kvH,hd) k/v. SSM: conv +
    state per layer. Hybrid: SSM states + per-application shared-block KV."""
    cd = cfg.compute_dtype
    cache: Dict[str, Any] = {}
    if cfg.family == "attn":
        shape = (cfg.n_layers, batch, s_max, cfg.kv_heads, cfg.hdim)
        cache["k"] = jnp.zeros(shape, cd)
        cache["v"] = jnp.zeros(shape, cd)
    elif cfg.family == "ssm":
        conv, st = ssm_mod.init_mamba2_state(cfg.ssm, cfg.d_model, batch, cd)
        cache["conv"] = jnp.broadcast_to(conv, (cfg.n_layers,) + conv.shape)
        cache["ssm"] = jnp.broadcast_to(st, (cfg.n_layers,) + st.shape)
    else:  # hybrid
        conv, st = ssm_mod.init_mamba2_state(cfg.ssm, cfg.d_model, batch, cd)
        cache["conv"] = jnp.broadcast_to(conv, (cfg.n_layers,) + conv.shape)
        cache["ssm"] = jnp.broadcast_to(st, (cfg.n_layers,) + st.shape)
        napps = cfg.n_layers // cfg.hybrid_every
        shape = (napps, batch, s_max, cfg.kv_heads, cfg.hdim)
        cache["k"] = jnp.zeros(shape, cd)
        cache["v"] = jnp.zeros(shape, cd)
    return cache


def cache_specs(cfg: ModelConfig, mesh, batch: Optional[int] = None,
                s_max: Optional[int] = None) -> Dict[str, Any]:
    """Sharding for the cache: batch over data axes (when divisible), heads
    over "model"; when kv heads can't shard (MQA / kv < tp), the SEQUENCE dim
    shards over "model" instead (flash-decoding style: attention reductions
    over the sharded context psum under GSPMD) — without this, gemma2-class
    decode replicates a 273 GB/device cache (EXPERIMENTS.md §Perf). batch=1
    (long_500k) keeps batch unsharded — the state is small by construction."""
    from .common import batch_axes

    dp = batch_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if (batch is None or batch % dp_size == 0) else None
    tp = mesh.shape[MODEL_AX]
    head_ax = MODEL_AX if cfg.kv_heads % tp == 0 and cfg.kv_heads >= tp else None
    seq_ax = None
    if head_ax is None and tp > 1 and (s_max is None or s_max % tp == 0):
        seq_ax = MODEL_AX
    specs: Dict[str, Any] = {}
    if cfg.family in ("attn", "hybrid"):
        specs["k"] = P(None, bspec, seq_ax, head_ax, None)
        specs["v"] = P(None, bspec, seq_ax, head_ax, None)
    if cfg.family in ("ssm", "hybrid"):
        nh = cfg.ssm.n_heads(cfg.d_model)
        ssm_head_ax = MODEL_AX if nh % tp == 0 else None
        specs["conv"] = P(None, bspec, None, None)
        specs["ssm"] = P(None, bspec, ssm_head_ax, None, None)
    return specs


def decode_step(
    cfg: ModelConfig,
    params: Dict[str, Any],
    cache: Dict[str, Any],
    inputs: Array,  # (B,1) tokens or (B,1,D) embeds
    cache_index: Array,  # scalar i32 — number of tokens already in cache
    mesh=None,
) -> Tuple[Array, Dict[str, Any]]:
    """One new token for every sequence in the batch. Returns (logits (B,V),
    updated cache). The ``decode_*``/``long_*`` dry-run shapes lower this."""
    cd = cfg.compute_dtype
    if cfg.input_mode == "tokens":
        h = params["embed"].astype(cd)[inputs]
    else:
        h = inputs.astype(cd)
    if cfg.embed_scale:
        h = h * jnp.sqrt(jnp.float32(cfg.d_model)).astype(cd)
    B = h.shape[0]
    positions = jnp.broadcast_to(cache_index, (B, 1))

    if cfg.family == "attn":
        windows = _layer_windows(cfg, 1)

        def body(h, xs):
            lp, window, ck, cv = xs
            lp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                              and p.ndim > 1 else p, lp)
            h2, _, new_kv = _attn_layer_fwd(
                cfg, mesh, lp, h, positions, window,
                kv_cache=(ck, cv), cache_index=cache_index, moe_mode="dense_ep",
            )
            return h2, new_kv

        h, new_kv = jax.lax.scan(
            body, h, (params["layers"], windows, cache["k"], cache["v"])
        )
        new_cache = {"k": new_kv[0], "v": new_kv[1]}
    elif cfg.family == "ssm":
        def body(h, xs):
            lp, conv, st = xs
            lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                and p.ndim > 1 else p, lp)
            out, (nconv, nst) = ssm_mod.mamba2_decode_step(
                lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm, (conv, st)
            )
            return h + out, (nconv, nst)

        h, (nconv, nst) = jax.lax.scan(
            body, h, (params["layers"], cache["conv"], cache["ssm"])
        )
        new_cache = {"conv": nconv, "ssm": nst}
    else:  # hybrid
        k = cfg.hybrid_every
        ngroups = cfg.n_layers // k
        stacked = jax.tree.map(
            lambda p: p.reshape((ngroups, k) + p.shape[1:]), params["layers"]
        )
        conv_g = cache["conv"].reshape((ngroups, k) + cache["conv"].shape[1:])
        ssm_g = cache["ssm"].reshape((ngroups, k) + cache["ssm"].shape[1:])
        sp = params["shared_block"]
        sp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                          and p.ndim > 1 else p, sp)

        def group_body(h, xs):
            lp_group, conv_l, ssm_l, ck, cv = xs

            def inner(h, ys):
                lp, conv, st = ys
                lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                    and p.ndim > 1 else p, lp)
                out, (nconv, nst) = ssm_mod.mamba2_decode_step(
                    lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm, (conv, st)
                )
                return h + out, (nconv, nst)

            h, (nconv, nst) = jax.lax.scan(inner, h, (lp_group, conv_l, ssm_l))
            att, (nk, nv) = attn_mod.attend(
                sp["attn"], rms_norm(h, sp["ln1"]), positions,
                rope_theta=cfg.rope_theta, query_scale=cfg.query_scale,
                kv_cache=(ck, cv), cache_index=cache_index,
            )
            h = h + att
            h = h + mlp_mod.mlp(sp["mlp"], rms_norm(h, sp["ln2"]), cfg.act)
            return h, (nconv, nst, nk, nv)

        h, (nconv, nst, nk, nv) = jax.lax.scan(
            group_body, h, (stacked, conv_g, ssm_g, cache["k"], cache["v"])
        )
        new_cache = {
            "conv": nconv.reshape(cache["conv"].shape),
            "ssm": nst.reshape(cache["ssm"].shape),
            "k": nk,
            "v": nv,
        }

    h = rms_norm(h, params["final_norm"])
    w_out = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cd)
    logits = jnp.einsum("bsd,dv->bsv", h, w_out)[:, 0]
    logits = soft_cap(logits.astype(jnp.float32), cfg.final_softcap)
    return _mask_pad_vocab(cfg, logits)[:, : cfg.vocab], new_cache


def prefill(
    cfg: ModelConfig,
    params: Dict[str, Any],
    inputs: Array,  # (B,S) or (B,S,D)
    s_max: int,
    mesh=None,
) -> Tuple[Array, Dict[str, Any]]:
    """Forward over the prompt, building the decode cache. Returns
    (last-position logits (B,V), cache filled to S)."""
    cd = cfg.compute_dtype
    B = inputs.shape[0]
    S = inputs.shape[1]
    cache = init_cache(cfg, B, s_max)
    if cfg.input_mode == "tokens":
        h = params["embed"].astype(cd)[inputs]
    else:
        h = inputs.astype(cd)
    if cfg.embed_scale:
        h = h * jnp.sqrt(jnp.float32(cfg.d_model)).astype(cd)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    zero = jnp.int32(0)

    if cfg.family == "attn":
        windows = _layer_windows(cfg, S)

        def body(h, xs):
            lp, window, ck, cv = xs
            lp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                              and p.ndim > 1 else p, lp)
            h2, _, new_kv = _attn_layer_fwd(
                cfg, mesh, lp, h, positions, window,
                kv_cache=(ck, cv), cache_index=zero,
            )
            return h2, new_kv

        if cfg.remat:
            body = jax.checkpoint(body)
        h, new_kv = jax.lax.scan(
            body, h, (params["layers"], windows, cache["k"], cache["v"])
        )
        cache = {"k": new_kv[0], "v": new_kv[1]}
    elif cfg.family == "ssm":
        def body(h, xs):
            lp, conv, st = xs
            lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                and p.ndim > 1 else p, lp)
            out, (nconv, nst) = ssm_mod.mamba2_block(
                lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm,
                state=(conv, st), return_state=True,
            )
            return h + out, (nconv, nst)

        if cfg.remat:
            body = jax.checkpoint(body)
        h, (nconv, nst) = jax.lax.scan(
            body, h, (params["layers"], cache["conv"], cache["ssm"])
        )
        cache = {"conv": nconv, "ssm": nst.astype(jnp.float32)}
    else:  # hybrid
        k = cfg.hybrid_every
        ngroups = cfg.n_layers // k
        stacked = jax.tree.map(
            lambda p: p.reshape((ngroups, k) + p.shape[1:]), params["layers"]
        )
        conv_g = cache["conv"].reshape((ngroups, k) + cache["conv"].shape[1:])
        ssm_g = cache["ssm"].reshape((ngroups, k) + cache["ssm"].shape[1:])
        sp = params["shared_block"]
        sp = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                          and p.ndim > 1 else p, sp)

        def group_body(h, xs):
            lp_group, conv_l, ssm_l, ck, cv = xs

            def inner(h, ys):
                lp, conv, st = ys
                lp_c = jax.tree.map(lambda p: p.astype(cd) if p.dtype == jnp.float32
                                    and p.ndim > 1 else p, lp)
                out, (nconv, nst) = ssm_mod.mamba2_block(
                    lp_c["mamba"], rms_norm(h, lp["ln"]), cfg.ssm,
                    state=(conv, st), return_state=True,
                )
                return h + out, (nconv, nst)

            h, (nconv, nst) = jax.lax.scan(inner, h, (lp_group, conv_l, ssm_l))
            att, (nk, nv) = attn_mod.attend(
                sp["attn"], rms_norm(h, sp["ln1"]), positions,
                rope_theta=cfg.rope_theta, query_scale=cfg.query_scale,
                kv_cache=(ck, cv), cache_index=zero,
            )
            h = h + att
            h = h + mlp_mod.mlp(sp["mlp"], rms_norm(h, sp["ln2"]), cfg.act)
            return h, (nconv, nst, nk, nv)

        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        h, (nconv, nst, nk, nv) = jax.lax.scan(
            group_body, h, (stacked, conv_g, ssm_g, cache["k"], cache["v"])
        )
        cache = {
            "conv": nconv.reshape(cache["conv"].shape),
            "ssm": nst.reshape(cache["ssm"].shape).astype(jnp.float32),
            "k": nk,
            "v": nv,
        }

    h = rms_norm(h, params["final_norm"])
    w_out = (
        params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    ).astype(cd)
    logits = jnp.einsum("bd,dv->bv", h[:, -1], w_out)
    logits = soft_cap(logits.astype(jnp.float32), cfg.final_softcap)
    return _mask_pad_vocab(cfg, logits)[:, : cfg.vocab], cache
