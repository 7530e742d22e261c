"""Mixture-of-Experts layer with SpGEMM-formulated dispatch — the paper's
technique as a first-class feature of the LM stack (DESIGN.md §4).

The token→expert dispatch is literally a sparse matrix S (slots × tokens):
dispatch = S @ X and combine = Sᵀ_weighted @ Y are SpMM calls into
``repro.core.local_spgemm.spmm`` (the same gather/segment-accumulate the
distributed SpGEMM uses, with the Pallas kernel on TPU). The capacity-bucket
structure mirrors the paper's column batching: each expert's slot block is a
narrow output column block sized by a symbolic count (the router histogram).

Two expert-parallel modes:
  * "a2a"      — training/prefill: tokens are split over the "model" axis
                 (sequence dimension), routed locally, exchanged with one
                 all_to_all, expert-processed (experts sharded over "model"),
                 and exchanged back. The EP analogue of AllToAll-Fiber.
  * "dense_ep" — decode (S==1): every device routes all its dp-local tokens,
                 processes only its expert shard and psum-combines over
                 "model" — trading compute replication for latency, the right
                 call at decode batch sizes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from ..core.local_spgemm import spmm
from ..core.sparse import SparseCOO
from .common import MODEL_AX, dense_init

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int
    n_shared: int = 0
    capacity_factor: float = 1.25
    normalize_topk: bool = True
    dispatch_mode: str = "spgemm"  # "spgemm" | "scatter" (equivalent; tested)


def init_moe(key, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> Dict[str, Array]:
    k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 7)
    E, F = cfg.n_experts, cfg.d_expert
    params = {
        "router": dense_init(k1, (d_model, E), dtype=jnp.float32),  # fp32 router
        "w_in": dense_init(k2, (E, d_model, F), in_axis=1, dtype=dtype),
        "w_gate": dense_init(k3, (E, d_model, F), in_axis=1, dtype=dtype),
        "w_out": dense_init(k4, (E, F, d_model), in_axis=1, dtype=dtype),
    }
    if cfg.n_shared:
        Fs = cfg.n_shared * F
        params["shared"] = {
            "w_in": dense_init(k5, (d_model, Fs), dtype=dtype),
            "w_gate": dense_init(k6, (d_model, Fs), dtype=dtype),
            "w_out": dense_init(k7, (Fs, d_model), dtype=dtype),
        }
    return params


def moe_specs(cfg: MoEConfig, tp: int = 1) -> Dict:
    e_ax = MODEL_AX if tp > 1 and cfg.n_experts % tp == 0 else None
    specs = {
        "router": P(None, None),
        "w_in": P(e_ax, None, None),
        "w_gate": P(e_ax, None, None),
        "w_out": P(e_ax, None, None),
    }
    if cfg.n_shared:
        fs_ax = MODEL_AX if tp > 1 and (cfg.n_shared * cfg.d_expert) % tp == 0 else None
        specs["shared"] = {
            "w_in": P(None, fs_ax),
            "w_gate": P(None, fs_ax),
            "w_out": P(fs_ax, None),
        }
    return specs


# ---------------------------------------------------------------------------
# local routing + dispatch (runs per device inside shard_map)
# ---------------------------------------------------------------------------
def _route(x_flat: Array, router_w: Array, cfg: MoEConfig):
    logits = x_flat.astype(jnp.float32) @ router_w  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, cfg.top_k)  # (T, k)
    if cfg.normalize_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style): E * Σ_e f_e * P_e
    E = router_w.shape[1]
    f = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=1), axis=0
    )  # fraction routed per expert (×k)
    aux = E * jnp.sum(f / cfg.top_k * jnp.mean(probs, axis=0))
    return top_p.astype(x_flat.dtype), top_e, aux


def _capacity(T: int, cfg: MoEConfig) -> int:
    c = int(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts) + 1
    return ((c + 7) // 8) * 8


def _dispatch_indices(top_e: Array, cfg: MoEConfig, cap: int):
    """slot position of each (token, k) assignment within its expert bucket."""
    T, k = top_e.shape
    eid = top_e.reshape(-1)  # (T*k,)
    onehot = jax.nn.one_hot(eid, cfg.n_experts, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot  # exclusive count per expert
    slot = jnp.take_along_axis(rank, eid[:, None], axis=1)[:, 0]  # (T*k,)
    keep = slot < cap
    return eid, slot, keep


def _dispatch(x_flat: Array, eid, slot, keep, cfg: MoEConfig, cap: int) -> Array:
    """Build (E, cap, D) expert input buffers. SpGEMM formulation: the
    dispatch matrix S is (E*cap × T) sparse with one 1 per kept assignment;
    buffers = S @ X via the core SpMM."""
    T = x_flat.shape[0]
    Tk = eid.shape[0]
    k = Tk // T
    token_of = jnp.repeat(jnp.arange(T), k)  # (T*k,) token index per assignment
    E, D = cfg.n_experts, x_flat.shape[1]
    if cfg.dispatch_mode == "spgemm":
        dest = eid * cap + slot  # row index in the (E*cap × T) dispatch matrix
        s = SparseCOO(
            rows=jnp.where(keep, dest, E * cap).astype(jnp.int32),
            cols=jnp.where(keep, token_of, T).astype(jnp.int32),
            vals=jnp.where(keep, 1.0, 0.0).astype(x_flat.dtype),
            nnz=jnp.int32(Tk),
            shape=(E * cap, T),
        )
        buf = spmm(s, x_flat)  # (E*cap, D)
        return buf.reshape(E, cap, D).astype(x_flat.dtype)
    # direct scatter (reference)
    buf = jnp.zeros((E, cap, D), x_flat.dtype)
    e_idx = jnp.where(keep, eid, E)
    s_idx = jnp.where(keep, slot, cap)
    return buf.at[e_idx, s_idx].add(x_flat[token_of], mode="drop")


def _combine(y_buf: Array, top_p, eid, slot, keep, T: int, cfg: MoEConfig,
             cap: int) -> Array:
    """Weighted gather back: X_out = Sᵀ_weighted @ Y (SpMM again)."""
    E, _, D = y_buf.shape
    Tk = eid.shape[0]
    k = Tk // T
    token_of = jnp.repeat(jnp.arange(T), k)
    w = top_p.reshape(-1)  # (T*k,)
    if cfg.dispatch_mode == "spgemm":
        s = SparseCOO(
            rows=jnp.where(keep, token_of, T).astype(jnp.int32),
            cols=jnp.where(keep, eid * cap + slot, E * cap).astype(jnp.int32),
            vals=jnp.where(keep, w, 0.0).astype(y_buf.dtype),
            nnz=jnp.int32(Tk),
            shape=(T, E * cap),
        )
        return spmm(s, y_buf.reshape(E * cap, D))
    src = y_buf[jnp.where(keep, eid, 0), jnp.where(keep, slot, 0)]  # (T*k, D)
    src = jnp.where(keep[:, None], src * w[:, None], 0)
    return jax.ops.segment_sum(src, token_of, num_segments=T)


def _expert_ffn(buf: Array, w_in: Array, w_gate: Array, w_out: Array) -> Array:
    """buf: (E_loc, C', D); expert weights (E_loc, D, F) / (E_loc, F, D)."""
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    h = jax.nn.silu(g) * h
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _shared_ffn(params: Dict[str, Array], x: Array, sharded: bool = False) -> Array:
    """Shared-expert FFN. When ``sharded``, weights arrive as model-axis
    shards of the F dimension (w_in (D, F/tp), w_out (F/tp, D)) and the
    output is psum'd — avoids all-gathering the shared weights every layer."""
    h = x @ params["w_in"]
    g = x @ params["w_gate"]
    out = (jax.nn.silu(g) * h) @ params["w_out"]
    if sharded:
        out = lax.psum(out, MODEL_AX)
    return out


# ---------------------------------------------------------------------------
# expert-parallel layer
# ---------------------------------------------------------------------------
def moe_layer(
    params: Dict[str, Array],
    x: Array,  # (B, S, D) — global
    cfg: MoEConfig,
    mesh,
    mode: str = "a2a",
) -> Tuple[Array, Array]:
    """Returns (output (B,S,D), aux loss scalar)."""
    from .common import batch_axes

    dp = batch_axes(mesh)
    tp = mesh.shape[MODEL_AX]
    B, S, D = x.shape

    if mode == "a2a" and S % tp == 0:
        x_spec = P(dp, MODEL_AX, None)

        def local(x_loc, router_w, w_in, w_gate, w_out, shared):
            b_l, s_l, _ = x_loc.shape
            T = b_l * s_l
            xf = x_loc.reshape(T, D)
            top_p, top_e, aux = _route(xf, router_w, cfg)
            cap = _capacity(T, cfg)
            eid, slot, keep = _dispatch_indices(top_e, cfg, cap)
            buf = _dispatch(xf, eid, slot, keep, cfg, cap)  # (E, cap, D)
            E_loc = cfg.n_experts // tp
            buf = buf.reshape(tp, E_loc, cap, D)
            buf = lax.all_to_all(buf, MODEL_AX, split_axis=0, concat_axis=0)
            buf = buf.reshape(tp, E_loc, cap, D).transpose(1, 0, 2, 3).reshape(
                E_loc, tp * cap, D
            )
            y = _expert_ffn(buf, w_in, w_gate, w_out)  # (E_loc, tp*cap, D)
            y = y.reshape(E_loc, tp, cap, D).transpose(1, 0, 2, 3)
            y = lax.all_to_all(y, MODEL_AX, split_axis=0, concat_axis=0)
            y = y.reshape(cfg.n_experts, cap, D)
            out = _combine(y, top_p, eid, slot, keep, T, cfg, cap)
            if shared is not None:
                out = out + _shared_ffn(shared, xf, sharded=shared_is_sharded)
            aux = lax.pmean(aux, MODEL_AX)
            for ax in dp:
                aux = lax.pmean(aux, ax)
            return out.reshape(b_l, s_l, D), aux

        shared = params.get("shared")
        fs = cfg.n_shared * cfg.d_expert
        shared_is_sharded = shared is not None and fs % tp == 0
        fs_ax = MODEL_AX if shared_is_sharded else None
        shared_spec = (
            {"w_in": P(None, fs_ax), "w_gate": P(None, fs_ax),
             "w_out": P(fs_ax, None)}
            if shared is not None
            else None
        )
        out, aux = _shard_map(
            local,
            mesh=mesh,
            in_specs=(
                x_spec,
                P(None, None),
                P(MODEL_AX, None, None),
                P(MODEL_AX, None, None),
                P(MODEL_AX, None, None),
                shared_spec,
            ),
            out_specs=(x_spec, P()),
            check_vma=False,
        )(x, params["router"], params["w_in"], params["w_gate"], params["w_out"],
          shared)
        return out, aux

    # dense_ep (decode / S not divisible): route everywhere, compute local
    # expert shard over all dp-local tokens, psum over "model"
    x_spec = P(dp, None, None)

    def local_dense(x_loc, router_w, w_in, w_gate, w_out, shared):
        b_l, s_l, _ = x_loc.shape
        T = b_l * s_l
        xf = x_loc.reshape(T, D)
        top_p, top_e, aux = _route(xf, router_w, cfg)
        cap = _capacity(T, cfg)
        eid, slot, keep = _dispatch_indices(top_e, cfg, cap)
        buf = _dispatch(xf, eid, slot, keep, cfg, cap)  # (E, cap, D)
        E_loc = cfg.n_experts // tp
        r = lax.axis_index(MODEL_AX)
        buf_loc = lax.dynamic_slice_in_dim(buf, r * E_loc, E_loc, axis=0)
        y_loc = _expert_ffn(buf_loc, w_in, w_gate, w_out)
        y = jnp.zeros((cfg.n_experts, cap, D), y_loc.dtype)
        y = lax.dynamic_update_slice_in_dim(y, y_loc, r * E_loc, axis=0)
        y = lax.psum(y, MODEL_AX)
        out = _combine(y, top_p, eid, slot, keep, T, cfg, cap)
        if shared is not None:
            out = out + _shared_ffn(shared, xf, sharded=shared_is_sharded)
        aux = lax.pmean(aux, MODEL_AX)
        for ax in dp:
            aux = lax.pmean(aux, ax)
        return out.reshape(b_l, s_l, D), aux

    shared = params.get("shared")
    fs = cfg.n_shared * cfg.d_expert
    shared_is_sharded = shared is not None and fs % tp == 0
    fs_ax = MODEL_AX if shared_is_sharded else None
    shared_spec = (
        {"w_in": P(None, fs_ax), "w_gate": P(None, fs_ax), "w_out": P(fs_ax, None)}
        if shared is not None
        else None
    )
    out, aux = _shard_map(
        local_dense,
        mesh=mesh,
        in_specs=(
            x_spec,
            P(None, None),
            P(MODEL_AX, None, None),
            P(MODEL_AX, None, None),
            P(MODEL_AX, None, None),
            shared_spec,
        ),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_in"], params["w_gate"], params["w_out"], shared)
    return out, aux
