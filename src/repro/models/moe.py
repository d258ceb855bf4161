"""Top-k MoE with sort-based capacity dispatch + expert parallelism.

Production path (mesh active): ``shard_map`` over (dp..., model) — tokens
stay on their dp shard, experts live on the ``model`` axis, dispatch crosses
``model`` with a single pair of all_to_alls (DESIGN.md §6). Expert weights
arrive fsdp-sharded on d_model and are all-gathered per layer (FSDP
semantics, honest collective bytes).

Fallback path (no mesh): identical math on one device.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import sharding
from .common import ModelConfig, dense_init, activate


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_moe(cfg: ModelConfig, key) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (e, d, f), cfg.pdtype),
        "w_up": dense_init(ks[2], (e, d, f), cfg.pdtype),
        "w_down": dense_init(ks[3], (e, f, d), cfg.pdtype),
    }


def _route(xt, router, top_k: int):
    """Token->expert assignment. Returns (weights, expert ids) (T, k)."""
    scores = (xt.astype(jnp.float32) @ router.astype(jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)  # qwen3 renorm
    return topv, topi


def _dispatch(xt, eids, n_experts: int, capacity: int):
    """Sort-based capacity dispatch (dropping): returns buffer (E, C, D),
    plus (slot, keep) to invert the dispatch."""
    t_tok, k = eids.shape
    tk = t_tok * k
    flat_e = eids.reshape(tk)
    flat_t = jnp.repeat(jnp.arange(t_tok, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e)
    se, st = flat_e[order], flat_t[order]
    first = jnp.searchsorted(se, jnp.arange(n_experts), side="left")
    pos_in_e = jnp.arange(tk, dtype=jnp.int32) - first[se].astype(jnp.int32)
    keep = pos_in_e < capacity
    slot = jnp.where(keep, se * capacity + pos_in_e, n_experts * capacity)
    buf = jnp.zeros((n_experts * capacity, xt.shape[-1]), xt.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], xt[st], 0), mode="drop")
    return buf.reshape(n_experts, capacity, -1), (slot, keep, st, order)


def _combine(out_buf, dispatch_info, weights, t_tok: int):
    slot, keep, st, order = dispatch_info
    e, c, d = out_buf.shape
    rows = out_buf.reshape(e * c, d)
    vals = jnp.where(keep[:, None],
                     jnp.take(rows, jnp.minimum(slot, e * c - 1), axis=0), 0)
    w_sorted = weights.reshape(-1)[order]
    out = jnp.zeros((t_tok, d), out_buf.dtype)
    return out.at[st].add(vals * w_sorted[:, None].astype(out_buf.dtype))


def _expert_ffn(buf, w_gate, w_up, w_down, cfg: ModelConfig):
    gate = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    h = activate(gate, up, cfg.act if cfg.act != "gelu" else "swiglu")
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(buf.dtype))


def _capacity(t_tok: int, k: int, e: int, cf: float) -> int:
    return max(1, int(math.ceil(t_tok * k / e * cf)))


def apply_moe(params, x, cfg: ModelConfig):
    """x: (B, S, D) -> (B, S, D)."""
    ctx = sharding.current()
    b, s, d = x.shape
    if ctx is None or ctx.tp_axis is None:
        return _apply_local(params, x, cfg)

    mesh = ctx.mesh
    tp = ctx.tp_axis
    m = mesh.shape[tp]
    dp = ctx.dp_axes
    e = cfg.n_experts
    assert e % m == 0, (e, m)
    e_loc = e // m
    fsdp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    # Sequence-shard dispatch over the model axis when S divides: every tp
    # rank routes a distinct S/m token slice (no duplicated expert flops).
    # Decode (S=1) falls back to tp-replicated dispatch: tiny and correct.
    seq_shard = s % m == 0 and s >= m
    x_spec = jax.sharding.PartitionSpec(fsdp_spec, tp if seq_shard else None,
                                        None)

    def local_fn(x_loc, router, w_gate, w_up, w_down):
        # x_loc (B_loc, S, D); w_* (E_loc, D/dp, F) -> FSDP all-gather
        if ctx.fsdp and dp:
            w_gate = jax.lax.all_gather(w_gate, dp, axis=1, tiled=True)
            w_up = jax.lax.all_gather(w_up, dp, axis=1, tiled=True)
            w_down = jax.lax.all_gather(w_down, dp, axis=2, tiled=True)
        bl, sl, dl = x_loc.shape
        t_tok = bl * sl
        xt = x_loc.reshape(t_tok, dl)
        weights, eids = _route(xt, router, cfg.top_k)
        cap = _capacity(t_tok, cfg.top_k, e, cfg.capacity_factor)
        buf, info = _dispatch(xt, eids, e, cap)             # (E, C, D)
        # ---- all_to_all over model axis: experts to their owners. ----
        buf = buf.reshape(m, e_loc, cap, dl)
        buf = jax.lax.all_to_all(buf, tp, split_axis=0, concat_axis=0,
                                 tiled=False)               # (m, e_loc, C, D)
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, m * cap, dl)
        out_buf = _expert_ffn(buf, w_gate, w_up, w_down, cfg)
        out_buf = out_buf.reshape(e_loc, m, cap, dl).transpose(1, 0, 2, 3)
        out_buf = jax.lax.all_to_all(out_buf, tp, split_axis=0,
                                     concat_axis=0, tiled=False)
        out_buf = out_buf.reshape(e, cap, dl)
        out = _combine(out_buf, info, weights, t_tok)
        return out.reshape(bl, sl, dl)

    out = shard_map(
        local_fn,
        mesh,
        in_specs=(
            x_spec,
            jax.sharding.PartitionSpec(None, None),
            jax.sharding.PartitionSpec(tp, fsdp_spec if ctx.fsdp else None,
                                       None),
            jax.sharding.PartitionSpec(tp, fsdp_spec if ctx.fsdp else None,
                                       None),
            jax.sharding.PartitionSpec(tp, None,
                                       fsdp_spec if ctx.fsdp else None),
        ),
        out_specs=x_spec,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out


def _apply_local(params, x, cfg: ModelConfig):
    b, s, d = x.shape
    t_tok = b * s
    xt = x.reshape(t_tok, d)
    weights, eids = _route(xt, params["router"], cfg.top_k)
    cap = _capacity(t_tok, cfg.top_k, cfg.n_experts, cfg.capacity_factor)
    buf, info = _dispatch(xt, eids, cfg.n_experts, cap)
    out_buf = _expert_ffn(buf, params["w_gate"], params["w_up"],
                          params["w_down"], cfg)
    out = _combine(out_buf, info, weights, t_tok)
    return out.reshape(b, s, d)
