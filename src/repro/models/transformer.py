"""Model assembly: block zoo + stage scans + train/decode entry points.

Layers are grouped into *stages* (cycles of a block pattern, see
``ModelConfig.stages``); each stage's params are stacked on a leading axis
and driven by one ``lax.scan`` (HLO size O(1) in depth). Block kinds:

  attn   pre-norm GQA attention + MLP (parallel_block: attn || mlp)
  local  sliding-window attention + MLP (griffin attention layers)
  moe    GQA attention + expert-parallel MoE FFN
  rec    RG-LRU recurrent block + MLP (griffin)
  rwkv   RWKV-6 time-mix + channel-mix
  enc    bidirectional attention + MLP (whisper encoder)
  dec    causal self-attn + cross-attn + MLP (whisper decoder)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..sharding import shard
from .common import ModelConfig, apply_norm, dense_init, init_norm
from . import layers, moe, rglru, rwkv


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------
def init_block(cfg: ModelConfig, kind: str, key) -> dict:
    ks = jax.random.split(key, 4)
    if kind == "rwkv":
        p = rwkv.init_rwkv_block(cfg, ks[0])
        p["ln1"] = init_norm(cfg)
        p["ln2"] = init_norm(cfg)
        return p
    if kind == "rec":
        return {"ln1": init_norm(cfg),
                "rec": rglru.init_rglru(cfg, ks[0]),
                "ln2": init_norm(cfg),
                "mlp": layers.init_mlp(cfg, ks[1])}
    if kind == "dec":
        return {"ln1": init_norm(cfg),
                "attn": layers.init_attention(cfg, ks[0]),
                "lnx": init_norm(cfg),
                "xattn": layers.init_attention(cfg, ks[1], cross=True),
                "ln2": init_norm(cfg),
                "mlp": layers.init_mlp(cfg, ks[2])}
    p = {"attn": layers.init_attention(cfg, ks[0])}
    if cfg.parallel_block:
        p["ln"] = init_norm(cfg)
    else:
        p["ln1"] = init_norm(cfg)
        p["ln2"] = init_norm(cfg)
    if kind == "moe":
        p["moe"] = moe.init_moe(cfg, ks[1])
    else:
        p["mlp"] = layers.init_mlp(cfg, ks[1])
    return p


def _attn_mask_kind(cfg: ModelConfig, kind: str) -> tuple[str, int]:
    if kind == "enc":
        return "bidir", 0
    if kind == "local":
        return "window", 0
    if cfg.kind == "vlm":
        return "prefix", cfg.n_img_tokens
    return "causal", 0


def apply_block(params, x, cfg: ModelConfig, kind: str,
                enc_out: Optional[jax.Array] = None):
    use_rope = cfg.rope_theta > 0
    if kind == "rwkv":
        x = x + rwkv.time_mix(params, apply_norm(params["ln1"], x, cfg), cfg)
        x = x + rwkv.channel_mix(params, apply_norm(params["ln2"], x, cfg),
                                 cfg)
        return x
    if kind == "rec":
        x = x + rglru.apply_rglru(params["rec"],
                                  apply_norm(params["ln1"], x, cfg), cfg)
        x = x + layers.apply_mlp(params["mlp"],
                                 apply_norm(params["ln2"], x, cfg), cfg)
        return x
    if kind == "dec":
        h = apply_norm(params["ln1"], x, cfg)
        x = x + layers.attention_full(params["attn"], h, cfg, mask="causal",
                                      use_rope=use_rope)
        h = apply_norm(params["lnx"], x, cfg)
        x = x + layers.attention_full(params["xattn"], h, cfg, mask="bidir",
                                      xkv=enc_out, use_rope=False)
        x = x + layers.apply_mlp(params["mlp"],
                                 apply_norm(params["ln2"], x, cfg), cfg)
        return x

    mask, prefix_len = _attn_mask_kind(cfg, kind)
    if cfg.parallel_block:  # command-r: shared-norm parallel attn + FFN
        h = apply_norm(params["ln"], x, cfg)
        return x + layers.attention_full(
            params["attn"], h, cfg, mask=mask, prefix_len=prefix_len,
            use_rope=use_rope) + layers.apply_mlp(params["mlp"], h, cfg)
    h = apply_norm(params["ln1"], x, cfg)
    x = x + layers.attention_full(params["attn"], h, cfg, mask=mask,
                                  prefix_len=prefix_len, use_rope=use_rope)
    h = apply_norm(params["ln2"], x, cfg)
    ffn = (moe.apply_moe(params["moe"], h, cfg) if kind == "moe"
           else layers.apply_mlp(params["mlp"], h, cfg))
    return x + ffn


def apply_block_decode(params, x, cache, cfg: ModelConfig, kind: str):
    use_rope = cfg.rope_theta > 0
    if kind == "rwkv":
        h = apply_norm(params["ln1"], x, cfg)
        o, tm_cache = rwkv.time_mix_decode(params, h, cache, cfg)
        x = x + o
        h2 = apply_norm(params["ln2"], x, cfg)
        x = x + rwkv.channel_mix(params, h2, cfg, last=cache["last_c"])
        return x, {**tm_cache, "last_c": h2}
    if kind == "rec":
        h = apply_norm(params["ln1"], x, cfg)
        o, rec_cache = rglru.apply_rglru_decode(params["rec"], h, cache, cfg)
        x = x + o
        x = x + layers.apply_mlp(params["mlp"],
                                 apply_norm(params["ln2"], x, cfg), cfg)
        return x, rec_cache
    if kind == "dec":
        h = apply_norm(params["ln1"], x, cfg)
        o, sc = layers.attention_decode(params["attn"], h, cache["self"],
                                        cfg, use_rope=use_rope)
        x = x + o
        h = apply_norm(params["lnx"], x, cfg)
        o, _ = layers.attention_decode(params["xattn"], h, cache["cross"],
                                       cfg, use_rope=False, cross=True)
        x = x + o
        x = x + layers.apply_mlp(params["mlp"],
                                 apply_norm(params["ln2"], x, cfg), cfg)
        return x, {**cache, "self": sc}

    mask = "window" if kind == "local" else "causal"
    if cfg.parallel_block:
        h = apply_norm(params["ln"], x, cfg)
        o, new_cache = layers.attention_decode(params["attn"], h, cache, cfg,
                                               mask=mask, use_rope=use_rope)
        return x + o + layers.apply_mlp(params["mlp"], h, cfg), new_cache
    h = apply_norm(params["ln1"], x, cfg)
    o, new_cache = layers.attention_decode(params["attn"], h, cache, cfg,
                                           mask=mask, use_rope=use_rope)
    x = x + o
    h = apply_norm(params["ln2"], x, cfg)
    ffn = (moe.apply_moe(params["moe"], h, cfg) if kind == "moe"
           else layers.apply_mlp(params["mlp"], h, cfg))
    return x + ffn, new_cache


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                     enc_len: int = 0) -> dict:
    if kind == "rwkv":
        return rwkv.make_rwkv_cache(cfg, batch)
    if kind == "rec":
        return rglru.make_rglru_cache(cfg, batch)
    if kind == "dec":
        return {"self": layers.make_attn_cache(cfg, batch, max_len),
                "cross": {**layers.make_attn_cache(cfg, batch, enc_len),
                          "kv_len": jnp.zeros((), jnp.int32)}}
    return layers.make_attn_cache(cfg, batch, max_len,
                                  windowed=(kind == "local"))


# --------------------------------------------------------------------------
# Stages (scan over stacked cycles)
# --------------------------------------------------------------------------
def init_stage(cfg: ModelConfig, pattern, rep: int, key) -> dict:
    def one_cycle(k):
        ks = jax.random.split(k, len(pattern))
        return {f"b{j}": init_block(cfg, kind, ks[j])
                for j, kind in enumerate(pattern)}
    keys = jax.random.split(key, rep)
    return jax.vmap(one_cycle)(keys)


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def apply_stage(stage_params, x, cfg: ModelConfig, pattern,
                enc_out: Optional[jax.Array] = None):
    def cycle(carry, cyc_params):
        h = carry
        for j, kind in enumerate(pattern):
            h = apply_block(cyc_params[f"b{j}"], h, cfg, kind, enc_out)
        # saved scan carries are the dominant train-memory term; store them
        # sequence-sharded over `model` (Megatron-SP style). Costs one
        # gather per layer — disable for models whose carries are small
        # (§Perf iteration).
        if cfg.seq_shard_carry:
            h = shard(h, "dp", "tp", None)
        return h, None

    body = _remat(cycle, cfg)
    x, _ = jax.lax.scan(body, x, stage_params)
    return x


def apply_stage_decode(stage_params, stage_cache, x, cfg: ModelConfig,
                       pattern):
    def cycle(carry, pc):
        cyc_params, cyc_cache = pc
        h = carry
        new_cache = {}
        for j, kind in enumerate(pattern):
            h, new_cache[f"b{j}"] = apply_block_decode(
                cyc_params[f"b{j}"], h, cyc_cache[f"b{j}"], cfg, kind)
        return h, new_cache

    x, new_caches = jax.lax.scan(cycle, x, (stage_params, stage_cache))
    return x, new_caches


# --------------------------------------------------------------------------
# Whole model
# --------------------------------------------------------------------------
def sinusoidal_pos(seq: int, d: int, offset=0) -> jax.Array:
    pos = offset + jnp.arange(seq)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2) * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d))
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def init_model(cfg: ModelConfig, key) -> dict:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    if cfg.cpd_embedding:  # the paper's technique as the embedding layer
        from ..tensorized import init_cpd_embedding

        params = {"embed_cpd": init_cpd_embedding(
            ks[0], cfg.vocab_padded, d, cfg.cpd_rank or 64,
            dtype=cfg.pdtype)}
    else:
        params = {"embed": dense_init(ks[0], (cfg.vocab_padded, d),
                                      cfg.pdtype, scale=0.02)}
    for i, (pat, rep) in enumerate(cfg.stages()):
        params[f"stage{i}"] = init_stage(cfg, pat, rep, ks[1 + i % 4])
    params["ln_f"] = init_norm(cfg)
    if not cfg.tie_embeddings and not cfg.cpd_embedding:
        params["head"] = dense_init(ks[5], (d, cfg.vocab_padded), cfg.pdtype)
    if cfg.n_enc_layers:
        params["enc"] = init_stage(cfg, ("enc",), cfg.n_enc_layers, ks[6])
        params["enc_ln_f"] = init_norm(cfg)
    return params


def embed_lookup(params, ids, cfg: ModelConfig):
    """Gather token embeddings in compute dtype.

    The optimization_barrier pins the bf16 cast *before* the gather — XLA
    otherwise swaps them and the gather + vocab-shard combine run on the
    f32 master table (2x HBM + 2x collective bytes).
    """
    if cfg.cpd_embedding:  # backward of this lookup IS spMTTKRP (§4)
        from ..tensorized import cpd_embed

        return cpd_embed(params["embed_cpd"], ids).astype(cfg.cdtype)
    # pin the bf16 cast before the gather so collectives move bf16
    table = jax.lax.optimization_barrier(params["embed"].astype(cfg.cdtype))
    return jnp.take(table, ids, axis=0)


def _logits(params, x, cfg: ModelConfig):
    x = apply_norm(params["ln_f"], x, cfg)
    if cfg.cpd_embedding:  # tied CPD head, no dense table materialized
        from ..tensorized import cpd_logits

        return shard(cpd_logits(params["embed_cpd"], x), "dp", None, "tp")
    logits = x @ head_matrix(params, cfg)
    return shard(logits, "dp", None, "tp")


def encode(params, enc_embeds, cfg: ModelConfig):
    """Whisper encoder over precomputed (stub) frame embeddings."""
    x = enc_embeds.astype(cfg.cdtype)
    x = x + sinusoidal_pos(x.shape[1], cfg.d_model).astype(cfg.cdtype)
    x = apply_stage(params["enc"], x, cfg, ("enc",))
    return apply_norm(params["enc_ln_f"], x, cfg)


def forward(params, cfg: ModelConfig, tokens: Optional[jax.Array] = None,
            embeds: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            return_hidden: bool = False) -> jax.Array:
    """Training / teacher-forced forward. Returns logits (B, S, Vp).

    vlm: ``embeds`` (B, P_img, D) stub patch embeddings are prepended.
    audio: ``enc_embeds`` (B, S_enc, D) stub frame embeddings feed the
    encoder; ``tokens`` are decoder inputs.
    """
    x = embed_lookup(params, tokens, cfg)
    if cfg.kind == "vlm" and embeds is not None:
        x = jnp.concatenate([embeds.astype(cfg.cdtype), x], axis=1)
    if cfg.rope_theta == 0:  # whisper: absolute sinusoidal positions
        x = x + sinusoidal_pos(x.shape[1], cfg.d_model).astype(cfg.cdtype)
    x = shard(x, "dp", None, None)
    enc_out = None
    if cfg.n_enc_layers:
        assert enc_embeds is not None
        enc_out = encode(params, enc_embeds, cfg)
    for i, (pat, rep) in enumerate(cfg.stages()):
        x = apply_stage(params[f"stage{i}"], x, cfg, pat, enc_out)
    if return_hidden:  # chunked-loss path: caller owns the head matmul
        return apply_norm(params["ln_f"], x, cfg)
    return _logits(params, x, cfg)


def head_matrix(params, cfg: ModelConfig):
    if cfg.cpd_embedding:
        from ..tensorized import dense_table

        return dense_table(params["embed_cpd"]).astype(cfg.cdtype).T
    if cfg.tie_embeddings:
        return params["embed"].astype(cfg.cdtype).T
    return params["head"].astype(cfg.cdtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0) -> dict:
    caches = {}
    for i, (pat, rep) in enumerate(cfg.stages()):
        def one_cycle(_):
            return {f"b{j}": init_block_cache(cfg, kind, batch, max_len,
                                              enc_len)
                    for j, kind in enumerate(pat)}
        caches[f"stage{i}"] = jax.vmap(one_cycle)(jnp.arange(rep))
    return caches


def decode_step(params, cache, cfg: ModelConfig, token: jax.Array):
    """token: (B, 1) int32 -> (logits (B, 1, Vp), new cache)."""
    x = embed_lookup(params, token, cfg)
    if cfg.rope_theta == 0:
        pos = _first_cache_len(cache, cfg)
        x = x + sinusoidal_pos(1, cfg.d_model,
                               offset=pos).astype(cfg.cdtype)[None]
    new_cache = {}
    for i, (pat, rep) in enumerate(cfg.stages()):
        x, new_cache[f"stage{i}"] = apply_stage_decode(
            params[f"stage{i}"], cache[f"stage{i}"], x, cfg, pat)
    return _logits(params, x, cfg), new_cache


def build_cross_caches(params, cfg: ModelConfig, enc_embeds, cache):
    """Run the encoder once and fill every decoder block's cross-attn KV."""
    enc_out = encode(params, enc_embeds, cfg)
    dt = cfg.cdtype
    kv_len = jnp.asarray(enc_out.shape[1], jnp.int32)
    new_cache = dict(cache)
    for i, (pat, rep) in enumerate(cfg.stages()):
        if "dec" not in pat:
            continue

        def fill(cyc_params):
            out = {}
            for j, kind in enumerate(pat):
                if kind != "dec":
                    continue
                xp = cyc_params[f"b{j}"]["xattn"]
                k = jnp.einsum("bsd,dhk->bshk", enc_out,
                               xp["wk"].astype(dt))
                v = jnp.einsum("bsd,dhk->bshk", enc_out,
                               xp["wv"].astype(dt))
                if "bk" in xp:
                    k = k + xp["bk"].astype(dt)
                    v = v + xp["bv"].astype(dt)
                out[f"b{j}"] = {"k": k, "v": v}
            return out

        kvs = jax.vmap(fill)(params[f"stage{i}"])
        sc = dict(cache[f"stage{i}"])
        for j, kind in enumerate(pat):
            if kind != "dec":
                continue
            cross = dict(sc[f"b{j}"]["cross"])
            cross["k"] = kvs[f"b{j}"]["k"]
            cross["v"] = kvs[f"b{j}"]["v"]
            cross["kv_len"] = jnp.broadcast_to(kv_len, (rep,))
            sc[f"b{j}"] = {**sc[f"b{j}"], "cross": cross}
        new_cache[f"stage{i}"] = sc
    return new_cache


def _first_cache_len(cache, cfg: ModelConfig):
    if "stage0" not in cache:  # 0-layer cost variants
        return jnp.zeros((), jnp.int32)
    leaf = cache["stage0"]
    if "b0" in leaf and isinstance(leaf["b0"], dict):
        b0 = leaf["b0"]
        if "self" in b0:
            return b0["self"]["len"][0]
        if "len" in b0:
            return b0["len"][0]
    return jnp.zeros((), jnp.int32)
