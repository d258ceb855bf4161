"""Model configuration + shared primitives (norms, init, dtype policy).

Params are plain nested dicts of jnp arrays ("pytree modules"): every layer
is an ``init_*(cfg, key) -> params`` plus an ``apply_*(params, x, ...)`` pair.
Layers of the same kind are stacked on a leading axis and driven by
``lax.scan`` so HLO size is O(1) in depth (512-chip compiles stay small).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Configuration
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    kind: str                      # dense | moe | hybrid | ssm | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # block behaviour
    norm: str = "rmsnorm"          # rmsnorm | layernorm | layernorm_np
    act: str = "swiglu"            # swiglu | geglu | gelu
    qkv_bias: bool = False
    qk_norm: bool = False
    parallel_block: bool = False   # command-r style attn || mlp
    tie_embeddings: bool = False
    rope_theta: float = 10000.0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # hybrid (griffin) / ssm
    block_pattern: tuple[str, ...] = ("attn",)   # cycle of block kinds
    window: int = 0                # sliding window for "local" attention
    lru_width: int = 0
    conv_width: int = 4
    # enc-dec (whisper)
    n_enc_layers: int = 0
    # vlm (paligemma)
    n_img_tokens: int = 0
    # dtypes / memory
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: str = "full"            # full | dots | none
    # distribution / serving knobs (§Perf hillclimb levers)
    seq_shard_carry: bool = True   # Megatron-SP: store scan carries S/tp
    kv_quant: bool = False         # int8 KV cache (per-row scales)
    # technique attachment (DESIGN.md §4): CPD-factorized embedding
    cpd_embedding: bool = False
    cpd_rank: int = 0

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return ((self.vocab + 127) // 128) * 128

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def stages(self) -> list[tuple[tuple[str, ...], int]]:
        """Split n_layers into (pattern-cycle, repeat) stages for scan."""
        pat = self.block_pattern
        full, rem = divmod(self.n_layers, len(pat))
        out = []
        if full:
            out.append((pat, full))
        if rem:
            out.append((pat[:rem], 1))
        return out

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND roofline and reporting)."""
        d, hd = self.d_model, self.hd
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * hd \
            + self.n_heads * hd * d
        if self.qkv_bias:
            attn += (self.n_heads + 2 * self.n_kv_heads) * hd
        gated = self.act in ("swiglu", "geglu")
        mlp = d * self.d_ff * (3 if gated else 2)
        if self.n_experts:
            mlp = self.n_experts * mlp + d * self.n_experts  # + router
        rec = 0
        if "rec" in self.block_pattern:
            w = self.lru_width or d
            # in/out proj + gates + conv
            rec = 2 * d * w + 2 * w * w // 1 + 3 * w + self.conv_width * w
        counts = {"attn": attn + mlp, "local": attn + mlp,
                  "rec": rec + mlp, "moe": attn + mlp,
                  "rwkv": 0, "enc": attn + mlp, "dec": 2 * attn + mlp}
        if self.kind == "ssm":
            # rwkv6: time-mix (r,k,v,g,w,o = 6 d^2 approx + loras) + channel mix
            tm = 5 * d * d + d * d + 7 * 32 * d * 2
            cm = 2 * d * self.d_ff
            per_layer = tm + cm
            total = self.n_layers * per_layer
        else:
            total = 0
            for pat, rep in self.stages():
                for kind in pat:
                    total += counts[kind] * rep
            if self.n_enc_layers:
                total += self.n_enc_layers * (attn + mlp)
        emb = self.vocab_padded * d
        total += emb if self.tie_embeddings else 2 * emb
        return int(total)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top_k experts only)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        gated = self.act in ("swiglu", "geglu")
        dense_mlp = d * self.d_ff * (3 if gated else 2)
        saved = (self.n_experts - self.top_k) * dense_mlp * self.n_layers
        return self.param_count() - saved


# --------------------------------------------------------------------------
# Primitives
# --------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2, 2, shape, jnp.float32)
            * std).astype(dtype)


def init_norm(cfg: ModelConfig, with_bias: bool = False):
    if cfg.norm == "layernorm_np":
        return {}  # OLMo: non-parametric LN
    p = {"scale": jnp.ones((cfg.d_model,), cfg.pdtype)}
    if cfg.norm == "layernorm" and with_bias:
        p["bias"] = jnp.zeros((cfg.d_model,), cfg.pdtype)
    return p


def apply_norm(params, x, cfg: ModelConfig, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    else:  # layernorm / layernorm_np
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    if params:
        xf = xf * params["scale"].astype(jnp.float32)
        if "bias" in params:
            xf = xf + params["bias"].astype(jnp.float32)
    return xf.astype(x.dtype)


def rms_head_norm(x, scale, eps: float = 1e-6):
    """QK-norm (per-head RMS norm), qwen3 style."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


def activate(h_gate, h_up, act: str):
    if act == "swiglu":
        return jax.nn.silu(h_gate) * h_up
    if act == "geglu":
        return jax.nn.gelu(h_gate) * h_up
    raise ValueError(act)
