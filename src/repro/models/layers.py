"""Shared neural building blocks: norms, RoPE, GQA attention, FFN.

Parameter trees are plain nested dicts; every ``init_*`` has a matching
``spec_*`` returning the same tree of :class:`PartitionSpec` built from
LOGICAL axis names — ``"batch"``, ``"model"``, ``"fsdp"``, ``"seq"`` —
that :func:`repro.launch.mesh.resolve_spec` later binds to mesh axes
according to the arch's distribution policy.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.models.config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def _dense(key, fan_in: int, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32)
            * (fan_in ** -0.5)).astype(dtype)


def norm_init(cfg: ModelConfig) -> Params:
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return p


def norm_spec(cfg: ModelConfig) -> Params:
    p = {"scale": P(None)}
    if cfg.norm == "layernorm":
        p["bias"] = P(None)
    return p


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig,
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:
        y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + eps)
        y = y * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B,T,H,D) with even D; positions: (T,) or (B,T)."""
    D = x.shape[-1]
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[None, :, None].astype(jnp.float32) * freqs
        ang = ang[..., None, :]                       # (1,T,1,half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs
        ang = ang[..., None, :]                       # (B,T,1,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (full or local-window), with optional qk-norm and qkv bias
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, cross: bool = False) -> Params:
    D, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p: Params = {
        "wq": _dense(ks[0], D, (D, H, hd), cfg.dtype),
        "wk": _dense(ks[1], D, (D, K, hd), cfg.dtype),
        "wv": _dense(ks[2], D, (D, K, hd), cfg.dtype),
        "wo": _dense(ks[3], H * hd, (H, hd, D), cfg.dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), jnp.float32)
        p["bk"] = jnp.zeros((K, hd), jnp.float32)
        p["bv"] = jnp.zeros((K, hd), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def attn_spec(cfg: ModelConfig, cross: bool = False) -> Params:
    # Head dims shard over "model" only when divisible; resolve_spec drops
    # the axis otherwise (checked there against the real mesh).
    p: Params = {
        "wq": P("fsdp", "model", None),
        "wk": P("fsdp", "model_kv", None),
        "wv": P("fsdp", "model_kv", None),
        "wo": P("model", None, "fsdp"),
    }
    if cfg.qkv_bias:
        p.update(bq=P("model", None), bk=P("model_kv", None),
                 bv=P("model_kv", None))
    if cfg.qk_norm:
        p.update(q_norm=P(None), k_norm=P(None))
    return p


def _qk_normalize(x: jax.Array, scale: jax.Array, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + eps) * scale
    return y.astype(x.dtype)


def attn_qkv(p: Params, x: jax.Array, cfg: ModelConfig,
             positions: Optional[jax.Array], kv_from: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Project to (q, k, v); applies bias, qk-norm, RoPE."""
    src = x if kv_from is None else kv_from
    q = jnp.einsum("btd,dhk->bthk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = _qk_normalize(q, p["q_norm"])
        k = _qk_normalize(k, p["k_norm"])
    if positions is not None and kv_from is None:   # no RoPE on cross-attn
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_out(p: Params, o: jax.Array) -> jax.Array:
    return jnp.einsum("bthk,hkd->btd", o, p["wo"])


def attn_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                 causal: bool = True, window: int = 0,
                 positions: Optional[jax.Array] = None,
                 kv_from: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder)."""
    if positions is None and kv_from is None:
        positions = jnp.arange(x.shape[1])
    q, k, v = attn_qkv(p, x, cfg, positions, kv_from)
    with jax.named_scope("attention"):
        o = ops.flash_attention(q, k, v, causal=causal, window=window)
    return attn_out(p, o)


def attn_decode(p: Params, x: jax.Array, cfg: ModelConfig,
                cache_k: jax.Array, cache_v: jax.Array, index: jax.Array, *,
                window: int = 0, ring: bool = False
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.  x: (B,1,D); cache: (B,S,K,hd); index: scalar.

    ``ring=True`` writes the new KV at ``index % S`` (bounded local-window
    cache, recurrentgemma); positions stay absolute for RoPE.
    """
    B, _, D = x.shape
    S = cache_k.shape[1]
    pos = jnp.full((B, 1), index, dtype=jnp.int32)
    q, k, v = attn_qkv(p, x, cfg, pos)
    slot = jnp.where(ring, index % S, jnp.minimum(index, S - 1))
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))
    if ring:
        # Ring cache: all S slots are valid once full; mask handles warmup.
        o = ops.decode_attention(q, cache_k, cache_v,
                                 jnp.minimum(index + 1, S), window=0)
    else:
        o = ops.decode_attention(q, cache_k, cache_v, index + 1,
                                 window=window)
    return attn_out(p, o), cache_k, cache_v


# ---------------------------------------------------------------------------
# Dense FFN: swiglu / geglu / gelu
# ---------------------------------------------------------------------------
def ffn_init(key, cfg: ModelConfig) -> Params:
    D, F = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p: Params = {"wo": _dense(ks[2], F, (F, D), cfg.dtype)}
    if cfg.ffn in ("swiglu", "geglu"):
        p["wi"] = _dense(ks[0], D, (D, F), cfg.dtype)
        p["wg"] = _dense(ks[1], D, (D, F), cfg.dtype)
    else:
        p["wi"] = _dense(ks[0], D, (D, F), cfg.dtype)
    return p


def ffn_spec(cfg: ModelConfig) -> Params:
    p: Params = {"wo": P("model", "fsdp"), "wi": P("fsdp", "model")}
    if cfg.ffn in ("swiglu", "geglu"):
        p["wg"] = P("fsdp", "model")
    return p


def ffn_forward(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = jnp.einsum("btd,df->btf", x, p["wi"])
    if cfg.ffn == "swiglu":
        g = jnp.einsum("btd,df->btf", x, p["wg"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(h.dtype) * h
    elif cfg.ffn == "geglu":
        g = jnp.einsum("btd,df->btf", x, p["wg"])
        h = jax.nn.gelu(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return jnp.einsum("btf,fd->btd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_init(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 2)
    Vp = cfg.vocab_padded
    return {
        "table": _dense(ks[0], cfg.d_model, (Vp, cfg.d_model), cfg.dtype),
        "head": _dense(ks[1], cfg.d_model, (cfg.d_model, Vp), cfg.dtype),
    }


def embed_spec(cfg: ModelConfig) -> Params:
    return {"table": P("vocab", None), "head": P("fsdp", "vocab")}


def embed(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0) * math.sqrt(cfg.d_model)


def unembed(p: Params, x: jax.Array, cfg: Optional[ModelConfig] = None
            ) -> jax.Array:
    logits = jnp.einsum("btd,dv->btv", x, p["head"])
    Vp = p["head"].shape[-1]
    if cfg is not None and Vp > cfg.vocab:
        # Padded vocab slots never win argmax / contribute to logsumexp.
        mask = jnp.where(jnp.arange(Vp) < cfg.vocab, 0.0, -1e30)
        logits = logits + mask.astype(logits.dtype)
    return logits
