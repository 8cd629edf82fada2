"""Core transformer layers: norms, RoPE, GQA attention (global / sliding-
window / cross), dense MLP variants.  All layers are pure functions over a
param dict; init_* functions return (params, logical_axes) pytrees with
matching structure so the launcher can derive shardings mechanically.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops

Params = dict
Axes = dict


def _norm_init(d: int):
    return jnp.ones((d,), jnp.float32), ("model_d",)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array,
         theta: Optional[float]) -> jax.Array:
    """Rotary embedding. x: (B, S, H, Dh); positions: (B, S).  ``theta``
    None: the model has no positional encoding (Jamba), x is returned."""
    if theta is None:
        return x
    dh = x.shape[-1]
    half = dh // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(cfg: ModelConfig, spec: LayerSpec, key) -> tuple[Params, Axes]:
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    pd = jnp.dtype(cfg.param_dtype)
    s_q = (2.0 / (D + H * Dh)) ** 0.5
    p: Params = {
        "wq": (jax.random.normal(ks[0], (D, H, Dh)) * s_q).astype(pd),
        "wk": (jax.random.normal(ks[1], (D, KV, Dh)) * s_q).astype(pd),
        "wv": (jax.random.normal(ks[2], (D, KV, Dh)) * s_q).astype(pd),
        "wo": (jax.random.normal(ks[3], (H, Dh, D)) * s_q).astype(pd),
    }
    a: Axes = {
        "wq": ("model_d", "heads", "head_dim"),
        "wk": ("model_d", "kv_heads", "head_dim"),
        "wv": ("model_d", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "model_d"),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, Dh), pd)
        p["bk"] = jnp.zeros((KV, Dh), pd)
        p["bv"] = jnp.zeros((KV, Dh), pd)
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    return p, a


def attention_forward(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Params,
    x: jax.Array,                       # (B, S, D)
    *,
    positions: jax.Array,               # (B, S)
    vision_kv: Optional[jax.Array] = None,   # (B, Nv, D) for cross layers
) -> tuple[jax.Array, dict]:
    """Full-sequence (train / prefill) attention. Returns (out, cache_state)."""
    B, S, D = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if spec.attn_type == "cross":
        src = vision_kv
    else:
        src = x
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"].astype(dt))
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    if spec.attn_type != "cross":
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        window = cfg.sliding_window if spec.attn_type == "local" else None
        out = ops.flash_attention(
            q, k, v, causal=True, window=window, softcap=cfg.attn_softcap)
        cache = {"k": k, "v": v}
    else:
        out = ops.flash_attention(
            q, k, v, causal=False, window=None, softcap=cfg.attn_softcap)
        cache = {"k": k, "v": v}      # cross KV is static across decode
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return constrain(out, ("batch", "seq", None)), cache


def attention_decode(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Params,
    x: jax.Array,                      # (B, 1, D)
    cache: dict,                       # {"k": (B,Smax,KV,Dh), "v": ...}
    lengths: jax.Array,                # (B,) tokens already in cache
    append: bool = False,
) -> tuple[jax.Array, dict]:
    """One decode step. ``append=False``: the new token's K/V is scattered
    into the cache before attention (cache flows through the layer scan —
    baseline). ``append=True`` (§Perf "cacheappend"): the cache is read-only
    here; the new token is merged into the softmax analytically and
    {"k_new","v_new"} deltas are returned for one batched commit outside the
    scan — removing the per-step full-cache rewrite the scan ys forces.
    """
    B, _, D = x.shape
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    if spec.attn_type == "cross":
        k_all, v_all = cache["k"], cache["v"]
        nv = k_all.shape[1]
        out = ops.decode_attention(
            q[:, 0], k_all, v_all, jnp.full((B,), nv, jnp.int32),
            softcap=cfg.attn_softcap)
        new_cache = {} if append else cache
    else:
        pos = lengths[:, None]                               # (B,1)
        q = rope(q, pos, cfg.rope_theta)
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
        if "bk" in p:
            k_new = k_new + p["bk"].astype(dt)
            v_new = v_new + p["bv"].astype(dt)
        k_new = rope(k_new, pos, cfg.rope_theta)
        window = cfg.sliding_window if spec.attn_type == "local" else None
        if append:
            out = ops.decode_attention(
                q[:, 0], cache["k"], cache["v"], lengths,
                window=window, softcap=cfg.attn_softcap,
                k_new=k_new[:, 0], v_new=v_new[:, 0])
            new_cache = {"k_new": k_new[:, 0], "v_new": v_new[:, 0]}
        else:
            bidx = jnp.arange(B)
            k_all = cache["k"].at[bidx, lengths].set(k_new[:, 0])
            v_all = cache["v"].at[bidx, lengths].set(v_new[:, 0])
            k_all = constrain(k_all, ("batch", "kv_seq", "kv_heads", None))
            v_all = constrain(v_all, ("batch", "kv_seq", "kv_heads", None))
            out = ops.decode_attention(
                q[:, 0], k_all, v_all, lengths + 1,
                window=window, softcap=cfg.attn_softcap)
            new_cache = {"k": k_all, "v": v_all}
    out = jnp.einsum("bhk,hkd->bd", out, p["wo"].astype(dt))[:, None]
    return constrain(out, ("batch", None, None)), new_cache


def init_attention_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                         max_seq: int, dtype) -> tuple[dict, dict]:
    KV, Dh = cfg.n_kv_heads, cfg.head_dim
    if spec.attn_type == "cross":
        shape = (batch, max(cfg.n_vision_tokens, 1), KV, Dh)
    else:
        shape = (batch, max_seq, KV, Dh)
    axes = ("batch", "kv_seq", "kv_heads", None)
    return ({"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)},
            {"k": axes, "v": axes})


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------
def init_mlp(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    D, F = cfg.d_model, cfg.d_ff
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    s_in = (2.0 / (D + F)) ** 0.5
    gated = cfg.mlp_act in ("swiglu", "geglu")
    p: Params = {
        "w_up": (jax.random.normal(ks[0], (D, F)) * s_in).astype(pd),
        "w_down": (jax.random.normal(ks[1], (F, D)) * s_in).astype(pd),
    }
    a: Axes = {"w_up": ("model_d", "ff"), "w_down": ("ff", "model_d")}
    if gated:
        p["w_gate"] = (jax.random.normal(ks[2], (D, F)) * s_in).astype(pd)
        a["w_gate"] = ("model_d", "ff")
    return p, a


def _act(name: str, x: jax.Array) -> jax.Array:
    if name in ("swiglu",):
        return jax.nn.silu(x)
    if name in ("geglu", "gelu"):
        return jax.nn.gelu(x, approximate=True)
    if name == "relu2":
        r = jax.nn.relu(x)
        return r * r
    raise ValueError(name)


def mlp_forward(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if "w_gate" in p:
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = _act(cfg.mlp_act, g) * h
    else:
        h = _act(cfg.mlp_act, h)
    h = constrain(h, ("batch", "seq", "ff"))
    out = jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))
    return constrain(out, ("batch", "seq", None))
