"""Plain float32 reference of a dense pre-norm GQA decoder (Qwen2,
InternLM2): RMSNorm, rotary embedding (rotate-half), causal grouped-query
attention with optional q/k/v bias, SwiGLU MLP, tied or untied unembedding.
Layers follow ``m["layers"]`` in order, each with its own weights; a
``local`` layer's query at position i sees keys j with i - j <
``sliding_window``.

Written from the published architecture, with no kernel, cache or batching,
and nothing imported from the program.  Every matrix product runs at
``Precision.HIGHEST``.  ``quant="fp8"`` rounds both operands of
every linear layer (weights per output channel, activations per token) to
that type first: the lower-precision control that the check must fail.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def qdq(x, axis, quant):
    """Round ``x`` to ``quant`` with one scale per slice along ``axis``."""
    if quant is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if quant == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(quant)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: (S, heads, Dh), position = row index."""
    S, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def hidden(m: dict, w: dict, toks, quant=None):
    """Final-normed hidden states (S, D) of a token sequence (S,)."""
    f32 = jnp.float32
    eps, theta = m["norm_eps"], m["rope_theta"]
    H, KV, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = H // KV
    S = toks.shape[0]
    x = w["embed"][toks].astype(f32)
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    causal = i >= j

    def lin(spec, a, wt, a_axis, w_axis):
        return jnp.einsum(spec, qdq(a, a_axis, quant),
                          qdq(wt.astype(f32), w_axis, quant), precision=HI)

    def layer(x, p, spec):
        kind, attn, mlp = spec
        if kind != "attn" or attn not in ("global", "local") or mlp != "dense":
            raise ValueError(f"dense_gqa has no {spec} layer")
        mask = causal & (i - j < m["sliding_window"]) if attn == "local" \
            else causal
        h = rms_norm(x, p["norm1"].astype(f32), eps)
        q = lin("sd,dhk->shk", h, p["wq"], -1, 0)
        k = lin("sd,dhk->shk", h, p["wk"], -1, 0)
        v = lin("sd,dhk->shk", h, p["wv"], -1, 0)
        if "bq" in p:
            q, k, v = (q + p["bq"].astype(f32), k + p["bk"].astype(f32),
                       v + p["bv"].astype(f32))
        q, k = rope(q, theta), rope(k, theta)
        q = q.reshape(S, KV, G, Dh) / math.sqrt(Dh)
        s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI)
        s = jnp.where(mask, s, NEG)
        pr = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", pr, v, precision=HI).reshape(S, H, Dh)
        x = x + lin("shk,hkd->sd", o, p["wo"], (1, 2), (0, 1))
        h = rms_norm(x, p["norm2"].astype(f32), eps)
        g = lin("sd,df->sf", h, p["w_gate"], -1, 0)
        u = lin("sd,df->sf", h, p["w_up"], -1, 0)
        return x + lin("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], -1, 0)

    # each group: a scan over its repeats, every position of the period
    # with its own weights and its own kind of layer
    at = 0
    for group in w["groups"]:
        specs = m["layers"][at:at + len(group)]
        reps = jax.tree.leaves(group)[0].shape[0]

        def period(x, ps, specs=specs):
            for p, spec in zip(ps, specs):
                x = layer(x, p, spec)
            return x, None

        x, _ = jax.lax.scan(period, x, group)
        at += len(group) * reps
    if at != len(m["layers"]):
        raise ValueError(f"weights of {at} layers, sizes of "
                         f"{len(m['layers'])}")
    return rms_norm(x, w["final_norm"].astype(f32), eps)


def logits(m: dict, w: dict, h, quant=None):
    """Logits (P, V) of hidden rows (P, D)."""
    f32 = jnp.float32
    if m["tie_embeddings"]:
        return jnp.einsum("pd,vd->pv", qdq(h, -1, quant),
                          qdq(w["embed"].astype(f32), -1, quant), precision=HI)
    return jnp.einsum("pd,dv->pv", qdq(h, -1, quant),
                      qdq(w["lm_head"].astype(f32), 0, quant), precision=HI)
