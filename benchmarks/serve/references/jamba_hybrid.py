"""Plain float32 reference of a Jamba decoder (arXiv:2403.19887; the HF
``JambaForCausalLM`` forward): Mamba-1 mixers beside grouped-query attention
with no positional encoding, each layer a pre-norm block of mixer then
SwiGLU MLP, RMSNorm throughout, tied or untied unembedding.  Layers follow
``m["layers"]`` in order, each with its own weights.

The Mamba mixer of a sequence x (S, D), as ``JambaMambaMixer`` computes it:

    x_in, z = split(x @ in_proj)                          (S, d_inner) each
    xc      = silu(causal depthwise conv of x_in, kernel d_conv, + conv_b)
    dt, B, C = split(xc @ x_proj)                         dt_rank, d_state, d_state
    dt, B, C = rms_norm(dt), rms_norm(B), rms_norm(C)     Jamba's inner norms
    dt      = softplus(dt @ dt_w + dt_bias)               (S, d_inner)
    h_t     = exp(dt_t * A) * h_{t-1} + dt_t * xc_t * B_t,  A = -exp(A_log)
    y_t     = h_t . C_t + Dskip * xc_t
    out     = (y * silu(z)) @ out_proj

with the scan run one position after another (``lax.scan``), h starting at
zero.  Written from the published description, with no kernel, cache or
batching, and nothing imported from the program.  Every matrix product runs
at ``Precision.HIGHEST``.  ``quant="fp8"`` rounds both operands of every
linear layer (weights per output channel, activations per token) to that
type first: the lower-precision control that the check must fail.
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


def selective_scan(xc, dt, A, B, C, Dskip):
    """y (S, d_inner) of the recurrence above, one position at a time."""
    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None]
        return h, jnp.einsum("dn,n->d", h, C_t, precision=HI) + Dskip * x_t

    h0 = jnp.zeros(A.shape, jnp.float32)
    _, y = jax.lax.scan(step, h0, (xc, dt, B, C))
    return y


def hidden(m: dict, w: dict, toks, quant=None):
    """Final-normed hidden states (S, D) of a token sequence (S,)."""
    f32 = jnp.float32
    eps = m["norm_eps"]
    H, KV, Dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    G = H // KV
    N, K, R = m["mamba_d_state"], m["mamba_conv"], m["mamba_dt_rank"]
    S = toks.shape[0]
    x = w["embed"][toks].astype(f32)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def lin(spec, a, wt, a_axis, w_axis):
        return jnp.einsum(spec, qdq(a, a_axis, quant),
                          qdq(wt.astype(f32), w_axis, quant), precision=HI)

    def mamba(h, p):
        xz = lin("sd,de->se", h, p["in_proj"], -1, 0)
        x_in, z = jnp.split(xz, 2, axis=-1)
        cw = p["conv_w"].astype(f32)                      # (K, d_inner)
        xp = jnp.concatenate([jnp.zeros((K - 1, x_in.shape[1]), f32), x_in])
        xc = sum(cw[i] * xp[i:i + S] for i in range(K)) + p["conv_b"].astype(f32)
        xc = jax.nn.silu(xc)
        proj = lin("se,ef->sf", xc, p["x_proj"], -1, 0)
        dt, B, C = proj[:, :R], proj[:, R:R + N], proj[:, R + N:]
        dt = rms_norm(dt, p["dt_norm"].astype(f32), eps)
        B = rms_norm(B, p["B_norm"].astype(f32), eps)
        C = rms_norm(C, p["C_norm"].astype(f32), eps)
        dt = jax.nn.softplus(lin("sr,re->se", dt, p["dt_w"], -1, 0)
                             + p["dt_bias"].astype(f32))
        A = -jnp.exp(p["A_log"].astype(f32))
        y = selective_scan(xc, dt, A, B, C, p["Dskip"].astype(f32))
        return lin("se,ed->sd", y * jax.nn.silu(z), p["out_proj"], -1, 0)

    def attention(h, p):
        q = lin("sd,dhk->shk", h, p["wq"], -1, 0)
        k = lin("sd,dhk->shk", h, p["wk"], -1, 0)
        v = lin("sd,dhk->shk", h, p["wv"], -1, 0)
        q = q.reshape(S, KV, G, Dh) / math.sqrt(Dh)
        s = jnp.einsum("skgd,tkd->kgst", q, k, precision=HI)
        pr = jax.nn.softmax(jnp.where(causal, s, NEG), axis=-1)
        o = jnp.einsum("kgst,tkd->skgd", pr, v, precision=HI).reshape(S, H, Dh)
        return lin("shk,hkd->sd", o, p["wo"], (1, 2), (0, 1))

    def layer(x, p, spec):
        kind, attn, mlp = spec
        if mlp != "dense" or (kind, attn) not in (("mamba", "global"),
                                                  ("attn", "global")):
            raise ValueError(f"jamba_hybrid has no {spec} layer")
        h = rms_norm(x, p["norm1"].astype(f32), eps)
        x = x + (mamba(h, p) if kind == "mamba" else attention(h, p))
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
