"""State-space / linear-recurrence blocks: Mamba (Jamba's mixer) and RWKV6.

Both expose:  init_* -> (params, axes);  *_forward (full sequence, returns
final recurrent state for prefill→decode handoff);  *_decode (single token);
init_*_state -> (state, axes).

The Mamba mixer is Jamba's (arXiv:2403.19887; HF ``JambaMambaMixer``): after
``x_proj`` it RMS-normalises dt (``dt_rank``), B and C (``d_state``) each
with its own scale.  ``mamba_forward`` takes each row's true length, so a
right-padded prefill hands decode the state at the prompt's end: the scan
gets dt = 0 past it (h stays as it is) and the conv state is the K-1 inputs
before it.  RWKV's prefill has no lengths yet: its state absorbs padding.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models.layers import rms_norm

Params = dict
Axes = dict


# ===========================================================================
# Mamba
# ===========================================================================
def init_mamba(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    D, Din, N, R, K = (cfg.d_model, cfg.d_inner, cfg.mamba_d_state,
                       cfg.dt_rank, cfg.mamba_conv)
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p: Params = {
        "in_proj": (jax.random.normal(ks[0], (D, 2 * Din)) * (D ** -0.5)).astype(pd),
        "conv_w": (jax.random.normal(ks[1], (K, Din)) * (K ** -0.5)).astype(pd),
        "conv_b": jnp.zeros((Din,), pd),
        "x_proj": (jax.random.normal(ks[2], (Din, R + 2 * N)) * (Din ** -0.5)).astype(pd),
        "dt_w": (jax.random.normal(ks[3], (R, Din)) * (R ** -0.5)).astype(pd),
        "dt_bias": jnp.full((Din,), math.log(math.expm1(0.01)), jnp.float32),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, N + 1, dtype=jnp.float32), (Din, N))).astype(jnp.float32),
        "Dskip": jnp.ones((Din,), jnp.float32),
        "out_proj": (jax.random.normal(ks[4], (Din, D)) * (Din ** -0.5)).astype(pd),
        # Jamba's inner norms of dt, B and C
        "dt_norm": jnp.ones((R,), jnp.float32),
        "B_norm": jnp.ones((N,), jnp.float32),
        "C_norm": jnp.ones((N,), jnp.float32),
    }
    a: Axes = {
        "in_proj": ("model_d", "d_inner"),
        "conv_w": ("conv", "d_inner"),
        "conv_b": ("d_inner",),
        "x_proj": ("d_inner", None),
        "dt_w": (None, "d_inner"),
        "dt_bias": ("d_inner",),
        "A_log": ("d_inner", "state"),
        "Dskip": ("d_inner",),
        "out_proj": ("d_inner", "model_d"),
        "dt_norm": (None,),
        "B_norm": ("state",),
        "C_norm": ("state",),
    }
    return p, a


def _mamba_conv(p: Params, x_in: jax.Array, conv_state: jax.Array,
                lengths=None):
    """Causal depthwise conv, kernel K (small, unrolled).

    x_in: (B, S, Din); conv_state: (B, K-1, Din) trailing context; lengths:
    (B,) int32 true lengths of right-padded rows, or None (all S).
    Returns (conv_out (B,S,Din), new_state (B,K-1,Din)): the K-1 inputs
    before each row's length, the old state filling what the row lacks.
    """
    K = p["conv_w"].shape[0]
    dt = x_in.dtype
    S = x_in.shape[1]
    padded = jnp.concatenate([conv_state.astype(dt), x_in], axis=1)
    out = p["conv_b"].astype(dt)[None, None]
    w = p["conv_w"].astype(dt)
    out = sum(w[i][None, None] * jax.lax.dynamic_slice_in_dim(padded, i, S, 1)
              for i in range(K)) + out
    if lengths is None:
        new_state = padded[:, S:]
    else:
        # inputs lengths-K+1 .. lengths-1 sit at padded[lengths .. +K-2]
        idx = lengths[:, None] + jnp.arange(K - 1)[None, :]
        new_state = jnp.take_along_axis(padded, idx[..., None], axis=1)
    return out, new_state


def mamba_forward(cfg: ModelConfig, p: Params, x: jax.Array, state: dict,
                  lengths=None):
    """Jamba's Mamba mixer over x (B, S, D) from ``state``.  ``lengths``
    (B,) int32: each row's true length in a right-padded batch; the state
    returned is the state at that length (None: all S positions)."""
    B, S, D = x.shape
    dt_ = x.dtype
    Din, N, R = cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank
    eps = cfg.norm_eps
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"].astype(dt_))
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = constrain(x_in, ("batch", "seq", "d_inner"))
    conv_out, conv_new = _mamba_conv(p, x_in, state["conv"], lengths)
    xc = jax.nn.silu(conv_out)
    proj = jnp.einsum("bse,ef->bsf", xc, p["x_proj"].astype(dt_))
    dt_low, Bm, Cm = jnp.split(proj, [R, R + N], axis=-1)
    dt_low = rms_norm(dt_low, p["dt_norm"], eps)
    Bm = rms_norm(Bm, p["B_norm"], eps)
    Cm = rms_norm(Cm, p["C_norm"], eps)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,re->bse", dt_low.astype(jnp.float32), p["dt_w"].astype(jnp.float32))
        + p["dt_bias"])
    if lengths is not None:
        # dt = 0 past the length: exp(0·A) = 1 and dt·x·B = 0, so h holds
        dt = jnp.where(jnp.arange(S)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    A = -jnp.exp(p["A_log"])
    y, h_fin = ops.ssm_scan(xc, dt, A, Bm, Cm, p["Dskip"], state["h"])
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"].astype(dt_))
    return (constrain(out, ("batch", "seq", None)),
            {"h": h_fin, "conv": conv_new})


def mamba_decode(cfg: ModelConfig, p: Params, x: jax.Array, state: dict):
    return mamba_forward(cfg, p, x, state)     # S=1 path is identical


def init_mamba_state(cfg: ModelConfig, batch: int) -> tuple[dict, dict]:
    Din, N, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_conv
    return (
        {"h": jnp.zeros((batch, Din, N), jnp.float32),
         "conv": jnp.zeros((batch, K - 1, Din), jnp.dtype(cfg.dtype))},
        {"h": ("batch", "d_inner", None), "conv": ("batch", None, "d_inner")},
    )


# ===========================================================================
# RWKV6 ("Finch")
# ===========================================================================
def init_rwkv(cfg: ModelConfig, key) -> tuple[Params, Axes]:
    D, F = cfg.d_model, cfg.d_ff
    H, K = cfg.rwkv_heads, cfg.rwkv_head_dim
    mix, dec = cfg.rwkv_lora_mix, cfg.rwkv_lora_decay
    pd = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 12)
    s = D ** -0.5
    p: Params = {
        # time-mix (ddlerp) params
        "mu_x": jnp.zeros((D,), jnp.float32),
        "mu": jnp.zeros((5, D), jnp.float32),          # w,k,v,r,g
        "maa_w1": (jax.random.normal(ks[0], (D, 5 * mix)) * s * 0.1).astype(pd),
        "maa_w2": (jax.random.normal(ks[1], (5, mix, D)) * 0.1 * mix ** -0.5).astype(pd),
        # data-dependent decay
        "decay_base": jnp.full((D,), -1.0, jnp.float32),
        "decay_w1": (jax.random.normal(ks[2], (D, dec)) * s * 0.1).astype(pd),
        "decay_w2": (jax.random.normal(ks[3], (dec, D)) * 0.1 * dec ** -0.5).astype(pd),
        "u": (jax.random.normal(ks[4], (H, K)) * 0.1).astype(jnp.float32),
        "wr": (jax.random.normal(ks[5], (D, D)) * s).astype(pd),
        "wk": (jax.random.normal(ks[6], (D, D)) * s).astype(pd),
        "wv": (jax.random.normal(ks[7], (D, D)) * s).astype(pd),
        "wg": (jax.random.normal(ks[8], (D, D)) * s).astype(pd),
        "wo": (jax.random.normal(ks[9], (D, D)) * s).astype(pd),
        "ln_x_scale": jnp.ones((D,), jnp.float32),
        "ln_x_bias": jnp.zeros((D,), jnp.float32),
        # channel-mix
        "mu_k_c": jnp.zeros((D,), jnp.float32),
        "mu_r_c": jnp.zeros((D,), jnp.float32),
        "wk_c": (jax.random.normal(ks[10], (D, F)) * s).astype(pd),
        "wv_c": (jax.random.normal(ks[11], (F, D)) * (F ** -0.5)).astype(pd),
        "wr_c": (jax.random.normal(ks[0], (D, D)) * s).astype(pd),
    }
    a: Axes = {
        "mu_x": ("model_d",), "mu": (None, "model_d"),
        "maa_w1": ("model_d", None), "maa_w2": (None, None, "model_d"),
        "decay_base": ("model_d",),
        "decay_w1": ("model_d", None), "decay_w2": (None, "model_d"),
        "u": ("rwkv_heads", None),
        "wr": ("model_d", "d_inner"), "wk": ("model_d", "d_inner"),
        "wv": ("model_d", "d_inner"), "wg": ("model_d", "d_inner"),
        "wo": ("d_inner", "model_d"),
        "ln_x_scale": ("model_d",), "ln_x_bias": ("model_d",),
        "mu_k_c": ("model_d",), "mu_r_c": ("model_d",),
        "wk_c": ("model_d", "ff"), "wv_c": ("ff", "model_d"),
        "wr_c": ("model_d", "d_inner"),
    }
    return p, a


def _token_shift(x: jax.Array, last: jax.Array) -> jax.Array:
    """xx_t = x_{t-1}, with `last` (B, D) filling position 0."""
    return jnp.concatenate([last[:, None], x[:, :-1]], axis=1)


def rwkv_time_mix(cfg: ModelConfig, p: Params, x: jax.Array, state: dict):
    """x: (B, S, D) pre-normed. Returns (out, new_state pieces)."""
    B, S, D = x.shape
    H, K = cfg.rwkv_heads, cfg.rwkv_head_dim
    dt = x.dtype
    mix = cfg.rwkv_lora_mix
    xx = _token_shift(x, state["shift_tm"].astype(dt))
    dx = xx - x
    x_base = x + dx * p["mu_x"].astype(dt)
    deltas = jnp.tanh(jnp.einsum("bsd,dm->bsm", x_base, p["maa_w1"].astype(dt)))
    deltas = deltas.reshape(B, S, 5, mix)
    deltas = jnp.einsum("bsim,imd->bsid", deltas, p["maa_w2"].astype(dt))
    mus = p["mu"].astype(dt)[None, None] + deltas            # (B,S,5,D)
    xw, xk, xv, xr, xg = [x + dx * mus[:, :, i] for i in range(5)]

    r = jnp.einsum("bsd,de->bse", xr, p["wr"].astype(dt)).reshape(B, S, H, K)
    k = jnp.einsum("bsd,de->bse", xk, p["wk"].astype(dt)).reshape(B, S, H, K)
    v = jnp.einsum("bsd,de->bse", xv, p["wv"].astype(dt)).reshape(B, S, H, K)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", xg, p["wg"].astype(dt)))

    w_log = p["decay_base"].astype(jnp.float32) + jnp.einsum(
        "bsm,md->bsd",
        jnp.tanh(jnp.einsum("bsd,dm->bsm", xw, p["decay_w1"].astype(dt))).astype(jnp.float32),
        p["decay_w2"].astype(jnp.float32))
    w = jnp.exp(-jnp.exp(w_log)).reshape(B, S, H, K)         # decay in (0,1)

    r = constrain(r, ("batch", "seq", "rwkv_heads", None))
    k = constrain(k, ("batch", "seq", "rwkv_heads", None))
    v = constrain(v, ("batch", "seq", "rwkv_heads", None))
    out, S_new = ops.rwkv6_scan(r, k, v, w, p["u"], state["wkv"])

    # per-head groupnorm
    of = out.astype(jnp.float32)
    mean = of.mean(-1, keepdims=True)
    var = of.var(-1, keepdims=True)
    of = (of - mean) * jax.lax.rsqrt(var + 64e-5)
    of = of.reshape(B, S, D) * p["ln_x_scale"] + p["ln_x_bias"]
    out = (of.astype(dt) * g)
    out = jnp.einsum("bsd,de->bse", out, p["wo"].astype(dt))
    return out, {"wkv": S_new, "shift_tm": x[:, -1]}


def rwkv_channel_mix(cfg: ModelConfig, p: Params, x: jax.Array, state: dict):
    dt = x.dtype
    xx = _token_shift(x, state["shift_cm"].astype(dt))
    dx = xx - x
    xk = x + dx * p["mu_k_c"].astype(dt)
    xr = x + dx * p["mu_r_c"].astype(dt)
    k = jnp.einsum("bsd,df->bsf", xk, p["wk_c"].astype(dt))
    k = jax.nn.relu(k) ** 2
    k = constrain(k, ("batch", "seq", "ff"))
    v = jnp.einsum("bsf,fd->bsd", k, p["wv_c"].astype(dt))
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, p["wr_c"].astype(dt)))
    return r * v, {"shift_cm": x[:, -1]}


def init_rwkv_state(cfg: ModelConfig, batch: int) -> tuple[dict, dict]:
    H, K = cfg.rwkv_heads, cfg.rwkv_head_dim
    D = cfg.d_model
    cdt = jnp.dtype(cfg.dtype)
    return (
        {"wkv": jnp.zeros((batch, H, K, K), jnp.float32),
         "shift_tm": jnp.zeros((batch, D), cdt),
         "shift_cm": jnp.zeros((batch, D), cdt)},
        {"wkv": ("batch", "rwkv_heads", None, None),
         "shift_tm": ("batch", None), "shift_cm": ("batch", None)},
    )
