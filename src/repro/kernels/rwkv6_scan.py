"""Pallas TPU WKV6 recurrence (RWKV-6 time-mix core).

The recurrence S <- diag(w_t)·S + k_tᵀv_t is inherently sequential in t, so
the kernel mirrors the official CUDA wkv6 structure adapted to TPU: grid
(B, H) parallelizes batch × heads; the (K, V) state lives in VMEM fp32 and a
fori loop walks the sequence.  Per-step work is VPU-shaped (outer product +
mat-vec over a 64×64 state), with r/k/v/w streamed HBM->VMEM once per (b,h)
block — bytes ≈ 4·T·K per program, the roofline term for this layer.

The kernel sees head-major fp32 views (B, H, T, K), so each block's last two
dimensions are (T, K) and step t reads one row at a dynamic sublane offset;
the TPU compiler accepts such single-row loads and stores only for 32-bit
data, hence the fp32 casts in the wrapper.

A chunked-matmul variant (MXU-friendly) is the recorded perf follow-up; the
jnp chunked path in ref.py is its oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            state, *, T):
    state[...] = s0_ref[0, 0]
    u = u_ref[0]                                             # (1, K)

    def step(t, _):
        r_t = r_ref[0, 0, pl.ds(t, 1), :]                    # (1, K)
        k_t = k_ref[0, 0, pl.ds(t, 1), :]
        v_t = v_ref[0, 0, pl.ds(t, 1), :]                    # (1, V)
        w_t = w_ref[0, 0, pl.ds(t, 1), :]
        S = state[...]                                       # (K, V)
        # r_t·(S + diag(u)·k_tᵀv_t) = r_t·S + (Σ r⊙u⊙k_t)·v_t
        o_ref[0, 0, pl.ds(t, 1), :] = jnp.dot(
            r_t, S, preferred_element_type=jnp.float32
        ) + jnp.sum(r_t * u * k_t) * v_t
        state[...] = w_t.T * S + k_t.T * v_t
        return 0

    jax.lax.fori_loop(0, T, step, 0)
    sT_ref[0, 0] = state[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_scan(r, k, v, w, u, state, *, interpret: bool = False):
    """r/k/w: (B,T,H,K); v: (B,T,H,V); u: (H,K); state: (B,H,K,V)."""
    B, T, H, K = r.shape
    V = v.shape[-1]
    head_major = lambda x: x.astype(jnp.float32).transpose(0, 2, 1, 3)
    seq = lambda d: pl.BlockSpec((1, 1, T, d), lambda b, h: (b, h, 0, 0))
    st = pl.BlockSpec((1, 1, K, V), lambda b, h: (b, h, 0, 0))
    out, sT = pl.pallas_call(
        functools.partial(_kernel, T=T),
        grid=(B, H),
        in_specs=[seq(K), seq(K), seq(V), seq(K),
                  pl.BlockSpec((1, 1, K), lambda b, h: (h, 0, 0)), st],
        out_specs=[seq(V), st],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), jnp.float32),
            jax.ShapeDtypeStruct((B, H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret,
    )(head_major(r), head_major(k), head_major(v), head_major(w),
      u.astype(jnp.float32)[:, None], state.astype(jnp.float32))
    return out.transpose(0, 2, 1, 3).astype(v.dtype), sT
