"""Pallas TPU flash attention (prefill): causal GQA with optional sliding
window and logit softcap.

Tiling: grid (B, H, n_q, n_kv), n_kv innermost with "arbitrary" semantics so
the (m, l, acc) accumulators live in VMEM scratch across kv steps.  The
kernel sees head-major views q (B, H, Sq, Dh) and k/v (B, KVH, Skv, Dh), so
every block's last two dimensions are (bq, Dh) or (bk, Dh): the TPU compiler
tiles the last two dimensions of a block by (8, 128), and a one-head slice
of the (S, H, Dh) layout would put a 1 in the sublane dimension.  Blocks:
q (bq, Dh), k/v (bk, Dh) per kv-head (GQA via h -> h // group index map).
MXU-aligned: bq, bk multiples of 128 when the sequence allows; accumulation
in fp32.  VMEM working set/step: bq·Dh + 2·bk·Dh + bq·bk (fp32 scores)
≈ 128·128·4·4 B ≈ 256 KiB at the default blocks — comfortably inside VMEM.

Validated against kernels/ref.py oracles in interpret mode (CPU), compiled
for a described v5e chip in tests/test_tpu_compile.py, and checked against
the jnp attention on the chip by chip_smoke.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, softcap, bq, bk, n_kv, scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq
    k_start = ki * bk
    # block-level skips: entirely-masked kv blocks do no compute
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, Dh)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > (q_pos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "q_block", "kv_block",
                     "interpret"))
def flash_attention(
    q: jax.Array,                 # (B, Sq, H, Dh)
    k: jax.Array,                 # (B, Skv, KVH, Dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    q_block: int = 128,
    kv_block: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Dense prefill: query i sits at key position i (no kv lengths)."""
    B, Sq, H, Dh = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    bq = min(q_block, Sq)
    bk = min(kv_block, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    n_q, n_kv = Sq // bq, Skv // bk
    grid = (B, H, n_q, n_kv)

    kern = functools.partial(
        _kernel, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, n_kv=n_kv, scale=Dh ** -0.5)

    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dh), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, qi, ki, g=G: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dh),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
