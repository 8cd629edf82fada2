"""Pallas TPU GQA decode attention (flash-decoding structure).

One new token per sequence attends over its KV cache.  Grid
(B, KVH, n_kv): the kv dimension is innermost/"arbitrary"; per-(b,kv-head)
accumulators (m, l, acc) for the G grouped query heads live in VMEM scratch
across kv blocks.  `lengths` (B,) rides in scalar-prefetch SMEM for masking
— the decode analogue of the paper's HBM-bound decode regime: bytes moved
are ~the live KV cache, which is exactly the term the engine model charges.

Like the prefill kernel it works on head-major views, q (B, KVH, G, Dh) and
k/v (B, KVH, S, Dh), so every block's last two dimensions are (G, Dh) or
(bk, Dh) and meet the TPU's (8, 128) block tiling.  The cache is stored
(B, S, KVH, Dh), so the wrapper transposes it on every call: this kernel is
not on the serving path (append-mode decode is pinned to the jnp path, see
``ops.decode_attention``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, window, softcap, bk, n_kv, scale, G, Dh):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    k_start = ki * bk
    run = k_start < length
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > length - 1 - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (G, Dh)
        kb = k_ref[0, 0].astype(jnp.float32)                  # (bk, Dh)
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        mask = k_pos < length
        if window is not None:
            mask &= k_pos > (length - 1 - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                   # (G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "softcap", "kv_block", "interpret"))
def decode_attention(
    q: jax.Array,                 # (B, H, Dh)
    k_cache: jax.Array,           # (B, S, KVH, Dh)
    v_cache: jax.Array,
    lengths: jax.Array,           # (B,) int32
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    kv_block: int = 256,
    interpret: bool = False,
) -> jax.Array:
    B, H, Dh = q.shape
    _, S, KVH, _ = k_cache.shape
    G = H // KVH
    bk = min(kv_block, S)
    assert S % bk == 0
    n_kv = S // bk
    q_in = q.reshape(B, KVH, G, Dh)

    kern = functools.partial(
        _kernel, window=window, softcap=softcap, bk=bk, n_kv=n_kv,
        scale=Dh ** -0.5, G=G, Dh=Dh)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, ki, lens: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, Dh),
                         lambda b, h, ki, lens: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dh),
                               lambda b, h, ki, lens: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, Dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, Dh), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), q_in, k_cache.transpose(0, 2, 1, 3),
      v_cache.transpose(0, 2, 1, 3))
    return out.reshape(B, H, Dh)
