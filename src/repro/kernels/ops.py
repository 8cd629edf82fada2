"""Jit'd dispatch wrappers around the compute hot-spots.

Every model-layer call site goes through this module. The implementation is
chosen by (in priority order): an explicit ``impl=`` argument, the default
set by the innermost :func:`default_impl` block, else by backend — Pallas
kernels on TPU, the memory-sane jnp paths elsewhere (CPU smoke tests and the
multi-pod dry-run; Pallas TPU kernels cannot lower on the CPU backend, and
running them in interpret mode inside a 512-way SPMD program would be
meaningless).

``impl`` values: "pallas" | "pallas_interpret" | "jnp" | "naive".
"""
from __future__ import annotations

import contextlib
from typing import Iterator, Optional

import jax
import jax.numpy as jnp

from . import ref

_DEFAULT_IMPL: str | None = None


@contextlib.contextmanager
def default_impl(impl: str | None) -> Iterator[None]:
    """Default ``impl`` for calls traced inside the block, restored on exit.

    Read at trace time: wrap the first call of a jitted function (or its
    ``lower``), not later calls, which reuse the traced program.
    """
    global _DEFAULT_IMPL
    prev, _DEFAULT_IMPL = _DEFAULT_IMPL, impl
    try:
        yield
    finally:
        _DEFAULT_IMPL = prev


def _impl(impl: str | None) -> str:
    if impl is not None:
        return impl
    if _DEFAULT_IMPL is not None:
        return _DEFAULT_IMPL
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, window=None, softcap=None,
                    kv_lens=None, q_offset=0, impl: Optional[str] = None):
    """GQA attention. q:(B,Sq,H,Dh) k/v:(B,Skv,KVH,Dh) -> (B,Sq,H,Dh)."""
    which = _impl(impl)
    if which == "naive":
        return ref.attention_naive(q, k, v, causal=causal, window=window,
                                   softcap=softcap, kv_lens=kv_lens,
                                   q_offset=q_offset)
    if which in ("pallas", "pallas_interpret"):
        if kv_lens is not None or not (isinstance(q_offset, int)
                                       and q_offset == 0):
            raise ValueError("the Pallas flash kernel is dense prefill; "
                             "kv_lens / q_offset need impl='jnp'")
        from . import flash_attention as fa
        return fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            interpret=(which == "pallas_interpret"))
    # jnp path: use the O(S) custom-VJP flash implementation whenever the
    # call is differentiable-shaped (dense packed batch, block-divisible);
    # otherwise the plain blockwise path (prefill/decode are not
    # differentiated).
    Sq, Skv = q.shape[1], k.shape[1]
    qb, kb = min(512, Sq), min(1024, Skv)
    if (kv_lens is None and isinstance(q_offset, int) and q_offset == 0
            and Sq % qb == 0 and Skv % kb == 0):
        return ref.flash_attention_trainable(
            q, k, v, causal, window, softcap, qb, kb)
    return ref.blockwise_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap, kv_lens=kv_lens,
                                   q_offset=q_offset)


def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     softcap=None, k_new=None, v_new=None,
                     impl: Optional[str] = None):
    """Single-token GQA decode. q:(B,H,Dh) cache:(B,S,KVH,Dh) -> (B,H,Dh)."""
    which = _impl(impl)
    if k_new is not None:
        # Append mode is PINNED to the jnp fallback, for every impl: the
        # Pallas decode kernel reads a committed cache and has no
        # (k_new, v_new) merge, and the analytic self-attention merge in
        # the fallback adds only O(B*H) work on top of the cache read, so
        # a kernel-side merge buys nothing measurable.  Contract (parity-
        # tested in tests/test_kernels.py): append over a read-only
        # L-token cache == committed decode over the same cache with the
        # token written at slot L and lengths L+1, for all window/softcap
        # combinations.
        return ref.decode_attention_direct(
            q, k_cache, v_cache, lengths, window=window, softcap=softcap,
            k_new=k_new, v_new=v_new)
    if which == "naive":
        return ref.decode_attention_naive(q, k_cache, v_cache, lengths,
                                          window=window, softcap=softcap)
    if which in ("pallas", "pallas_interpret"):
        from . import decode_attention as da
        return da.decode_attention(
            q, k_cache, v_cache, lengths, window=window, softcap=softcap,
            interpret=(which == "pallas_interpret"))
    return ref.decode_attention_direct(q, k_cache, v_cache, lengths,
                                       window=window, softcap=softcap)


# --------------------------------------------------------------------------
# RWKV6
# --------------------------------------------------------------------------
_DECODE_FASTPATH = True


def set_decode_fastpath(enabled: bool) -> None:
    """§Perf lever (variant "decodefast"): single-step recurrent updates for
    RWKV/Mamba decode instead of the padded chunk machinery.  Dry-run
    baselines disable this so before/after is recorded; runtime default on."""
    global _DECODE_FASTPATH
    _DECODE_FASTPATH = enabled


def rwkv6_scan(r, k, v, w, u, state, *, impl: Optional[str] = None):
    if r.shape[1] == 1 and _DECODE_FASTPATH:  # decode: single state update
        return ref.rwkv6_single_step(r, k, v, w, u, state)
    which = _impl(impl)
    if which == "naive":
        return ref.rwkv6_sequential(r, k, v, w, u, state)
    if which in ("pallas", "pallas_interpret"):
        from . import rwkv6_scan as rk
        return rk.rwkv6_scan(r, k, v, w, u, state,
                             interpret=(which == "pallas_interpret"))
    return ref.rwkv6_chunked(r, k, v, w, u, state)


# --------------------------------------------------------------------------
# Mamba selective scan
# --------------------------------------------------------------------------
def ssm_scan(x, dt, A, Bm, Cm, D, h0, *, impl: Optional[str] = None):
    if x.shape[1] == 1 and _DECODE_FASTPATH:  # decode: single state update
        return ref.ssm_single_step(x, dt, A, Bm, Cm, D, h0)
    which = _impl(impl)
    if which == "naive":
        return ref.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
    if which in ("pallas", "pallas_interpret"):
        from . import ssm_scan as ss
        return ss.ssm_scan(x, dt, A, Bm, Cm, D, h0,
                           interpret=(which == "pallas_interpret"))
    return ref.ssm_chunked(x, dt, A, Bm, Cm, D, h0)


# --------------------------------------------------------------------------
# MoE gating
# --------------------------------------------------------------------------
def moe_gating(logits, top_k, *, impl: Optional[str] = None):
    which = _impl(impl)
    if which in ("pallas", "pallas_interpret"):
        from . import moe_gating as mg
        return mg.moe_gating(logits, top_k,
                             interpret=(which == "pallas_interpret"))
    return ref.topk_gating(logits, top_k)
