"""Operations and bytes that the algorithm needs, from a model's sizes.

Counted from real prompt lengths and live KV, never from padded lengths or
``max_seq``, with every parameter read once per step, so that no change
that removes waste can push a share of a peak past 1.  Sizes come from the
configuration file (``configs/<name>.json``), not from the program.
"""
from __future__ import annotations

BF16 = 2


def layer_params(m: dict) -> int:
    """Parameters of one decoder layer (attention, gated MLP, two norms)."""
    D, H, KV, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    attn = D * H * Dh + 2 * D * KV * Dh + H * Dh * D
    if m.get("qkv_bias"):
        attn += H * Dh + 2 * KV * Dh
    return attn + 3 * D * F + 2 * D


def kv_bytes_per_token(m: dict) -> int:
    return m["n_layers"] * 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def unembed_flops(m: dict) -> int:
    """One position's logits over the vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, q_len: int, kv_start: int) -> int:
    """Causal attention of ``q_len`` new queries after ``kv_start`` cached
    tokens, all layers: QK^T and PV over the keys each query may see."""
    seen = q_len * kv_start + q_len * (q_len + 1) // 2
    return m["n_layers"] * 4 * m["n_heads"] * m["head_dim"] * seen


def prefill_flops(m: dict, L: int) -> int:
    """Prompt of L tokens: every layer over L tokens, causal attention, and
    the logits of the last position only."""
    return (2 * m["n_layers"] * layer_params(m) * L + attn_flops(m, L, 0)
            + unembed_flops(m))


def decode_flops(m: dict, ctx: int) -> int:
    """One output token attending to ``ctx`` cached tokens and itself."""
    return (2 * m["n_layers"] * layer_params(m) + attn_flops(m, 1, ctx)
            + unembed_flops(m))


def flash_flops(m: dict, L: int) -> int:
    """One flash-kernel call: one layer's causal attention over L tokens."""
    return 2 * m["n_heads"] * m["head_dim"] * L * (L + 1)


def flash_bytes(m: dict, L: int) -> int:
    """One flash-kernel call reads q, k, v and writes o once each."""
    return (2 * L * m["n_heads"] + 2 * L * m["n_kv_heads"]) * m["head_dim"] * BF16


def param_bytes(m: dict) -> int:
    """All weights, in their served types (norm scales are float32)."""
    D, V = m["d_model"], m["vocab_size"]
    norms = m["n_layers"] * 2 * D + D
    mats = m["n_layers"] * (layer_params(m) - 2 * D) + V * D
    if not m["tie_embeddings"]:
        mats += D * V
    return mats * BF16 + norms * 4


def decode_step_bytes(m: dict, ctxs) -> int:
    """One decode step over active slots with cached lengths ``ctxs``: every
    weight once (an untied embedding table only for its looked-up rows),
    each slot's live KV read, one new token's KV written per slot."""
    n = len(ctxs)
    w = param_bytes(m)
    if not m["tie_embeddings"]:
        w -= (m["vocab_size"] - n) * m["d_model"] * BF16
    return w + (sum(ctxs) + n) * kv_bytes_per_token(m)
