"""Operations and bytes that the algorithm needs, from a model's sizes.

Counted from real prompt lengths and live KV, never from padded lengths or
``max_seq``, with every parameter read once per step, so that no change
that removes waste can push a share of a peak past 1.  Sizes come from the
configuration file (``configs/<name>.json``), not from the program: its
``model`` sizes, with the published values of the keys it cut under
``published`` (``sizes``).

Layers may differ: the counts walk the layer pattern (``layers``), so a
``local`` attention layer sees at most ``sliding_window`` keys and an
``moe`` layer holds ``n_experts`` of the published experts.
"""
from __future__ import annotations

import collections

BF16 = 2
F32 = 4


def sizes(config: dict) -> dict:
    """What the counts read of a configuration file: its ``model`` sizes,
    with its ``published`` values (of the keys it cut) under
    ``"published"``."""
    return dict(config["model"], published=config.get("published", {}))


def groups(m: dict) -> list:
    """The layer pattern as ``(period, repeat)`` pairs in order, each
    period a tuple of layer specs: from ``groups`` (``[{"period": [...],
    "repeat": n}, ...]``) or from one ``period`` repeated over
    ``n_layers``."""
    if "groups" in m:
        if "period" in m:
            raise ValueError("a model states either groups or a period")
        return [(tuple(g["period"]), g["repeat"]) for g in m["groups"]]
    period = tuple(m["period"])
    if m["n_layers"] % len(period):
        raise ValueError(f"{m['n_layers']} layers are not whole periods "
                         f"of {len(period)}")
    return [(period, m["n_layers"] // len(period))]


def layers(m: dict) -> tuple:
    """Every decoder layer in order, as ``(kind, attn_type, mlp)``."""
    return tuple((s["kind"], s["attn_type"], s["mlp"])
                 for period, rep in groups(m) for _ in range(rep)
                 for s in period)


def windows(m: dict) -> tuple:
    """Each attention layer's window in order: ``sliding_window`` for a
    ``local`` layer, None for a ``global`` one."""
    out = []
    for kind, attn, _ in layers(m):
        if kind != "attn" or attn not in ("global", "local"):
            raise ValueError(f"no counts for a {kind} layer of {attn} type")
        out.append(m["sliding_window"] if attn == "local" else None)
    return tuple(out)


def experts(m: dict) -> tuple:
    """(experts held here, published experts, experts per token)."""
    held = m["n_experts"]
    return held, m.get("published", {}).get("n_experts", held), m["moe_top_k"]


def _attn_params(m: dict) -> int:
    D, H, KV, Dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    p = D * H * Dh + 2 * D * KV * Dh + H * Dh * D
    if m.get("qkv_bias"):
        p += H * Dh + 2 * KV * Dh
    return p


def _norms(m: dict, mlp: str) -> int:
    return m["d_model"] * (1 if mlp == "none" else 2)


def _expert_params(m: dict) -> int:
    """One expert's gated MLP."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def _mlp_matrices(m: dict, mlp: str) -> int:
    """The MLP's bf16 matrices held here: a gated MLP of ``d_ff``, or the
    held experts; an expert layer's float32 router is apart."""
    if mlp == "dense":
        return 3 * m["d_model"] * m["d_ff"]
    if mlp == "moe":
        return experts(m)[0] * _expert_params(m)
    return 0


def _router(m: dict, mlp: str) -> int:
    """The router's weights: one score for each published expert."""
    return m["d_model"] * experts(m)[1] if mlp == "moe" else 0


def layer_params(m: dict, layer: tuple) -> int:
    """Parameters of one decoder layer ``(kind, attn_type, mlp)`` as held
    here: attention, the MLP (an expert layer's router and held experts),
    its norms."""
    _, _, mlp = layer
    return (_attn_params(m) + _mlp_matrices(m, mlp) + _router(m, mlp)
            + _norms(m, mlp))


def layer_flops(m: dict, layer: tuple) -> int:
    """One token through one layer's weights, attention scores apart: 2 a
    weight it uses.  An expert layer scores all published experts, and of
    the ``k`` experts the token goes to, the share ``E_held / E_pub`` lands
    on the experts held here."""
    _, _, mlp = layer
    if mlp != "moe":
        return 2 * layer_params(m, layer)
    held, pub, k = experts(m)
    return (2 * (_attn_params(m) + _norms(m, mlp) + _router(m, mlp))
            + 2 * _expert_params(m) * k * held // pub)


def _kv_per_layer(m: dict) -> int:
    """One position's K and V in one attention layer."""
    return 2 * m["n_kv_heads"] * m["head_dim"] * BF16


def kv_bytes_per_token(m: dict) -> int:
    """The cache's bytes for one position: K and V of every attention
    layer."""
    return len(windows(m)) * _kv_per_layer(m)


def unembed_flops(m: dict) -> int:
    """One position's logits over the vocabulary."""
    return 2 * m["d_model"] * m["vocab_size"]


def _seen(start: int, n: int, window) -> int:
    """Keys seen by queries at positions ``start .. start + n - 1``: query
    ``i`` sees ``i + 1``, or ``min(i + 1, window)`` in a window."""
    a, b = start + 1, start + n
    if window is None:
        return (a + b) * n // 2
    c = min(b, window)
    below = (a + c) * (c - a + 1) // 2 if c >= a else 0
    return below + window * max(0, b - max(a, window + 1) + 1)


def attn_flops(m: dict, q_len: int, kv_start: int) -> int:
    """Causal attention of ``q_len`` new queries after ``kv_start`` cached
    tokens, all layers: QK^T and PV over the keys each query may see."""
    per_key = 4 * m["n_heads"] * m["head_dim"]
    return sum(per_key * _seen(kv_start, q_len, w) for w in windows(m))


def prefill_flops(m: dict, L: int) -> int:
    """Prompt of L tokens: every layer over L tokens, causal attention, and
    the logits of the last position only."""
    return (sum(layer_flops(m, x) for x in layers(m)) * L
            + attn_flops(m, L, 0) + unembed_flops(m))


def decode_flops(m: dict, ctx: int) -> int:
    """One output token attending to ``ctx`` cached tokens and itself."""
    return (sum(layer_flops(m, x) for x in layers(m)) + attn_flops(m, 1, ctx)
            + unembed_flops(m))


def flash_flops(m: dict, L: int, window=None) -> int:
    """One flash-kernel call: one layer's causal attention over L tokens,
    in a window of ``window`` keys where given."""
    return 4 * m["n_heads"] * m["head_dim"] * _seen(0, L, window)


def flash_calls(m: dict) -> dict:
    """Flash-kernel calls of one prefill: the number of layers of each
    window (None: full causal)."""
    return collections.Counter(windows(m))


def flash_bytes(m: dict, L: int) -> int:
    """One flash-kernel call reads q, k, v and writes o once each."""
    return (2 * L * m["n_heads"] + 2 * L * m["n_kv_heads"]) * m["head_dim"] * BF16


def param_bytes(m: dict) -> int:
    """All weights, in their served types (norm scales and routers are
    float32)."""
    D, V = m["d_model"], m["vocab_size"]
    b = D * F32 + V * D * BF16
    if not m["tie_embeddings"]:
        b += D * V * BF16
    for _, _, mlp in layers(m):
        b += ((_attn_params(m) + _mlp_matrices(m, mlp)) * BF16
              + (_router(m, mlp) + _norms(m, mlp)) * F32)
    return b


def experts_hit(m: dict, n: int) -> float:
    """Held experts that a step of ``n`` tokens is expected to use, under
    uniform routing (each token's ``k`` experts drawn evenly from the
    published ones): ``E_held * (1 - (1 - k / E_pub) ** n)``."""
    held, pub, k = experts(m)
    return held * (1 - (1 - k / pub) ** n)


def decode_step_bytes(m: dict, ctxs) -> int:
    """One decode step over active slots with cached lengths ``ctxs``: every
    weight once (an untied embedding table only for its looked-up rows, an
    expert layer only for the held experts that the step is expected to
    use), each slot's live KV read (a ``local`` layer's last
    ``sliding_window - 1`` positions), one new token's KV written per
    slot."""
    n = len(ctxs)
    w = param_bytes(m)
    if not m["tie_embeddings"]:
        w -= (m["vocab_size"] - n) * m["d_model"] * BF16
    moe = sum(mlp == "moe" for _, _, mlp in layers(m))
    if moe:
        idle = experts(m)[0] - experts_hit(m, n)
        w -= round(moe * idle * _expert_params(m) * BF16)
    live = sum(sum(c if win is None else min(c, win - 1) for c in ctxs) + n
               for win in windows(m))
    return w + live * _kv_per_layer(m)
