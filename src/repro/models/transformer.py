"""Period-grouped scanned decoder stack.

The stack is a sequence of groups; each group is `lax.scan` over stacked
per-period parameters.  One entry point per execution mode:

  * ``loss_fn`` / ``forward(mode="train")``   — teacher-forced training
  * ``prefill``                                — full sequence, returns cache
  * ``decode_step``                            — one token against the cache

Caches generalize across families: attention layers carry (k, v) buffers,
Mamba layers carry (conv, h) states, RWKV layers carry (wkv, shifts).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

Params = Any


# ===========================================================================
# Init
# ===========================================================================
def _init_layer(cfg: ModelConfig, spec: LayerSpec, key):
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p: dict = {}
    a: dict = {}
    p["norm1"], a["norm1"] = L._norm_init(D)
    if spec.kind == "attn":
        p["mixer"], a["mixer"] = L.init_attention(cfg, spec, ks[0])
    elif spec.kind == "mamba":
        p["mixer"], a["mixer"] = SSM.init_mamba(cfg, ks[0])
    elif spec.kind == "rwkv":
        p["mixer"], a["mixer"] = SSM.init_rwkv(cfg, ks[0])
    else:
        raise ValueError(spec.kind)
    if spec.kind != "rwkv":
        if spec.mlp == "dense":
            p["norm2"], a["norm2"] = L._norm_init(D)
            p["mlp"], a["mlp"] = L.init_mlp(cfg, ks[1])
        elif spec.mlp == "moe":
            p["norm2"], a["norm2"] = L._norm_init(D)
            p["mlp"], a["mlp"] = MOE.init_moe(cfg, ks[1])
    else:
        p["norm2"], a["norm2"] = L._norm_init(D)      # rwkv channel-mix norm
    if cfg.use_post_norms:
        p["post_norm1"], a["post_norm1"] = L._norm_init(D)
        p["post_norm2"], a["post_norm2"] = L._norm_init(D)
    return p, a


def init_params(cfg: ModelConfig, key) -> Params:
    return _init(cfg, key)[0]


def param_axes(cfg: ModelConfig) -> Any:
    """Logical-axes pytree matching init_params' structure.

    Axes depend only on the config's *structure* (which sub-params exist),
    which `reduced()` preserves — so build them from the tiny config to avoid
    allocating full-size parameters.
    """
    small = cfg.reduced(repeat_cap=1)
    return _init(small, jax.random.PRNGKey(0))[1]


def _padded_vocab(cfg: ModelConfig) -> int:
    if cfg.vocab_pad_to:
        return -(-cfg.vocab_size // cfg.vocab_pad_to) * cfg.vocab_pad_to
    return cfg.vocab_size


def _init(cfg: ModelConfig, key):
    ks = jax.random.split(key, 8 + len(cfg.groups))
    pd = jnp.dtype(cfg.param_dtype)
    D, V = cfg.d_model, _padded_vocab(cfg)
    p: dict = {}
    a: dict = {}
    if cfg.n_codebooks:
        p["embed"] = (jax.random.normal(ks[0], (cfg.n_codebooks, V, D)) * 0.02).astype(pd)
        a["embed"] = (None, "vocab", "model_d")
    else:
        p["embed"] = (jax.random.normal(ks[0], (V, D)) * 0.02).astype(pd)
        a["embed"] = ("vocab", "model_d")
    if cfg.n_vision_tokens:
        p["vision_proj"] = (jax.random.normal(ks[1], (D, D)) * (D ** -0.5)).astype(pd)
        a["vision_proj"] = ("model_d", None)
    p["final_norm"] = jnp.ones((D,), jnp.float32)
    a["final_norm"] = ("model_d",)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            p["lm_head"] = (jax.random.normal(ks[2], (cfg.n_codebooks, D, V)) * 0.02).astype(pd)
            a["lm_head"] = (None, "model_d", "vocab")
        else:
            p["lm_head"] = (jax.random.normal(ks[2], (D, V)) * 0.02).astype(pd)
            a["lm_head"] = ("model_d", "vocab")

    for gi, (period, rep) in enumerate(cfg.groups):
        gkey = ks[8 + gi]
        reps_p = []
        for r in range(rep):
            rkey = jax.random.fold_in(gkey, r)
            layer_ps = []
            for li, spec in enumerate(period):
                lp, la = _init_layer(cfg, spec, jax.random.fold_in(rkey, li))
                layer_ps.append(lp)
            reps_p.append(tuple(layer_ps))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *reps_p)
        p[f"g{gi}"] = stacked
        # axes: same per-layer axes with a leading "layers" dim.
        # _init_layer was already called above for every repeat; rebuild the
        # axes tree from the first repeat's structure (axes are static).
        layer_axes = tuple(
            _init_layer(cfg.reduced(repeat_cap=1), spec,
                        jax.random.PRNGKey(0))[1]
            for spec in period)
        a[f"g{gi}"] = jax.tree.map(
            lambda ax: ("layers",) + tuple(ax),
            layer_axes,
            is_leaf=lambda v: isinstance(v, tuple) and all(
                isinstance(e, (str, type(None))) for e in v))
    return p, a


def abstract_params(cfg: ModelConfig) -> Any:
    """ShapeDtypeStruct pytree of the params — no allocation."""
    return jax.eval_shape(
        lambda k: _init(cfg, k)[0], jax.random.PRNGKey(0))


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    tree = abstract_params(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        n = 1
        for s in leaf.shape:
            n *= s
        if active_only and cfg.n_experts:
            keys = "/".join(str(k) for k in path)
            if any(w in keys for w in ("w_gate", "w_up", "w_down")) and (
                    "mlp" in keys):
                # expert weights: only top_k of n_experts active per token
                n = n * cfg.moe_top_k // cfg.n_experts
        total += n
    return total


# ===========================================================================
# Layer application
# ===========================================================================
def _apply_layer(cfg: ModelConfig, spec: LayerSpec, p, x, state, *, mode,
                 positions, lengths, vision_kv, append=False):
    aux = {"lb_loss": jnp.float32(0.0), "z_loss": jnp.float32(0.0)}
    h_in = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if spec.kind == "attn":
        if mode == "decode":
            mix_out, new_mix_state = L.attention_decode(
                cfg, spec, p["mixer"], h_in, state["mixer"], lengths,
                append=append)
        else:
            mix_out, new_mix_state = L.attention_forward(
                cfg, spec, p["mixer"], h_in,
                positions=positions, vision_kv=vision_kv)
    elif spec.kind == "mamba":
        st = state["mixer"] if state is not None else SSM.init_mamba_state(
            cfg, x.shape[0])[0]
        # in decode ``lengths`` counts cached tokens; in prefill, the rows'
        # true lengths, past which the state must not move
        mix_out, new_mix_state = SSM.mamba_forward(
            cfg, p["mixer"], h_in, st,
            lengths=lengths if mode == "prefill" else None)
    elif spec.kind == "rwkv":
        st = state["mixer"] if state is not None else SSM.init_rwkv_state(
            cfg, x.shape[0])[0]
        tm_state = {"wkv": st["wkv"], "shift_tm": st["shift_tm"]}
        mix_out, tm_new = SSM.rwkv_time_mix(cfg, p["mixer"], h_in, tm_state)
        x = x + mix_out
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        cm_out, cm_new = SSM.rwkv_channel_mix(
            cfg, p["mixer"], h2, {"shift_cm": st["shift_cm"]})
        x = constrain(x + cm_out, ("batch", "seq", None))
        new_state = {"mixer": {**tm_new, **cm_new}}
        return x, new_state, aux
    else:
        raise ValueError(spec.kind)

    if cfg.use_post_norms:
        mix_out = L.rms_norm(mix_out, p["post_norm1"], cfg.norm_eps)
    x = x + mix_out

    if spec.mlp != "none":
        h2 = L.rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.mlp == "dense":
            mlp_out = L.mlp_forward(cfg, p["mlp"], h2)
        else:
            mlp_out, moe_aux = MOE.moe_forward(cfg, p["mlp"], h2)
            aux = {k: aux[k] + moe_aux[k] for k in aux}
        if cfg.use_post_norms:
            mlp_out = L.rms_norm(mlp_out, p["post_norm2"], cfg.norm_eps)
        x = x + mlp_out
    x = constrain(x, ("batch", "seq", None))
    return x, {"mixer": new_mix_state}, aux


def _init_layer_state(cfg: ModelConfig, spec: LayerSpec, batch: int,
                      max_seq: int):
    cdt = jnp.dtype(cfg.dtype)
    if spec.kind == "attn":
        st, ax = L.init_attention_cache(cfg, spec, batch, max_seq, cdt)
    elif spec.kind == "mamba":
        st, ax = SSM.init_mamba_state(cfg, batch)
    elif spec.kind == "rwkv":
        st, ax = SSM.init_rwkv_state(cfg, batch)
    else:
        raise ValueError(spec.kind)
    return {"mixer": st}, {"mixer": ax}


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Zeroed decode cache + matching logical-axes pytree."""
    cache: dict = {}
    axes: dict = {}
    for gi, (period, rep) in enumerate(cfg.groups):
        sts, axs = [], []
        for spec in period:
            st, ax = _init_layer_state(cfg, spec, batch, max_seq)
            sts.append(st)
            axs.append(ax)
        cache[f"g{gi}"] = jax.tree.map(
            lambda s: jnp.broadcast_to(s[None], (rep,) + s.shape).copy()
            if rep > 1 else s[None],
            tuple(sts))
        axes[f"g{gi}"] = jax.tree.map(
            lambda ax: ("layers",) + tuple(ax),
            tuple(axs),
            is_leaf=lambda v: isinstance(v, tuple) and all(
                isinstance(e, (str, type(None))) for e in v))
    return cache, axes


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_seq)[0])


def cache_axes(cfg: ModelConfig):
    """Logical-axes pytree matching init_cache's structure (no allocation of
    full-size buffers — built from the structure-preserving reduced config)."""
    return init_cache(cfg.reduced(repeat_cap=1), batch=1, max_seq=8)[1]


def cache_insert(cfg: ModelConfig, cache, prefill_cache, slot, length):
    """Write a single-sequence prefill cache (batch==1) into batch slot
    ``slot`` of a decode cache. ``length`` = prompt tokens (static int).

    Self-attention leaves are (rep, B, S, KV, Dh): the first `length`
    positions are written; recurrent/cross/shift states are written whole.
    """
    new_cache = {}
    for gi, (period, rep) in enumerate(cfg.groups):
        def merge(spec_idx):
            spec = period[spec_idx]
            dst = cache[f"g{gi}"][spec_idx]["mixer"]
            src = prefill_cache[f"g{gi}"][spec_idx]["mixer"]
            out = {}
            for k, d in dst.items():
                s = src[k]
                if spec.kind == "attn" and spec.attn_type != "cross" and k in ("k", "v"):
                    out[k] = d.at[:, slot, :length].set(
                        s[:, 0, :length].astype(d.dtype))
                else:
                    out[k] = d.at[:, slot].set(s[:, 0].astype(d.dtype))
            return {"mixer": out}

        new_cache[f"g{gi}"] = tuple(merge(i) for i in range(len(period)))
    return new_cache


# ===========================================================================
# Stack
# ===========================================================================
def _run_group(cfg: ModelConfig, period, stacked_p, h, *, mode, positions,
               lengths, vision_kv, stacked_state=None, append=False):
    """Scan over the group's repeats. Returns (h, new_states|None, aux)."""

    def body(carry, xs):
        h = carry
        if mode == "decode":
            p_per, st_per = xs
        else:
            p_per, st_per = xs, None
        new_states = []
        aux_tot = {"lb_loss": jnp.float32(0.0), "z_loss": jnp.float32(0.0)}
        for i, spec in enumerate(period):
            st_i = st_per[i] if st_per is not None else None
            h, st_new, aux = _apply_layer(
                cfg, spec, p_per[i], h, st_i, mode=mode,
                positions=positions, lengths=lengths, vision_kv=vision_kv,
                append=append)
            new_states.append(st_new)
            aux_tot = {k: aux_tot[k] + aux[k] for k in aux_tot}
        ys = (tuple(new_states), aux_tot) if mode in ("prefill", "decode") \
            else aux_tot
        return h, ys

    if cfg.remat and mode == "train":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (stacked_p, stacked_state) if mode == "decode" else stacked_p
    h, ys = jax.lax.scan(body, h, xs)
    if mode in ("prefill", "decode"):
        states, aux = ys
        aux = jax.tree.map(jnp.sum, aux)
        return h, states, aux
    aux = jax.tree.map(jnp.sum, ys)
    return h, None, aux


def _embed(cfg: ModelConfig, params, tokens):
    cdt = jnp.dtype(cfg.dtype)
    emb = params["embed"].astype(cdt)
    if cfg.n_codebooks:
        # tokens: (B, S, C)
        parts = [emb[c][tokens[..., c]] for c in range(cfg.n_codebooks)]
        h = sum(parts)
    else:
        h = emb[tokens]
    return constrain(h, ("batch", "seq", None))


def _unembed(cfg: ModelConfig, params, h):
    cdt = h.dtype
    if cfg.tie_embeddings:
        w = params["embed"].astype(cdt)
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    elif cfg.n_codebooks:
        w = params["lm_head"].astype(cdt)
        logits = jnp.einsum("bsd,cdv->bscv", h, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(cdt))
    if cfg.final_softcap is not None:
        logits = cfg.final_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.final_softcap).astype(logits.dtype)
    Vp = logits.shape[-1]
    if Vp != cfg.vocab_size:
        # mask padded vocab rows out of the softmax (and argmax sampling)
        pad_mask = jnp.arange(Vp) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, jnp.asarray(-1e30, logits.dtype))
    axes = ("batch", "seq", None, "vocab") if cfg.n_codebooks else (
        "batch", "seq", "vocab")
    return constrain(logits, axes)


def forward(cfg: ModelConfig, params, tokens, *, vision_embeds=None,
            mode: str = "train", lengths=None):
    """Full-sequence pass. Returns (logits, cache|None, aux).  ``lengths``
    (B,) int32, prefill only: each row's true length in a right-padded
    batch (see ``prefill``)."""
    h = _embed(cfg, params, tokens)
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    vision_kv = None
    if cfg.n_vision_tokens:
        assert vision_embeds is not None, "vlm requires vision_embeds"
        vision_kv = jnp.einsum(
            "bnd,de->bne", vision_embeds.astype(h.dtype),
            params["vision_proj"].astype(h.dtype))
    caches = {}
    aux_tot = {"lb_loss": jnp.float32(0.0), "z_loss": jnp.float32(0.0)}
    for gi, (period, rep) in enumerate(cfg.groups):
        h, states, aux = _run_group(
            cfg, period, params[f"g{gi}"], h, mode=mode,
            positions=positions, lengths=lengths, vision_kv=vision_kv)
        if states is not None:
            caches[f"g{gi}"] = states
        aux_tot = {k: aux_tot[k] + aux[k] for k in aux_tot}
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    return logits, (caches if mode == "prefill" else None), aux_tot


def prefill(cfg: ModelConfig, params, tokens, lengths=None, *,
            vision_embeds=None):
    """Returns (logits, cache). Cache seq capacity == prompt length.

    ``lengths`` (B,) int32: each row's true length when ``tokens`` is
    right-padded.  Recurrent layers then return their state at that length;
    attention ignores it, since a causal query below the length never sees
    the padding.  A traced argument: one program serves every length of a
    padded shape.  None: every row is all of ``tokens``."""
    logits, cache, _ = forward(cfg, params, tokens, vision_embeds=vision_embeds,
                               mode="prefill", lengths=lengths)
    return logits, cache


_CACHE_APPEND_DEFAULT = False


def set_cache_append(enabled: bool) -> None:
    """§Perf lever (variant "cacheappend"): read-only cache inside the layer
    scan + one batched commit per group — see attention_decode."""
    global _CACHE_APPEND_DEFAULT
    _CACHE_APPEND_DEFAULT = enabled


def decode_step(cfg: ModelConfig, params, cache, tokens, lengths,
                append: bool | None = None):
    """One decode step.

    tokens: (B,) int32 — or (B, C) for codebook models.
    lengths: (B,) tokens already in the cache (i.e. position of new token).
    Returns (logits (B, V) or (B, C, V), new_cache).
    """
    append = _CACHE_APPEND_DEFAULT if append is None else append
    if cfg.n_codebooks:
        tok = tokens[:, None, :]            # (B,1,C)
    else:
        tok = tokens[:, None]               # (B,1)
    h = _embed(cfg, params, tok)
    B = h.shape[0]
    new_cache = {}
    for gi, (period, rep) in enumerate(cfg.groups):
        h, states, _ = _run_group(
            cfg, period, params[f"g{gi}"], h, mode="decode",
            positions=None, lengths=lengths, vision_kv=None,
            stacked_state=cache[f"g{gi}"], append=append)
        if not append:
            new_cache[f"g{gi}"] = states
        else:
            # commit per-layer deltas with ONE batched update per leaf —
            # the stacked cache is never rewritten inside the scan
            bidx = jnp.arange(B)
            merged = []
            for li, spec in enumerate(period):
                old = cache[f"g{gi}"][li]["mixer"]
                delta = states[li]["mixer"]
                if spec.kind == "attn" and spec.attn_type != "cross":
                    k = old["k"].at[:, bidx, lengths].set(delta["k_new"])
                    v = old["v"].at[:, bidx, lengths].set(delta["v_new"])
                    merged.append({"mixer": {"k": k, "v": v}})
                elif spec.kind == "attn":
                    merged.append({"mixer": old})        # cross: unchanged
                else:
                    merged.append({"mixer": delta})      # recurrent states
            new_cache[f"g{gi}"] = tuple(merged)
    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _unembed(cfg, params, h)
    return logits[:, 0], new_cache


# ===========================================================================
# Loss
# ===========================================================================
def loss_fn(cfg: ModelConfig, params, batch):
    """batch: {"tokens": (B,S[,C]), "labels": (B,S[,C]),
               optional "vision_embeds"}."""
    logits, _, aux = forward(
        cfg, params, batch["tokens"],
        vision_embeds=batch.get("vision_embeds"), mode="train")
    labels = batch["labels"]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = (lse - gold).mean()
    z = (lse ** 2).mean()
    loss = ce + 1e-4 * z + 1e-2 * aux["lb_loss"] + 1e-3 * aux["z_loss"]
    return loss, {"ce": ce, "z": z, **aux}
