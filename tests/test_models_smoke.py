"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, output shapes + no NaNs (+ finite grads)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, list_archs
from repro.configs.shapes import SHAPES, applicable
from repro.models import transformer as T

pytestmark = pytest.mark.slow  # JAX compile-heavy (minutes on CPU)

ARCHS = list_archs()


def _batch(cfg, key, B=2, S=16):
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.n_vision_tokens:
        batch["vision_embeds"] = jax.random.normal(
            key, (B, cfg.n_vision_tokens, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_grads(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    batch = _batch(cfg, key)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: T.loss_fn(cfg, p, batch), has_aux=True)(params)
    assert jnp.isfinite(loss), arch
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    logits, _, _ = T.forward(cfg, params, batch["tokens"],
                             vision_embeds=batch.get("vision_embeds"))
    V = cfg.vocab_size
    want = ((2, 16, cfg.n_codebooks, V) if cfg.n_codebooks else (2, 16, V))
    assert logits.shape == want
    assert not bool(jnp.isnan(logits).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        # capacity drops differ between full-seq and decode; disable drops
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key)
    B, S = 2, 10
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks else (B, S)
    tokens = jax.random.randint(key, shape, 0, cfg.vocab_size)
    vis = (jax.random.normal(key, (B, cfg.n_vision_tokens, cfg.d_model),
                             jnp.float32) if cfg.n_vision_tokens else None)
    full, _, _ = T.forward(cfg, params, tokens, vision_embeds=vis)
    cache, _ = T.init_cache(cfg, B, max_seq=S + 2)
    if cfg.n_vision_tokens:
        # seed cross-attn cache from a prefill of length 1
        _, pf = T.prefill(cfg, params, tokens[:, :1], vision_embeds=vis)
        cache = _copy_cross(cfg, cache, pf)
    outs = []
    for t in range(S):
        logits, cache = T.decode_step(
            cfg, params, cache, tokens[:, t],
            jnp.full((B,), t, jnp.int32))
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    tol = 2e-4 * float(jnp.max(jnp.abs(full))) + 1e-4
    assert float(jnp.max(jnp.abs(dec - full))) < tol, arch


def _copy_cross(cfg, cache, pf_cache):
    out = {}
    for gi, (period, rep) in enumerate(cfg.groups):
        entries = []
        for li, spec in enumerate(period):
            dst = cache[f"g{gi}"][li]["mixer"]
            if spec.kind == "attn" and spec.attn_type == "cross":
                entries.append(pf_cache[f"g{gi}"][li])
            else:
                entries.append({"mixer": dst})
        out[f"g{gi}"] = tuple(entries)
    return out


def test_moe_block_dispatch_matches_dense_oracle():
    """§Perf `blockdispatch` lever: group-capacity dispatch stays exact."""
    base = get_config("granite-moe-1b-a400m").reduced()
    key = jax.random.PRNGKey(5)
    B, S = 4, 16
    tokens = jax.random.randint(key, (B, S), 0, base.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    cfg_d = dataclasses.replace(base, moe_impl="dense")
    cfg_b = dataclasses.replace(base, moe_impl="capacity",
                                capacity_factor=16.0, moe_block_dispatch=4)
    params = T.init_params(cfg_d, key)
    ld, gd = jax.value_and_grad(
        lambda p: T.loss_fn(cfg_d, p, batch)[0])(params)
    lb, gb = jax.value_and_grad(
        lambda p: T.loss_fn(cfg_b, p, batch)[0])(params)
    assert abs(float(ld) - float(lb)) < 1e-5
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gb)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_decode_append_mode_exact():
    """§Perf `cacheappend` lever: append-merge decode equals full forward."""
    cfg = get_config("gemma2-27b").reduced()
    key = jax.random.PRNGKey(6)
    params = T.init_params(cfg, key)
    B, S = 2, 10
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    full, _, _ = T.forward(cfg, params, tokens)
    cache, _ = T.init_cache(cfg, B, max_seq=S + 2)
    outs = []
    for t in range(S):
        logits, cache = T.decode_step(cfg, params, cache, tokens[:, t],
                                      jnp.full((B,), t, jnp.int32),
                                      append=True)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    assert float(jnp.max(jnp.abs(dec - full))) < 1e-4


def test_vocab_padding_exact():
    """§Perf `vocabpad` lever: padded logits masked out of softmax/argmax."""
    cfg = dataclasses.replace(
        get_config("granite-moe-1b-a400m").reduced(), vocab_pad_to=48)
    key = jax.random.PRNGKey(7)
    params = T.init_params(cfg, key)
    tokens = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    logits, _, _ = T.forward(cfg, params, tokens)
    assert logits.shape[-1] == 144          # 128 -> padded to 3*48
    assert float(logits[..., cfg.vocab_size:].max()) < -1e29
    loss, _ = T.loss_fn(cfg, params, {"tokens": tokens, "labels": tokens})
    assert jnp.isfinite(loss)


def test_moe_capacity_matches_dense_oracle():
    base = get_config("granite-moe-1b-a400m").reduced()
    key = jax.random.PRNGKey(2)
    batch = _batch(base, key)
    cfg_d = dataclasses.replace(base, moe_impl="dense")
    cfg_c = dataclasses.replace(base, moe_impl="capacity",
                                capacity_factor=16.0)
    params = T.init_params(cfg_d, key)
    ld, gd = jax.value_and_grad(lambda p: T.loss_fn(cfg_d, p, batch)[0])(params)
    lc, gc = jax.value_and_grad(lambda p: T.loss_fn(cfg_c, p, batch)[0])(params)
    assert abs(float(ld) - float(lc)) < 1e-5
    for a, b in zip(jax.tree.leaves(gd), jax.tree.leaves(gc)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_param_counts_plausible():
    # full configs should land near their advertised sizes
    expect = {
        "qwen2-1.5b": (1.2e9, 2.1e9),
        "internlm2-1.8b": (1.5e9, 2.3e9),
        "minitron-4b": (3.5e9, 5.3e9),
        "gemma2-27b": (24e9, 30e9),
        "kimi-k2-1t-a32b": (0.85e12, 1.25e12),
        "jamba-1.5-large-398b": (3.2e11, 4.7e11),
        "jamba2-3b": (2.9e9, 3.2e9),
        "rwkv6-1.6b": (1.2e9, 2.2e9),
        "granite-moe-1b-a400m": (0.9e9, 1.7e9),
        "llama-3.2-vision-11b": (8e9, 12e9),
        "musicgen-large": (1.6e9, 2.9e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_active_params_moe():
    cfg = get_config("kimi-k2-1t-a32b")
    n_active = cfg.active_param_count()
    assert n_active < 0.1 * cfg.param_count()     # a32b of 1t
    assert 20e9 < n_active < 60e9


def test_long_context_applicability():
    skip = {a: applicable(get_config(a), SHAPES["long_500k"])[0]
            for a in ARCHS}
    assert skip["rwkv6-1.6b"] and skip["jamba-1.5-large-398b"]
    assert skip["jamba2-3b"]
    assert skip["gemma2-27b"]                      # sliding-window local
    assert not skip["qwen2-1.5b"] and not skip["kimi-k2-1t-a32b"]
    assert sum(skip.values()) == 4
