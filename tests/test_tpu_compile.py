"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

The TPU compiler rejects what interpret mode accepts: blocks off the (8, 128)
tiling, single-row dynamic loads of 16-bit data, programs larger than the
chip's memory.  These compiles catch that without a chip, at the widths the
chip runs: the Pallas kernels of the serving path, and the full-width
``qwen2-1.5b`` prefill and decode steps.  Nothing runs, so nothing here
says anything about results or speed.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every pytest worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.launch.hlo_analysis import summarize_cost
from repro.models import transformer as T

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("S", [8, 128, 1024])
def test_flash_attention_qwen2_widths(one_chip, S):
    from repro.kernels.flash_attention import flash_attention
    cfg = get_config("qwen2-1.5b")
    q = _sds(one_chip, (1, S, cfg.n_heads, cfg.head_dim))
    kv = _sds(one_chip, (1, S, cfg.n_kv_heads, cfg.head_dim))
    _assert_kernel(_compile(flash_attention, q, kv, kv))


def test_qwen2_prefill_step_full_width(one_chip):
    cfg = get_config("qwen2-1.5b")
    params = _on(one_chip, T.abstract_params(cfg))
    toks = _sds(one_chip, (1, 1024), jnp.int32)
    with ops.default_impl("pallas"):
        compiled = _compile(lambda p, t: T.prefill(cfg, p, t), params, toks)
    _assert_kernel(compiled)
    assert summarize_cost(compiled)["memory"]["peak_bytes_per_device"] \
        < V5E_HBM_BYTES


def test_qwen2_append_decode_step_full_width(one_chip):
    cfg = get_config("qwen2-1.5b")
    params = _on(one_chip, T.abstract_params(cfg))
    cache = _on(one_chip, T.abstract_cache(cfg, 8, 2048))
    toks = _sds(one_chip, (8,), jnp.int32)
    lens = _sds(one_chip, (8,), jnp.int32)
    compiled = _compile(
        lambda p, c, t, l: T.decode_step(cfg, p, c, t, l, append=True),
        params, cache, toks, lens)
    assert summarize_cost(compiled)["memory"]["peak_bytes_per_device"] \
        < V5E_HBM_BYTES


def test_decode_attention_qwen2_widths(one_chip):
    from repro.kernels.decode_attention import decode_attention
    cfg = get_config("qwen2-1.5b")
    B, S = 8, 2048
    q = _sds(one_chip, (B, cfg.n_heads, cfg.head_dim))
    cache = _sds(one_chip, (B, S, cfg.n_kv_heads, cfg.head_dim))
    lens = _sds(one_chip, (B,), jnp.int32)
    _assert_kernel(_compile(decode_attention, q, cache, cache, lens))


def test_rwkv6_scan_rwkv6_widths(one_chip):
    from repro.kernels.rwkv6_scan import rwkv6_scan
    cfg = get_config("rwkv6-1.6b")
    B, S, H, K = 1, 1024, cfg.rwkv_heads, cfg.rwkv_head_dim
    assert (H, K) == (32, 64)
    seq = _sds(one_chip, (B, S, H, K))
    _assert_kernel(_compile(
        rwkv6_scan, seq, seq, seq, _sds(one_chip, (B, S, H, K), jnp.float32),
        _sds(one_chip, (H, K), jnp.float32),
        _sds(one_chip, (B, H, K, K), jnp.float32)))


def test_ssm_scan_jamba_widths(one_chip):
    from repro.kernels.ssm_scan import ssm_scan
    cfg = get_config("jamba-1.5-large-398b")
    B, S, Din, N = 1, 256, cfg.d_inner, cfg.mamba_d_state
    f32 = jnp.float32
    _assert_kernel(_compile(
        ssm_scan, _sds(one_chip, (B, S, Din)), _sds(one_chip, (B, S, Din), f32),
        _sds(one_chip, (Din, N), f32), _sds(one_chip, (B, S, N)),
        _sds(one_chip, (B, S, N)), _sds(one_chip, (Din,), f32),
        _sds(one_chip, (B, Din, N), f32)))


def test_jamba2_prefill_step_full_width(one_chip):
    """The served prefill of ``jamba2-3b`` at its longest bucket: the Pallas
    scan at d_inner 5120 over 2048 positions in 26 layers, the flash kernel
    at 20 query heads over 1 KV head, the true length an argument."""
    cfg = get_config("jamba2-3b")
    params = _on(one_chip, T.abstract_params(cfg))
    toks = _sds(one_chip, (1, 2048), jnp.int32)
    lens = _sds(one_chip, (1,), jnp.int32)
    with ops.default_impl("pallas"):
        compiled = _compile(lambda p, t, n: T.prefill(cfg, p, t, n),
                            params, toks, lens)
    _assert_kernel(compiled)
    assert summarize_cost(compiled)["memory"]["peak_bytes_per_device"] \
        < V5E_HBM_BYTES


def test_jamba2_append_decode_step_full_width(one_chip):
    """The served decode of ``jamba2-3b``: 128 slots of recurrent state and
    4096 positions of KV, append mode."""
    cfg = get_config("jamba2-3b")
    params = _on(one_chip, T.abstract_params(cfg))
    cache = _on(one_chip, T.abstract_cache(cfg, 128, 4096))
    toks = _sds(one_chip, (128,), jnp.int32)
    lens = _sds(one_chip, (128,), jnp.int32)
    compiled = _compile(
        lambda p, c, t, l: T.decode_step(cfg, p, c, t, l, append=True),
        params, cache, toks, lens)
    assert summarize_cost(compiled)["memory"]["peak_bytes_per_device"] \
        < V5E_HBM_BYTES


def test_moe_gating_granite_widths(one_chip):
    from repro.kernels.moe_gating import moe_gating_topk
    cfg = get_config("granite-moe-1b-a400m")
    logits = _sds(one_chip, (1024, cfg.n_experts), jnp.float32)
    _assert_kernel(_compile(
        lambda lg: moe_gating_topk(lg, cfg.moe_top_k), logits))
