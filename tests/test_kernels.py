"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gating import moe_gating_topk
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.ssm_scan import ssm_scan

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ---------------------------------------------------------------------------
# flash attention (prefill)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KVH,Dh", [
    (1, 128, 4, 4, 64),       # MHA
    (2, 256, 8, 2, 64),       # GQA 4:1
    (1, 256, 4, 1, 128),      # MQA
    (2, 128, 12, 2, 64),      # qwen2-like ratio
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes(B, S, H, KVH, Dh, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KVH, Dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KVH, Dh), dtype)
    o = flash_attention(q, k, v, causal=True, interpret=True)
    o_ref = ref.attention_naive(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                - o_ref.astype(jnp.float32))))
    assert err < _tol(dtype), err


@pytest.mark.parametrize("window,softcap", [(64, None), (None, 30.0),
                                            (128, 50.0)])
def test_flash_attention_window_softcap(window, softcap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    o = flash_attention(q, k, v, causal=True, window=window,
                        softcap=softcap, interpret=True)
    o_ref = ref.attention_naive(q, k, v, causal=True, window=window,
                                softcap=softcap)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 2e-5


def test_flash_kernel_refuses_ragged_calls():
    """The kernel is dense prefill; ragged or offset calls raise rather
    than run on another path."""
    from repro.kernels import ops
    q = jnp.zeros((2, 8, 4, 16))
    kv = jnp.zeros((2, 8, 2, 16))
    with pytest.raises(ValueError, match="dense prefill"):
        ops.flash_attention(q, kv, kv, kv_lens=jnp.array([8, 5]),
                            impl="pallas_interpret")
    with pytest.raises(ValueError, match="dense prefill"):
        ops.flash_attention(q, kv, kv, q_offset=3, impl="pallas_interpret")


def test_flash_vjp_matches_naive_autodiff():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (2, 128, 4, 32), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 32), jnp.float32)
    f_ref = lambda q, k, v: (ref.attention_naive(q, k, v) ** 2).sum()
    f_new = lambda q, k, v: (ref.flash_attention_trainable(
        q, k, v, True, None, None, 64, 64) ** 2).sum()
    g_ref = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    g_new = jax.grad(f_new, (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_new):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,KVH,Dh,win,cap", [
    (2, 512, 8, 2, 64, None, None),
    (1, 256, 4, 4, 128, None, 30.0),
    (2, 512, 4, 2, 64, 128, None),
    (3, 256, 16, 2, 64, None, None),
])
def test_decode_attention(B, S, H, KVH, Dh, win, cap):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, KVH, Dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, KVH, Dh), jnp.float32)
    lens = jax.random.randint(ks[3], (B,), S // 4, S)
    o = decode_attention(q, kc, vc, lens, window=win, softcap=cap,
                         interpret=True)
    o_ref = ref.decode_attention_naive(q, kc, vc, lens, window=win,
                                       softcap=cap)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 2e-5


def test_decode_direct_jnp_path():
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 8, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.float32)
    lens = jnp.array([100, 37])
    o = ref.decode_attention_direct(q, kc, vc, lens)
    o_ref = ref.decode_attention_naive(q, kc, vc, lens)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-5


@pytest.mark.parametrize("win,cap", [(None, None), (64, None),
                                     (None, 30.0), (32, 50.0)])
def test_decode_append_mode_parity(win, cap):
    """The pinned append-mode contract (see ``ops.decode_attention``):
    attending over a read-only L-token cache with the current token's
    (k_new, v_new) merged analytically must equal committed decode over
    the same cache with the token written at slot L and lengths L+1 —
    for plain, windowed, and softcapped attention."""
    from repro.kernels import ops
    B, S, H, KVH, Dh = 3, 128, 8, 2, 64
    ks = jax.random.split(KEY, 5)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, S, KVH, Dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, S, KVH, Dh), jnp.float32)
    k_new = jax.random.normal(ks[3], (B, KVH, Dh), jnp.float32)
    v_new = jax.random.normal(ks[4], (B, KVH, Dh), jnp.float32)
    lens = jnp.array([100, 37, S - 1])     # incl. a boundary: slot S-1
    # append path through the dispatch wrapper (pinned jnp fallback even
    # when a Pallas impl is requested)
    o_append = ops.decode_attention(q, kc, vc, lens, window=win,
                                    softcap=cap, k_new=k_new, v_new=v_new,
                                    impl="pallas_interpret")
    # committed reference: write the token at slot ``lengths``, bump lens
    idx = jnp.arange(S)
    at = (idx[None, :, None, None] == lens[:, None, None, None])
    kc2 = jnp.where(at, k_new[:, None], kc)
    vc2 = jnp.where(at, v_new[:, None], vc)
    o_ref = ref.decode_attention_naive(q, kc2, vc2, lens + 1, window=win,
                                       softcap=cap)
    assert float(jnp.max(jnp.abs(o_append - o_ref))) < 1e-5


# ---------------------------------------------------------------------------
# RWKV6
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,K", [(2, 64, 2, 16), (1, 96, 4, 32)])
def test_rwkv6_kernel(B, T, H, K):
    ks = jax.random.split(KEY, 6)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5 - 1))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, K, K)) * 0.1
    o_ref, s_ref = ref.rwkv6_sequential(r, k, v, w, u, s0)
    o, sT = rwkv6_scan(r, k, v, w, u, s0, interpret=True)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(sT - s_ref))) < 1e-4


def test_rwkv6_chunked_matches_sequential():
    ks = jax.random.split(KEY, 6)
    B, T, H, K = 2, 80, 2, 16        # non-multiple of chunk (pad path)
    r = jax.random.normal(ks[0], (B, T, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, T, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, T, H, K)) * 0.5
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, T, H, K)) * 0.5 - 1))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    s0 = jnp.zeros((B, H, K, K))
    o_ref, s_ref = ref.rwkv6_sequential(r, k, v, w, u, s0)
    o, sT = ref.rwkv6_chunked(r, k, v, w, u, s0, chunk=32)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(sT - s_ref))) < 1e-4


# ---------------------------------------------------------------------------
# Mamba selective scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,Din,N,bd", [(2, 32, 64, 8, 32),
                                          (1, 64, 128, 16, 128)])
def test_ssm_kernel(B, T, Din, N, bd):
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, T, Din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Din))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (Din, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N))
    Cm = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (Din,))
    h0 = jnp.zeros((B, Din, N))
    y_ref, h_ref = ref.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
    y, hT = ssm_scan(x, dt, A, Bm, Cm, D, h0, d_block=bd, interpret=True)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(hT - h_ref))) < 1e-4


def test_ssm_kernel_carries_the_state_across_time_blocks():
    """Four time blocks of 16, from a nonzero state, with dt = 0 past
    position 37: y matches the sequential scan, and the final state is the
    sequential scan's state after position 37 (dt = 0 leaves h as it is)."""
    ks = jax.random.split(KEY, 7)
    B, T, Din, N, L = 2, 64, 128, 16, 37
    x = jax.random.normal(ks[0], (B, T, Din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Din))) * 0.1
    dt = jnp.where(jnp.arange(T)[None, :, None] < L, dt, 0.0)
    A = -jnp.exp(jax.random.normal(ks[2], (Din, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N))
    Cm = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (Din,))
    h0 = jax.random.normal(ks[6], (B, Din, N))
    y_ref, _ = ref.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
    _, h_L = ref.ssm_sequential(x[:, :L], dt[:, :L], A, Bm[:, :L], Cm[:, :L],
                                D, h0)
    y, hT = ssm_scan(x, dt, A, Bm, Cm, D, h0, d_block=64, t_block=16,
                     interpret=True)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(hT - h_L))) < 1e-4


def test_ssm_chunked_matches_sequential():
    ks = jax.random.split(KEY, 6)
    B, T, Din, N = 2, 50, 32, 8      # pad path
    x = jax.random.normal(ks[0], (B, T, Din))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, Din))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (Din, N)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, N))
    Cm = jax.random.normal(ks[4], (B, T, N))
    D = jax.random.normal(ks[5], (Din,))
    h0 = jnp.zeros((B, Din, N))
    y_ref, _ = ref.ssm_sequential(x, dt, A, Bm, Cm, D, h0)
    y, _ = ref.ssm_chunked(x, dt, A, Bm, Cm, D, h0, chunk=16)
    assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-4


# ---------------------------------------------------------------------------
# MoE gating
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,E,k", [(100, 32, 4), (64, 8, 3), (257, 384, 8)])
def test_moe_gating_kernel(T, E, k):
    logits = jax.random.normal(jax.random.PRNGKey(T), (T, E)) * 2
    w_ref, i_ref, _ = ref.topk_gating(logits, k)
    w, i = moe_gating_topk(logits, k, t_block=64, interpret=True)
    assert bool(jnp.all(i == i_ref))
    assert float(jnp.max(jnp.abs(w - w_ref))) < 1e-6


def test_blockwise_attention_vs_naive_with_lens():
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (2, 64, 4, 32))
    k = jax.random.normal(ks[1], (2, 96, 2, 32))
    v = jax.random.normal(ks[2], (2, 96, 2, 32))
    lens = jnp.array([50, 96])
    o = ref.blockwise_attention(q, k, v, causal=False, kv_lens=lens,
                                q_block=16, kv_block=32)
    o_ref = ref.attention_naive(q, k, v, causal=False, kv_lens=lens)
    assert float(jnp.max(jnp.abs(o - o_ref))) < 1e-5
