"""A Jamba model (Mamba layers beside attention) served through the engine's
path agrees with the benchmark's plain float32 reference
(``benchmarks/serve/references/jamba_hybrid.py``) at a reduced size on the
CPU: prefill of a right-padded prompt, the slot insert, then append-mode
decode through the cache.

Model: d_model 64, d_inner 128, d_state 4, dt_rank 8, one period of (Mamba,
Mamba, Mamba, attention), no window, float32 weights and compute, seeded
random weights with every norm scale and bias spread away from 1 and 0.

Tolerance: both sides compute in float32 from the same weights and differ
only in the order of their sums (the program's chunked associative scan and
blockwise attention against a sequential scan and a dense softmax): they
agree to within 1e-6 of the largest logit (2.1e-7 to 8.2e-7 at the lengths
below), and ``TOL`` allows 5e-5 of it.  The faults below must read above
100 x ``TOL``; a state that absorbed the padding reads 1.0 to 1.5.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import EngineConfig, Request, ServingEngine

SERVE = Path(__file__).resolve().parents[1] / "benchmarks" / "serve"
LENGTHS = (1, 3, 5, 13, 100)        # none a power of two
N_DECODE = 6
TOL = 5e-5


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load(SERVE / "references" / "jamba_hybrid.py", "hybrid_ref")
WEIGHTS = _load(SERVE / "weights.py", "hybrid_weights")


def _cfg():
    m = get_config("jamba2-3b").groups[0][0][0]
    a = get_config("jamba2-3b").groups[0][0][7]
    return dataclasses.replace(
        get_config("jamba2-3b").reduced(d_model=64, vocab=256),
        groups=(((m, m, m, a), 1),), mamba_d_state=4, mamba_dt_rank=8)


def _init_rule(cfg):
    D, H, Dh, F = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    Din, R, K = cfg.d_inner, cfg.dt_rank, cfg.mamba_conv
    s_q, s_in = (2 / (D + H * Dh)) ** 0.5, (2 / (D + F)) ** 0.5
    spread = [1.0, 0.2]
    return {"normal": {
        "embed": [0.0, 1.0 / D ** 0.5],
        **{k: spread for k in ("norm1", "norm2", "final_norm", "dt_norm",
                               "B_norm", "C_norm", "Dskip")},
        **{k: [0.0, s_q] for k in ("wq", "wk", "wv", "wo")},
        **{k: [0.0, s_in] for k in ("w_up", "w_gate", "w_down")},
        "in_proj": [0.0, D ** -0.5], "conv_w": [0.0, K ** -0.5],
        "conv_b": [0.0, 0.5], "x_proj": [0.0, Din ** -0.5],
        "dt_w": [0.0, R ** -0.5], "dt_bias": [np.log(np.expm1(0.05)), 0.5],
        "A_log": [0.7, 0.5], "out_proj": [0.0, Din ** -0.5]}}


def _ref_sizes(cfg):
    return {"norm_eps": cfg.norm_eps, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "mamba_d_state": cfg.mamba_d_state, "mamba_conv": cfg.mamba_conv,
            "mamba_dt_rank": cfg.dt_rank,
            "tie_embeddings": cfg.tie_embeddings,
            "layers": tuple((s.kind, s.attn_type, s.mlp)
                            for s in cfg.layer_specs())}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = WEIGHTS.make_weights(T.abstract_params(cfg), _init_rule(cfg),
                                  2**33 + 17)
    return cfg, params


def _ref_logits(cfg, params, toks):
    w = WEIGHTS.plain(params)
    m = _ref_sizes(cfg)
    return np.asarray(REF.logits(m, w, REF.hidden(m, w, jnp.asarray(toks))))


def _padded(L):
    return max(8, 1 << (L - 1).bit_length())       # the engine's bucket


def _served_logits(cfg, params, toks, L, masked=True):
    """The engine's path, logits kept: ``toks[:L]`` right-padded to the
    engine's bucket and prefilled (with its true length unless ``masked``
    is False, the unmasked prefill), inserted into slot 1 of a 3-slot cache
    whose other slots hold another prompt, then append-mode decode steps
    fed ``toks[L:]``.  Logits (1 + len(toks) - L, V) at positions L-1 .. ."""
    P = _padded(L)
    pt = np.zeros((1, P), np.int32)
    pt[0, :L] = toks[:L]
    cache, _ = T.init_cache(cfg, 3, 256)
    other = np.arange(1, 12, dtype=np.int32)[None] % cfg.vocab_size
    _, pf = T.prefill(cfg, params, jnp.asarray(other),
                      jnp.array([11], jnp.int32))
    for slot in (0, 2):
        cache = T.cache_insert(cfg, cache, pf, slot, 11)
    lg, pf = T.prefill(cfg, params, jnp.asarray(pt),
                       jnp.array([L], jnp.int32) if masked else None)
    cache = T.cache_insert(cfg, cache, pf, 1, L)
    out = [lg[0, L - 1]]
    lengths = np.array([11, L, 11], np.int32)
    for t in toks[L:]:
        step_toks = jnp.array([3, t, 5], jnp.int32)
        lg, cache = T.decode_step(cfg, params, cache, step_toks,
                                  jnp.asarray(lengths), append=True)
        out.append(lg[1])
        lengths += 1
    return np.stack([np.asarray(x) for x in out])


def _sequence(cfg, L):
    rng = np.random.default_rng(L)
    return rng.integers(1, cfg.vocab_size, L + N_DECODE).astype(np.int32)


def _gap(served, ref):
    return float(np.max(np.abs(served - ref)) / np.max(np.abs(ref)))


@pytest.mark.parametrize("L", LENGTHS)
def test_prefill_then_decode_matches_the_reference(model, L):
    cfg, params = model
    toks = _sequence(cfg, L)
    ref = _ref_logits(cfg, params, toks)[L - 1:]
    assert _gap(_served_logits(cfg, params, toks, L), ref) < TOL


@pytest.mark.parametrize("L", LENGTHS)
def test_masked_prefill_state_is_the_unpadded_state(model, L):
    """The state a padded prefill hands decode, at the true length, is the
    state of the same prompt prefilled unpadded, to float32 rounding (the
    projections of a padded and an unpadded row sum in different orders:
    about 1e-6 of states of order 1)."""
    cfg, params = model
    toks = _sequence(cfg, L)[:L]
    P = _padded(L)
    pt = np.zeros((1, P), np.int32)
    pt[0, :L] = toks
    _, masked = T.prefill(cfg, params, jnp.asarray(pt),
                          jnp.array([L], jnp.int32))
    _, plain = T.prefill(cfg, params, jnp.asarray(toks[None]))
    for i, spec in enumerate(cfg.groups[0][0]):
        if spec.kind != "mamba":
            continue
        for k in ("h", "conv"):
            a = masked["g0"][i]["mixer"][k]
            b = plain["g0"][i]["mixer"][k]
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=f"layer {i} {k}")


@pytest.mark.parametrize("L", LENGTHS)
def test_state_that_absorbed_padding_is_outside_the_tolerance(model, L):
    """The prefill without lengths (its Mamba state runs on over the pad
    tokens) decodes off the reference, by far more than ``TOL``."""
    cfg, params = model
    toks = _sequence(cfg, L)
    ref = _ref_logits(cfg, params, toks)[L - 1:]
    assert _gap(_served_logits(cfg, params, toks, L, masked=False), ref) \
        > 100 * TOL


def test_reference_without_the_inner_norms_is_outside_the_tolerance(
        model, monkeypatch):
    """The reference with Jamba's dt, B and C norms left out (the only
    norms of width dt_rank or d_state) disagrees with the program."""
    cfg, params = model
    L = 13
    toks = _sequence(cfg, L)
    served = _served_logits(cfg, params, toks, L)
    norm = REF.rms_norm
    inner = (cfg.dt_rank, cfg.mamba_d_state)
    monkeypatch.setattr(REF, "rms_norm", lambda x, s, eps: x if x.shape[-1]
                        in inner else norm(x, s, eps))
    assert _gap(served, _ref_logits(cfg, params, toks)[L - 1:]) > 100 * TOL


def test_engine_serves_the_reference_tokens(model):
    """Greedy requests through ``ServingEngine`` (padded prefill with the
    true length, insert, append decode, on-device argmax): each served
    token is the reference's best at its position, within ``TOL`` of the
    logits' scale."""
    cfg, params = model
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=128))
    prompts = {L: _sequence(cfg, L)[:L].tolist() for L in LENGTHS}
    for L, p in prompts.items():
        eng.submit(Request(rid=L, prompt=p, max_new_tokens=N_DECODE))
    done = eng.run()
    assert len(done) == len(LENGTHS)
    for r in done:
        seq = prompts[r.rid] + r.generated
        ref = _ref_logits(cfg, params, np.asarray(seq, np.int32))
        L = r.rid
        rows = ref[L - 1:L - 1 + N_DECODE]
        got = rows[np.arange(N_DECODE), r.generated]
        assert np.all(rows.max(-1) - got <= TOL * np.abs(rows).max())


def test_prefill_and_insert_spans_count_padding_state_and_kv(model):
    """With the tracer on, each ``prefill`` span carries the padding of its
    bucket and each ``insert`` span the bytes written into the slot: every
    Mamba layer's h (d_inner x d_state float32) and conv state ((d_conv-1) x
    d_inner in the compute dtype) whole, and the prompt's K and V in the
    attention layer."""
    from repro.obs import SpanTracer
    cfg, params = model
    tracer = SpanTracer(enabled=True)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=128),
                        tracer=tracer)
    for L in LENGTHS:
        eng.submit(Request(rid=L, prompt=_sequence(cfg, L)[:L].tolist(),
                           max_new_tokens=2))
    eng.run()
    spans = [e for e in tracer.events if e["ph"] == "X"]
    prefill = {e["args"]["rid"]: e["args"] for e in spans
               if e["name"] == "prefill"}
    insert = {e["args"]["rid"]: e["args"] for e in spans
              if e["name"] == "insert"}
    assert sorted(prefill) == sorted(insert) == sorted(LENGTHS)
    Din, N, K = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_conv
    itemsize = jnp.dtype(cfg.dtype).itemsize
    state = 3 * (Din * N * 4 + (K - 1) * Din * itemsize)    # 3 Mamba layers
    kv = 2 * cfg.n_kv_heads * cfg.head_dim * itemsize       # 1 attention
    for L in LENGTHS:
        assert prefill[L]["pad_tokens"] == _padded(L) - L
        assert insert[L]["state_bytes"] == state
        assert insert[L]["kv_bytes"] == L * kv


def test_one_prefill_program_per_bucket(monkeypatch):
    """Prompts of 9 to 16 tokens share the bucket of 16: the engine traces
    one prefill program for all of them, the true length an argument."""
    cfg = get_config("qwen2-1.5b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    traces = []
    orig = T.prefill

    def counting(*a, **k):
        traces.append(a[2].shape)
        return orig(*a, **k)

    monkeypatch.setattr(T, "prefill", counting)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_seq=64))
    for L in range(9, 17):
        eng.submit(Request(rid=L, prompt=list(range(1, L + 1)),
                           max_new_tokens=1))
    eng.run()
    assert traces == [(1, 16)]
    assert list(eng._prefill_cache) == [16]
