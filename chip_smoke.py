"""Serve full-width qwen2-1.5b on one TPU chip through the engine's normal path.

    python chip_smoke.py

Builds ``qwen2-1.5b`` at its published widths (random bf16 weights from a
fixed seed) on the chip and serves seeded requests through
``ServingEngine`` -> ``models/transformer.py`` -> ``kernels/ops.py``, where
prefill runs the Pallas flash kernel.  It then checks, on the same chip:

  * every request finished with all its tokens, none rejected;
  * the Pallas flash kernel agrees with the jnp attention at Qwen2-1.5B
    widths (``ATTN_ATOL`` / ``ATTN_RTOL``);
  * every token the engine chose scores within ``LOGIT_TOL`` of the best
    logit of a full-sequence forward over the same tokens with jnp
    attention (random bf16 weights give near-tied logits, so exact token
    equality is not the test);
  * the engine's compiled prefill program contains the Pallas kernel.

The lines before the last are informational.  The last line is
``{"ok": true, "device": {...}}``.  With no TPU, or when any check fails,
the script exits non-zero without printing it.

Compile cache: JAX keeps it in ``$JAX_COMPILATION_CACHE_DIR`` when that is
set, and otherwise in ``.jax_cache`` next to this script, so a second run
from the same checkout skips most compilation.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path
from typing import Mapping, Optional

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving.engine import (EngineConfig, Request,  # noqa: E402
                                  ServingEngine)

SEED = 0
ARCH = "qwen2-1.5b"
ENGINE = EngineConfig(max_batch=8, max_seq=2048)
N_REQUESTS = 12          # > max_batch, so slots are reused
PROMPT_LENS = (16, 1000)  # prefill pads these to 16 ... 1024
NEW_TOKENS = 32
KERNEL_SEQ_LENS = (8, 128, 1024)

# bf16 output of two attention implementations: a few ulps (2^-7 relative).
ATTN_ATOL = ATTN_RTOL = 2e-2
# Best reference logit minus the logit of the engine's token.  Logits of
# these random weights stay below 4 in magnitude, where a bf16 ulp is 2^-6;
# 2^-3 allows 8 ulps for two bf16 paths that round in different orders.
LOGIT_TOL = 0.125


def compile_cache_dir(environ: Mapping[str, str]) -> Optional[Path]:
    """Directory to set for JAX's persistent compilation cache, or None
    when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads it itself)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return ROOT / ".jax_cache"


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent-cache
    reads included), and persistent-cache hits, from JAX's monitoring
    events."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def init_params(cfg: ModelConfig, seed: int):
    """Parameters built by one program on the default device."""
    params = jax.jit(functools.partial(T.init_params, cfg))(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def make_requests(vocab: int, n: int, lens: tuple[int, int],
                  new_tokens: int, seed: int) -> list[Request]:
    """``n`` requests with prompt lengths spread geometrically over
    ``lens``, in a seeded random order."""
    rng = np.random.default_rng(seed)
    lengths = np.geomspace(lens[0], lens[1], n).round().astype(int)
    return [Request(rid=i, prompt=rng.integers(0, vocab, L).tolist(),
                    max_new_tokens=new_tokens)
            for i, L in enumerate(rng.permutation(lengths))]


def serve(cfg: ModelConfig, params, requests: list[Request],
          ecfg: EngineConfig) -> ServingEngine:
    engine = ServingEngine(cfg, params, ecfg)
    for r in requests:
        engine.submit(r)
    engine.run()
    jax.block_until_ready(engine.cache)
    return engine


def check_served(engine: ServingEngine, requests: list[Request]) -> None:
    done = {r.rid: r for r in engine.finished}
    if sorted(done) != sorted(r.rid for r in requests):
        raise SystemExit(f"finished {sorted(done)}, submitted "
                         f"{sorted(r.rid for r in requests)}")
    short = {rid: len(r.generated) for rid, r in done.items()
             if len(r.generated) != r.max_new_tokens}
    if short:
        raise SystemExit(f"requests without all their tokens "
                         f"(rid: tokens): {short}")


def kernel_parity(cfg: ModelConfig, seq_lens, impl: str) -> float:
    """Worst ratio of |kernel - jnp| to ATTN_ATOL + ATTN_RTOL·|jnp| over
    causal attention at the config's head widths; fails above 1."""
    worst = 0.0
    kernel = jax.jit(functools.partial(ops.flash_attention, impl=impl))
    plain = jax.jit(functools.partial(ops.flash_attention, impl="jnp"))
    dt = jnp.dtype(cfg.dtype)
    for S in seq_lens:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(S), 3)
        q = jax.random.normal(kq, (1, S, cfg.n_heads, cfg.head_dim), dt)
        k = jax.random.normal(kk, (1, S, cfg.n_kv_heads, cfg.head_dim), dt)
        v = jax.random.normal(kv, (1, S, cfg.n_kv_heads, cfg.head_dim), dt)
        got = kernel(q, k, v).astype(jnp.float32)
        want = plain(q, k, v).astype(jnp.float32)
        ratio = float(jnp.max(jnp.abs(got - want)
                              / (ATTN_ATOL + ATTN_RTOL * jnp.abs(want))))
        print(f"flash kernel vs jnp, S={S}: worst |err|/tol {ratio:.4f}")
        worst = max(worst, ratio)
    if worst > 1.0:
        raise SystemExit(f"flash kernel disagrees with jnp attention: "
                         f"|err|/tol {worst:.4f} > 1")
    return worst


def reference_margins(cfg: ModelConfig, params, finished: list[Request],
                      seq_len: int) -> np.ndarray:
    """For each request and generated position: the best logit of a
    full-sequence jnp-attention forward over prompt + generated tokens,
    minus the logit of the token the engine chose there."""
    n = finished[0].max_new_tokens

    def margins(params, toks, pos, chosen):
        logits, _, _ = T.forward(cfg, params, toks)
        lg = logits[0, pos].astype(jnp.float32)               # (n, V)
        picked = jnp.take_along_axis(lg, chosen[:, None], axis=-1)[:, 0]
        return lg.max(axis=-1) - picked

    fn = jax.jit(margins)
    out = []
    with ops.default_impl("jnp"):
        for r in finished:
            seq = r.prompt + r.generated[:-1]
            toks = np.zeros((1, seq_len), np.int32)
            toks[0, :len(seq)] = seq
            pos = len(r.prompt) - 1 + np.arange(n, dtype=np.int32)
            out.append(np.asarray(fn(params, toks, pos,
                                     np.asarray(r.generated, np.int32))))
    return np.stack(out)


def check_margins(m: np.ndarray) -> None:
    print(f"engine tokens vs jnp reference: {m.size} positions, "
          f"{float(np.mean(m == 0)):.4f} exact argmax, margin max "
          f"{float(m.max()):.5f} mean {float(m.mean()):.5f} "
          f"(tolerance {LOGIT_TOL})")
    if float(m.max()) > LOGIT_TOL:
        raise SystemExit(f"engine token scores {float(m.max()):.5f} below "
                         f"the reference's best logit (> {LOGIT_TOL})")


def prefill_has_kernel(engine: ServingEngine, padded: int) -> bool:
    """True if the engine's compiled prefill program for ``padded`` tokens
    holds a Pallas TPU kernel."""
    toks = jnp.zeros((1, padded), jnp.int32)
    text = engine._prefill_fn(padded).lower(
        engine.params, toks).compile().as_text()
    return "tpu_custom_call" in text


def main() -> None:
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform!r}")
    cache = compile_cache_dir(os.environ)
    if cache is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    clock = CompileClock()

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_params(cfg, SEED)
    print(f"init: {time.perf_counter() - t0:.2f} s, params "
          f"{tree_bytes(params)} bytes ({cfg.param_dtype})")

    requests = make_requests(cfg.vocab_size, N_REQUESTS, PROMPT_LENS,
                             NEW_TOKENS, SEED)
    c0, t0 = clock.seconds, time.perf_counter()
    engine = serve(cfg, params, requests, ENGINE)
    serve_s, compile_s = time.perf_counter() - t0, clock.seconds - c0
    check_served(engine, requests)
    print(f"kv cache: {tree_bytes(engine.cache)} bytes "
          f"(batch {ENGINE.max_batch} x {ENGINE.max_seq})")
    print(f"served: {len(engine.finished)} requests, "
          f"{sum(len(r.generated) for r in engine.finished)} tokens, "
          f"{engine.steps} engine steps, "
          f"{len(engine._prefill_cache)} prefill programs "
          f"(padded {sorted(engine._prefill_cache)})")
    print(f"serve: {serve_s:.2f} s wall, of which {compile_s:.2f} s "
          f"tracing + compiling, {serve_s - compile_s:.2f} s the rest")

    if not prefill_has_kernel(engine, max(engine._prefill_cache)):
        raise SystemExit("compiled prefill holds no Pallas kernel "
                         "(no tpu_custom_call)")
    print("compiled prefill holds the Pallas kernel (tpu_custom_call)")
    kernel_parity(cfg, KERNEL_SEQ_LENS, impl="pallas")
    check_margins(reference_margins(cfg, params, engine.finished,
                                    ENGINE.max_seq))

    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    print(f"compile total: {clock.seconds:.2f} s, persistent-cache hits "
          f"{clock.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
