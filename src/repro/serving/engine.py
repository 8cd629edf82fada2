"""Single-instance serving engine: continuous batching over the JAX model.

Runs real models on CPU (tests/examples) and is shaped like the TPU data
plane: slot-based batch, paged-block admission control (kv_cache.py),
bucketed prefill compilation, greedy/temperature sampling, TPOT/TTFT
metrics.  Chunked prefill is approximated at request granularity: at most
``prefill_budget_tokens`` of prompt work is admitted per engine step.

Each step records its phases as wall-clock spans on the ``engine`` track of
a ``repro.obs.trace.SpanTracer`` (the module's disabled ``TRACER`` unless one
is given), each with arg ``step``, the step counter:

* ``step``: the whole step.
* ``admit``: admission; ``padded_tokens`` (the prefill programs' lengths),
  ``rejected``, and ``stop`` (``no_slot`` | ``no_blocks`` | ``budget``) when
  it left requests queued.  Inside it, per request (arg ``rid``):
  ``prefill`` (``new_program`` when the padded length had no program yet;
  ``pad_tokens``, the padded length less the prompt's), ``insert``
  (``state_bytes``, the recurrent and conv state written whole into the
  slot; ``kv_bytes``, the prompt's K and V) and ``first_token``.
* ``decode``: the batch's input tokens and temperatures, and the dispatch of
  the decode program, which also samples every slot's next token on the
  device.
* ``sample``: the one read of the batch's tokens to the host, then per slot
  the bookkeeping and retirement, which touch only host data; ``syncs``, the
  reads from device to host made in it: 1 (``host_syncs`` counts every such
  read, the first token of each admitted request included).

The decode program takes the argmax of every slot's logits; where some
active slot has a temperature above 0 the host picks the program's other
variant, which draws those slots' tokens from ``logits / temperature`` with
a key folded from the engine's seed and the step counter.  The all-greedy
variant passes no temperatures and draws nothing; both are compiled when
the engine is built.  The prefill and decode programs run under
``jax.named_scope`` of those names, so the device ops of a profiler trace
carry the phase too.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.serving.kv_cache import BlockManager, OutOfBlocks


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int
    temperature: float = 0.0
    arrival_t: float = 0.0
    # filled during serving:
    admit_t: float = -1.0            # taken from the queue for prefill
    generated: list[int] = dataclasses.field(default_factory=list)
    first_token_t: float = -1.0
    finish_t: float = -1.0
    slot: int = -1

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens

    @property
    def ttft(self) -> float:
        return self.first_token_t - self.arrival_t

    @property
    def tpot(self) -> float:
        n = len(self.generated)
        if n <= 1 or self.first_token_t < 0:
            return 0.0
        return (self.finish_t - self.first_token_t) / (n - 1)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8
    max_seq: int = 256
    block_size: int = 16
    prefill_budget_tokens: int = 512
    seed: int = 0


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig,
                 tracer: Optional[obs_trace.SpanTracer] = None):
        self.cfg = cfg
        self.params = params
        self.ecfg = ecfg
        self.tracer = tracer if tracer is not None else obs_trace.TRACER
        self.cache, _ = T.init_cache(cfg, ecfg.max_batch, ecfg.max_seq)
        self.blocks = BlockManager(
            n_blocks=ecfg.max_batch * (ecfg.max_seq // ecfg.block_size),
            block_size=ecfg.block_size)
        self.lengths = np.zeros(ecfg.max_batch, dtype=np.int32)
        self.slot_req: list[Optional[Request]] = [None] * ecfg.max_batch
        self.queue: deque[Request] = deque()
        self.finished: list[Request] = []
        # first tokens split ``key``; decode steps fold ``decode_key``
        self.key, decode_key = jax.random.split(jax.random.PRNGKey(ecfg.seed))

        # append-mode decode (§Perf "cacheappend"): exact, and avoids the
        # full-cache rewrite per step — the serving default
        def decode(p, c, t, l, sampling):
            """The batch's next tokens (int32[B]) and the new cache.
            ``sampling`` is None (all greedy: the argmax) or (temperatures
            float32[B], step): slots above 0 draw at their temperature."""
            with jax.named_scope("decode"):
                logits, c = T.decode_step(cfg, p, c, t, l, append=True)
                tok = jnp.argmax(logits, -1)
                if sampling is not None:
                    temps, step = sampling
                    hot = temps > 0
                    lg = logits.astype(jnp.float32) / jnp.where(
                        hot, temps, 1.0)[:, None]
                    drawn = jax.random.categorical(
                        jax.random.fold_in(decode_key, step), lg)
                    tok = jnp.where(hot, drawn, tok)
                return tok, c
        self._decode = jax.jit(decode)
        # compile both variants now, so that neither the first step with a
        # temperature nor the first all-greedy one compiles while serving
        B, zeros = ecfg.max_batch, jnp.zeros(ecfg.max_batch, jnp.int32)
        for sampling in (None, (np.zeros(B, np.float32), 0)):
            jax.block_until_ready(
                self._decode(params, self.cache, zeros, zeros, sampling))
        self._prefill_cache: dict[int, Callable] = {}
        self._state_bytes, self._kv_bytes = _slot_bytes(cfg, self.cache,
                                                        ecfg.max_batch)
        self.steps = 0
        self.host_syncs = 0              # reads from device to host

    # -- public -----------------------------------------------------------
    def submit(self, req: Request) -> None:
        # The real engine stamps requests with *epoch* wall time: its
        # latencies are reported against client-visible arrival clocks,
        # not a sim clock — the one layer where time.time() is correct.
        req.arrival_t = req.arrival_t or time.time()  # lint: allow[sim-clock-purity]
        self.queue.append(req)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slot_req)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        while (self.queue or self.n_active) and self.steps < max_steps:
            self.step()
        return self.finished

    # -- internals ----------------------------------------------------------
    def _prefill_fn(self, padded_len: int):
        if padded_len not in self._prefill_cache:
            cfg = self.cfg

            def prefill(p, toks, lengths):
                with jax.named_scope("prefill"):
                    return T.prefill(cfg, p, toks, lengths)
            self._prefill_cache[padded_len] = jax.jit(prefill)
        return self._prefill_cache[padded_len]

    def _admit(self, args: dict) -> None:
        """Prefills queued requests into free slots, in order, while the
        step's prompt budget lasts.  With the tracer on, notes in ``args``
        (the ``admit`` span's) the padded tokens, the requests rejected and
        why admission stopped with requests queued."""
        span, step = self.tracer.span, self.steps
        budget = self.ecfg.prefill_budget_tokens
        padded_tokens = rejected = 0
        stop = None
        while self.queue and budget > 0:
            req = self.queue[0]
            L = len(req.prompt)
            if L + req.max_new_tokens > self.ecfg.max_seq:
                self.queue.popleft()
                # epoch stamp, same clock as arrival_t (see submit())
                req.finish_t = time.time()  # lint: allow[sim-clock-purity]
                self.finished.append(req)      # rejected: too long
                rejected += 1
                continue
            free_slots = [i for i, r in enumerate(self.slot_req) if r is None]
            if not free_slots:
                stop = "no_slot"
                break
            if not self.blocks.can_allocate(L + req.max_new_tokens):
                stop = "no_blocks"
                break
            if L > budget and self.n_active > 0:
                stop = "budget"                 # defer big prefill (chunking)
                break
            self.queue.popleft()
            # epoch stamp, same clock as arrival_t (see submit())
            req.admit_t = time.time()  # lint: allow[sim-clock-purity]
            slot = free_slots[0]
            self.blocks.allocate(req.rid, L)
            padded = max(8, 1 << (L - 1).bit_length())
            toks = np.zeros((1, padded), np.int32)
            toks[0, :L] = req.prompt
            new = padded not in self._prefill_cache
            with span("prefill", track="engine", step=step, rid=req.rid,
                      new_program=new, pad_tokens=padded - L):
                # the true length is an argument: one program per bucket
                logits, pf_cache = self._prefill_fn(padded)(
                    self.params, jnp.asarray(toks), np.array([L], np.int32))
            with span("insert", track="engine", step=step, rid=req.rid,
                      state_bytes=self._state_bytes,
                      kv_bytes=L * self._kv_bytes):
                self.cache = T.cache_insert(self.cfg, self.cache, pf_cache,
                                            slot, L)
            with span("first_token", track="engine", step=step, rid=req.rid):
                first = self._sample(logits[0, L - 1], req)
            req.generated.append(first)
            # epoch stamp, same clock as arrival_t (see submit())
            req.first_token_t = time.time()  # lint: allow[sim-clock-purity]
            self.blocks.append_token(req.rid)
            req.slot = slot
            self.slot_req[slot] = req
            # lengths = number of tokens whose KV is in the cache
            self.lengths[slot] = L
            budget -= L
            padded_tokens += padded
            if req.done:
                self._retire(req)
        if self.tracer.enabled:
            args.update(padded_tokens=padded_tokens, rejected=rejected,
                        stop=stop or ("budget" if self.queue else None))

    def _sample(self, logits, req: Request) -> int:
        """A request's first token from the prompt's last logits (V,)."""
        if req.temperature <= 0:
            tok = jnp.argmax(logits)
        else:
            self.key, sub = jax.random.split(self.key)
            tok = jax.random.categorical(sub, logits / req.temperature)
        self.host_syncs += 1             # int() waits for the device
        return int(tok)

    def _retire(self, req: Request) -> None:
        # epoch stamp, same clock as arrival_t (see submit())
        req.finish_t = time.time()  # lint: allow[sim-clock-purity]
        self.finished.append(req)
        self.blocks.free_seq(req.rid)
        if req.slot >= 0 and self.slot_req[req.slot] is req:
            self.slot_req[req.slot] = None
            self.lengths[req.slot] = 0
        req.slot = -1

    def step(self) -> None:
        self.steps += 1
        span = self.tracer.span
        with span("step", track="engine", step=self.steps):
            with span("admit", track="engine", step=self.steps) as args:
                self._admit(args)
            active = [r for r in self.slot_req if r is not None]
            if active:
                self._decode_and_sample(active)

    def _decode_and_sample(self, active: list[Request]) -> None:
        span, step = self.tracer.span, self.steps
        with span("decode", track="engine", step=step):
            toks = np.zeros(self.ecfg.max_batch, np.int32)
            temps = np.zeros(self.ecfg.max_batch, np.float32)
            for r in active:
                toks[r.slot] = r.generated[-1]
                temps[r.slot] = r.temperature
            # the greedy-only variant unless some slot samples
            sampling = (temps, step) if (temps > 0).any() else None
            # decode writes the new token's KV at position `lengths`
            next_toks, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(self.lengths), sampling)
        syncs = self.host_syncs
        with span("sample", track="engine", step=step) as args:
            next_toks = jax.device_get(next_toks).tolist()
            self.host_syncs += 1         # the step's one read to the host
            # epoch stamp, same clock as arrival_t (see submit())
            now = time.time()  # lint: allow[sim-clock-purity]
            for r in active:
                r.generated.append(next_toks[r.slot])
                self.lengths[r.slot] += 1
                try:
                    self.blocks.append_token(r.rid)
                except OutOfBlocks:
                    r.max_new_tokens = len(r.generated)
                if r.done or self.lengths[r.slot] + 1 >= self.ecfg.max_seq:
                    r.finish_t = now
                    self._retire(r)
            args["syncs"] = self.host_syncs - syncs


def _slot_bytes(cfg: ModelConfig, cache, batch: int) -> tuple[int, int]:
    """What ``cache_insert`` writes into one slot: (bytes of the state that
    it writes whole, bytes of one position's self-attention K and V)."""
    state = kv = 0
    for gi, (period, _) in enumerate(cfg.groups):
        for spec, layer in zip(period, cache[f"g{gi}"]):
            n = sum(x.nbytes for x in layer["mixer"].values()) // batch
            if spec.kind == "attn" and spec.attn_type != "cross":
                kv += n // layer["mixer"]["k"].shape[2]
            else:
                state += n
    return state, kv
