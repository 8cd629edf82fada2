"""The open loop around ``ServingEngine.submit`` / ``step`` and the
records that the metric readers in ``metrics/`` take their numbers from.

Every timestamp is ``time.time()``, the clock the engine stamps
``arrival_t`` and ``first_token_t`` with.  Requests are timed from their due
time, not from when the harness got round to submitting them.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import Optional

import jax

WARM_RID = 1 << 40          # warm-up request ids start here


@dataclasses.dataclass
class ReqRec:
    rid: int
    prompt_len: int
    max_new: int
    due: float                       # epoch seconds
    submit_t: float = math.nan
    admit_step_t0: float = math.nan  # start of the step that admitted it
    first_t: float = math.nan
    token_t: list = dataclasses.field(default_factory=list)
    rejected: bool = False


@dataclasses.dataclass
class StepRec:
    index: int
    t0: float
    t1: float
    admitted: list                   # rids that got their first token
    prompt_tokens: int               # prompt tokens admitted in this step
    decode_ctx: list                 # cached tokens seen by each decode token
    occupancy: int                   # slots in use while the step decoded


class CompileClock:
    """Counts of tracing, compiling and persistent-cache reads, from JAX's
    monitoring events (copied from chip_smoke.py, with counts added)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.traces = 0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += secs
        if event == self.EVENTS[0]:
            self.traces += 1
        elif event == self.EVENTS[2]:
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def events(self) -> int:
        return self.traces + self.compiles + self.cache_hits


@dataclasses.dataclass
class Timeline:
    """Epoch times that divide a run: traffic starts at ``start``; the
    measured window is [w0, w1); with tracing, [w1, t1) is profiled."""
    start: float
    w0: float
    w1: float
    t1: float


class OpenLoop:
    def __init__(self, engine, request_cls):
        self.engine = engine
        self.Request = request_cls
        self.reqs: dict[int, ReqRec] = {}
        self.steps: list[StepRec] = []
        self.lateness: list[float] = []
        self.marks: dict[str, int] = {}
        self.trace_on = self.trace_off = math.nan

    # -- warm-up ---------------------------------------------------------
    def warm(self, prompts: list[list[int]]) -> None:
        """Serve one request of each prompt through the engine's own path:
        its prefill bucket, cache insert, first-token sampling and a decode
        step, so that each shape's programs are compiled before the window."""
        for i, p in enumerate(prompts):
            self.engine.submit(self.Request(rid=WARM_RID + i, prompt=p,
                                            max_new_tokens=2, arrival_t=1.0))
        while self.engine.queue or self.engine.n_active:
            self.engine.step()

    # -- one step ----------------------------------------------------------
    def _step(self, annotate: bool) -> None:
        eng = self.engine
        mb = eng.ecfg.max_batch
        touched = [r for r in eng.slot_req if r is not None]
        touched += list(itertools.islice(eng.queue, mb))
        before = {r.rid: len(r.generated) for r in touched}
        n_fin = len(eng.finished)
        idx = len(self.steps)
        t0 = time.time()
        if annotate:
            with jax.profiler.TraceAnnotation(f"bench_step_{idx}"):
                eng.step()
        else:
            eng.step()
        t1 = time.time()
        admitted, prompt_tokens, ctx = [], 0, []
        for r in touched:
            rec = self.reqs.get(r.rid)
            n0, n1 = before[r.rid], len(r.generated)
            if rec is None or n1 == n0:
                continue
            if n0 == 0:
                admitted.append(r.rid)
                prompt_tokens += rec.prompt_len
                rec.admit_step_t0 = t0
                rec.first_t = r.first_token_t
                rec.token_t.append(r.first_token_t)
            for j in range(max(n0, 1), n1):
                ctx.append(rec.prompt_len + j - 1)
                rec.token_t.append(t1)
        for r in eng.finished[n_fin:]:
            if not r.generated and r.rid in self.reqs:
                self.reqs[r.rid].rejected = True
        self.steps.append(StepRec(idx, t0, t1, admitted, prompt_tokens, ctx,
                                  len(ctx)))

    # -- the open loop -----------------------------------------------------
    def serve(self, items, tl: Timeline, drain_cap_s: float,
              trace_dir: Optional[str], clock: Optional[CompileClock] = None
              ) -> None:
        """Submit ``items`` at their due times (offsets from ``tl.start``)
        between engine steps, step while there is work, sleep only while the
        engine is idle.  Profiles [w1, t1) into ``trace_dir`` when given.
        After t1, keeps serving until every request due before w1 has its
        first token, for at most ``drain_cap_s``.  Notes ``clock``'s event
        count and queue length as the window opens and closes in
        ``self.marks``."""
        eng = self.engine
        due = [tl.start + it.due_s for it in items]
        nxt = 0
        rid0 = len(self.reqs)
        tracing = False
        stop = tl.t1 + drain_cap_s
        while True:
            now = time.time()
            for name, t in (("w0", tl.w0), ("w1", tl.w1)):
                if name not in self.marks and now >= t:
                    self.marks[name] = clock.events() if clock else 0
                    self.marks["queue_" + name] = len(eng.queue)
            if trace_dir and not tracing and now >= tl.w1 and now < tl.t1:
                jax.profiler.start_trace(trace_dir)
                tracing = True
                now = self.trace_on = time.time()
            if tracing and now >= tl.t1:
                self.trace_off = now
                jax.profiler.stop_trace()
                tracing = False
                trace_dir = None
                now = time.time()
            if now >= tl.t1 and (now >= stop or self._window_served(tl.w1)):
                break
            while nxt < len(items) and due[nxt] <= now:
                it, rid = items[nxt], rid0 + nxt
                req = self.Request(rid=rid, prompt=it.prompt,
                                   max_new_tokens=it.max_new_tokens,
                                   arrival_t=due[nxt])
                self.reqs[rid] = ReqRec(rid, len(it.prompt),
                                        it.max_new_tokens, due[nxt], now)
                eng.submit(req)
                self.lateness.append(now - due[nxt])
                nxt += 1
            if eng.queue or eng.n_active:
                self._step(annotate=tracing)
                continue
            wake = min(([due[nxt]] if nxt < len(items) else [])
                       + [b for b in (tl.w1, tl.t1, stop) if b > now])
            if tracing:
                with jax.profiler.TraceAnnotation("bench_wait"):
                    time.sleep(max(0.0, wake - time.time()))
            else:
                time.sleep(max(0.0, wake - time.time()))
        if tracing:
            self.trace_off = time.time()
            jax.profiler.stop_trace()

    def _window_served(self, w1: float) -> bool:
        return all(r.rejected or not math.isnan(r.first_t)
                   for r in self.reqs.values() if r.due < w1)


# ---------------------------------------------------------------------------
# Arithmetic the metric readers share
# ---------------------------------------------------------------------------
def percentile(values, p: float) -> float:
    """Nearest-rank percentile; +inf entries (requests that failed) sort
    last and count."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]


def window_steps(run, phase: str = "window") -> list[StepRec]:
    lo, hi = (run.tl.w0, run.tl.w1) if phase == "window" else (run.tl.w1,
                                                                run.tl.t1)
    return [s for s in run.steps if lo <= s.t0 < hi]


def ttfts_due_in_window(run) -> list[float]:
    out = []
    for r in run.reqs.values():
        if run.tl.w0 <= r.due < run.tl.w1:
            ok = not r.rejected and not math.isnan(r.first_t)
            out.append(r.first_t - r.due if ok else math.inf)
    return out


def gaps_ending_in_window(run) -> list[float]:
    out = []
    for r in run.reqs.values():
        t = r.token_t
        out += [b - a for a, b in zip(t, t[1:]) if run.tl.w0 <= b < run.tl.w1]
    return out


def tokens_in_window(run) -> int:
    return sum(run.tl.w0 <= t < run.tl.w1
               for r in run.reqs.values() for t in r.token_t)


def decode_only(steps) -> list[StepRec]:
    return [s for s in steps if not s.admitted and s.decode_ctx]


def admitting(steps) -> list[StepRec]:
    return [s for s in steps if s.admitted]


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    model: dict                  # counts.sizes of the configuration file
    max_batch: int
    peak: dict                   # peaks.json entry of this device
    tl: Timeline
    steps: list
    reqs: dict
    setup_s: float
    trace: Optional[object] = None   # trace_reduce.Trace of [w1, t1)


def _step_labels(run) -> dict:
    kinds = {}
    for s in run.steps:
        kinds[f"bench_step_{s.index}"] = ("admitting step" if s.admitted
                                          else "decode-only step")
    kinds["bench_wait"] = "waiting for an arrival"
    return kinds


def step_idle_share(run) -> Optional[float]:
    """Percent of the time inside traced engine steps with no device op."""
    from trace_reduce import busy, idle_share_in
    if run.trace is None:
        return None
    spans = [(e.start, e.end) for e in run.trace.host_spans
             if e.name.startswith("bench_step_")]
    shares = [idle_share_in(busy(evs), spans)
              for evs in run.trace.device_ops.values()]
    shares = [s for s in shares if s is not None]
    return 100.0 * sum(shares) / len(shares) if shares else None


def breakdown(run, op_label) -> dict:
    """Top device operations by summed time, and the longest idle gaps
    named by what the host was doing, over the traced segment."""
    from trace_reduce import busy, longest_gaps, top_ops
    evs = [e for v in run.trace.device_ops.values() for e in v]
    spans = run.trace.host_spans
    if not evs or not spans:
        return {"device_ops": top_ops(evs, key=op_label), "idle_gaps": []}
    lo, hi = spans[0].start, spans[-1].end
    return {"device_ops": top_ops(evs, key=op_label),
            "idle_gaps": longest_gaps(busy(evs), spans, _step_labels(run),
                                      lo, hi)}
