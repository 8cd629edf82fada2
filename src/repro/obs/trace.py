"""Span tracer emitting Chrome trace-event JSON (Perfetto-viewable).

Every run has two clocks: *wall time* (how long the controller actually
spent — solver calls, window processing) and *sim time* (when things
happened inside the simulated cluster — telemetry windows, launches,
drains, request lifecycles).  The tracer keeps them on separate process
tracks so Perfetto renders both without unit confusion:

* pid 1 (``wall``): wall-clock spans, ``ts`` in µs since tracer start.
* pid 2 (``sim``):  sim-clock spans, ``ts`` = sim seconds × 1e6.

Output is the Chrome trace-event "JSON object format"
(``{"traceEvents": [...]}``); load it at https://ui.perfetto.dev or
``chrome://tracing``.  Events use ``ph="X"`` (complete spans, with
``dur``), ``ph="i"`` (instants), and ``ph="M"`` (track metadata).

Request lifecycles are *sampled* (every ``sample_every``-th request id)
so a 100k-request trace stays loadable; each sampled request contributes
a ``queue+prefill`` span (arrival → first token) and a ``decode`` span
(first token → finish) on the sim track.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, ContextManager, Iterator, Optional

__all__ = ["SpanTracer", "validate_chrome_trace", "TRACER",
           "WALL_PID", "SIM_PID", "wall_now"]

WALL_PID = 1
SIM_PID = 2


def wall_now() -> float:
    """Monotonic wall-clock reading, for *observability only*.

    Sim-scope code (orchestrators, simulator, traces) must never branch
    on wall time — the `sim-clock-purity` lint rule bans direct
    ``time.*`` reads there.  But measuring how long the real solver
    spent is observability, not simulation semantics, so this is the
    one sanctioned wall read for sim-scope modules: routing through
    ``obs`` keeps the dual-clock boundary (sim time for semantics, wall
    time for measurement) visible at every call site."""
    return time.perf_counter()


class SpanTracer:
    """Collects trace events in memory; ``to_chrome()`` serialises them.

    When ``enabled`` is False every record call is a boolean check and an
    early return, and ``span()`` yields without touching the clock.

    ``annotate``, when given, is a factory of context managers (such as
    ``jax.profiler.TraceAnnotation``) that an enabled ``span()`` also enters,
    under ``"<track>.<name>"``: each span then lands in that profiler's own
    trace too, on its clock.  This module imports nothing to make one.
    """

    def __init__(self, enabled: bool = True, *, sample_every: int = 16,
                 annotate: Optional[Callable[[str], ContextManager]] = None):
        if sample_every < 1:
            raise ValueError("sample_every must be >= 1")
        self.enabled = enabled
        self.sample_every = sample_every
        self.annotate = annotate
        self.events: list[dict] = []
        self._t0 = time.perf_counter()
        self._named_tracks: set[tuple[int, int]] = set()
        self._meta(WALL_PID, "wall")
        self._meta(SIM_PID, "sim")

    # -- track bookkeeping ---------------------------------------------------
    def _meta(self, pid: int, name: str) -> None:
        self.events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": name}})

    def _tid(self, pid: int, track: str) -> int:
        # Stable small tids per (pid, track name) so Perfetto groups rows.
        tid = _TRACKS.setdefault(track, len(_TRACKS) + 1)
        if (pid, tid) not in self._named_tracks:
            self._named_tracks.add((pid, tid))
            self.events.append({
                "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
                "args": {"name": track}})
        return tid

    def _wall_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # -- wall-clock spans ----------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str, *, track: str = "control",
             **args) -> Iterator[dict]:
        """Time a wall-clock region (solver call, window handler).  Yields
        the span's args, to which the region may add what it learns before
        it ends."""
        if not self.enabled:
            yield args
            return
        start = self._wall_us()
        try:
            if self.annotate is None:
                yield args
            else:
                with self.annotate(f"{track}.{name}"):
                    yield args
        finally:
            self.events.append({
                "name": name, "ph": "X", "pid": WALL_PID,
                "tid": self._tid(WALL_PID, track),
                "ts": start, "dur": self._wall_us() - start,
                "args": _clean(args)})

    def wall_span(self, name: str, start_s: float, end_s: float, *,
                  track: str = "control", **args) -> None:
        """Record an already-measured wall-clock interval (perf_counter
        seconds relative to tracer start)."""
        if not self.enabled:
            return
        self.events.append({
            "name": name, "ph": "X", "pid": WALL_PID,
            "tid": self._tid(WALL_PID, track),
            "ts": start_s * 1e6, "dur": max(0.0, end_s - start_s) * 1e6,
            "args": _clean(args)})

    # -- sim-clock spans -----------------------------------------------------
    def sim_span(self, name: str, t0: float, t1: float, *,
                 track: str = "windows", **args) -> None:
        """Record a sim-time interval (seconds of simulated time)."""
        if not self.enabled:
            return
        self.events.append({
            "name": name, "ph": "X", "pid": SIM_PID,
            "tid": self._tid(SIM_PID, track),
            "ts": t0 * 1e6, "dur": max(0.0, t1 - t0) * 1e6,
            "args": _clean(args)})

    def instant(self, name: str, t: float, *, track: str = "events",
                scope: str = "p", **args) -> None:
        """A sim-time instant (launch, drain, preemption, stockout)."""
        if not self.enabled:
            return
        self.events.append({
            "name": name, "ph": "i", "pid": SIM_PID,
            "tid": self._tid(SIM_PID, track),
            "ts": t * 1e6, "s": scope, "args": _clean(args)})

    # -- sampled request lifecycles ------------------------------------------
    def sampled(self, rid: int) -> bool:
        return self.enabled and rid % self.sample_every == 0

    def request_span(self, rid: int, arrival: float,
                     first_token: Optional[float], finish: float, *,
                     gpu: str = "", bucket: str = "",
                     model: str = "") -> None:
        """Emit the sampled lifecycle of one request on the sim track:
        queue+prefill (arrival → first token) then decode (→ finish)."""
        if not self.sampled(rid):
            return
        track = f"requests/{gpu}" if gpu else "requests"
        args = {"rid": rid, "bucket": bucket, "model": model,
                "latency_s": round(finish - arrival, 6)}
        if first_token is not None and first_token >= arrival:
            self.sim_span("queue+prefill", arrival, first_token,
                          track=track, **args)
            self.sim_span("decode", first_token, finish, track=track,
                          **args)
        else:
            self.sim_span("request", arrival, finish, track=track, **args)

    # -- export --------------------------------------------------------------
    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object format."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms",
                "otherData": {"clock_note":
                              "pid 1 = wall us, pid 2 = sim s * 1e6"}}

    def to_json(self) -> str:
        return json.dumps(self.to_chrome())

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    def clear(self) -> None:
        self.events = [e for e in self.events if e.get("ph") == "M"]


_TRACKS: dict[str, int] = {}


def _clean(args: dict) -> dict:
    return {k: v for k, v in args.items() if v is not None and v != ""}


_VALID_PH = {"X", "B", "E", "i", "I", "M", "C", "b", "e", "n", "s", "t",
             "f"}


def validate_chrome_trace(obj: object) -> list[str]:
    """Validate the trace-event schema Perfetto's JSON importer expects.
    Returns a list of problems (empty means valid)."""
    errs: list[str] = []
    if isinstance(obj, list):
        events = obj
    elif isinstance(obj, dict):
        events = obj.get("traceEvents")
        if not isinstance(events, list):
            return ["object format requires a 'traceEvents' array"]
    else:
        return [f"trace must be an array or object, got "
                f"{type(obj).__name__}"]
    for i, e in enumerate(events):
        w = f"traceEvents[{i}]"
        if not isinstance(e, dict):
            errs.append(f"{w} must be an object")
            continue
        ph = e.get("ph")
        if ph not in _VALID_PH:
            errs.append(f"{w}.ph invalid: {ph!r}")
            continue
        if not isinstance(e.get("name"), str) or not e["name"]:
            errs.append(f"{w}.name must be a non-empty string")
        for fld in ("pid", "tid"):
            if not isinstance(e.get(fld), int):
                errs.append(f"{w}.{fld} must be an int")
        if ph == "M":
            if not isinstance(e.get("args"), dict):
                errs.append(f"{w}: metadata event needs an args object")
            continue
        ts = e.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"{w}.ts must be a non-negative number (µs)")
        if ph == "X":
            dur = e.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"{w}.dur must be a non-negative number (µs)")
        if ph in ("i", "I") and e.get("s") not in (None, "g", "p", "t"):
            errs.append(f"{w}.s must be one of g/p/t")
        if "args" in e and not isinstance(e["args"], dict):
            errs.append(f"{w}.args must be an object")
    return errs


#: Process-global tracer, off by default: tracing is opt-in per run
#: (benchmarks and examples construct their own or flip this on).
TRACER = SpanTracer(enabled=False)
