"""Reduction of a JAX profiler trace to device busy time, idle gaps and
kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
planes' ``XLA Ops`` lines (one event per operation run on the chip) and the
host spans that the harness wrote with ``TraceAnnotation`` (``bench_step_<i>``
around each engine step, ``bench_wait`` around each idle sleep).  Everything
else works on plain (start, end) seconds, so tests can hand-build traces.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Ev:
    name: str
    start: float          # seconds, on the trace's own clock
    end: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    device_ops: dict          # plane name -> [Ev]
    host_spans: list          # [Ev] named bench_step_<i> / bench_wait


def _stats(e) -> dict:
    try:
        return {str(k): v for k, v in e.stats}
    except Exception:
        return {}


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    dev, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [Ev(e.name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9, _stats(e))
                            for e in line.events]
            dev[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench_"):
                        host.append(Ev(e.name, e.start_ns * 1e-9,
                                       (e.start_ns + e.duration_ns) * 1e-9))
    return Trace(dev, sorted(host, key=lambda e: e.start))


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: float, hi: float) -> float:
    """Seconds of the merged intervals that lie inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy(events) -> list[tuple[float, float]]:
    return union((e.start, e.end) for e in events)


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the devices traced."""
    if not trace.device_ops:
        return 0.0
    return sum(overlap(busy(evs), float("-inf"), float("inf"))
               for evs in trace.device_ops.values()) / len(trace.device_ops)


def idle_share_in(merged, spans) -> float | None:
    """1 - busy share of the time inside ``spans`` [(start, end)]."""
    total = sum(e - s for s, e in spans)
    if total <= 0:
        return None
    return 1.0 - sum(overlap(merged, s, e) for s, e in spans) / total


def idle_gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals of [lo, hi) in which no operation ran."""
    gaps, t = [], lo
    for s, e in merged:
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def label_at(spans, labels: dict, t: float) -> str:
    """What the host was doing at ``t``: the label of the span holding it."""
    for sp in spans:
        if sp.start <= t < sp.end:
            return labels.get(sp.name, sp.name)
    return "harness (between steps)"


def top_ops(events, n: int = 10, key=None) -> list[list]:
    """Device operations with the most summed time: [[label, seconds]]."""
    key = key or (lambda e: e.name)
    tot: dict[str, float] = {}
    for e in events:
        tot[key(e)] = tot.get(key(e), 0.0) + e.dur
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def longest_gaps(merged, spans, labels, lo, hi, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps, each named by what the host was doing."""
    gaps = sorted(idle_gaps(merged, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[label_at(spans, labels, (s + e) / 2), e - s] for s, e in gaps]


def matching(events, pattern: str) -> list[Ev]:
    """Events whose name or any string stat contains ``pattern``."""
    return [e for e in events
            if pattern in e.name
            or any(isinstance(v, str) and pattern in v
                   for v in e.stats.values())]


HLO_TEXT = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>[a-z0-9]+\[[0-9,]*\])?")


def op_label(e: Ev) -> str:
    """A device operation's name for the breakdown: the JAX op path that the
    trace carries for it when it has one; else, where the trace names it by
    its whole HLO instruction, the instruction's name and result shape."""
    v = e.stats.get("tf_op")
    if isinstance(v, str) and v:
        return v
    m = HLO_TEXT.match(e.name)
    if m:
        return m["name"] + (" " + m["type"] if m["type"] else "")
    return e.name
