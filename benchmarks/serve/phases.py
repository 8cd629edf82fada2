"""Where the time of the engine's steps goes, by the engine's own phases.

    python3 benchmarks/serve/phases.py --workload <cell> --seed <n> \
        --seconds <s>

Serves the cell as ``run.py --trace 1`` does (the same weights, traffic,
warm-up, ramp, window and profiled seconds after it) with the engine's span
tracer on, each span also written into the profiler's trace as
``engine.<phase>``.  Prints one JSON line:

* ``tails``: ``ttft_p90_ms`` and ``itl_p95_ms`` of the window, which the
  profiler does not cover.  Set against ``run.py --trace 0`` on the same
  seed, they give what the tracer costs when it is on.
* ``readings``: the four readings of the engine's spans below, and the
  cell's per-layer metrics read from the same run.
* ``admission``: what the ``admit`` and ``prefill`` spans say of the
  window's steps: why admission stopped with requests queued, the requests
  rejected, the share of the prefill programs' tokens that is padding, and
  each prefill that built a new program (a compile inside the window).
* ``phase_idle_ms``: per traced step of each kind, the mean milliseconds in
  which no op ran on the device, by the innermost engine span that held
  them.
* ``idle_gaps``: the longest idle gaps of the traced segment, each named
  ``<step kind> / <innermost engine span>`` where a span held it;
  ``device_ops``: the ops with the most device time, by op path;
  ``programs``: the programs with the most device time, by name.

Nothing is checked against the reference: ``run.py``'s runs do that.  Exits
non-zero, printing no result, on a machine without an accelerator.

This script is a stopgap: it copies ``run.run_cell``'s serving path and reads
the profile's ``engine.*`` events itself because the benchmark's existing
files (``run.py``, ``runlib.py``, ``trace_reduce.py``) do not pass a tracer
to the engine nor keep those events.  The ``benchmark`` change that makes
these readings per-layer metrics moves the readers into ``metrics/`` and
deletes this file.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import tempfile  # noqa: E402

import run  # noqa: E402  (puts src/ and this directory on the path)
import counts  # noqa: E402
import runlib  # noqa: E402
import trace_reduce  # noqa: E402
from runlib import (admitting, decode_only, percentile,  # noqa: E402
                    window_steps)
from trace_reduce import Ev, busy, overlap  # noqa: E402

STEP_KINDS = ("decode-only step", "admitting step")
MODULES_LINE = "XLA Modules"
PROGRAM_ID = re.compile(r"\(\d+\)$")


# ---------------------------------------------------------------------------
# Readings of the engine's spans
# ---------------------------------------------------------------------------
def engine_step(s: runlib.StepRec, offset: int) -> int:
    """The engine's step counter after the loop's step ``s``, when the
    counter read ``offset`` before the loop's first step: every loop step is
    one engine step."""
    return offset + s.index + 1


def by_step(events: list[dict], name: str) -> dict:
    """{engine step: event} of the tracer's ``name`` spans (one a step)."""
    return {e["args"]["step"]: e for e in events
            if e.get("ph") == "X" and e["name"] == name}


def admit_wait_ms_p90(run_, admit_t: dict):
    """Admission: 90th percentile of the engine's admit stamp (taken from
    the queue for prefill) minus the due time, over requests due in the
    window.  Unlike ``queue_wait_ms.p90``, which stops at the start of the
    admitting step, it holds the prefills, inserts and first-token syncs of
    the requests admitted before it in that step."""
    v = [admit_t[r.rid] - r.due for r in run_.reqs.values()
         if run_.tl.w0 <= r.due < run_.tl.w1 and r.rid in admit_t]
    return percentile(v, 90) * 1e3 if v else None


def admit_ms_per_ktok(run_, events: list[dict], offset: int):
    """Prefill: host time of the ``admit`` spans of the window's admitting
    steps per 1,000 prompt tokens admitted in them: the admitting step
    without the decode of the batch that follows admission."""
    spans = by_step(events, "admit")
    s = [x for x in admitting(window_steps(run_))
         if engine_step(x, offset) in spans]
    tok = sum(x.prompt_tokens for x in s)
    # span durations are in us: us per token is ms per 1,000 tokens
    return sum(spans[engine_step(x, offset)]["dur"] for x in s) / tok \
        if tok else None


def host_syncs_decode(run_, events: list[dict], offset: int):
    """Sampling: mean reads from device to host (``syncs`` of the ``sample``
    span) per decode-only step of the window."""
    spans = by_step(events, "sample")
    v = [spans[k]["args"]["syncs"]
         for k in (engine_step(x, offset)
                   for x in decode_only(window_steps(run_)))
         if k in spans]
    return statistics.fmean(v) if v else None


def admission(run_, events: list[dict], offset: int) -> dict:
    """What the ``admit`` and ``prefill`` spans say of the window's steps:
    ``stops``, the steps that left requests queued, by why admission stopped
    (``no_slot`` | ``no_blocks`` | ``budget``); ``rejected``, requests too
    long to serve; ``pad_share``, percent of the prefill programs' tokens
    that is padding; ``new_programs``, each prefill that built its program,
    as [engine step, rid, prompt tokens, ms]."""
    steps = {engine_step(x, offset): x for x in window_steps(run_)}
    admits = [e for k, e in by_step(events, "admit").items() if k in steps]
    if not admits:
        return {}
    stops: dict[str, int] = {}
    for e in admits:
        why = e["args"].get("stop")
        if why:
            stops[why] = stops.get(why, 0) + 1
    padded = sum(e["args"]["padded_tokens"] for e in admits)
    real = sum(steps[e["args"]["step"]].prompt_tokens for e in admits)
    new = [[e["args"]["step"], e["args"]["rid"],
            run_.reqs[e["args"]["rid"]].prompt_len, e["dur"] * 1e-3]
           for e in events
           if e.get("ph") == "X" and e["name"] == "prefill"
           and e["args"]["step"] in steps and e["args"].get("new_program")]
    return {"stops": stops,
            "rejected": sum(e["args"]["rejected"] for e in admits),
            "pad_share": 100.0 * (padded - real) / padded if padded else None,
            "new_programs": new}


def _traced_steps(run_, kind: str) -> list[Ev]:
    """The host spans (``bench_step_<i>``) of the traced steps of a kind."""
    steps = window_steps(run_, "trace")
    pick = decode_only(steps) if kind == STEP_KINDS[0] else admitting(steps)
    names = {f"bench_step_{x.index}" for x in pick}
    return [h for h in run_.trace.host_spans if h.name in names]


def _inside(spans: list[Ev], lo: float, hi: float) -> list[Ev]:
    return [sp for sp in spans if lo <= sp.start and sp.end <= hi]


def _idle(merged, intervals) -> float:
    """Seconds of the disjoint ``intervals`` in which no op ran."""
    return sum(e - s - overlap(merged, s, e) for s, e in intervals)


def _device_busy(run_) -> list | None:
    evs = [e for v in run_.trace.device_ops.values() for e in v] \
        if run_.trace is not None else []
    return busy(evs) if evs else None


def sample_idle_ms_decode(run_, spans: list[Ev]):
    """Sampling: mean, per traced decode-only step, of the milliseconds
    inside its ``engine.sample`` span in which no op ran on the device."""
    merged = _device_busy(run_)
    hosts = _traced_steps(run_, STEP_KINDS[0]) if merged else []
    sample = [sp for sp in spans if sp.name == "engine.sample"]
    v = [_idle(merged, [(sp.start, sp.end)
                        for sp in _inside(sample, h.start, h.end)])
         for h in hosts]
    return statistics.fmean(v) * 1e3 if v else None


def phase_idle_ms(run_, spans: list[Ev]) -> dict:
    """Per traced step of each kind, the mean milliseconds with no device
    op, split by the innermost engine span that held them (``outside engine
    spans`` for the rest of the step), with the steps' count and mean
    length."""
    merged = _device_busy(run_)
    out = {}
    for kind in STEP_KINDS if merged else ():
        hosts = _traced_steps(run_, kind)
        tot: dict[str, float] = {}
        for h in hosts:
            inner = _inside(spans, h.start, h.end)
            for node in [h] + inner:
                kids = trace_reduce.union(
                    (c.start, c.end) for c in _inside(inner, node.start,
                                                      node.end)
                    if c is not node)
                name = node.name if node is not h else "outside engine spans"
                tot[name] = tot.get(name, 0.0) + (
                    _idle(merged, [(node.start, node.end)])
                    - _idle(merged, kids))
        if hosts:
            n = len(hosts)
            out[kind] = {"steps": n,
                         "step_ms": sum(h.dur for h in hosts) / n * 1e3,
                         "idle_ms": sum(tot.values()) / n * 1e3,
                         **{k: v / n * 1e3 for k, v in sorted(tot.items())}}
    return out


def labelled_gaps(run_, spans: list[Ev], n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the traced segment, ranked and named
    by ``trace_reduce.longest_gaps`` as ``runlib.breakdown`` names them,
    but, where a step held the gap's middle, ``<step kind> / <innermost
    engine span>``, or ``<step kind> / outside engine spans`` for the
    harness's own code around ``engine.step()``."""
    merged = _device_busy(run_)
    hosts = run_.trace.host_spans if merged else []
    if not hosts:
        return []
    labels = runlib._step_labels(run_)
    named = [Ev(f"{labels.get(h.name, h.name)} / {sp.name}", sp.start, sp.end)
             for h in hosts for sp in _inside(spans, h.start, h.end)]
    # a gap takes the name of the first span that holds it: one thread's
    # spans nest, so the innermost is the one that began last
    named.sort(key=lambda sp: -sp.start)
    steps = [Ev(f"{labels[h.name]} / outside engine spans", h.start, h.end)
             for h in hosts if h.name.startswith("bench_step_")]
    return trace_reduce.longest_gaps(merged, named + steps + hosts, labels,
                                     hosts[0].start, hosts[-1].end, n)


def read_profile(trace_dir: str) -> tuple[list[Ev], list[Ev]]:
    """From the profiler's trace in ``trace_dir``: the ``engine.*`` host
    spans, sorted by start, on the device trace's clock; and the runs of
    whole programs on the devices (the ``XLA Modules`` line).  A second
    walk of the file that ``trace_reduce.load`` reads, which keeps neither
    (see the module's docstring)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    pd = ProfileData.from_file(paths[-1])
    spans, programs = [], []
    for plane in pd.planes:
        host = plane.name.startswith("/host:")
        if not host and not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if not host and line.name != MODULES_LINE:
                continue
            for e in line.events:
                if not host or e.name.startswith("engine."):
                    (spans if host else programs).append(Ev(
                        e.name, e.start_ns * 1e-9,
                        (e.start_ns + e.duration_ns) * 1e-9))
    return sorted(spans, key=lambda e: e.start), programs


def by_program(programs: list[Ev], n: int = 10) -> list[list]:
    """Device seconds and runs of each program, most time first:
    [[name, seconds, runs]].  A run's name ends in ``(<program id>)``,
    which is dropped; the engine's programs read ``jit_prefill`` and
    ``jit_decode``."""
    tot: dict[str, list] = {}
    for e in programs:
        name = PROGRAM_ID.sub("", e.name)
        t = tot.setdefault(name, [name, 0.0, 0])
        t[1] += e.dur
        t[2] += 1
    return sorted(tot.values(), key=lambda t: -t[1])[:n]


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------
def phases(name: str, bench: dict, config: dict, traffic: dict, *,
           seed: int, seconds: float, profile_s: float, devices, peak: dict,
           t_start: float) -> dict:
    """One run of a cell with the engine's spans on; returns the line."""
    import jax
    import numpy as np

    import traffic as traffic_mod
    import weights
    from repro.models import transformer as T
    from repro.obs.trace import SpanTracer
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    clock = runlib.CompileClock()
    m = config["model"]
    cfg = run.model_config(m)
    ecfg = EngineConfig(**config["engine"])
    params = weights.make_weights(T.abstract_params(cfg), config["init"], seed)
    items = traffic_mod.schedule(traffic, m["vocab_size"], seed,
                                 [traffic["ramp_s"], seconds,
                                  profile_s + run.TAIL_S])
    tracer = SpanTracer(enabled=True, annotate=jax.profiler.TraceAnnotation)
    engine = ServingEngine(cfg, params, ecfg, tracer=tracer)
    loop = runlib.OpenLoop(engine, Request)
    rng = np.random.default_rng(seed)
    loop.warm([rng.integers(0, m["vocab_size"], L).tolist()
               for L in sorted({len(it.prompt) for it in items})])
    tracer.clear()
    offset = engine.steps

    start = time.time()
    w0 = start + traffic["ramp_s"]
    tl = runlib.Timeline(start, w0, w0 + seconds, w0 + seconds + profile_s)
    trace_dir = tempfile.mkdtemp(prefix="serve_phases_")
    loop.serve(items, tl, traffic["drain_cap_s"], trace_dir, clock)
    run_ = runlib.Run(counts.sizes(config), ecfg.max_batch, peak, tl,
                      loop.steps, loop.reqs, tl.w0 - t_start)
    run_.trace = trace_reduce.load(trace_dir)
    spans, programs = read_profile(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    held = engine.finished + [r for r in engine.slot_req if r is not None]
    admit_t = {r.rid: r.admit_t for r in held if r.admit_t >= 0}
    events = tracer.events

    readings = {
        "admit_wait_ms.p90": admit_wait_ms_p90(run_, admit_t),
        "admit_ms_per_ktok": admit_ms_per_ktok(run_, events, offset),
        "host_syncs.decode": host_syncs_decode(run_, events, offset),
        "sample_idle_ms.decode": sample_idle_ms_decode(run_, spans)}
    for spec in run.cell_metrics(bench, name, True):
        readings[spec["name"]] = run.read_metric(spec["name"], run_)
    window = window_steps(run_)
    dev = devices[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices)},
           "tails": {k: run.read_metric(k, run_)
                     for k in ("ttft_p90_ms", "itl_p95_ms")},
           "readings": {k: v for k, v in readings.items() if v is not None},
           "window": {"steps": len(window),
                      "admitting": len(admitting(window)),
                      "occupancy.decode": statistics.fmean(
                          x.occupancy for x in decode_only(window))
                      if decode_only(window) else None,
                      "compile_events": loop.marks.get("w1", 0)
                      - loop.marks.get("w0", 0)},
           "admission": admission(run_, events, offset),
           "spans": {"tracer": sum(e.get("ph") == "X" for e in events),
                     "profiler": len(spans)},
           "phase_idle_ms": phase_idle_ms(run_, spans),
           "idle_gaps": labelled_gaps(run_, spans),
           "device_ops": runlib.breakdown(run_,
                                          trace_reduce.op_label)["device_ops"],
           "programs": by_program(programs)}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    bench, cell, config, traffic, _ = run.cell_files(args.workload)
    devices = run.require_devices(cell["chips"])
    import peaks
    peak = peaks.peaks(devices[0].device_kind)
    run.set_compile_cache()
    out = phases(args.workload, bench, config, traffic, seed=args.seed,
                 seconds=args.seconds, profile_s=run.TRACE_S,
                 devices=devices, peak=peak, t_start=T_START)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
