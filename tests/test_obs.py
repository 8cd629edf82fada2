"""Observability stack (ISSUE 6 tentpole): the labeled metrics registry,
the dual-clock span tracer, SolveStats solver instrumentation, and the
decision/attainment satellites.

Each hypothesis property has a plain deterministic core so the logic is
exercised even where hypothesis is not installed (the stub in
``_hypothesis_compat`` skips the ``@given`` wrappers).
"""
import json
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (Melange, ModelPerf, PAPER_GPUS, build_problem,
                        make_workload, solve)
from repro.core.ilp import ILPProblem, SolveStats
from repro.obs import (DEFAULT_LATENCY_BUCKETS, MetricsRegistry, SIM_PID,
                       SpanTracer, WALL_PID, parse_prometheus, report_dict,
                       render_report, validate_chrome_trace,
                       validate_snapshot)
from repro.orchestrator import ClusterOrchestrator, run_static
from repro.orchestrator.timeline import Decision, Timeline, WindowRecord
from repro.traces import FleetEvent, TraceSegment, WorkloadTrace


# ---------------------------------------------------------------------------
# metrics registry: label invariants
# ---------------------------------------------------------------------------
def test_counter_labels_and_values():
    reg = MetricsRegistry()
    c = reg.counter("melange_test_total", "help text", ("gpu", "tier"))
    c.labels(gpu="A100", tier="spot").inc()
    c.labels("A100", "spot").inc(2)            # positional == kw child
    c.labels(gpu="L4", tier="ondemand").inc(5)
    snap = reg.snapshot()
    series = snap["metrics"][0]["series"]
    vals = {tuple(sorted(s["labels"].items())): s["value"] for s in series}
    assert vals[(("gpu", "A100"), ("tier", "spot"))] == 3
    assert vals[(("gpu", "L4"), ("tier", "ondemand"))] == 5


def test_label_invariants_rejected():
    reg = MetricsRegistry()
    c = reg.counter("melange_labeled_total", "", ("gpu",))
    with pytest.raises(ValueError):
        c.inc()                                # unlabeled parent
    with pytest.raises(ValueError):
        c.labels(gpu="A100", region="x")       # unknown label
    with pytest.raises(ValueError):
        c.labels(region="us")                  # missing declared label
    with pytest.raises(ValueError):
        c.labels("A100", "extra")              # wrong arity
    with pytest.raises(ValueError):
        c.labels("A100", gpu="A100")           # positional + kw mix
    with pytest.raises(ValueError):
        c.labels(gpu="A100").labels(gpu="A100")  # re-labeling a child
    with pytest.raises(ValueError):
        c.labels(gpu="A100").inc(-1)           # counters only go up
    with pytest.raises(ValueError):
        reg.counter("melange_dup_total", "", ("a", "a"))
    with pytest.raises(ValueError):
        reg.counter("bad name", "")
    with pytest.raises(ValueError):
        reg.gauge("melange_labeled_total")     # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("melange_labeled_total", "", ("other",))  # label mismatch


def test_get_or_create_returns_same_family():
    reg = MetricsRegistry()
    a = reg.counter("melange_x_total", "", ("gpu",))
    b = reg.counter("melange_x_total", "", ("gpu",))
    assert a is b


# ---------------------------------------------------------------------------
# histogram bucket edges
# ---------------------------------------------------------------------------
def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("melange_lat_seconds", "", buckets=(0.1, 1.0, 10.0))
    # boundary values land in their own bucket (le semantics: v <= bound)
    h.observe(0.1)
    h.observe(0.10001)
    h.observe(1.0)
    h.observe(10.0)
    h.observe(11.0)       # overflow -> +Inf bucket
    assert h.counts == [1, 2, 1, 1]
    assert h.cumulative() == [1, 3, 4, 5]
    assert h.count == 5
    assert h.sum == pytest.approx(0.1 + 0.10001 + 1.0 + 10.0 + 11.0)


def test_histogram_bucket_validation():
    reg = MetricsRegistry()
    with pytest.raises(ValueError):
        reg.histogram("melange_bad_seconds", "", buckets=(1.0, 0.5))
    with pytest.raises(ValueError):
        reg.histogram("melange_bad2_seconds", "", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("melange_bad3_seconds", "", buckets=())
    # a trailing +Inf is accepted and folded into the implicit bucket
    h = reg.histogram("melange_inf_seconds", "", buckets=(1.0, math.inf))
    assert h.buckets == (1.0,)
    assert len(h.counts) == 2


def test_labeled_histogram_children_independent():
    reg = MetricsRegistry()
    h = reg.histogram("melange_hl_seconds", "", ("gpu",),
                      buckets=(1.0, 2.0))
    h.labels(gpu="A100").observe(0.5)
    h.labels(gpu="L4").observe(1.5)
    a = h.labels(gpu="A100")
    b = h.labels(gpu="L4")
    assert a.counts == [1, 0, 0] and b.counts == [0, 1, 0]


# ---------------------------------------------------------------------------
# prometheus exposition round-trip
# ---------------------------------------------------------------------------
def _populated_registry():
    reg = MetricsRegistry()
    reg.counter("melange_events_total", "events", ("gpu",)) \
        .labels(gpu="A100").inc(7)
    reg.gauge("melange_cost_per_hour", "fleet cost").set(12.5)
    h = reg.histogram("melange_lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    # label values needing escaping
    reg.counter("melange_weird_total", "", ("model",)) \
        .labels(model='say "hi"\\\n').inc()
    return reg


def test_prometheus_round_trip():
    reg = _populated_registry()
    text = reg.to_prometheus()
    types, samples = parse_prometheus(text)
    assert types == {"melange_events_total": "counter",
                     "melange_cost_per_hour": "gauge",
                     "melange_lat_seconds": "histogram",
                     "melange_weird_total": "counter"}
    by = {(s.name, tuple(sorted(s.labels.items()))): s.value
          for s in samples}
    assert by[("melange_events_total", (("gpu", "A100"),))] == 7
    assert by[("melange_cost_per_hour", ())] == 12.5  # lint: allow[float-eq] (exact hand-set value)
    assert by[("melange_lat_seconds_count", ())] == 3
    assert by[("melange_lat_seconds_sum", ())] == pytest.approx(5.55)
    assert by[("melange_lat_seconds_bucket", (("le", "0.1"),))] == 1
    assert by[("melange_lat_seconds_bucket", (("le", "1"),))] == 2
    assert by[("melange_lat_seconds_bucket", (("le", "+Inf"),))] == 3
    # escaped label value survives the round trip
    weird = [s for s in samples if s.name == "melange_weird_total"]
    assert weird[0].labels["model"] == 'say "hi"\\\n'


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all!")
    with pytest.raises(ValueError):
        parse_prometheus('m{gpu="a" 1')       # unclosed label block
    with pytest.raises(ValueError):
        parse_prometheus('m{gpu=unquoted} 1')


# ---------------------------------------------------------------------------
# snapshots: schema + JSONL
# ---------------------------------------------------------------------------
def test_snapshot_validates_and_jsonl_parses():
    reg = _populated_registry()
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    # jsonl: header + one line per family, each valid JSON
    lines = reg.to_jsonl().strip().split("\n")
    head = json.loads(lines[0])
    assert head["n_metrics"] == len(lines) - 1 == len(snap["metrics"])
    for ln in lines[1:]:
        json.loads(ln)
    # snapshot -> json -> snapshot still validates
    assert validate_snapshot(json.loads(json.dumps(snap))) == []


def test_validate_snapshot_catches_corruption():
    reg = _populated_registry()
    snap = reg.snapshot()
    bad = json.loads(json.dumps(snap))
    for m in bad["metrics"]:
        if m["kind"] == "histogram":
            m["series"][0]["counts"] = m["series"][0]["counts"][:-1]
    assert validate_snapshot(bad)
    assert validate_snapshot({"namespace": 3, "metrics": "x"})
    assert validate_snapshot([1, 2])
    bad2 = json.loads(json.dumps(snap))
    bad2["metrics"][0]["kind"] = "summary"
    assert validate_snapshot(bad2)
    bad3 = json.loads(json.dumps(snap))
    bad3["metrics"][0]["series"][0]["labels"] = {}
    errs = validate_snapshot(bad3)
    assert errs if bad3["metrics"][0]["labelnames"] else not errs


def test_disabled_registry_records_nothing():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("melange_a_total", "", ("gpu",))
    g = reg.gauge("melange_b")
    h = reg.histogram("melange_c_seconds")
    c.labels(gpu="A100").inc(5)
    g.set(3.0)
    g.inc()
    h.observe(1.0)
    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    for m in snap["metrics"]:
        for s in m["series"]:
            assert s.get("value", 0) == 0 and s.get("count", 0) == 0


# ---------------------------------------------------------------------------
# span tracer: chrome trace schema round-trip
# ---------------------------------------------------------------------------
def test_tracer_chrome_schema_round_trip():
    tr = SpanTracer(enabled=True, sample_every=2)
    with tr.span("resolve:rescale", track="solver", t=60.0):
        pass
    tr.sim_span("window", 0.0, 300.0, track="windows", arrived=10)
    tr.instant("stockout", 120.0, gpu="A100")
    tr.request_span(0, 1.0, 1.5, 4.0, gpu="A100", model="m")
    tr.request_span(4, 2.0, None, 5.0, gpu="L4")     # no first token
    obj = json.loads(tr.to_json())
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"resolve:rescale", "window", "stockout",
            "queue+prefill", "decode", "request"} <= names
    # both clocks present, with process_name metadata for each
    pids = {e["pid"] for e in evs}
    assert {WALL_PID, SIM_PID} <= pids
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"wall", "sim"} <= procs
    # sim spans put ts in sim-microseconds
    win = next(e for e in evs if e["name"] == "window")
    assert win["ts"] == 0.0 and win["dur"] == pytest.approx(300e6)  # lint: allow[float-eq] (exact hand-set value)


def test_tracer_sampling_and_disabled():
    tr = SpanTracer(enabled=True, sample_every=4)
    assert tr.sampled(0) and tr.sampled(8)
    assert not tr.sampled(1) and not tr.sampled(6)
    tr.request_span(3, 0.0, 0.5, 1.0, gpu="A100")    # not sampled -> no-op
    assert not [e for e in tr.events if e["ph"] == "X"]

    off = SpanTracer(enabled=False)
    assert not off.sampled(0)
    with off.span("x"):
        pass
    off.sim_span("w", 0, 1)
    off.instant("i", 0)
    assert [e for e in off.events if e["ph"] != "M"] == []

    with pytest.raises(ValueError):
        SpanTracer(sample_every=0)


def test_tracer_annotate_hook_entered_once_per_enabled_span():
    entered, exited = [], []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)
            return False

    tr = SpanTracer(enabled=True, annotate=Annotation)
    with tr.span("step", track="engine", step=1) as args:
        with tr.span("sample", track="engine", step=1) as inner:
            inner["syncs"] = 3
        args["occupancy"] = 3
    with tr.span("resolve"):
        pass
    assert entered == ["engine.step", "engine.sample", "control.resolve"]
    assert exited == ["engine.sample", "engine.step", "control.resolve"]
    # tracer-only records are not annotations
    tr.sim_span("w", 0, 1)
    tr.wall_span("x", 0.0, 1.0)
    assert len(entered) == 3
    spans = {e["name"]: e for e in tr.events if e["ph"] == "X"}
    assert spans["sample"]["args"] == {"step": 1, "syncs": 3}
    assert spans["step"]["args"] == {"step": 1, "occupancy": 3}

    off = SpanTracer(enabled=False, annotate=Annotation)
    with off.span("step", track="engine") as args:
        args["occupancy"] = 1
    assert len(entered) == 3
    assert [e for e in off.events if e["ph"] != "M"] == []


def test_tracer_clear_keeps_metadata():
    tr = SpanTracer(enabled=True)
    tr.sim_span("w", 0, 1)
    tr.clear()
    assert tr.events and all(e["ph"] == "M" for e in tr.events)


def test_validate_chrome_trace_catches_bad_events():
    assert validate_chrome_trace("nope")
    assert validate_chrome_trace({"no_events": []})
    assert validate_chrome_trace({"traceEvents": [{"ph": "Z"}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                          "ts": -5, "dur": 1}]})
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                          "ts": 0}]})                     # X without dur
    assert validate_chrome_trace(
        {"traceEvents": [{"ph": "i", "name": "a", "pid": 1, "tid": 1,
                          "ts": 0, "s": "q"}]})           # bad scope
    ok = {"traceEvents": [{"ph": "i", "name": "a", "pid": 1, "tid": 1,
                           "ts": 0, "s": "p"}]}
    assert validate_chrome_trace(ok) == []


# ---------------------------------------------------------------------------
# SolveStats: conservation property + round trip
# ---------------------------------------------------------------------------
def _random_problem(seed: int) -> ILPProblem:
    rng = np.random.default_rng(seed)
    N = int(rng.integers(2, 7))
    M = int(rng.integers(2, 5))
    loads = rng.uniform(0.1, 0.95, size=(N, M))
    costs = rng.uniform(0.5, 8.0, size=M).round(2)
    n_buckets = int(rng.integers(1, N + 1))
    bucket_of = rng.integers(0, n_buckets, size=N).astype(int)
    caps = (rng.integers(1, 6, size=M).astype(float)
            if rng.random() < 0.5 else None)
    return ILPProblem(loads, costs, [f"g{j}" for j in range(M)],
                      bucket_of, caps=caps)


def _check_solve_stats_case(seed: int) -> None:
    prob = _random_problem(seed)
    sol = solve(prob, time_budget_s=2.0)
    if sol is None:
        return
    st_ = sol.stats
    assert st_ is not None
    assert st_.consistent(), (
        f"seed {seed}: nodes={st_.nodes} pruned={st_.pruned_total} "
        f"considered={st_.comps_considered}")
    assert st_.phase_total_s <= sol.solve_time_s + 1e-6
    assert st_.n_slices == prob.loads.shape[0]
    assert st_.n_columns == prob.loads.shape[1]
    assert st_.nodes >= 1
    assert sum(st_.nodes_by_depth) == st_.nodes
    # incumbent trajectory is non-increasing in cost and ends at the answer
    costs = [c for _, c in st_.incumbents]
    assert all(b <= a + 1e-9 for a, b in zip(costs, costs[1:]))
    if costs:
        assert costs[-1] == pytest.approx(sol.cost)


def test_solve_stats_conservation_smoke():
    for seed in range(12):
        _check_solve_stats_case(seed)


@pytest.mark.slow
@settings(max_examples=150, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_property_solve_stats_conservation(seed):
    """(nodes - 1) + Σ pruned == comps_considered on every solve; phase
    times sum to at most the recorded solve time."""
    _check_solve_stats_case(seed)


def test_solve_stats_real_problem_and_dict_round_trip():
    wl = make_workload("mixed", 4.0)
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    sol = solve(build_problem(wl, mel.profile, 4), time_budget_s=1.0)
    st_ = sol.stats
    assert st_ is not None and st_.consistent()
    assert st_.greedy_s >= 0 and st_.polish_s >= 0 and st_.bnb_s >= 0
    assert st_.phase_total_s <= sol.solve_time_s + 1e-6
    d = st_.to_dict()
    json.dumps(d)                             # JSON-serializable as-is
    back = SolveStats.from_dict(json.loads(json.dumps(d)))
    assert back == st_


def test_allocation_surfaces_solve_stats():
    wl = make_workload("mixed", 2.0)
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    alloc = mel.allocate(wl, time_budget_s=1.0)
    assert alloc is not None
    assert alloc.solution.stats is not None
    assert alloc.solution.stats.consistent()


# ---------------------------------------------------------------------------
# satellite: Decision.to_dict key-collision fix + JSON round trip
# ---------------------------------------------------------------------------
def test_decision_detail_cannot_shadow_fields():
    st_ = SolveStats(n_slices=3, nodes=2, comps_considered=1)
    d = Decision(300.0, "rescale",
                 {"t": -1.0, "kind": "sneaky", "solve_time_s": 0.25,
                  "solve_stats": st_})
    dd = d.to_dict()
    # the decision's own fields win; detail lives under its own key
    assert dd["t"] == 300.0 and dd["kind"] == "rescale"  # lint: allow[float-eq] (exact hand-set value)
    assert dd["detail"]["t"] == -1.0 and dd["detail"]["kind"] == "sneaky"  # lint: allow[float-eq] (exact hand-set value)
    assert isinstance(dd["detail"]["solve_stats"], dict)
    back = Decision.from_dict(json.loads(json.dumps(dd)))
    assert back.t == 300.0 and back.kind == "rescale"  # lint: allow[float-eq] (exact hand-set value)
    assert back.detail["t"] == -1.0  # lint: allow[float-eq] (exact hand-set value)
    assert back.solve_stats == st_            # dict form converts back


def test_timeline_json_round_trip_with_stats():
    tl = Timeline()
    tl.windows.append(WindowRecord(
        t0=0.0, t1=300.0, arrived=10, completed=8, dropped=2, slo_ok=7,
        observed_rate=10 / 300, fleet={"A100": 2}, draining={},
        cost_rate=7.3))
    tl.record_decision(300.0, "rescale", solve_time_s=0.2,
                       solve_stats=SolveStats(nodes=1),
                       add={"A100": 1}, kind_detail="x")
    back = Timeline.from_json(tl.to_json())
    assert len(back.windows) == 1 and len(back.decisions) == 1
    assert back.windows[0].slo_attainment == pytest.approx(0.7)
    assert back.decisions[0].kind == "rescale"
    assert back.decisions[0].solve_stats == SolveStats(nodes=1)
    assert back.solve_stats() == [SolveStats(nodes=1)]
    assert back.summary()["slo_attainment"] == pytest.approx(0.7)


def test_window_record_round_trip_every_field():
    """PR 10 satellite: ``WindowRecord.to_dict``/``from_dict`` and the
    Timeline JSON path preserve every field — including ``events`` and
    the per-model drill-down — and ignore unknown keys on the way in."""
    rec = WindowRecord(
        t0=600.0, t1=900.0, arrived=42, completed=40, dropped=2, slo_ok=39,
        observed_rate=42 / 300, fleet={"A100": 2, "L4": 1},
        draining={"L4": 1}, cost_rate=9.25,
        events=[{"kind": "preemption", "gpu": "A100:spot", "n": 1}],
        per_model={"chat": {"arrived": 30, "completed": 29, "dropped": 1,
                            "slo_ok": 29, "fleet": {"A100": 2}}})
    d = rec.to_dict()
    back = WindowRecord.from_dict(json.loads(json.dumps(d)))
    assert back == rec                        # dataclass field equality
    assert back.model_attainment("chat") == pytest.approx(29 / 30)
    # forward compatibility: unknown keys are dropped, not fatal
    assert WindowRecord.from_dict({**d, "added_in_pr99": 1}) == rec
    tl = Timeline()
    tl.windows.append(rec)
    back_tl = Timeline.from_json(tl.to_json())
    assert back_tl.windows == [rec]


# ---------------------------------------------------------------------------
# satellite: dropped-inclusive attainment is one number on both paths
# ---------------------------------------------------------------------------
def test_window_attainment_is_dropped_inclusive():
    rec = WindowRecord(t0=0, t1=1, arrived=10, completed=6, dropped=4,
                       slo_ok=6, observed_rate=10.0, fleet={}, draining={},
                       cost_rate=0.0)
    # 6 in-SLO completions over (6 completed + 4 dropped): 60%, not 100%
    assert rec.slo_attainment == pytest.approx(0.6)
    empty = WindowRecord(t0=0, t1=1, arrived=0, completed=0, dropped=0,
                         slo_ok=0, observed_rate=0.0, fleet={}, draining={},
                         cost_rate=0.0)
    assert empty.slo_attainment == 1.0  # lint: allow[float-eq] (exact hand-set value)


@pytest.mark.slow
def test_attainment_paths_agree_on_trace_with_drops():
    """The request-level path (OrchestratorResult.slo_attainment) and the
    window path (Timeline.summary) must pin to the same number on a run
    that drops requests."""
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    tr = WorkloadTrace("steady", [
        TraceSegment(0.0, 120.0, 2.0, {"mixed": 1.0})], seed=3)
    # kill the whole (tiny) fleet mid-trace and never replace it: every
    # later arrival is dropped by drop_stranded
    tr = tr.with_events([FleetEvent(60.0, "preemption", "A100", 99)])
    res = run_static(mel, {"A100": 1}, tr, seed=3, apply_preemptions=True)
    assert res.n_dropped > 0, "scenario must actually drop requests"
    # precondition for exact equality: no 1-token completions (they have
    # no TPOT sample; the request path excludes them, the window path
    # counts them as in-SLO)
    assert all(r.decoded > 1 for r in res.requests if not r.dropped)
    assert res.timeline.summary()["slo_attainment"] == \
        pytest.approx(res.slo_attainment)
    assert res.slo_attainment < 1.0


# ---------------------------------------------------------------------------
# integration: an observed elastic run
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_observed_elastic_run_end_to_end():
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    tr = WorkloadTrace("ramp", [
        TraceSegment(0.0, 200.0, 1.0, {"mixed": 1.0}),
        TraceSegment(200.0, 400.0, 4.0, {"mixed": 1.0}),
    ], seed=11)
    reg = MetricsRegistry(enabled=True)
    tracer = SpanTracer(enabled=True, sample_every=8)
    orch = ClusterOrchestrator(mel, tr, window_s=100.0,
                               launch_delay_s=10.0, solver_budget_s=0.5,
                               seed=11, spot_preemptions=False,
                               metrics=reg, tracer=tracer)
    res = orch.run()
    assert res.conserved

    snap = reg.snapshot()
    assert validate_snapshot(snap) == []
    by_name = {m["name"]: m for m in snap["metrics"]}
    wins = by_name["melange_windows_total"]["series"][0]["value"]
    assert wins == len(res.timeline.windows)
    comp = by_name["melange_requests_completed_total"]["series"][0]["value"]
    assert comp == res.n_completed
    fleet = by_name["melange_fleet_instances"]
    assert all(s["labels"].get("gpu") for s in fleet["series"])

    # prometheus exposition of the same registry round-trips
    types, samples = parse_prometheus(reg.to_prometheus())
    assert types["melange_fleet_instances"] == "gauge"

    # chrome trace validates and carries both clocks + window spans
    obj = tracer.to_chrome()
    assert validate_chrome_trace(obj) == []
    evs = obj["traceEvents"]
    assert sum(1 for e in evs
               if e["name"] == "window" and e["ph"] == "X") \
        == len(res.timeline.windows)
    assert any(e["name"] == "resolve:rescale" for e in evs)

    # every re-solve decision carries a consistent SolveStats whose phase
    # times sum to <= the recorded solve latency
    resolves = [d for d in res.timeline.decisions
                if d.kind in ("rescale", "failure")]
    assert resolves, "ramp trace must trigger at least one re-solve"
    for d in resolves:
        st_ = d.solve_stats
        assert st_ is not None and st_.consistent()
        assert st_.phase_total_s <= d.detail["solve_time_s"] + 1e-6

    # autoscaler history surfaces the same stats objects
    for h in res.autoscaler_history:
        if h.get("event") in ("rescale", "failure"):
            assert h.get("solve_stats") is not None

    # the run report renders from the recorded timeline + snapshot
    rep = report_dict(res.timeline, snap)
    assert rep["summary"]["windows"] == len(res.timeline.windows)
    assert rep["solve_stats"]["solves"] == len(res.timeline.solve_stats())
    text = render_report(res.timeline, snap, title="test run")
    assert "slo attainment" in text and "phase split" in text


def test_disabled_observability_is_inert():
    """With registry and tracer disabled the orchestrator records nothing
    beyond its timeline — the zero-overhead-when-disabled contract."""
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    tr = WorkloadTrace("steady", [
        TraceSegment(0.0, 100.0, 1.0, {"mixed": 1.0})], seed=5)
    reg = MetricsRegistry(enabled=False)
    tracer = SpanTracer(enabled=False)
    orch = ClusterOrchestrator(mel, tr, window_s=50.0, solver_budget_s=0.5,
                               seed=5, spot_preemptions=False,
                               metrics=reg, tracer=tracer)
    res = orch.run()
    assert res.timeline.windows                  # timeline still recorded
    for m in reg.snapshot()["metrics"]:
        for s in m["series"]:
            assert s.get("value", 0) == 0 and s.get("count", 0) == 0
    assert [e for e in tracer.events if e["ph"] != "M"] == []
