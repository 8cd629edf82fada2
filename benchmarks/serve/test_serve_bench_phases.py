"""The readings of the engine's spans (``phases.py``): on hand-built runs
and traces, on a trace recorded on the CPU through the tracer's annotate
hook, and on a reduced cell served end to end."""
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import phases  # noqa: E402
import runlib  # noqa: E402
import trace_reduce as tr  # noqa: E402
from runlib import ReqRec, StepRec  # noqa: E402
from trace_reduce import Ev  # noqa: E402


def _x(name, step, dur_us, **args):
    return {"ph": "X", "name": name, "dur": dur_us,
            "args": {"step": step, **args}}


def _run():
    """Window [10, 20); the engine's counter read 40 before the loop's
    first step.  Loop steps 0 and 2 decode only (3 and 2 slots); step 1
    admits requests 0 and 1 (8 + 12 prompt tokens); step 3 lies after the
    window."""
    reqs = {0: ReqRec(0, 8, 4, due=10.5, admit_step_t0=11.0),
            1: ReqRec(1, 12, 4, due=10.9, admit_step_t0=11.0),
            2: ReqRec(2, 5, 4, due=9.0, admit_step_t0=9.0),
            3: ReqRec(3, 5, 4, due=19.5)}
    steps = [StepRec(0, 10.0, 10.1, [], 0, [5, 6, 7], 3),
             StepRec(1, 11.0, 11.3, [0, 1], 20, [8], 1),
             StepRec(2, 11.3, 11.4, [], 0, [9, 10], 2),
             StepRec(3, 21.0, 21.1, [], 0, [11], 1)]
    return runlib.Run({}, 4, {}, runlib.Timeline(0, 10, 20, 20), steps, reqs,
                      0.0)


def _admit(step, dur_us, padded=0, rejected=0, stop=None):
    return _x("admit", step, dur_us, padded_tokens=padded, rejected=rejected,
              stop=stop)


EVENTS = [_x("step", 41, 100e3), _admit(41, 5.0),
          _x("sample", 41, 50e3, syncs=3),
          _admit(42, 240e3, padded=32, rejected=1, stop="no_slot"),
          _x("prefill", 42, 150e3, rid=0, new_program=True),
          _x("prefill", 42, 60e3, rid=1, new_program=False),
          _x("sample", 42, 40e3, syncs=1),
          _admit(43, 1.0, stop="no_slot"), _x("sample", 43, 30e3, syncs=2),
          _admit(44, 1.0, stop="budget"),
          _x("prefill", 44, 90e3, rid=3, new_program=True),
          _x("sample", 44, 30e3, syncs=9),
          {"ph": "M", "name": "thread_name", "args": {"name": "engine"}}]


def test_span_readings_join_window_steps_on_the_engine_counter():
    run = _run()
    assert phases.engine_step(run.steps[1], 40) == 42
    # the admitting step's admit span: 240 ms for 20 prompt tokens
    assert phases.admit_ms_per_ktok(run, EVENTS, 40) == \
        pytest.approx(240e3 / 20)
    # decode-only window steps 41 and 43; 44 lies after the window
    assert phases.host_syncs_decode(run, EVENTS, 40) == pytest.approx(2.5)
    # a counter off by one step joins the wrong spans
    assert phases.host_syncs_decode(run, EVENTS, 41) == pytest.approx(5.0)


def test_admission_reads_the_window_steps_admit_and_prefill_spans():
    run = _run()
    a = phases.admission(run, EVENTS, 40)
    # step 44 (loop step 3) lies after the window
    assert a["stops"] == {"no_slot": 2}
    assert a["rejected"] == 1
    # the admitting step's 20 prompt tokens ran as 32 padded ones
    assert a["pad_share"] == pytest.approx(100.0 * (32 - 20) / 32)
    # the one prefill in the window that built its program: request 0
    assert a["new_programs"] == [[42, 0, 8, pytest.approx(150.0)]]
    assert phases.admission(run, [], 40) == {}


def test_admit_wait_counts_requests_due_in_the_window():
    run = _run()
    admit_t = {0: 11.05, 1: 11.2, 2: 9.0}       # 2 was due in the ramp
    v = phases.admit_wait_ms_p90(run, admit_t)
    assert v == pytest.approx((11.05 - 10.5) * 1e3)
    assert v >= runlib.percentile([11.0 - 10.5, 11.0 - 10.9], 90) * 1e3


def test_readings_are_absent_without_the_engines_spans():
    """An engine that records no spans: nothing to read, nothing raised."""
    run = _run()
    assert phases.admit_wait_ms_p90(run, {}) is None
    assert phases.admit_ms_per_ktok(run, [], 40) is None
    assert phases.host_syncs_decode(run, [], 40) is None
    assert phases.sample_idle_ms_decode(run, []) is None
    assert phases.phase_idle_ms(run, []) == {}
    assert phases.labelled_gaps(run, []) == []


def _traced_run():
    """A decode-only step [0, 50) ms whose decode program runs 0-30 ms and
    two sampling ops 35-36 and 45-46 ms; a wait; an admitting step
    [200, 300) ms whose prefill runs 210-240 ms."""
    dev = {"/device:TPU:0": [Ev("fusion.1", 0.0, 0.030,
                                {"tf_op": "jit(decode)/decode/dot"}),
                             Ev("argmax.2", 0.035, 0.036),
                             Ev("argmax.2", 0.045, 0.046),
                             Ev("fusion.7", 0.210, 0.240,
                                {"tf_op": "jit(prefill)/prefill/dot"})]}
    host = [Ev("bench_step_0", 0.0, 0.050), Ev("bench_wait", 0.050, 0.200),
            Ev("bench_step_1", 0.200, 0.300)]
    spans = [Ev("engine.step", 0.001, 0.049), Ev("engine.admit", 0.001, 0.002),
             Ev("engine.decode", 0.002, 0.004),
             Ev("engine.sample", 0.004, 0.048),
             Ev("engine.step", 0.201, 0.299), Ev("engine.admit", 0.201, 0.250),
             Ev("engine.prefill", 0.202, 0.210)]
    steps = [StepRec(0, 0.0, 0.05, [], 0, [5, 6], 2),
             StepRec(1, 0.2, 0.3, [7], 10, [], 0)]
    run = runlib.Run({}, 2, {}, runlib.Timeline(0, 0, 0, 0.3), steps, {}, 0.0,
                     trace=tr.Trace(dev, host))
    return run, spans


def test_idle_inside_sample_and_the_split_by_phase():
    run, spans = _traced_run()
    # sample [4, 48) ms: busy 26 + 1 + 1 ms, so 16 ms idle
    assert phases.sample_idle_ms_decode(run, spans) == pytest.approx(16.0)
    split = phases.phase_idle_ms(run, spans)
    d = split["decode-only step"]
    assert d["steps"] == 1 and d["step_ms"] == pytest.approx(50.0)
    assert d["engine.sample"] == pytest.approx(16.0)
    assert d["engine.step"] == pytest.approx(1.0)     # 48-49 ms
    assert d["outside engine spans"] == pytest.approx(1.0)   # 49-50 ms
    assert d["engine.admit"] == d["engine.decode"] == pytest.approx(0.0)
    assert d["idle_ms"] == pytest.approx(18.0)
    # the split adds up to the step's idle time
    assert d["idle_ms"] * 1e-3 == pytest.approx(
        50e-3 * tr.idle_share_in(tr.busy(run.trace.device_ops[
            "/device:TPU:0"]), [(0.0, 0.05)]))
    a = split["admitting step"]
    assert a["engine.prefill"] == pytest.approx(8.0)   # 202-210 ms
    assert a["engine.admit"] == pytest.approx(10.0 + 1.0)
    assert a["idle_ms"] == pytest.approx(70.0)


def test_a_gap_inside_sample_is_named_by_its_phase():
    run, spans = _traced_run()
    gaps = phases.labelled_gaps(run, spans)
    assert gaps[0] == ["waiting for an arrival", pytest.approx(0.164)]
    names = [g[0] for g in gaps]
    assert names[1] == "admitting step / engine.step"       # 240-300 ms
    assert names.count("decode-only step / engine.sample") == 2
    assert ["decode-only step / engine.sample", pytest.approx(0.009)] in gaps
    # a gap in the harness's own code inside a step, outside engine spans
    bare = phases.labelled_gaps(run, [sp for sp in spans if sp.start > 0.1])
    assert ["decode-only step / outside engine spans",
            pytest.approx(0.009)] in bare
    assert bare[0] == gaps[0]                       # a wait is no step
    # the same gaps and lengths as the harness's own breakdown
    plain = runlib.breakdown(run, tr.op_label)["idle_gaps"]
    assert [g[1] for g in gaps] == [pytest.approx(g[1]) for g in plain]
    assert [g[0].split(" / ")[0] for g in gaps] == [g[0] for g in plain]


def test_device_time_by_program():
    runs = [Ev("jit_decode(71)", 0.0, 0.05), Ev("jit_prefill(9)", 0.1, 0.12),
            Ev("jit_decode(71)", 0.2, 0.25), Ev("jit_prefill(12)", 0.3, 0.31),
            Ev("jit_argmax(5)", 0.26, 0.2601)]
    got = phases.by_program(runs)
    assert [g[0] for g in got] == ["jit_decode", "jit_prefill", "jit_argmax"]
    assert got[0][1:] == [pytest.approx(0.10), 2]
    assert got[1][1:] == [pytest.approx(0.03), 2]


def test_engine_spans_of_a_trace_recorded_through_the_hook(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs.trace import SpanTracer
    tracer = SpanTracer(enabled=True, annotate=jax.profiler.TraceAnnotation)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench_step_{i}"):
            with tracer.span("step", track="engine", step=i):
                with tracer.span("sample", track="engine", step=i):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [e.name for e in t.host_spans] == [f"bench_step_{i}"
                                              for i in range(3)]
    spans, programs = phases.read_profile(str(tmp_path))
    assert [e.name for e in spans] == ["engine.step", "engine.sample"] * 3
    assert programs == []              # the CPU has no TPU device plane
    for i, h in enumerate(t.host_spans):
        step, sample = spans[2 * i:2 * i + 2]
        assert h.start <= step.start <= sample.start
        assert sample.end <= step.end <= h.end
    assert len([e for e in tracer.events if e["ph"] == "X"]) == 6


def test_reduced_cell_with_the_engines_spans(monkeypatch):
    """The reduced cell of the rehearsal, served with the spans on and a
    profiled second after the window: the host-side readings come out and
    agree with the harness's own; the CPU has no device plane, so the
    reading of device idle time is absent."""
    import jax

    import test_serve_bench_rehearsal as rehearsal
    import traffic
    from repro.kernels import ops
    monkeypatch.setitem(traffic.SAMPLERS, "tiny", rehearsal._tiny_sampler)
    bench, config, tf, _ = rehearsal.tiny_cell()
    with ops.default_impl("jnp"):
        out = phases.phases(rehearsal.CELL, bench, config, tf, seed=2**33 + 5,
                            seconds=2.0, profile_s=1.0,
                            devices=jax.devices(), peak=rehearsal.PEAK,
                            t_start=0.0)
    r = out["readings"]
    for k in ("admit_wait_ms.p90", "admit_ms_per_ktok", "host_syncs.decode"):
        assert k in r and r[k] > 0
    assert "sample_idle_ms.decode" not in r
    # the argmax path reads the batch's tokens once a decode step
    assert r["host_syncs.decode"] == 1
    assert r["admit_ms_per_ktok"] < r["prefill_ms_per_ktok"]
    assert r["admit_wait_ms.p90"] >= r["queue_wait_ms.p90"]
    assert all(v > 0 and math.isfinite(v) for v in out["tails"].values())
    assert out["spans"]["tracer"] > 0 and out["spans"]["profiler"] > 0
    assert out["window"]["compile_events"] == 0
    assert out["phase_idle_ms"] == {} and out["idle_gaps"] == []
    a = out["admission"]
    assert a["new_programs"] == [] and a["rejected"] == 0
    assert 0 <= a["pad_share"] < 100
    assert set(a["stops"]) <= {"no_slot", "no_blocks", "budget"}
    assert out["device"]["platform"] == "cpu"
