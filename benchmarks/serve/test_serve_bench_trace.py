"""Trace reduction: busy union, idle share and gaps, kernel time and the
breakdown, on hand-built traces; host spans from a recorded CPU trace."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import runlib  # noqa: E402
import trace_reduce as tr  # noqa: E402
from trace_reduce import Ev  # noqa: E402


def test_union_and_overlap():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]
    m = tr.union([(0, 1), (2, 4)])
    assert tr.overlap(m, 0.5, 3) == pytest.approx(1.5)
    assert tr.overlap(m, 5, 6) == 0


def test_idle_share_and_gaps():
    merged = tr.union([(0.0, 0.2), (0.5, 1.0)])
    # one step [0, 1): busy 0.7 of 1.0
    assert tr.idle_share_in(merged, [(0.0, 1.0)]) == pytest.approx(0.3)
    assert tr.idle_share_in(merged, []) is None
    assert tr.idle_gaps(merged, 0.0, 1.5) == [(0.2, 0.5), (1.0, 1.5)]
    assert tr.idle_gaps(merged, -0.5, 0.1) == [(-0.5, 0.0)]


def _trace():
    dev = {"/device:TPU:0": [
        Ev("fusion.1", 0.00, 0.01), Ev("custom-call.3", 0.01, 0.04,
                                       {"tf_op": "jit(f)/flash_attention"}),
        Ev("fusion.1", 0.06, 0.07), Ev("fusion.2", 0.20, 0.25)]}
    host = [Ev("bench_step_0", 0.0, 0.1), Ev("bench_wait", 0.1, 0.2),
            Ev("bench_step_1", 0.2, 0.3)]
    return tr.Trace(dev, host)


def test_kernel_time_busy_and_breakdown():
    t = _trace()
    k = tr.matching(t.device_ops["/device:TPU:0"], "flash_attention")
    assert [e.name for e in k] == ["custom-call.3"]
    assert sum(e.dur for e in k) == pytest.approx(0.03)
    assert tr.busy_s(t) == pytest.approx(0.04 + 0.01 + 0.05)
    ops = tr.top_ops(t.device_ops["/device:TPU:0"], key=tr.op_label)
    assert ops[0] == ["fusion.2", pytest.approx(0.05)]
    assert ["jit(f)/flash_attention", pytest.approx(0.03)] in ops
    # the v5e trace names an op by its whole HLO instruction
    hlo = "%copy.96 = bf16[28,32,4096,2,128]{4,3,2,1,0:T(2,128)} copy(bf16[28])"
    assert tr.op_label(Ev(hlo, 0, 1)) == "copy.96 bf16[28,32,4096,2,128]"
    assert tr.op_label(Ev("%while.2 = (s32[], bf16[2]) while(x)", 0, 1)) == \
        "while.2"

    steps = [runlib.StepRec(0, 0, 0.1, [7], 10, [10], 1),
             runlib.StepRec(1, 0.2, 0.3, [], 0, [11], 1)]
    run = runlib.Run({}, 1, {}, runlib.Timeline(0, 0, 0, 0.3), steps, {},
                     0.0, trace=t)
    # inside steps: [0, 0.1) busy 0.05 (0-0.04, 0.06-0.07); [0.2, 0.3) 0.05
    assert runlib.step_idle_share(run) == pytest.approx(50.0)
    b = runlib.breakdown(run, tr.op_label)
    names = [g[0] for g in b["idle_gaps"]]
    assert b["idle_gaps"][0] == ["waiting for an arrival", pytest.approx(0.13)]
    assert "admitting step" in names and "decode-only step" in names
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_trace_reads_nothing():
    run = runlib.Run({}, 1, {}, runlib.Timeline(0, 0, 1, 1), [], {}, 0.0)
    assert runlib.step_idle_share(run) is None


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(3):
        with jax.profiler.TraceAnnotation(f"bench_step_{i}"):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench_wait"):
        pass
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    names = [e.name for e in t.host_spans]
    assert names == ["bench_step_0", "bench_step_1", "bench_step_2",
                     "bench_wait"]
    assert all(e.end >= e.start for e in t.host_spans)
    assert t.device_ops == {}          # the CPU has no TPU device plane
