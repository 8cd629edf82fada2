"""Percentile, gap and window arithmetic and the metric readers, on a
hand-made timeline that holds a stall and failed requests."""
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import pytest  # noqa: E402

import run as bench_run  # noqa: E402
import runlib  # noqa: E402
from runlib import ReqRec, StepRec  # noqa: E402

M = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2, "d_ff": 3,
     "vocab_size": 5, "n_layers": 2, "qkv_bias": False, "tie_embeddings": True,
     "period": [{"kind": "attn", "attn_type": "global", "mlp": "dense"}]}
PEAK = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e4}


def test_percentile_nearest_rank_with_failures():
    assert runlib.percentile([3, 1, 2, 4], 50) == 2
    assert runlib.percentile(list(range(1, 11)), 90) == 9
    assert math.isinf(runlib.percentile([1, 2, math.inf], 90))
    assert runlib.percentile([1] * 9 + [math.inf], 90) == 1
    assert math.isnan(runlib.percentile([], 50))


def _timeline():
    """Window [10, 20).  Request 0 due at 9 (ramp) streams through the
    window; request 1 is due at 11 and waits out a 3 s stall; request 2 is
    due at 12 and rejected; request 3 is due at 19 and never served; request
    4 is due at 21, after the window."""
    r0 = ReqRec(0, 4, 9, due=9.0, admit_step_t0=9.0, first_t=9.1,
                token_t=[9.1, 9.6, 10.1, 10.6, 14.6, 15.1])
    r1 = ReqRec(1, 8, 3, due=11.0, admit_step_t0=14.0, first_t=14.5,
                token_t=[14.5, 14.6, 15.1])
    r2 = ReqRec(2, 5, 2, due=12.0, rejected=True)
    r3 = ReqRec(3, 5, 2, due=19.0)
    r4 = ReqRec(4, 5, 2, due=21.0, admit_step_t0=21.0, first_t=21.2,
                token_t=[21.2])
    reqs = {r.rid: r for r in (r0, r1, r2, r3, r4)}
    steps = [
        StepRec(0, 10.0, 10.1, [], 0, [5], 1),
        StepRec(1, 10.1, 10.6, [], 0, [6], 1),
        StepRec(2, 10.6, 14.0, [], 0, [], 0),              # nothing decoded
        StepRec(3, 14.0, 14.6, [1], 8, [7, 8], 2),         # admits r1; stall
        StepRec(4, 14.6, 15.1, [], 0, [8, 9], 2),
        StepRec(5, 21.0, 21.2, [4], 5, [], 0),             # after window
    ]
    tl = runlib.Timeline(0.0, 10.0, 20.0, 20.0)
    return runlib.Run(M, 4, PEAK, tl, steps, reqs, setup_s=12.5)


def test_ttft_counts_failures_and_unserved_as_inf():
    run = _timeline()
    v = sorted(runlib.ttfts_due_in_window(run))
    assert v[0] == pytest.approx(3.5)                    # r1: 14.5 - 11
    assert v[1:] == [math.inf, math.inf]                 # r2 rejected, r3 never
    assert math.isinf(bench_run.read_metric("ttft_p90_ms", run))


def test_gaps_end_in_window_and_hold_the_stall():
    run = _timeline()
    g = sorted(runlib.gaps_ending_in_window(run))
    # r0: 9.6->10.1, 10.1->10.6, 10.6->14.6 (stall), 14.6->15.1; r1: two
    assert g == pytest.approx(sorted([0.5, 0.5, 4.0, 0.5, 0.1, 0.5]))
    assert bench_run.read_metric("itl_p95_ms", run) == pytest.approx(4000.0)
    # tokens stamped in [10, 20): r0 four, r1 three
    assert runlib.tokens_in_window(run) == 7
    assert bench_run.read_metric("setup_s", run) == pytest.approx(12.5)


def test_host_clock_layer_metrics():
    run = _timeline()
    assert bench_run.read_metric("queue_wait_ms.p90", run) == \
        pytest.approx(3000.0)
    assert bench_run.read_metric("prefill_ms_per_ktok", run) == \
        pytest.approx(0.6 / 8 * 1e6)
    # decode-only steps: 0 (0.1 s), 1 (0.5 s), 4 (0.5 s); step 2 decoded none
    assert bench_run.read_metric("decode_step_ms", run) == \
        pytest.approx(1100 / 3)


def test_share_metrics_from_counts():
    import counts
    run = _timeline()
    f = sum(counts.decode_flops(M, c) for c in (5, 6, 8, 9))
    assert bench_run.read_metric("mfu.decode", run) == \
        pytest.approx(100 * f / (1.1 * PEAK["bf16_flops_per_s"]))
    b = sum(counts.decode_step_bytes(M, c) for c in ([5], [6], [8, 9]))
    assert bench_run.read_metric("hbm_share.decode", run) == \
        pytest.approx(100 * b / (1.1 * PEAK["hbm_bytes_per_s"]))
    # the admitting step: r1's prompt and the two tokens it decoded
    f = counts.prefill_flops(M, 8) + sum(counts.decode_flops(M, c)
                                         for c in (7, 8))
    assert bench_run.read_metric("mfu.prefill", run) == pytest.approx(
        100 * f / (0.6 * PEAK["bf16_flops_per_s"]))


def test_trace_metrics_read_nothing_without_a_trace():
    run = _timeline()
    for name in ("flash_roofline", "idle_share.busy"):
        assert bench_run.read_metric(name, run) is None
