"""The ``jamba2-arena`` cell rehearsed on the CPU at a reduced size through
``run.run_cell``, with the Pallas kernels in interpret mode: a sound program
reads ``correct`` true, and three planted faults read it false (a Mamba
state that absorbed the prompt's padding, Jamba's inner norms dropped, rope
left on).  The scan's byte count by hand, and its readers on a hand-made
trace."""
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import counts_ssm  # noqa: E402
import run  # noqa: E402
import runlib  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import ssm as SSM  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from runlib import ReqRec, StepRec  # noqa: E402
from test_serve_bench_rehearsal import PEAK, _tiny_sampler  # noqa: E402
from trace_reduce import Ev, Trace  # noqa: E402

CELL = "jamba2-arena"
MAMBA = {"kind": "mamba", "attn_type": "global", "mlp": "dense"}
ATTN = {"kind": "attn", "attn_type": "global", "mlp": "dense"}


def tiny_cell(**model_over):
    """The cell's files with the model cut to d_model 64, d_inner 128,
    d_state 4, dt_rank 8, 4 query heads over 1 KV head of 16, one period
    of (Mamba, Mamba, Mamba, attention); 4 slots; short requests."""
    bench, cell, config, traffic, checks = run.cell_files(CELL)
    m = dict(config["model"], d_model=64, n_heads=4, n_kv_heads=1,
             head_dim=16, d_ff=128, vocab_size=256, mamba_d_state=4,
             mamba_dt_rank=8,
             groups=[{"period": [MAMBA, MAMBA, MAMBA, ATTN], "repeat": 1}],
             **model_over)
    D, H, Dh, F = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    Din, R = m["mamba_expand"] * D, m["mamba_dt_rank"]
    normal = dict(config["init"]["normal"])
    # matrices at the reduced widths, by the file's own rule; the embedding
    # (tied head) widened so that the logits spread as at full width
    mean, std = normal["embed"]
    normal["embed"] = [mean, std * math.sqrt(config["model"]["d_model"] / D)]
    normal.update({k: [0.0, math.sqrt(2 / (D + H * Dh))]
                   for k in ("wq", "wk", "wv", "wo")})
    normal.update({k: [0.0, math.sqrt(2 / (D + F))]
                   for k in ("w_up", "w_gate", "w_down")})
    normal.update(in_proj=[0.0, D ** -0.5], x_proj=[0.0, Din ** -0.5],
                  out_proj=[0.0, Din ** -0.5], dt_w=[0.0, R ** -0.5])
    config = dict(config, model=m, init=dict(config["init"], normal=normal),
                  engine=dict(config["engine"], max_batch=4, max_seq=128,
                              prefill_budget_tokens=128))
    traffic = dict(traffic, sampler="tiny", rate=6.0, ramp_s=1.0,
                   drain_cap_s=10.0)
    return bench, config, traffic, dict(checks, min_tokens=32)


def run_tiny(impl="pallas_interpret", seed=2**33 + 5, **model_over):
    bench, config, traffic, checks = tiny_cell(**model_over)
    with ops.default_impl(impl):
        return run.run_cell(CELL, bench, config, traffic, checks, seed=seed,
                            seconds=2.0, trace=False, devices=jax.devices(),
                            peak=PEAK, t_start=0.0)


@pytest.fixture(autouse=True)
def tiny_sampler(monkeypatch):
    monkeypatch.setitem(traffic_mod.SAMPLERS, "tiny", _tiny_sampler)


def _unmasked_prefill(orig):
    def prefill(cfg, params, tokens, lengths=None, **kw):
        return orig(cfg, params, tokens, None, **kw)
    return prefill


@pytest.mark.parametrize("fault", [None, "padded_state", "norms_dropped",
                                   "rope_on"])
def test_reduced_jamba_cell(monkeypatch, fault):
    over = {}
    if fault == "padded_state":
        monkeypatch.setattr(T, "prefill", _unmasked_prefill(T.prefill))
    elif fault == "norms_dropped":
        monkeypatch.setattr(SSM, "rms_norm", lambda x, scale, eps: x)
    elif fault == "rope_on":
        over["rope_theta"] = 10000.0
    out = run_tiny(impl="pallas_interpret" if fault is None else "jnp",
                   **over)
    chk = out["check"]["logit_gap_max"]
    assert out["correct"] is (fault is None), chk
    assert (chk["value"] <= chk["limit"]) is (fault is None)
    assert out["check"]["tokens_compared"]["value"] >= 32
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}


def test_scan_bytes_by_hand():
    m = {"d_model": 2560, "mamba_expand": 2, "mamba_d_state": 16}
    # x, dt, y: 3 * 128 * 5120 * 4; B, C: 2 * 128 * 16 * 4;
    # A, h0, hT: 3 * 5120 * 16 * 4; D: 5120 * 4
    assert counts_ssm.scan_bytes(m, 128) == (7_864_320 + 16_384 + 983_040
                                             + 20_480)
    assert [counts_ssm.padded(L) for L in (1, 8, 9, 90, 128, 129, 2000)] == \
        [8, 8, 16, 128, 128, 256, 2048]
    config = run.cell_files(CELL)[2]
    assert counts_ssm.mamba_layers(config["model"]) == 26


def _traced_run():
    """Trace [10, 12): one admitting step (prompts of 90 and 5 tokens) and
    a decode-only step.  On the device: two scan calls of 3 ms, and a cast
    and a fusion that read the scan's output (not the kernel's)."""
    m = {"d_model": 2560, "mamba_expand": 2, "mamba_d_state": 16,
         "groups": [{"period": [MAMBA, ATTN], "repeat": 1}]}
    reqs = {0: ReqRec(0, 90, 4, due=10.0), 1: ReqRec(1, 5, 4, due=10.0)}
    steps = [StepRec(0, 10.1, 10.2, [0, 1], 95, [], 0),
             StepRec(1, 10.2, 10.3, [], 0, [90, 5], 2)]
    # as the chip's trace names them: by the whole HLO instruction
    ops_ = [Ev("%ssm_scan.3 = (f32[1,128,5120]{2,1,0:T(8,128)}, "
               "f32[1,5120,16]{2,1,0:T(8,128)}) custom-call(%a, %b)",
               10.11, 10.113),
            Ev("%convert_element_type.5 = f32[1,128,5120]{2,1,0} "
               "convert(%ssm_scan.3)", 10.113, 10.114),
            Ev("%ssm_scan.4 = (f32[1,8,5120]{2,1,0:T(8,128)}, "
               "f32[1,5120,16]{2,1,0:T(8,128)}) custom-call(%c, %d)",
               10.12, 10.123),
            Ev("%fusion.1 = bf16[1,128,2560]{2,1,0} fusion(%ssm_scan.4)",
               10.13, 10.15)]
    run_ = runlib.Run(m, 4, {"hbm_bytes_per_s": 819e9}, runlib.Timeline(
        0.0, 0.0, 10.0, 12.0), steps, reqs, 1.0)
    run_.trace = Trace({"/device:TPU:0": ops_}, [])
    return run_


def test_scan_readers_on_a_hand_made_trace():
    r = _traced_run()
    assert [e.start for e in counts_ssm.kernel_events(r.trace)] == [
        10.11, 10.12]
    # one Mamba layer: one call per prompt, at 128 and 8 positions
    ideal = (counts_ssm.scan_bytes(r.model, 128)
             + counts_ssm.scan_bytes(r.model, 8)) / 819e9
    assert run.read_metric("ssm_scan_roofline", r) == pytest.approx(
        100 * ideal / 0.006)
    assert run.read_metric("ssm_scan_ms_per_ktok", r) == pytest.approx(
        6.0 / 95 * 1000)
    r.trace = None
    assert run.read_metric("ssm_scan_roofline", r) is None
    assert run.read_metric("ssm_scan_ms_per_ktok", r) is None


def test_parent_fails_fast_on_a_config_without_rope():
    """The cell's configuration states ``rope_theta`` null: a program whose
    attention always applies rope cannot take it (the parent's fails at its
    first trace), where this one builds the published model."""
    from repro.configs import get_config
    m = run.cell_files(CELL)[2]["model"]
    assert m["rope_theta"] is None
    assert run.model_config(m) == get_config("jamba2-3b")
    assert np.isclose(get_config("jamba2-3b").param_count(), 3.029e9,
                      rtol=1e-3)
