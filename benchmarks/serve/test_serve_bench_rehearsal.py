"""A cell run end to end on the CPU at a reduced size, with the Pallas
kernels in interpret mode, through ``run.run_cell`` (the entry point's own
look for a chip is skipped by calling past it); the faults that the check
must catch; and the entry point's refusal of the CPU."""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import run  # noqa: E402
import traffic as traffic_mod  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402

PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
CELL = "qwen2-arena"


def _tiny_sampler(rng, n):
    i = np.clip(rng.lognormal(np.log(12), 0.5, n), 2, 40).astype(int)
    o = np.clip(rng.lognormal(np.log(16), 0.5, n), 2, 40).astype(int)
    return i, o


GLOBAL, LOCAL = ({"kind": "attn", "attn_type": t, "mlp": "dense"}
                 for t in ("global", "local"))
# a global layer, then (local, local, local, global) twice: prompts and
# answers of up to 40 tokens cross the window of 16
MIXED = {"groups": [{"period": [GLOBAL], "repeat": 1},
                    {"period": [LOCAL, LOCAL, LOCAL, GLOBAL], "repeat": 2}],
         "sliding_window": 16}


def tiny_model(pattern=None) -> dict:
    """The configuration's model at a few widths: two layers of its own
    pattern, or the layer ``pattern`` given (a dict of model keys that
    replaces ``period`` and ``n_layers``)."""
    m = dict(run.cell_files(CELL)[2]["model"], d_model=64, n_heads=4,
             n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256, n_layers=2)
    if pattern is None:
        return m
    return dict({k: v for k, v in m.items() if k not in ("period", "n_layers")},
                **pattern)


def tiny_cell(pattern=None, **traffic_over):
    """The cell's files with the model cut to a few widths and layers (see
    ``tiny_model``), the engine to 4 slots and the traffic to short
    requests."""
    bench, cell, config, traffic, checks = run.cell_files(CELL)
    m = tiny_model(pattern)
    D, H, Dh, F = m["d_model"], m["n_heads"], m["head_dim"], m["d_ff"]
    s_q, s_in = math.sqrt(2 / (D + H * Dh)), math.sqrt(2 / (D + F))
    # the embedding (tied head) widened so that the logits spread as widely
    # as at the configuration's own width, and gaps read on the same scale
    mean, std = config["init"]["normal"]["embed"]
    embed = [mean, std * math.sqrt(config["model"]["d_model"] / D)]
    normal = dict(config["init"]["normal"], embed=embed,
                  **{k: [0.0, s_q] for k in ("wq", "wk", "wv", "wo")},
                  **{k: [0.0, s_in] for k in ("w_up", "w_gate", "w_down")})
    config = dict(config, model=m, init=dict(config["init"], normal=normal),
                  engine=dict(config["engine"], max_batch=4, max_seq=128,
                              prefill_budget_tokens=128))
    traffic = dict(traffic, sampler="tiny", rate=6.0, ramp_s=1.0,
                   drain_cap_s=10.0, **traffic_over)
    checks = dict(checks, min_tokens=32)
    return bench, config, traffic, checks


def run_tiny(seed=2**33 + 1, trace=False, impl="pallas_interpret",
             pattern=None, **over):
    bench, config, traffic, checks = tiny_cell(pattern, **over)
    with ops.default_impl(impl):
        return run.run_cell(CELL, bench, config, traffic, checks, seed=seed,
                            seconds=2.0, trace=trace, devices=jax.devices(),
                            peak=PEAK, t_start=0.0)


@pytest.fixture(autouse=True)
def tiny_sampler(monkeypatch):
    monkeypatch.setitem(traffic_mod.SAMPLERS, "tiny", _tiny_sampler)
    monkeypatch.setattr(run, "TRACE_S", 1.0)


def test_reduced_cell_end_to_end(capsys):
    out = run_tiny()
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "check"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 10
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    chk = out["check"]["logit_gap_max"]
    assert chk["value"] <= chk["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert "compile events inside the window: 0" in err
    assert err[-1].startswith("check tokens_compared")
    json.dumps(out)


def test_reduced_cell_traced():
    out = run_tiny(trace=True)
    assert out["correct"] is True
    assert "queue_wait_ms.p90" in out["metrics"]
    assert "ttft_p90_ms" not in out["metrics"]
    # the CPU has no device plane: the trace's metrics read nothing
    assert "flash_roofline" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _stale_cache(orig):
    def decode_step(cfg, p, c, t, l, append=None):
        return orig(cfg, p, c, t, l, append=append)[0], c
    return decode_step


def _half_batch(orig):
    def decode_step(cfg, p, c, t, l, append=None):
        lg, c2 = orig(cfg, p, c, t, l, append=append)
        h = lg.shape[0] // 2
        return lg.at[h:].set(lg[:h]), c2
    return decode_step


def _bias_dropped(orig):
    def init(self, cfg, params, ecfg):
        orig(self, cfg, params, ecfg)
        self.params = jax.tree_util.tree_map_with_path(
            lambda p, x: x * 0 if str(p[-1].key) in ("bq", "bk", "bv") else x,
            params)
    return init


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered", "bias_dropped"])
def test_faults_come_out_not_correct(monkeypatch, fault):
    if fault == "bias_dropped":
        monkeypatch.setattr(engine_mod.ServingEngine, "__init__",
                            _bias_dropped(engine_mod.ServingEngine.__init__))
    elif fault == "state_unchanged":
        monkeypatch.setattr(T, "decode_step", _stale_cache(T.decode_step))
    elif fault == "half_batch":
        monkeypatch.setattr(T, "decode_step", _half_batch(T.decode_step))
    else:
        orig = engine_mod.ServingEngine._sample
        monkeypatch.setattr(engine_mod.ServingEngine, "_sample",
                            lambda self, lg, req: (orig(self, lg, req) + 1)
                            % self.cfg.vocab_size)
    # a backlog keeps every slot busy, so the upper half of the batch decodes
    out = run_tiny(arrivals="backlog", backlog=24, impl="jnp")
    assert out["correct"] is False
    chk = out["check"]["logit_gap_max"]
    assert chk["value"] > chk["limit"]


def _position_0_everywhere(orig):
    """The reference given each group's first period position's weights at
    every position (the single-stack view of the weights)."""
    def plain(params):
        w = orig(params)
        return dict(w, groups=[tuple(g[0] for _ in g) for g in w["groups"]])
    return plain


def _no_window(orig):
    def ref_sizes(model):
        m = orig(model)
        return dict(m, layers=tuple((k, "global", f)
                                    for k, _, f in m["layers"]))
    return ref_sizes


@pytest.mark.parametrize("fault", [None, "position_0_weights", "no_window"])
def test_reduced_mixed_cell(monkeypatch, fault):
    """A model whose layers differ (two groups, windowed and full attention)
    served through the harness reads ``correct`` true; a reference that
    sees only each group's first position's weights, or no window, reads
    it false."""
    import weights
    if fault == "position_0_weights":
        monkeypatch.setattr(weights, "plain", _position_0_everywhere(
            weights.plain))
    elif fault == "no_window":
        monkeypatch.setattr(run, "ref_sizes", _no_window(run.ref_sizes))
    out = run_tiny(pattern=MIXED, impl="jnp" if fault else "pallas_interpret")
    chk = out["check"]["logit_gap_max"]
    assert out["correct"] is (fault is None), chk
    assert (chk["value"] <= chk["limit"]) is (fault is None)
    assert out["check"]["tokens_compared"]["value"] >= 32


def test_entry_point_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert p.stdout.strip() == ""
