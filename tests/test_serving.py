"""Serving engine + heterogeneous cluster integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Melange, ModelPerf, PAPER_GPUS
from repro.models import transformer as T
from repro.serving import EngineConfig, Request, ServingCluster, ServingEngine

pytestmark = pytest.mark.slow  # discrete-event simulator heavy


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("internlm2-1.8b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    return cfg, params


def _ref_generate(cfg, params, prompt, n_new):
    toks = list(prompt)
    for _ in range(n_new):
        logits, _, _ = T.forward(cfg, params, jnp.asarray([toks]))
        toks.append(int(jnp.argmax(logits[0, len(toks) - 1])))
    return toks[len(prompt):]


def test_engine_matches_reference(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, cfg.vocab_size, size=L))
               for L in (5, 9, 13, 7)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    done = eng.run()
    assert len(done) == 4
    for r in done:
        assert r.generated == _ref_generate(cfg, params, prompts[r.rid], 6)
        assert r.ttft >= 0 and r.tpot >= 0
    # all cache blocks returned
    assert eng.blocks.n_used == 0
    eng.blocks.check_invariants()


def test_engine_rejects_too_long(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_seq=32))
    eng.submit(Request(rid=0, prompt=list(range(1, 30)), max_new_tokens=20))
    done = eng.run()
    assert len(done) == 1 and done[0].generated == []


def test_engine_continuous_batching_overlap(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_seq=64))
    for i in range(5):                      # more requests than slots
        eng.submit(Request(rid=i, prompt=[3 + i, 5, 7], max_new_tokens=4))
    done = eng.run()
    assert len(done) == 5
    assert eng.n_active == 0 and not eng.queue


def test_cluster_routes_and_serves(setup):
    cfg, params = setup
    mel = Melange(PAPER_GPUS, ModelPerf.llama2_7b(), 0.12)
    cluster = ServingCluster(
        cfg, params, {"A100": 1, "A10G": 1}, mel.profile,
        EngineConfig(max_batch=2, max_seq=64))
    rng = np.random.default_rng(1)
    for i in range(8):
        cluster.submit(Request(
            rid=i, prompt=list(rng.integers(1, cfg.vocab_size, size=6)),
            max_new_tokens=4))
    stats = cluster.run()
    assert stats.completed == 8
    assert sum(stats.per_instance.values()) == 8
    assert len(stats.per_instance) >= 1


# ---------------------------------------------------------------------------
# the engine's phase spans (repro.obs.trace.SpanTracer, track "engine")
# ---------------------------------------------------------------------------
PHASE_PROMPTS = (5, 9, 13, 7, 11, 6)


def _serve_phases(cfg, params, tracer):
    """Six requests through two slots, so steps both admit and only decode;
    the last one is too long and is rejected.  Returns the engine and, per
    step, the slots active before it."""
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_seq=64),
                        tracer=tracer)
    rng = np.random.default_rng(3)
    for i, L in enumerate(PHASE_PROMPTS):
        eng.submit(Request(rid=i, prompt=list(rng.integers(1, cfg.vocab_size,
                                                           size=L)),
                           max_new_tokens=4 + i % 3))
    eng.submit(Request(rid=99, prompt=[1] * 60, max_new_tokens=8))
    before = {}
    while eng.queue or eng.n_active:
        before[eng.steps + 1] = eng.n_active
        eng.step()
    return eng, before


class _Entered:
    """Stands in for ``jax.profiler.TraceAnnotation``: notes each entry."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(self.name)

    def __exit__(self, *exc):
        return False


@pytest.fixture(scope="module")
def traced(setup):
    from repro.obs import SpanTracer
    entered = []
    tracer = SpanTracer(enabled=True,
                        annotate=lambda name: _Entered(entered, name))
    eng, before = _serve_phases(*setup, tracer)
    spans = [e for e in tracer.events if e["ph"] == "X"]
    return eng, spans, entered, before


def _by_step(spans):
    out = {}
    for e in spans:
        out.setdefault(e["args"]["step"], {}).setdefault(e["name"], []).append(e)
    return out


def test_engine_spans_every_step_with_its_phases(traced):
    eng, spans, entered, _ = traced
    steps = _by_step(spans)
    assert sorted(steps) == list(range(1, eng.steps + 1))
    admitted = set()
    for k, ph in steps.items():
        (step,) = ph["step"]
        (admit,) = ph["admit"]
        assert step["args"] == {"step": k}
        assert step["ts"] <= admit["ts"]
        assert admit["ts"] + admit["dur"] <= step["ts"] + step["dur"] + 1e-3
        rids = [e["args"]["rid"] for e in ph.get("prefill", [])]
        for name in ("prefill", "insert", "first_token"):
            assert [e["args"]["rid"] for e in ph.get(name, [])] == rids
            for e in ph.get(name, []):
                assert admit["ts"] <= e["ts"]
                assert e["ts"] + e["dur"] <= admit["ts"] + admit["dur"] + 1e-3
        admitted.update(rids)
        assert ("decode" in ph) == ("sample" in ph)
    # every served request was prefilled once; the too-long one never
    assert admitted == set(range(len(PHASE_PROMPTS)))
    first = steps[1]["admit"][0]["args"]
    assert first["rejected"] == 0 and first["stop"] == "no_slot"
    assert first["padded_tokens"] == 8 + 16        # prompts of 5 and 9
    assert sum(ph["admit"][0]["args"]["rejected"]
               for ph in steps.values()) == 1
    news = [e["args"]["new_program"] for e in spans if e["name"] == "prefill"]
    assert news.count(True) == 2          # padded lengths 8 and 16
    # the annotate hook was entered once per span, named by track
    assert len(entered) == len(spans)
    assert set(entered) == {f"engine.{e['name']}" for e in spans}


def test_sample_syncs_equal_the_active_slots(traced):
    eng, spans, _, before = traced
    steps = _by_step(spans)
    decode_only = [k for k, ph in steps.items()
                   if "prefill" not in ph and "sample" in ph]
    assert decode_only
    for k in decode_only:
        assert steps[k]["sample"][0]["args"]["syncs"] == before[k] > 0
    # every token served was read from the device once, first ones included
    assert eng.host_syncs == sum(len(r.generated) for r in eng.finished)


def test_admit_stamp_lies_between_arrival_and_first_token(traced):
    eng = traced[0]
    served = [r for r in eng.finished if r.generated]
    assert len(served) == len(PHASE_PROMPTS)
    for r in served:
        assert r.arrival_t <= r.admit_t <= r.first_token_t
    (rejected,) = [r for r in eng.finished if not r.generated]
    assert rejected.admit_t < 0            # never taken for prefill


def test_tracer_off_records_nothing_and_serves_the_same(setup, traced):
    from repro.obs import SpanTracer, trace as obs_trace
    eng_on = traced[0]
    off = SpanTracer(enabled=False)
    eng_off, _ = _serve_phases(*setup, off)
    assert [e for e in off.events if e["ph"] != "M"] == []
    default = ServingEngine(*setup, EngineConfig(max_batch=2, max_seq=64))
    assert default.tracer is obs_trace.TRACER and not default.tracer.enabled
    tokens = lambda eng: {r.rid: r.generated for r in eng.finished}
    assert tokens(eng_off) == tokens(eng_on)
