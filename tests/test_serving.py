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
    """The decode program samples the whole batch, so a step reads its
    tokens to the host once, however many slots are active."""
    eng, spans, _, before = traced
    steps = _by_step(spans)
    decode_only = [k for k, ph in steps.items()
                   if "prefill" not in ph and "sample" in ph]
    assert any(before[k] > 1 for k in decode_only)
    for k in decode_only:
        assert steps[k]["sample"][0]["args"]["syncs"] == 1
    # one read per step that decoded, and one per first token served
    decoded = sum("sample" in ph for ph in steps.values())
    served = sum(bool(r.generated) for r in eng.finished)
    assert eng.host_syncs == decoded + served


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


# ---------------------------------------------------------------------------
# sampling inside the decode program
# ---------------------------------------------------------------------------
def _submit(eng, cfg, lens_and_new, seed, temps=None):
    rng = np.random.default_rng(seed)
    for i, (L, n) in enumerate(lens_and_new):
        eng.submit(Request(rid=i, prompt=list(rng.integers(1, cfg.vocab_size,
                                                           size=L)),
                           max_new_tokens=n,
                           temperature=temps[i] if temps else 0.0))


def test_batched_greedy_tokens_are_the_per_slot_argmax(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    # slots 0, 1, 2; slot 1 retires early and leaves a hole between two
    # busy slots, and slot 3 stays empty
    _submit(eng, cfg, [(5, 8), (9, 2), (7, 8)], seed=11)
    ref = jax.jit(lambda c, t, l: jnp.argmax(
        T.decode_step(cfg, params, c, t, l, append=True)[0], -1))
    eng.step()
    checked = holes = 0
    while eng.n_active:
        active = {r.slot: r for r in eng.slot_req if r is not None}
        toks = np.zeros(4, np.int32)
        for slot, r in active.items():
            toks[slot] = r.generated[-1]
        want = np.asarray(ref(eng.cache, jnp.asarray(toks),
                              jnp.asarray(eng.lengths)))
        eng.step()
        for slot, r in active.items():
            assert r.generated[-1] == want[slot]
            checked += 1
        holes += sorted(active) == [0, 2]
    assert checked >= 12 and holes >= 4
    assert {r.rid: len(r.generated) for r in eng.finished} == {0: 8, 1: 2,
                                                                2: 8}


def _serve_temps(cfg, params, temps, seed=0):
    eng = ServingEngine(cfg, params,
                        EngineConfig(max_batch=4, max_seq=64, seed=seed))
    _submit(eng, cfg, [(5, 6), (9, 6), (7, 6)], seed=12, temps=temps)
    return {r.rid: r.generated for r in eng.run()}


def test_a_sampled_slot_leaves_the_greedy_slots_alone(setup):
    cfg, params = setup
    greedy = _serve_temps(cfg, params, [0.0, 0.0, 0.0])
    mixed = _serve_temps(cfg, params, [0.0, 1.0, 0.0], seed=3)
    assert mixed[0] == greedy[0] and mixed[2] == greedy[2]
    assert len(mixed[1]) == 6
    assert all(0 <= t < cfg.vocab_size for t in mixed[1])
    assert mixed[1] != greedy[1]           # drawn at 1.0, not the argmax
    assert _serve_temps(cfg, params, [0.0, 1.0, 0.0], seed=3) == mixed
    assert _serve_temps(cfg, params, [0.0, 1.0, 0.0], seed=4)[1] != mixed[1]
    # near 0 the draw is the argmax
    assert _serve_temps(cfg, params, [0.0, 1e-4, 0.0], seed=3) == greedy


def test_both_decode_variants_compile_when_the_engine_is_built(
        setup, monkeypatch):
    """Each trace of the decode program calls ``T.decode_step`` once: the
    two variants are traced while the engine is built, and serving greedy
    and temperature requests traces nothing more."""
    cfg, params = setup
    traces, orig = [], T.decode_step

    def counted(*a, **k):
        traces.append(1)
        return orig(*a, **k)
    monkeypatch.setattr(T, "decode_step", counted)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    assert len(traces) == 2
    _submit(eng, cfg, [(5, 4), (9, 6), (7, 4)], seed=15,
            temps=[0.0, 1.0, 0.0])
    eng.run()
    assert len(traces) == 2 and len(eng.finished) == 3


def test_a_hot_slot_draws_in_the_decode_steps(setup):
    """Served step by step beside greedy slots, a slot at temperature 1.0
    gets a token other than its argmax in some decode step, and the greedy
    slots get theirs in every one."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    _submit(eng, cfg, [(5, 8), (9, 8), (7, 8)], seed=14,
            temps=[0.0, 1.0, 0.0])
    ref = jax.jit(lambda c, t, l: jnp.argmax(
        T.decode_step(cfg, params, c, t, l, append=True)[0], -1))
    eng.step()
    off_argmax = 0
    while eng.n_active:
        active = {r.slot: r for r in eng.slot_req if r is not None}
        toks = np.zeros(4, np.int32)
        for slot, r in active.items():
            toks[slot] = r.generated[-1]
        want = np.asarray(ref(eng.cache, jnp.asarray(toks),
                              jnp.asarray(eng.lengths)))
        eng.step()
        for slot, r in active.items():
            if r.temperature > 0:
                off_argmax += r.generated[-1] != want[slot]
            else:
                assert r.generated[-1] == want[slot]
    assert off_argmax > 0


def test_the_decode_program_draws_from_logits_over_temperature(setup):
    """Slots 0-2 at temperature t, slot 3 greedy.  Over 512 steps the mean
    logit of the drawn tokens lies within 4 standard errors of its
    expectation under softmax(logits / t), and more than 10 from the
    expectation at each other temperature tried; slot 3 always gets its
    argmax; the engine's seed picks the stream."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    toks = jnp.asarray([5, 17, 99, 3], jnp.int32)
    lens = jnp.zeros(4, jnp.int32)
    lg = np.asarray(T.decode_step(cfg, params, eng.cache, toks, lens,
                                  append=True)[0], np.float64)
    n, temps, rows = 512, (0.05, 0.2, 1.0), lg[:3]

    def z_scores(drawn):
        got = np.take_along_axis(rows, drawn.T, axis=1).mean()
        out = []
        for t in temps:
            p = np.exp((rows - rows.max(-1, keepdims=True)) / t)
            p /= p.sum(-1, keepdims=True)
            mean = (p * rows).sum(-1)
            var = (p * rows ** 2).sum(-1) - mean ** 2
            out.append((got - mean.mean()) / (np.sqrt(var.sum() / n) / 3))
        return np.abs(out)

    for i, t in enumerate(temps):
        hot = jnp.asarray([t, t, t, 0.0], jnp.float32)
        drawn = np.stack([np.asarray(eng._decode(params, eng.cache, toks,
                                                 lens, (hot, step))[0])
                          for step in range(n)])
        assert (drawn[:, 3] == lg[3].argmax()).all()
        z = z_scores(drawn[:, :3])
        assert z[i] < 4
        assert all(z[j] > 10 for j in range(len(temps)) if j != i)
    # another engine seed, another stream
    other = ServingEngine(cfg, params,
                          EngineConfig(max_batch=4, max_seq=64, seed=1))
    draw = lambda e, step: np.asarray(e._decode(
        params, e.cache, toks, lens, (jnp.ones(4, jnp.float32), step))[0])
    assert not all(np.array_equal(draw(eng, k), draw(other, k))
                   for k in range(4))


def test_a_decode_step_reads_the_device_once(setup, monkeypatch):
    """Every read of a device array to the host, explicit (``device_get``)
    or implicit (``int()``, ``np.asarray``), fills ``ArrayImpl._value``;
    the CPU backend does not enforce ``jax.transfer_guard_device_to_host``,
    so the reads are counted there.  ``ArrayImpl`` is private to JAX; this
    was checked against jax 0.9.0, and moves to the transfer guard once the
    CPU backend enforces it."""
    from jax._src import array
    cfg, params = setup
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=4, max_seq=64))
    _submit(eng, cfg, [(5, 6), (9, 6)], seed=13, temps=[0.0, 1.0])
    eng.step()                             # admits: first tokens read by int()
    n = [len(r.generated) for r in eng.slot_req if r is not None]
    value, reads = array.ArrayImpl._value, []

    def counted(self):
        if self._npy_value is None:
            reads.append(self.shape)
        return value.fget(self)
    monkeypatch.setattr(array.ArrayImpl, "_value", property(counted))
    eng.step()
    eng.step()
    assert reads == [(4,), (4,)]           # the batch's tokens, once a step
    assert [len(r.generated) for r in eng.slot_req if r is not None] == [
        k + 2 for k in n]
