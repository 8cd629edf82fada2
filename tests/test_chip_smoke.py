"""chip_smoke.py's phases rehearsed on the CPU: a reduced qwen2 served with
the Pallas kernels in interpret mode, checked as the script checks the
full-width model on the chip."""
import dataclasses
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.serving.engine import EngineConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg():
    # the chip's dtypes at reduced widths, so the bf16 tolerances are tested
    return dataclasses.replace(get_config("qwen2-1.5b").reduced(),
                               dtype="bfloat16", param_dtype="bfloat16")


def test_compile_cache_dir(smoke):
    assert smoke.compile_cache_dir({}) == ROOT / ".jax_cache"
    assert smoke.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None


def test_kernel_parity_interpret(smoke, cfg):
    assert smoke.kernel_parity(cfg, (8, 128), impl="pallas_interpret") <= 1


def test_serve_matches_reference_interpret(smoke, cfg):
    params = smoke.init_params(cfg, smoke.SEED)
    requests = smoke.make_requests(cfg.vocab_size, 5, (5, 100), 6,
                                   smoke.SEED)
    lens = sorted(len(r.prompt) for r in requests)
    assert lens[0] == 5 and lens[-1] == 100
    ecfg = EngineConfig(max_batch=2, max_seq=128)
    with ops.default_impl("pallas_interpret"):
        engine = smoke.serve(cfg, params, requests, ecfg)
    smoke.check_served(engine, requests)
    assert len(engine._prefill_cache) >= 3     # several padded lengths
    m = smoke.reference_margins(cfg, params, engine.finished, ecfg.max_seq)
    assert m.shape == (5, 6) and np.all(m >= 0)
    smoke.check_margins(m)


def test_check_served_rejects_short_request(smoke, cfg):
    requests = smoke.make_requests(cfg.vocab_size, 2, (5, 10), 4, 0)
    engine = type("E", (), {"finished": requests})()
    requests[0].generated = [1, 2, 3, 4]
    requests[1].generated = []                  # rejected: no tokens
    with pytest.raises(SystemExit, match="without all their tokens"):
        smoke.check_served(engine, requests)
