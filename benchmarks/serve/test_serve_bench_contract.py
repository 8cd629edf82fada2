"""BENCHMARK.json names only files that exist, and every metric is read
where it is reported; the control that the check must fail, at a size a
test run holds."""
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(HERE)]

import pytest  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert (HERE / "traffic" / f"{w['traffic']}.json").is_file()
        assert (HERE / "checks" / f"{w['name']}.json").is_file()
        assert w["config"] in {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and (HERE / "metrics" /
                                          f"{m['name']}.py").is_file()


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_its_cut(entry):
    """Each key of ``model`` that the file's ``reduced`` lists has its
    published value under ``published``, and a cut file says under
    ``deployment`` how many chips share each layer and how."""
    config = json.loads((ROOT / entry["file"]).read_text())
    published = config.get("published", {})
    for key in config["reduced"]:
        assert key in config["model"], key
        assert key in published and published[key] != config["model"][key]
    if config["reduced"] or entry["reduced"]:
        assert config.get("deployment", "").strip()


def test_model_config_from_a_period_or_from_groups():
    """One period over ``n_layers`` and the same layers as groups give the
    ModelConfig that the program was given before groups could be
    stated."""
    import run
    from repro.configs.base import LayerSpec, ModelConfig
    m = json.loads((HERE / "configs" / "qwen2-1.5b.json").read_text())["model"]
    fields = {k: v for k, v in m.items() if k not in ("period", "n_layers")}
    before = ModelConfig(groups=(((LayerSpec(**m["period"][0]),), 28),),
                         **fields)
    assert run.model_config(m) == before
    as_groups = dict(fields, groups=[{"period": m["period"], "repeat": 28}])
    assert run.model_config(as_groups) == before


def test_every_cell_reports_what_its_layer_metrics_move():
    import run
    cells = [w["name"] for w in BENCH["workloads"]]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for cell in cells:
        names = {m["name"] for m in run.cell_metrics(BENCH, cell, False)}
        assert "setup_s" in names and len(names) >= 2
        layer = run.cell_metrics(BENCH, cell, True)
        assert layer
        for m in layer:
            assert m["moves"] in e2e and m["moves"] in names


def test_control_reads_far_above_the_program(monkeypatch):
    """The check's control, the float32 reference with its linear layers
    in fp8 in the program's place, read by ``run.run_cell`` on the served
    sample at a reduced size over three seeds."""
    import jax

    import calibrate
    import test_serve_bench_rehearsal as rehearsal
    import traffic
    from repro.kernels import ops
    monkeypatch.setitem(traffic.SAMPLERS, "tiny", rehearsal._tiny_sampler)
    bench, config, tr, checks = rehearsal.tiny_cell(arrivals="backlog",
                                                    backlog=24)
    with ops.default_impl("jnp"):
        got = calibrate.calibrate(rehearsal.CELL, bench, config, tr, checks,
                                  [1, 2, 3], 2.0, ["fp8"], jax.devices(),
                                  rehearsal.PEAK)
    s = got["summary"]
    assert s["control_fp8_min"] > 3 * s["program_max"]
    assert all(r["correct"] for r in got["rows"])
    # in the program's place, the control fails the cell's own limit
    assert s["control_fp8_min"] > checks["logit_gap_max"]
