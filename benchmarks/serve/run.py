"""Benchmark of the serving engine on the chip, one cell per run.

    python3 benchmarks/serve/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``configs/<name>.json``:
model sizes, engine settings, weight init, plain reference) and a traffic
mix (``traffic/<name>.json``); ``checks/<cell>.json`` holds the limits of
the comparison that decides ``correct``; each metric is read by
``metrics/<metric>.py``.  So a cell, a configuration, a mix or a metric is
added by adding files.

A run makes its weights on the device from ``--seed``, warms every shape its
traffic uses through ``ServingEngine``'s own path, offers the traffic open
loop (a ramp, then the window of ``--seconds``), and prints one JSON line:
end-to-end metrics with ``--trace 0``; with ``--trace 1`` per-layer metrics,
for which a further few seconds after the window are profiled.  It then
compares a seeded sample of the served tokens with the configuration's
float32 reference.  It exits non-zero, printing no result, on a machine
without an accelerator or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import counts  # noqa: E402

TRACE_S = 5.0        # profiled seconds after the window (--trace 1)
TAIL_S = 10.0        # arrivals scheduled past the window, for the drain


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def cell_files(name: str, root: Path = ROOT):
    """The cell's BENCHMARK.json entry and the files it names."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config = load_json(HERE / "configs" / f"{cell['config']}.json")
    traffic = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    checks = load_json(HERE / "checks" / f"{name}.json")
    return bench, cell, config, traffic, checks


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def read_metric(name: str, run):
    spec = importlib.util.spec_from_file_location(
        "serve_bench_metric_" + name.replace(".", "_"),
        HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def require_devices(n: int):
    """The accelerators of this machine; exits when there are none, or
    fewer than ``n``.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit("no accelerator: JAX found only the CPU")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def set_compile_cache() -> None:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR when set,
    else the fixed ``.jax_cache/`` at the checkout's root."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def model_config(model: dict):
    """The program's ModelConfig for the configuration file's sizes, its
    layer pattern stated as ``groups`` or as one ``period`` over
    ``n_layers`` (``counts.groups``)."""
    from repro.configs.base import LayerSpec, ModelConfig
    fields = {k: v for k, v in model.items()
              if k not in ("period", "n_layers", "groups")}
    groups = tuple((tuple(LayerSpec(**s) for s in period), rep)
                   for period, rep in counts.groups(model))
    return ModelConfig(groups=groups, **fields)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def ref_sizes(model: dict) -> dict:
    """What the reference reads: the scalar sizes, and every layer in
    order as ``(kind, attn_type, mlp)`` under ``"layers"`` (hashable, so
    that ``check`` caches one program per configuration)."""
    out = {k: v for k, v in model.items() if isinstance(v, (int, float, str))}
    return dict(out, layers=counts.layers(model))


def run_cell(name: str, bench: dict, config: dict, traffic: dict,
             checks: dict, *, seed: int, seconds: float, trace: bool,
             devices, peak: dict, t_start: float, controls=()) -> dict:
    """One run of a cell; returns the result line as a dict.  Each of
    ``controls`` (a precision of ``check.gaps``) is also read on the same
    sample, under ``"controls"``: the benchmark's own runs pass none."""
    import jax
    import numpy as np

    import check
    import runlib
    import traffic as traffic_mod
    import weights
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    clock = runlib.CompileClock()
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    m = config["model"]
    cfg = model_config(m)
    ecfg = EngineConfig(**config["engine"])
    t = time.time()
    params = weights.make_weights(T.abstract_params(cfg), config["init"], seed)
    log(f"weights: {time.time() - t:.2f} s")

    items = traffic_mod.schedule(traffic, m["vocab_size"], seed,
                                 [traffic["ramp_s"], seconds,
                                  TRACE_S + TAIL_S])
    engine = ServingEngine(cfg, params, ecfg)
    loop = runlib.OpenLoop(engine, Request)
    lens = sorted({len(it.prompt) for it in items})
    rng = np.random.default_rng(seed)
    t, e0 = time.time(), clock.events()
    loop.warm([rng.integers(0, m["vocab_size"], L).tolist() for L in lens])
    log(f"warm-up: {len(lens)} prompt lengths ({lens[0]}..{lens[-1]}) in "
        f"{time.time() - t:.2f} s; {clock.events() - e0} compile events "
        f"({clock.cache_hits} persistent-cache reads so far)")

    start = time.time()
    w0 = start + traffic["ramp_s"]
    tl = runlib.Timeline(start, w0, w0 + seconds,
                         w0 + seconds + (TRACE_S if trace else 0.0))
    trace_dir = tempfile.mkdtemp(prefix="serve_trace_") if trace else None
    loop.serve(items, tl, traffic["drain_cap_s"], trace_dir, clock)
    tail = sorted(((r.first_t - r.due, r.prompt_len) for r in loop.reqs.values()
                   if tl.w0 <= r.due < tl.w1 and not math.isnan(r.first_t)),
                  reverse=True)[:8]
    log("ttft tail: " + ", ".join(f"{v * 1e3:.1f} ms (prompt {n})"
                                  for v, n in tail))
    in_window = loop.marks.get("w1", clock.events()) - loop.marks["w0"]
    log(f"compile events inside the window: {in_window}")
    late = sorted(loop.lateness)
    log(f"submission lateness: p50 {runlib.percentile(late, 50) * 1e3:.3f} ms "
        f"p99 {runlib.percentile(late, 99) * 1e3:.3f} ms "
        f"max {late[-1] * 1e3:.3f} ms over {len(late)} requests")
    stats = dev.memory_stats() or {}
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    log(f"peak_bytes_in_use: {peak_bytes} (bytes_limit "
        f"{stats.get('bytes_limit', 'n/a')})")

    run = runlib.Run(counts.sizes(config), ecfg.max_batch, peak, tl,
                     loop.steps, loop.reqs, tl.w0 - t_start)
    result_device = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devices), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if trace:
        import trace_reduce
        run.trace = trace_reduce.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result_device["busy_s"] = trace_reduce.busy_s(run.trace)
        result_device["window_s"] = loop.trace_off - loop.trace_on
        breakdown = runlib.breakdown(run, trace_reduce.op_label)
    metrics = {}
    for spec in cell_metrics(bench, name, trace):
        v = read_metric(spec["name"], run)
        if v is not None and math.isfinite(v):
            metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        else:
            log(f"metric {spec['name']}: nothing to read ({v})")
    window = runlib.window_steps(run)
    log(f"window: {len(window)} steps, "
        f"{len(runlib.admitting(window))} admitting, mean occupancy "
        f"{statistics.fmean(s.occupancy for s in window) if window else 0:.2f}"
        f"/{ecfg.max_batch}, queue at close {len(engine.queue)}")

    attempted = len(loop.reqs)
    failed = sum(r.rejected for r in loop.reqs.values()) + sum(
        math.isnan(r.first_t) and not r.rejected
        for r in loop.reqs.values() if tl.w0 <= r.due < tl.w1
        and traffic["drain_cap_s"] > 0)
    finished = [r for r in engine.finished if r.rid < runlib.WARM_RID]

    # free the engine's state before the reference runs on the same chip
    del engine, loop
    gc.collect()

    sample = check.sample(finished, seed, checks["sample_tokens"],
                          checks["max_requests"])
    t = time.time()
    w = weights.plain(params)
    g = check.gaps(config["reference"], ref_sizes(m), w, sample)
    gap_max = check.widest(g)
    control = {q: check.widest(check.gaps(config["reference"], ref_sizes(m),
                                          w, sample, quant=q))
               for q in controls}
    log(f"reference: {len(sample)} requests, {g.size} served tokens, "
        f"{time.time() - t:.2f} s; exact argmax share "
        f"{float(np.mean(g == 0)) if g.size else 0:.4f}")
    limits = {"logit_gap_max": (gap_max, checks["logit_gap_max"], "<="),
              "tokens_compared": (int(g.size), checks["min_tokens"], ">=")}
    correct = (gap_max <= checks["logit_gap_max"]
               and g.size >= checks["min_tokens"])
    for k, (v, lim, op) in limits.items():
        log(f"check {k}: {v} (must be {op} {lim})")
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": result_device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["controls"] = control
    out["check"] = {k: {"value": v, "limit": lim, "must_be": op}
                    for k, (v, lim, op) in limits.items()}
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic, checks = cell_files(args.workload)
    devices = require_devices(cell["chips"])
    import peaks
    peak = peaks.peaks(devices[0].device_kind)
    set_compile_cache()
    out = run_cell(args.workload, bench, config, traffic, checks,
                   seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices, peak=peak,
                   t_start=T_START)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
