"""Sweep of offered rates for a steady (Poisson) cell, to find its knee.

    python3 benchmarks/serve/sweep.py --workload <cell> --seed <n> \
        --rates 2 2.5 3 --transition 15 --seconds 30

One process: weights and warm-up once, then each rate in rising order on the
same engine, with no reset between rates: ``--transition`` seconds to settle
from the previous rate, then ``--seconds`` measured.  Prints one JSON line
per rate: queue length as the measured part opens and closes, mean
occupancy, tokens/s completed, and TTFT and inter-token tails.  The knee is
the highest rate whose queue does not grow through its measured part.
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--transition", type=float, default=15.0)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args()
    bench, cell, config, traffic, checks = run.cell_files(args.workload)
    devices = run.require_devices(cell["chips"])
    run.set_compile_cache()

    import numpy as np

    import runlib
    import traffic as traffic_mod
    import weights
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig, Request, ServingEngine

    m = config["model"]
    cfg = run.model_config(m)
    ecfg = EngineConfig(**config["engine"])
    params = weights.make_weights(T.abstract_params(cfg), config["init"],
                                  args.seed)
    seg = args.transition + args.seconds
    plans = [traffic_mod.schedule(dict(traffic, rate=r), m["vocab_size"],
                                  args.seed, [args.transition, args.seconds])
             for r in sorted(args.rates)]
    engine = ServingEngine(cfg, params, ecfg)
    loop = runlib.OpenLoop(engine, Request)
    lens = sorted({len(it.prompt) for p in plans for it in p})
    rng = np.random.default_rng(args.seed)
    t = time.time()
    loop.warm([rng.integers(0, m["vocab_size"], L).tolist() for L in lens])
    run.log(f"warm-up: {len(lens)} lengths in {time.time() - t:.1f} s")
    for rate, items in zip(sorted(args.rates), plans):
        start = time.time()
        tl = runlib.Timeline(start, start + args.transition, start + seg,
                             start + seg)
        loop.marks = {}
        loop.serve(items, tl, 0.0, None)
        r = runlib.Run(m, ecfg.max_batch, {}, tl, loop.steps, loop.reqs,
                       0.0)
        w = runlib.window_steps(r)
        ttft = runlib.ttfts_due_in_window(r)
        print(json.dumps({
            "rate": rate, "queue_at_open": loop.marks["queue_w0"], "queue_at_close": len(engine.queue),
            "occupancy": statistics.fmean(s.occupancy for s in w) if w else 0,
            "tok_s": runlib.tokens_in_window(r) / args.seconds,
            "ttft_p50_ms": runlib.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": runlib.percentile(ttft, 90) * 1e3,
            "itl_p95_ms": runlib.percentile(
                runlib.gaps_ending_in_window(r), 95) * 1e3,
            "decode_step_ms": statistics.fmean(
                s.t1 - s.t0 for s in runlib.decode_only(w)) * 1e3,
            "steps": len(w)}), flush=True)


if __name__ == "__main__":
    main()
