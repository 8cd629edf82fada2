"""Readings that the limits of ``checks/<cell>.json`` are set from.

    python3 benchmarks/serve/calibrate.py --workload <cell> --seconds 20 \
        --seeds 11 12 13 --controls fp8

One process serves the cell once per seed, as ``run.py`` does (its own
ramp, window and sample), and prints per seed the program's reading
(``logit_gap_max``) and each control's: the configuration's reference with
every linear layer in ``fp8``, in the program's place, read on the same
prompts and tokens.  The benchmark's own runs never run a control.
"""
from __future__ import annotations

import argparse
import json
import time

import run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=["fp8"])
    args = ap.parse_args()
    bench, cell, config, traffic, checks = run.cell_files(args.workload)
    devices = run.require_devices(cell["chips"])
    import peaks
    peak = peaks.peaks(devices[0].device_kind)
    run.set_compile_cache()
    print(json.dumps(calibrate(args.workload, bench, config, traffic, checks,
                               args.seeds, args.seconds, args.controls,
                               devices, peak)), flush=True)


def calibrate(name, bench, config, traffic, checks, seeds, seconds,
              controls, devices, peak) -> dict:
    rows = []
    for seed in seeds:
        t = time.time()
        out = run.run_cell(name, bench, config, traffic, checks, seed=seed,
                           seconds=seconds, trace=False, devices=devices,
                           peak=peak, t_start=t, controls=controls)
        row = {"seed": seed, "correct": out["correct"],
               "logit_gap_max": out["check"]["logit_gap_max"]["value"],
               "tokens": out["check"]["tokens_compared"]["value"],
               **{f"control_{q}": out["controls"][q] for q in controls}}
        run.log("calibrate " + json.dumps(row))
        rows.append(row)
    summary = {"program_max": max(r["logit_gap_max"] for r in rows)}
    for q in controls:
        summary[f"control_{q}_min"] = min(r[f"control_{q}"] for r in rows)
    return {"rows": rows, "summary": summary}


if __name__ == "__main__":
    main()
