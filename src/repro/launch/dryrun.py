import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# NOTE: the lines above MUST run before any other import (including
# `from repro...`): jax locks the platform and device count at first
# initialization.  This is a compile-only analysis on 512 virtual CPU
# devices; naming the CPU platform keeps it off an attached TPU, which
# belongs to one process at a time.
#
# CPU-faithfulness fix: XLA's CPU backend legalizes bf16 dots by inserting
# f32 converts of the operands; while-loop-invariant code motion then hoists
# those converts out of the layer scan, materializing f32 copies of entire
# stacked weight/cache tensors (a pure CPU-lowering artifact — TPU MXUs
# consume bf16 natively and no such converts exist in the TPU pipeline).
# Disabling the hoisting passes keeps memory_analysis() representative of
# the TPU memory picture. FLOP/byte counts are unaffected.
os.environ["XLA_FLAGS"] += (
    " --xla_disable_hlo_passes=while-loop-invariant-code-motion,"
    "while-loop-expensive-invariant-code-motion")

import argparse
import json
import logging
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import get_config, list_archs
from repro.configs.shapes import SHAPES, applicable, get_shape
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import lower_cell

log = logging.getLogger(__name__)


def run_cell(arch: str, shape: str, multi_pod: bool, variant: str,
             out_dir: Path, reduced: bool = False) -> dict:
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    case = get_shape(shape)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "variant": variant,
        "kind": case.kind, "seq_len": case.seq_len,
        "global_batch": case.global_batch,
        "n_params": cfg.param_count(),
        "n_params_active": cfg.active_param_count(),
        "ok": False,
    }
    ok, reason = applicable(cfg, case)
    if not ok:
        rec["skipped"] = reason
        _write(out_dir, rec)
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    try:
        t0 = time.perf_counter()
        lowered = lower_cell(cfg, case, mesh, variant)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)
        rec.update(hlo_analysis.summarize_cost(compiled))
        log.info("%s", compiled.memory_analysis())
        log.info("%s", {k: v for k, v in (rec.get("memory") or {}).items()})
        txt = compiled.as_text()
        rec["collectives"] = {
            k: v for k, v in hlo_analysis.analyze_collectives(txt).items()
            if k != "details"}
        fc = hlo_analysis.full_cost(txt)
        rec["flops_tc"] = fc["flops"]          # trip-count-corrected
        rec["bytes_tc"] = fc["bytes"]
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    _write(out_dir, rec)
    return rec


def _write(out_dir: Path, rec: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}__{rec['variant']}.json"
    (out_dir / name).write_text(json.dumps(rec, indent=1, default=str))


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="all",
                    help="shape case or 'all'")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs (CI sanity)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--verbose", action="store_true",
                    help="DEBUG-level logging (per-cell HLO details)")
    args = ap.parse_args()

    # stdout at message-only format so default output is byte-identical
    # to the old print()s; --verbose turns on DEBUG for repro loggers only
    # (root stays INFO — jax's own DEBUG chatter would drown the report)
    logging.basicConfig(level=logging.INFO, format="%(message)s",
                        stream=sys.stdout)
    if args.verbose:
        logging.getLogger("repro").setLevel(logging.DEBUG)
        log.setLevel(logging.DEBUG)

    archs = list_archs() if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[
        args.mesh]
    out_dir = Path(args.out)

    n_ok = n_fail = n_skip = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                t0 = time.perf_counter()
                rec = run_cell(arch, shape, mp, args.variant, out_dir,
                               reduced=args.reduced)
                dt = time.perf_counter() - t0
                status = ("SKIP" if "skipped" in rec
                          else "OK" if rec["ok"] else "FAIL")
                n_ok += status == "OK"
                n_fail += status == "FAIL"
                n_skip += status == "SKIP"
                log.info("[%s] %s × %s × %s (%.1fs) %s", status, arch,
                         shape, "multi" if mp else "single", dt,
                         rec.get("error", ""))
                if "traceback" in rec:
                    log.debug("%s", rec["traceback"])
    log.info("done: %d ok, %d skipped, %d failed", n_ok, n_skip, n_fail)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
