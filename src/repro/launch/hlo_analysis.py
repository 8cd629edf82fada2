"""Post-SPMD HLO text analysis: collective-traffic accounting for the
roofline model.

``compiled.cost_analysis()`` gives HLO FLOPs/bytes but no collective traffic,
so we parse ``compiled.as_text()``: every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, with

  * per-device link bytes modeled as
      all-gather:        result_bytes × (k-1)/k
      reduce-scatter:    operand_bytes × (k-1)/k
      all-reduce:        2 × operand_bytes × (k-1)/k      (ring)
      all-to-all:        operand_bytes × (k-1)/k
      collective-permute: operand_bytes
    where k = replica-group size, and
  * collectives inside while bodies multiplied by the loop trip count
    (inferred from the largest integer constant in the condition
    computation — exact for lax.scan loops).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\(?[a-z0-9]+\[[^\]=]*?\].*?)\s+"
    r"(?P<op>[\w\-]+)\((?P<operands>[^)]*)\)")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")

_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s+\([^)]*\)\s*->")

_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims.strip():
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def analyze_collectives(hlo_text: str) -> dict[str, Any]:
    """Returns {"per_op": {op: bytes}, "total_bytes": int, "count": int,
    "by_computation": {...}} — per-device link bytes."""
    # 1) split into computations
    comp_of_line: list[tuple[str, str]] = []
    current = "__toplevel__"
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if (not line.startswith(" ")) and ("->" in line) and ("{" in line):
            m = _COMP_RE.match(stripped.lstrip("%"))
            if m or stripped.startswith(("ENTRY", "%")):
                name = stripped.split()[0].lstrip("%")
                if name == "ENTRY":
                    name = stripped.split()[1].lstrip("%")
                current = name.rstrip("(").strip()
        comp_of_line.append((current, line))

    # 2) first pass: result sizes for every named instruction
    result_bytes: dict[str, int] = {}
    instrs: list[dict] = []
    for comp, line in comp_of_line:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op, operands = (m.group("name"), m.group("type"),
                                        m.group("op"), m.group("operands"))
        rb = _type_bytes(type_str)
        result_bytes[name] = rb
        instrs.append({"comp": comp, "name": name, "op": op,
                       "operands": operands, "bytes": rb, "line": line})

    # 3) constants per computation (for trip-count inference)
    const_by_comp: dict[str, list[int]] = defaultdict(list)
    for comp, line in comp_of_line:
        for c in re.findall(r"constant\((\d+)\)", line):
            const_by_comp[comp].append(int(c))

    # 4) while instructions: body/cond linkage
    while_edges = []         # (enclosing_comp, body_comp, trip_count)
    for ins in instrs:
        if ins["op"] != "while":
            continue
        mb = re.search(r"body=%?([\w.\-]+)", ins["line"])
        mc = re.search(r"condition=%?([\w.\-]+)", ins["line"])
        trip = 1
        if mc:
            consts = const_by_comp.get(mc.group(1), [])
            if consts:
                trip = max(consts)
        if mb:
            while_edges.append((ins["comp"], mb.group(1), max(1, trip)))

    # 5) computation multipliers (fixpoint over nesting)
    mult: dict[str, float] = defaultdict(lambda: 1.0)
    for _ in range(8):                       # nesting depth bound
        changed = False
        for enc, body, trip in while_edges:
            new = mult[enc] * trip
            if mult[body] != new:
                mult[body] = new
                changed = True
        if not changed:
            break

    # 6) collective accounting
    per_op: dict[str, float] = defaultdict(float)
    count = 0
    details = []
    for ins in instrs:
        base_op = ins["op"]
        matched = next((c for c in COLLECTIVES
                        if base_op == c or base_op.startswith(c + ".")
                        or base_op.startswith(c + "-start")), None)
        if matched is None:
            continue
        line = ins["line"]
        # group size
        k = 0
        mg = _GROUPS_BRACE_RE.search(line)
        if mg:
            k = len([x for x in mg.group(1).split(",") if x.strip() != ""])
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                k = int(mi.group(2))
        k = max(k, 2)
        operand_bytes = 0
        for opnd in ins["operands"].split(","):
            nm = opnd.strip().lstrip("%")
            nm = nm.split(" ")[-1].lstrip("%")
            operand_bytes += result_bytes.get(nm, 0)
        rb = ins["bytes"]
        frac = (k - 1) / k
        if matched == "all-gather":
            link = rb * frac
        elif matched == "reduce-scatter":
            link = operand_bytes * frac
        elif matched == "all-reduce":
            link = 2 * (operand_bytes or rb) * frac
        elif matched == "all-to-all":
            link = (operand_bytes or rb) * frac
        else:                                  # collective-permute
            link = operand_bytes or rb
        m = mult[ins["comp"]]
        per_op[matched] += link * m
        count += 1
        details.append({"op": matched, "comp": ins["comp"], "mult": m,
                        "group": k, "link_bytes": link})
    return {
        "per_op": dict(per_op),
        "total_bytes": float(sum(per_op.values())),
        "count": count,
        "details": details[:200],
    }


_SHAPE_ONE_RE = re.compile(r"^\(?([a-z0-9]+)\[([\d,]*)\]")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_NO_TRAFFIC_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota",
}


def _parse_dims(type_str: str):
    m = _SHAPE_ONE_RE.match(type_str.strip())
    if not m:
        return None, ()
    dims = tuple(int(d) for d in m.group(2).split(",") if d.strip())
    return m.group(1), dims


def full_cost(hlo_text: str) -> dict[str, Any]:
    """Trip-count-aware FLOP/byte model from post-SPMD HLO text.

    ``compiled.cost_analysis()`` counts each while body ONCE (XLA's
    HloCostAnalysis has no static trip counts), which undercounts scanned
    transformer stacks by ~n_layers×n_microbatches.  This walks the text:

      * multiplier(comp) — product of enclosing loop trip counts (inferred
        from the largest constant in each while condition — exact for
        lax.scan) composed through fusion/call edges;
      * FLOPs — 2·|out|·K for every ``dot`` (K from the lhs operand's
        contracting dims); matmul-only by design, matching the MXU roofline
        and the 6ND MODEL_FLOPS convention;
      * bytes — Σ (result + operand) sizes of materializing instructions
        (fusion bodies are skipped; their traffic is counted at the fusion
        call site), an HBM-traffic estimate consistent across variants.
    """
    # --- split into computations and parse instructions
    comp_of_line: list[tuple[str, str]] = []
    current = "__toplevel__"
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if (not line.startswith(" ")) and ("->" in line) and ("{" in line):
            name = stripped.split()[0].lstrip("%")
            if name == "ENTRY":
                name = stripped.split()[1].lstrip("%")
            current = name.rstrip("(").strip()
        comp_of_line.append((current, line))

    shapes: dict[str, tuple[str, tuple]] = {}
    instrs: list[dict] = []
    const_by_comp: dict[str, list[int]] = defaultdict(list)
    for comp, line in comp_of_line:
        for c in re.findall(r"constant\((\d+)\)", line):
            const_by_comp[comp].append(int(c))
        m = _INSTR_RE.match(line)
        if not m:
            continue
        dt, dims = _parse_dims(m.group("type"))
        name = m.group("name")
        shapes[name] = (dt, dims)
        instrs.append({"comp": comp, "name": name, "op": m.group("op"),
                       "operands": m.group("operands"),
                       "type": m.group("type"), "line": line})

    # --- call graph: (caller, callee, trip)
    edges: list[tuple[str, str, float]] = []
    fusion_bodies: set[str] = set()
    for ins in instrs:
        line = ins["line"]
        if ins["op"] == "while":
            mb = re.search(r"body=%?([\w.\-]+)", line)
            mc = re.search(r"condition=%?([\w.\-]+)", line)
            trip = 1
            if mc:
                consts = const_by_comp.get(mc.group(1), [])
                if consts:
                    trip = max(consts)
            if mb:
                edges.append((ins["comp"], mb.group(1), max(1, trip)))
            if mc:
                edges.append((ins["comp"], mc.group(1), max(1, trip)))
        else:
            for key in ("calls", "to_apply"):
                mm = re.search(key + r"=%?([\w.\-]+)", line)
                if mm:
                    edges.append((ins["comp"], mm.group(1), 1.0))
                    fusion_bodies.add(mm.group(1))

    mult: dict[str, float] = defaultdict(lambda: 0.0)
    # roots: computations never called
    called = {c for _, c, _ in edges}
    for comp in {c for c, _ in comp_of_line}:
        if comp not in called:
            mult[comp] = 1.0
    for _ in range(16):
        changed = False
        for caller, callee, trip in edges:
            new = mult[caller] * trip
            if new > mult[callee]:
                mult[callee] = new
                changed = True
        if not changed:
            break

    # --- FLOPs (dots) and bytes
    flops = 0.0
    bytes_ = 0.0
    per_comp: dict[str, dict] = defaultdict(lambda: {"flops": 0.0,
                                                     "bytes": 0.0})
    for ins in instrs:
        m_ = mult[ins["comp"]] or 1.0
        if ins["op"] == "dot":
            _, out_dims = _parse_dims(ins["type"])
            out_elems = 1
            for d in out_dims:
                out_elems *= d
            lhs = ins["operands"].split(",")[0].strip().lstrip("%")
            lhs = lhs.split(" ")[-1].lstrip("%")
            k = 1
            mc = _CONTRACT_RE.search(ins["line"])
            if mc and lhs in shapes:
                ldims = shapes[lhs][1]
                for ci in (int(x) for x in mc.group(1).split(",")
                           if x.strip()):
                    if ci < len(ldims):
                        k *= ldims[ci]
            f = 2.0 * out_elems * k * m_
            flops += f
            per_comp[ins["comp"]]["flops"] += f
        if (ins["comp"] not in fusion_bodies
                and ins["op"] not in _NO_TRAFFIC_OPS):
            op = ins["op"]
            rb = _type_bytes(ins["type"])

            def _operand_bytes(index=None):
                total = 0
                for k_, opnd in enumerate(ins["operands"].split(",")):
                    if index is not None and k_ != index:
                        continue
                    nm = opnd.strip().lstrip("%").split(" ")[-1].lstrip("%")
                    if nm in shapes:
                        dt, dd = shapes[nm]
                        n = 1
                        for d in dd:
                            n *= d
                        total += n * _DTYPE_BYTES.get(dt, 4)
                return total

            # per-op HBM-traffic model: sliced/windowed ops touch only the
            # window, not the whole operand; control flow is bookkeeping
            if op in ("while", "conditional", "call", "reshape", "bitcast"):
                b = 0.0
            elif op in ("dynamic-slice", "slice", "gather"):
                b = 2.0 * rb
            elif op == "dynamic-update-slice":
                b = 2.0 * _operand_bytes(1)        # read+write the update
            elif op == "scatter":
                b = 3.0 * _operand_bytes(2)        # updates r/w + index read
            elif op in ("copy", "transpose", "concatenate", "reverse",
                        "copy-start", "copy-done"):
                b = 2.0 * rb
            elif op in ("broadcast",):
                b = float(rb)
            else:
                b = float(rb + _operand_bytes())
            b *= m_
            bytes_ += b
            per_comp[ins["comp"]]["bytes"] += b
    return {"flops": flops, "bytes": bytes_,
            "per_comp": {k: v for k, v in sorted(
                per_comp.items(), key=lambda kv: -kv[1]["flops"])[:20]}}


def summarize_cost(compiled) -> dict[str, Any]:
    out: dict[str, Any] = {}
    try:
        ca = compiled.cost_analysis()
        out["flops"] = float(ca.get("flops", 0.0))
        out["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        out["transcendentals"] = float(ca.get("transcendentals", 0.0))
    except Exception as e:                      # pragma: no cover
        out["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "code_bytes": int(ma.generated_code_size_in_bytes),
        }
        out["memory"]["peak_bytes_per_device"] = (
            out["memory"]["argument_bytes"] + out["memory"]["temp_bytes"]
            + out["memory"]["output_bytes"] - out["memory"]["alias_bytes"])
    except Exception as e:                      # pragma: no cover
        out["memory_analysis_error"] = repr(e)
    return out
