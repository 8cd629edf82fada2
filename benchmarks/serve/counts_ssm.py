"""Bytes that the Pallas selective scan (``ssm_scan``) must move, from a
model's sizes, the length it runs at, and which device events of a trace
are its calls.

Kept apart from ``counts.py``, which counts attention layers only: this
serves the scan's own readers (``metrics/ssm_scan_*.py``).  A call
scans one prompt of one Mamba layer at the prompt's padded length, reading
x and dt streamed in float32, A, B and C, D and the initial state h0 once
each, and writing y in float32 and the final state hT once each.
"""
from __future__ import annotations

import counts
from trace_reduce import op_label

F32 = 4
KERNEL = "ssm_scan"


def mamba_layers(m: dict) -> int:
    """Mamba layers in the model: one scan call each per prefill."""
    return sum(kind == "mamba" for kind, _, _ in counts.layers(m))


def padded(L: int) -> int:
    """The length a prompt of L tokens is prefilled at: the engine's bucket
    (``ServingEngine._admit``), the next power of two and at least 8.  The
    benchmark's runs pass the engine no span tracer, so its ``pad_tokens``
    counter is not there to read."""
    return max(8, 1 << (L - 1).bit_length())


def scan_bytes(m: dict, T: int) -> int:
    """One call over T positions: x, dt (T, d_inner), B, C (T, d_state), y
    (T, d_inner) in float32; A (d_inner, d_state), D (d_inner), h0 and hT
    (d_inner, d_state)."""
    Din, N = m["mamba_expand"] * m["d_model"], m["mamba_d_state"]
    return F32 * (3 * T * Din + 2 * T * N + 3 * Din * N + Din)


def kernel_events(trace) -> list:
    """The scan kernel's device events: those whose HLO instruction is the
    kernel's custom call (``%ssm_scan.<n> = (f32[...], ...) custom-call``),
    not the casts that its jitted wrapper runs around it."""
    return [e for evs in trace.device_ops.values() for e in evs
            if op_label(e).startswith(KERNEL)]
