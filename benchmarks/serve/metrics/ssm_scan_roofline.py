"""Kernels: the Pallas selective scan's share of its roofline in the traced
segment: for each call (one per Mamba layer per prefill), the bytes it must
move at the prompt's padded length over HBM bandwidth, summed, over the
kernel's summed device time in the trace.  The scan does a few FLOPs a
byte on the vector unit, so bytes bound it."""
from counts_ssm import kernel_events, mamba_layers, padded, scan_bytes
from runlib import admitting, window_steps


def read(run):
    if run.trace is None or not mamba_layers(run.model):
        return None
    t = sum(e.dur for e in kernel_events(run.trace))
    s = admitting(window_steps(run, "trace"))
    if not t or not s:
        return None
    m = run.model
    best = sum(mamba_layers(m) * scan_bytes(m, padded(run.reqs[rid].prompt_len))
               for x in s for rid in x.admitted) / run.peak["hbm_bytes_per_s"]
    return 100.0 * best / t
