"""Kernels: the Pallas flash-attention kernel's share of its roofline in
the traced segment: for each call (one per attention layer per prefill),
the larger of its causal-attention FLOPs (in the layer's window, if any)
over peak and its q, k, v, o bytes over HBM bandwidth, summed, over the
kernel's summed device time in the trace."""
from counts import flash_bytes, flash_calls, flash_flops
from runlib import admitting, window_steps
from trace_reduce import matching

KERNEL = "flash_attention"


def read(run):
    if run.trace is None:
        return None
    ev = [e for evs in run.trace.device_ops.values()
          for e in matching(evs, KERNEL)]
    t = sum(e.dur for e in ev)
    s = admitting(window_steps(run, "trace"))
    if not t or not s:
        return None
    m, pk = run.model, run.peak
    calls = flash_calls(m).items()
    best = sum(n * max(flash_flops(m, L, w) / pk["bf16_flops_per_s"],
                       flash_bytes(m, L) / pk["hbm_bytes_per_s"])
               for L in (run.reqs[rid].prompt_len
                         for x in s for rid in x.admitted)
               for w, n in calls)
    return 100.0 * best / t
