"""Model step: decode model FLOPs of the window's decode-only steps over
their host time times the chip's peak."""
from counts import decode_flops
from runlib import decode_only, window_steps


def read(run):
    s = decode_only(window_steps(run))
    t = sum(x.t1 - x.t0 for x in s)
    if not t:
        return None
    f = sum(decode_flops(run.model, c) for x in s for c in x.decode_ctx)
    return 100.0 * f / (t * run.peak["bf16_flops_per_s"])
