"""Model step: model FLOPs of the window's admitting steps (each admitted
prompt at its real length, and the tokens the step decoded) over their host
time times the chip's peak."""
from counts import decode_flops, prefill_flops
from runlib import admitting, window_steps


def read(run):
    s = admitting(window_steps(run))
    t = sum(x.t1 - x.t0 for x in s)
    if not t:
        return None
    f = sum(prefill_flops(run.model, run.reqs[rid].prompt_len)
            for x in s for rid in x.admitted)
    f += sum(decode_flops(run.model, c) for x in s for c in x.decode_ctx)
    return 100.0 * f / (t * run.peak["bf16_flops_per_s"])
