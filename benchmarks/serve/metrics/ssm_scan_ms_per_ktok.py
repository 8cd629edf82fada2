"""SSM scan: the Pallas selective scan's device time in the traced segment
per 1,000 prompt tokens admitted in its steps, at their real lengths (the
padding the kernel also walks is its cost).  Read beside
``prefill_ms_per_ktok``, the host time of a whole admitting step."""
from counts_ssm import kernel_events
from runlib import admitting, window_steps


def read(run):
    if run.trace is None:
        return None
    t = sum(e.dur for e in kernel_events(run.trace))
    tok = sum(x.prompt_tokens for x in admitting(window_steps(run, "trace")))
    return t / tok * 1e6 if t and tok else None
