"""Prefill: host time of the window's admitting steps per 1,000 prompt
tokens admitted in them."""
from runlib import admitting, window_steps


def read(run):
    s = admitting(window_steps(run))
    tok = sum(x.prompt_tokens for x in s)
    return sum(x.t1 - x.t0 for x in s) / tok * 1e6 if tok else None
