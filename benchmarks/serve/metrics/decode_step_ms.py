"""Decode: mean host time of the window's decode-only steps."""
from runlib import decode_only, window_steps


def read(run):
    s = decode_only(window_steps(run))
    return sum(x.t1 - x.t0 for x in s) / len(s) * 1e3 if s else None
