"""Decode: bytes a decode-only step needs (every weight once, each slot's
live KV read, the new KV written) over its host time times HBM bandwidth."""
from counts import decode_step_bytes
from runlib import decode_only, window_steps


def read(run):
    s = decode_only(window_steps(run))
    t = sum(x.t1 - x.t0 for x in s)
    if not t:
        return None
    b = sum(decode_step_bytes(run.model, x.decode_ctx) for x in s)
    return 100.0 * b / (t * run.peak["hbm_bytes_per_s"])
