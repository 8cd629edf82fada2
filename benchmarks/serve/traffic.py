"""Open-loop traffic from a data file of parameters.

A traffic file (``traffic/<name>.json``) names a length sampler and an
arrival process.  A run is cut into consecutive segments (the ramp, the
measured window, the tail after it).  Each segment gets its own fixed
requests: (prompt, output) lengths, due times and their order, all drawn
once from the file's ``size_seed`` and the segment's index.  ``--seed``
draws only the prompts' token ids.  So every seed offers the window the
same work at the same times: the lengths and the order of the long answers
decide how full the batch is, and a seed that reordered them would change
the tails it measures.  Every run warms the same shapes.

Arrival processes:
  * ``poisson``: ``n = ceil(rate * length)`` arrivals in a segment; the
    ``n + 1`` exponential gaps around them are rescaled to sum to the
    segment's length (a Poisson process given its count);
  * ``backlog``: ``backlog`` requests, all due when the traffic starts.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


# Copied from repro.core.workload (the paper's §6.1 / App. A.1 length
# distributions); the benchmark keeps its own copy so that no PR changes the
# yardstick by editing the program.
def _lognormal(rng, median, sigma, size, lo, hi):
    x = rng.lognormal(mean=math.log(median), sigma=sigma, size=size)
    return np.clip(x, lo, hi).astype(int)


def sample_arena(rng: np.random.Generator, n: int):
    """Short-context chat: inputs & outputs < 2000, output-skewed."""
    i = _lognormal(rng, median=90, sigma=1.3, size=n, lo=1, hi=2000)
    o = _lognormal(rng, median=210, sigma=0.9, size=n, lo=1, hi=2000)
    return i, o


def sample_pubmed(rng: np.random.Generator, n: int):
    """Document summarization: long inputs (papers), short outputs."""
    i = _lognormal(rng, median=3200, sigma=0.55, size=n, lo=200, hi=32000)
    o = _lognormal(rng, median=230, sigma=0.45, size=n, lo=30, hi=1200)
    return i, o


SAMPLERS = {"arena": sample_arena, "pubmed": sample_pubmed}


@dataclasses.dataclass
class Item:
    """One request of the schedule: due ``due_s`` after the traffic starts."""
    due_s: float
    prompt: list[int]
    max_new_tokens: int


def n_requests(spec: dict, length_s: float) -> int:
    if spec["arrivals"] == "backlog":
        return int(spec["backlog"])
    return int(math.ceil(spec["rate"] * length_s))


def sizes(spec: dict, n: int, segment: int = 0
          ) -> tuple[np.ndarray, np.ndarray]:
    """A segment's fixed multiset of (prompt, output) lengths."""
    rng = np.random.default_rng([spec["size_seed"], segment])
    return SAMPLERS[spec["sampler"]](rng, n)


def gaps(spec: dict, n: int, segment: int = 0) -> np.ndarray:
    """A segment's fixed multiset of the ``n + 1`` gaps (seconds) before,
    between and after its ``n`` arrivals, with mean ``1 / rate``."""
    if spec["arrivals"] == "backlog":
        return np.zeros(n + 1)
    if spec["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['arrivals']!r}")
    rng = np.random.default_rng([spec["size_seed"] + 1, segment])
    g = rng.exponential(1.0, n + 1)
    return g / g.mean() / spec["rate"]


def schedule(spec: dict, vocab: int, seed: int,
             segments_s: list[float]) -> list[Item]:
    """Requests in order of their due times over consecutive segments of
    the given lengths (a backlog: one segment); ``seed`` draws the token
    ids."""
    if spec["arrivals"] == "backlog":
        segments_s = segments_s[:1]
    rng = np.random.default_rng(seed)
    items, t0 = [], 0.0
    for k, length in enumerate(segments_s):
        n = n_requests(spec, length)
        ins, outs = sizes(spec, n, k)
        g = gaps(spec, n, k)
        if spec["arrivals"] == "poisson":
            g = g * (length / g.sum())
        for d, L, o in zip(t0 + np.cumsum(g)[:n], ins, outs):
            prompt = rng.integers(0, vocab, int(L)).tolist()
            items.append(Item(float(d), prompt, int(o)))
        t0 += length
    return items
