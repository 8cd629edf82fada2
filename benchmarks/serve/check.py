"""The comparison that decides ``correct``.

Once the window has closed and the engine's state is freed, a sample of the
requests the engine finished, drawn from the seed and holding the longest of
them, is run through the configuration's plain float32 reference over its
prompt and served tokens.  The number compared is the widest gap by which a
served token's reference logit lies below the reference's best logit at its
position (``logit_gap_max``).  Decoding is greedy, so a sound engine serves
the reference's best token up to bf16 rounding.

The control (``control_gap_max``) puts the reference itself, with every
linear layer computed in a lower precision, in the program's place: at each
position of the same sequences it takes the token that the lower precision
ranks first and reads that token's gap in the float32 reference.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
CHUNK = 256          # positions unembedded at once


def reference_module(name: str):
    spec = importlib.util.spec_from_file_location(
        f"serve_bench_ref_{name}", HERE / "references" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def sample(finished: list, seed: int, tokens: int, max_requests: int) -> list:
    """Finished requests to compare: the longest (prompt plus served
    tokens), then others in a seeded order until ``tokens`` served tokens
    are in the sample."""
    done = [r for r in finished if r.generated]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.generated), r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(seed)
    picked, n = [longest], len(longest.generated)
    for j in rng.permutation(len(rest)):
        if n >= tokens or len(picked) >= max_requests:
            break
        picked.append(rest[j])
        n += len(rest[j].generated)
    return picked


def bucket(n: int) -> int:
    return max(CHUNK, 1 << (n - 1).bit_length())


@functools.lru_cache(maxsize=None)
def _gap_fn(ref_name: str, m_items: tuple, quant):
    ref = reference_module(ref_name)
    m = dict(m_items)

    def fn(w, toks, pos, chosen):
        h = ref.hidden(m, w, toks)
        hq = ref.hidden(m, w, toks, quant) if quant is not None else h

        def chunk(args):
            p, c = args
            lg = ref.logits(m, w, h[p])                       # (CHUNK, V)
            if quant is not None:
                c = jnp.argmax(ref.logits(m, w, hq[p], quant), -1)
            return lg.max(-1) - jnp.take_along_axis(lg, c[:, None], -1)[:, 0]

        return jax.lax.map(chunk, (pos.reshape(-1, CHUNK),
                                   chosen.reshape(-1, CHUNK))).reshape(-1)

    return jax.jit(fn)


def gaps(ref_name: str, m: dict, w: dict, reqs: list, quant=None) -> np.ndarray:
    """Gap of every served position of ``reqs`` (the engine's tokens when
    ``quant`` is None, else the ``quant`` reference's own first choices)."""
    fn = _gap_fn(ref_name, tuple(sorted(m.items())), quant)
    out = []
    for r in reqs:
        seq = list(r.prompt) + list(r.generated[:-1])
        S = bucket(len(seq))
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        n = len(r.generated)
        pos = np.zeros(S, np.int32)
        pos[:n] = len(r.prompt) - 1 + np.arange(n)
        chosen = np.zeros(S, np.int32)
        chosen[:n] = r.generated
        g = np.asarray(fn(w, toks, pos, chosen))
        out.append(g[:n])
    return np.concatenate(out) if out else np.zeros(0)


def widest(g: np.ndarray) -> float:
    """The number compared: the widest gap, +inf when nothing was served."""
    return float(g.max()) if g.size else float("inf")
