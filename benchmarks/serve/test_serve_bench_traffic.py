"""The benchmark's traffic generator: its copy of the paper's samplers, its
arrivals and its fixed multiset of sizes."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import traffic  # noqa: E402
from repro.core import workload  # noqa: E402

ARENA = {"sampler": "arena", "arrivals": "poisson", "rate": 3.0,
         "size_seed": 7}


@pytest.mark.parametrize("name", ["arena", "pubmed"])
def test_sampler_copy_reproduces_the_program(name):
    ours = traffic.SAMPLERS[name](np.random.default_rng(123), 5000)
    theirs = getattr(workload, f"sample_{name}")(np.random.default_rng(123),
                                                 5000)
    for a, b in zip(ours, theirs):
        assert np.array_equal(a, b)
        assert np.allclose(np.quantile(a, [0.1, 0.5, 0.9]),
                           np.quantile(b, [0.1, 0.5, 0.9]))


def test_arena_quantiles_match_the_paper():
    i, o = traffic.sample_arena(np.random.default_rng(0), 20000)
    assert 80 <= np.median(i) <= 100 and 190 <= np.median(o) <= 230
    assert i.min() >= 1 and i.max() <= 2000 and o.max() <= 2000


def test_poisson_gaps_have_the_stated_mean_rate():
    g = traffic.gaps(ARENA, 400)
    assert len(g) == 401 and np.all(g > 0)
    assert np.isclose(1.0 / g.mean(), ARENA["rate"])
    cv = g.std() / g.mean()
    assert 0.8 < cv < 1.2                     # exponential: cv = 1


def test_schedule_count_rate_and_order():
    items = traffic.schedule(ARENA, 1000, seed=2**33 + 5,
                             segments_s=[20, 50])
    assert len(items) == 60 + 150             # ceil(3.0 * 20) + ceil(3.0 * 50)
    due = [it.due_s for it in items]
    assert due == sorted(due)
    assert sum(d < 20 for d in due) == 60     # each segment holds its count
    assert 0 < due[0] and due[-1] < 70
    assert all(0 <= t < 1000 for it in items for t in it.prompt)


def _segment(items, lo, hi):
    return [it for it in items if lo <= it.due_s < hi]


def test_every_seed_serves_the_same_sizes_in_its_own_order():
    """Every seed serves each segment the same lengths at the same due
    times, in the same order; its own order is that of its token ids."""
    segs = [10, 30, 5]
    a = traffic.schedule(ARENA, 1000, seed=1, segments_s=segs)
    b = traffic.schedule(ARENA, 1000, seed=2**33 + 7, segments_s=segs)
    shape = lambda s: [(it.due_s, len(it.prompt), it.max_new_tokens)
                       for it in s]
    assert shape(a) == shape(b)
    for lo, hi in ((0, 10), (10, 40), (40, 45)):
        assert len(_segment(a, lo, hi)) == int(np.ceil(3.0 * (hi - lo)))
    assert [it.prompt for it in a] != [it.prompt for it in b]
    again = traffic.schedule(ARENA, 1000, seed=1, segments_s=segs)
    assert [it.prompt for it in again] == [it.prompt for it in a]
    # the window's requests do not depend on how long the ramp is
    c = traffic.schedule(ARENA, 1000, seed=1, segments_s=[25, 30, 5])
    sizes = lambda s: [(round(it.due_s - s[0].due_s, 9), len(it.prompt),
                        it.max_new_tokens) for it in s]
    assert sizes(_segment(c, 25, 55)) == sizes(_segment(a, 10, 40))


def test_backlog_is_due_at_once():
    spec = {"sampler": "arena", "arrivals": "backlog", "backlog": 40,
            "size_seed": 3}
    items = traffic.schedule(spec, 500, seed=9, segments_s=[99])
    assert len(items) == 40
    assert not any(it.due_s for it in items)


def test_unknown_arrival_process_is_refused():
    with pytest.raises(ValueError):
        traffic.gaps(dict(ARENA, arrivals="bursty"), 10)
