"""One generator for every mix: the same sizes and gaps for every seed,
in another order."""
import itertools
from pathlib import Path

import pytest

from bench.harness import traffic

DATA = Path(__file__).resolve().parent / "data" / "traffic"


def test_lognormal_quantiles_are_clipped_and_fixed():
    d = {"kind": "lognormal", "median": 128, "sigma": 0.8, "min": 32,
         "max": 512}
    q = traffic.quantiles(d, 16)
    assert len(q) == 16 and min(q) >= 32 and max(q) <= 512
    assert sorted(q)[7] <= 128 <= sorted(q)[8]


def test_choice_must_split_the_cycle_exactly():
    d = {"kind": "choice", "lengths": [512, 1024, 2048],
         "weights": [0.5, 0.3, 0.2]}
    assert sorted(traffic.quantiles(d, 20)) == [512] * 10 + [1024] * 6 + \
        [2048] * 4
    with pytest.raises(ValueError):
        traffic.quantiles(d, 7)


def test_arrival_gaps_fill_the_cycle():
    g = traffic.arrival_gaps(2.0, 20)
    assert sum(g) == pytest.approx(10.0)
    assert g == sorted(g)


def test_open_stream_same_multiset_other_order():
    mix = traffic.load("tiny_open", DATA)
    a = list(itertools.islice(traffic.open_stream(mix, 50304, 1), 20))
    b = list(itertools.islice(traffic.open_stream(mix, 50304, 2), 20))
    assert sorted(len(d.prompt) for d in a) == \
        sorted(len(d.prompt) for d in b)
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]
    assert a[-1].at_s == pytest.approx(b[-1].at_s)
    again = list(itertools.islice(traffic.open_stream(mix, 50304, 1), 20))
    assert [d.prompt for d in again] == [d.prompt for d in a]


def test_closed_stream_rounds_take_one_cycle_across_clients():
    mix = traffic.load("decode_heavy")
    n = mix["clients"]
    cycle = sorted(traffic.quantiles(mix["output"], n))
    walks = {}
    for seed in (3, 2 ** 31 + 5):
        streams = [traffic.closed_stream(mix, 50304, seed, c)
                   for c in range(n)]
        rounds = [[next(s).max_new for s in streams] for _ in range(3)]
        for r in rounds:
            assert sorted(r) == cycle
        # the seed relabels clients: the set of per-client walks is fixed
        walks[seed] = sorted(tuple(r[c] for r in rounds) for c in range(n))
    assert walks[3] == walks[2 ** 31 + 5]


def test_prompt_lengths_shortest_first():
    assert traffic.prompt_lengths(traffic.load("tiny_open", DATA)) == \
        [512, 1024, 2048]
    assert traffic.prompt_lengths(traffic.load("decode_heavy")) == [256]
