"""One general generator for every traffic mix.

A mix is a JSON file ``bench/traffic/<mix>.json`` of parameters only:

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next request
  when the previous one completes) or ``"open"`` (arrivals on a schedule at
  ``rate_per_s``, whether or not earlier requests finished);
* ``slots``, ``slots_len``: the serving session's slot batch and per-slot
  cache length;
* ``prompt`` and ``output``: length distributions, each ``{"kind":
  "fixed", "length": n}``, ``{"kind": "choice", "lengths": [...],
  "weights": [...]}`` or ``{"kind": "lognormal", "median": m, "sigma": s,
  "min": a, "max": b}``;
* ``arrivals``: ``"poisson"`` (open loop);
* ``cycle``: how many draws make one cycle (below);
* ``warmup_s``: seconds of this traffic before the measured window;
* ``sample``: how many finished requests the correctness check compares.

Every seed gets the same sizes and the same arrival gaps, in another order:
the values of a distribution are its quantiles at ``(i + 0.5) / cycle``.
An open loop takes each cycle in a fresh permutation drawn from the seed;
a closed loop's clients walk the cycle as a Latin square whose rows the
seed assigns. So the seed changes which request is long and the token
ids, not the amount of work. Token ids are uniform over ``[1, vocab)``;
decoding is greedy and no request stops early.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load(name: str, directory: Path = TRAFFIC_DIR) -> dict:
    path = directory / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def quantiles(dist: dict, n: int) -> List[int]:
    """``n`` lengths whose multiset follows ``dist`` exactly (fixed,
    choice) or by its quantiles at (i + 0.5) / n (lognormal)."""
    kind = dist["kind"]
    if kind == "fixed":
        return [int(dist["length"])] * n
    if kind == "choice":
        counts = [w * n for w in dist["weights"]]
        if any(abs(c - round(c)) > 1e-9 for c in counts) or \
                round(sum(counts)) != n:
            raise ValueError(f"cycle {n} does not split by weights "
                             f"{dist['weights']}")
        out = []
        for length, c in zip(dist["lengths"], counts):
            out += [int(length)] * int(round(c))
        return out
    if kind == "lognormal":
        nd = NormalDist()
        mu = math.log(dist["median"])
        vals = [math.exp(mu + dist["sigma"] * nd.inv_cdf((i + 0.5) / n))
                for i in range(n)]
        return [int(min(dist["max"], max(dist["min"], round(v))))
                for v in vals]
    raise ValueError(f"unknown length distribution {kind!r}")


def max_length(dist: dict) -> int:
    kind = dist["kind"]
    if kind == "fixed":
        return int(dist["length"])
    if kind == "choice":
        return int(max(dist["lengths"]))
    return int(dist["max"])


def prompt_lengths(mix: dict) -> List[int]:
    """The prompt lengths the mix draws, shortest first."""
    n = mix.get("cycle", mix.get("clients", 1))
    return sorted(set(quantiles(mix["prompt"], n)))


def arrival_gaps(rate: float, n: int) -> List[float]:
    """``n`` exponential inter-arrival gaps at their quantiles, scaled so
    that one cycle lasts exactly ``n / rate`` seconds."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / rate / sum(raw)
    return [g * scale for g in raw]


@dataclasses.dataclass(frozen=True)
class Draw:
    """One request of the stream: who sends it, when (open loop: seconds
    after the traffic starts), and what."""
    index: int
    client: int
    at_s: float
    prompt: List[int]
    max_new: int


def _cycles(values: List, rng: np.random.Generator) -> Iterator:
    while True:
        for i in rng.permutation(len(values)):
            yield values[i]


def closed_stream(mix: dict, vocab: int, seed: int, client: int
                  ) -> Iterator[Draw]:
    """Client ``client``'s requests. The clients walk one cycle of
    quantiles as a Latin square: client ``c`` takes quantile
    ``(p[c] + r) mod n`` in its round ``r``, with ``p`` a permutation of
    the clients drawn from the seed. Every round of all clients together
    takes the whole cycle once, and the seed only relabels the clients, so
    the load the session sees is the same for every seed."""
    n = mix["clients"]
    outs = quantiles(mix["output"], n)
    prompts = quantiles(mix["prompt"], n)
    start = int(np.random.default_rng([seed, 2]).permutation(n)[client])
    tok_rng = np.random.default_rng([seed, 1, client])
    r = 0
    while True:
        q = (start + r) % n
        yield Draw(index=r * n + client, client=client, at_s=0.0,
                   prompt=tok_rng.integers(1, vocab, prompts[q]).tolist(),
                   max_new=outs[q])
        r += 1


def open_stream(mix: dict, vocab: int, seed: int) -> Iterator[Draw]:
    """Arrivals at ``rate_per_s`` with Poisson gaps (quantiles, permuted
    per cycle), prompt and output lengths permuted per cycle."""
    n = mix["cycle"]
    rng = np.random.default_rng([seed, 3])
    gaps = _cycles(arrival_gaps(mix["rate_per_s"], n), rng)
    prompts = _cycles(quantiles(mix["prompt"], n), rng)
    outs = _cycles(quantiles(mix["output"], n), rng)
    tok_rng = np.random.default_rng([seed, 4])
    t = 0.0
    i = 0
    while True:
        t += next(gaps)
        length = next(prompts)
        yield Draw(index=i, client=i, at_s=t,
                   prompt=tok_rng.integers(1, vocab, length).tolist(),
                   max_new=next(outs))
        i += 1
