"""Which device programs in a trace are decode chunks and which are
admission waves.

Every program of the engine is named ``jit__unknown(<id>)`` in the trace
(its jitted callables wrap ``functools.partial``), so programs are told
apart by what ran inside them: the expert kernels run once or more per
layer and pass over the layer stack, so a program with at least
``layers * decode_chunk`` expert-kernel calls ran a decode chunk, and one
with fewer (but some) ran one pass: an admission prefill.
"""
from __future__ import annotations

from typing import List, Tuple

from bench.harness import trace as tr

EXPERT_KERNELS = ("expert_quant_matmul",)


def classify(trace: tr.Trace, layers: int, decode_chunk: int,
             whole: bool = False
             ) -> Tuple[List[tr.Event], List[tr.Event]]:
    """(decode programs, prefill programs): module events overlapping the
    window, uncut; with ``whole``, every one the trace holds."""
    lo, hi = trace.window
    kernels = tr.matching(trace.ops(), EXPERT_KERNELS)
    decode, prefill = [], []
    for m in trace.modules():
        if not whole and (m[2] <= lo or m[1] >= hi):
            continue
        passes = tr.model_passes(m, kernels, layers)
        if passes >= decode_chunk:
            decode.append(m)
        elif passes > 0:
            prefill.append(m)
    return decode, prefill


def clipped_fraction(ev: tr.Event, window: tr.Interval) -> float:
    """Share of an event's duration that lies inside the window."""
    s, e = max(ev[1], window[0]), min(ev[2], window[1])
    return max(0.0, e - s) / (ev[2] - ev[1]) if ev[2] > ev[1] else 0.0


def decode_steps(decode: List[tr.Event], window: tr.Interval,
                 decode_chunk: int) -> float:
    """Decode steps run inside the window (a chunk cut by the window
    counts by the share of its time inside)."""
    return sum(clipped_fraction(m, window) for m in decode) * decode_chunk
