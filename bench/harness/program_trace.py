"""The program's own spans in a profiler trace: the ``dymoe.*`` host spans
of the serving session, with their stats.

The session opens host spans named ``dymoe.<what>``
(``repro.serving.spans``) in the same trace as the benchmark's ``bench.*``
spans and the device's programs, on one clock. :func:`load` reads a trace
as :func:`bench.harness.trace.load_xspace` does and adds ``program``:
``[(thread, name, start_ns, end_ns, stats), ...]``, threads named as
``Trace.host`` names them. Everything else (``host``, ``ops()``,
``modules()``, the window) is the base reduction's, unchanged.

The model's programs also carry named scopes (``attention``, ``router``,
``moe_dispatch``, ``experts``, ``moe_combine``, ``lm_head``, ``sample``,
``kv_freeze``, inside ``layers``), but only as HLO op metadata: a TPU
trace taken with ``enable_hlo_proto=False`` keeps no framework name on its
``XLA Ops`` events (their stats are the device offset, duration and time
scale alone), so no reader here attributes device time to scopes.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
from typing import List, Optional, Sequence, Tuple

from bench.harness import trace as tr

ProgramSpan = Tuple[str, str, float, float, dict]

PROGRAM_PREFIX = "dymoe."
# the jitted program of one decode chunk of the serving session
DECODE_PROGRAM = "jit_decode_many_batched("


@dataclasses.dataclass
class ProgramTrace(tr.Trace):
    program: List[ProgramSpan] = dataclasses.field(default_factory=list)

    def to_json(self) -> dict:
        return {**super().to_json(),
                "program": [list(p) for p in self.program]}

    @classmethod
    def from_json(cls, d: dict) -> "ProgramTrace":
        base = tr.Trace.from_json(d)
        return cls(device=base.device, host=base.host, window=base.window,
                   program=[tuple(p) for p in d.get("program", [])])


def load(log_dir: str) -> ProgramTrace:
    """:func:`bench.harness.trace.load_xspace` plus the program's spans."""
    from jax.profiler import ProfileData

    base = tr.load_xspace(log_dir)
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    program: List[ProgramSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            thread = f"{line.name}#{i}"
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIX):
                    program.append((thread, e.name, e.start_ns, e.end_ns,
                                    dict(e.stats)))
    return ProgramTrace(device=base.device, host=base.host,
                        window=base.window, program=program)


def save_excerpt(trace: ProgramTrace, path: str) -> None:
    """One chunk boundary of the window, whole, as gzipped JSON small
    enough to keep as test data: from the start of the first
    ``dymoe.step`` inside the window that dispatched a decode chunk to the
    end of that step, of the chunk's program and of the chunk's replay job,
    whichever is last. It holds the boundary's admission programs and the
    decode program, with every operation wholly inside (named by its HLO
    instruction alone, as :func:`bench.harness.trace.save_excerpt` names
    them) and every span (cut to it)."""
    lo, hi = trace.window
    replays = {p[4].get("chunk"): p for p in spans(trace, "replay")
               if p[4].get("kind") == "chunk"}
    steps = spans(trace, "step")
    mods = decode_programs(trace)
    win = None
    for m, d in zip(mods, chunk_of(mods, trace)):
        step = [p for p in steps if d is not None and p[0] == d[0]
                and p[2] <= d[2] < p[3] and p[2] >= lo]
        replay = replays.get(d[4]["chunk"]) if d is not None else None
        if step and replay is not None:
            end = max(step[0][3], m[2], replay[3])
            if end <= hi:
                win = (step[0][2], end)
                break
    if win is None:
        raise ValueError("no whole chunk boundary inside the window")
    dev = {k: [(n.split(" = ", 1)[0], s, e) for n, s, e in v
               if win[0] <= s and e <= win[1]]
           for k, v in trace.device.items()}
    host = [(th, n, max(s, win[0]), min(e, win[1]))
            for th, n, s, e in trace.host if e > win[0] and s < win[1]]
    program = [(th, n, max(s, win[0]), min(e, win[1]), st)
               for th, n, s, e, st in trace.program
               if e > win[0] and s < win[1]]
    out = ProgramTrace(device=dev, host=host, window=win, program=program)
    with gzip.open(path, "wt") as f:
        json.dump(out.to_json(), f)


def load_excerpt(path: str) -> ProgramTrace:
    with gzip.open(path, "rt") as f:
        return ProgramTrace.from_json(json.load(f))


# ------------------------------------------------------------- reading

def traced(ctx) -> Optional[ProgramTrace]:
    """A reader's trace, where it holds the program's spans; None for a
    trace reduced without them (:func:`bench.harness.trace.load_xspace`)
    or from a program that opens none."""
    t = ctx.trace
    return t if isinstance(t, ProgramTrace) and t.program else None


def spans(trace: ProgramTrace, name: str) -> List[ProgramSpan]:
    """Program spans called ``dymoe.<name>``, in start order."""
    full = PROGRAM_PREFIX + name
    return sorted((p for p in trace.program if p[1] == full),
                  key=lambda p: p[2])


def decode_programs(trace: ProgramTrace) -> List[tr.Event]:
    """Executions of the decode-chunk program that overlap the window, in
    start order."""
    lo, hi = trace.window
    return sorted((m for m in trace.modules()
                   if m[0].startswith(DECODE_PROGRAM)
                   and m[2] > lo and m[1] < hi), key=lambda m: m[1])


def chunk_of(modules: Sequence[tr.Event], trace: ProgramTrace
             ) -> List[Optional[ProgramSpan]]:
    """Each decode program's ``dymoe.dispatch`` span: the last one that
    started before the program did (the session keeps one decode chunk in
    flight, so that is the chunk's own enqueue), or None where the trace
    holds no such span."""
    disp = spans(trace, "dispatch")
    starts = [d[2] for d in disp]
    out = []
    for m in modules:
        i = bisect.bisect_right(starts, m[1]) - 1
        out.append(disp[i] if i >= 0 else None)
    return out


def name_gaps(gap_list: Sequence[tr.Interval], trace: ProgramTrace,
              stepper_thread: str, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named by the span that covers
    most of it, the innermost one where several cover as much (the
    shortest: a span's children lie inside it): the driving thread's
    benchmark and program spans first, then any other thread's, else
    ``untraced``."""
    every = [(th, nm, s, e) for th, nm, s, e in trace.host
             if nm != "bench.window"]
    every += [(th, nm, s, e) for th, nm, s, e, _ in trace.program]
    mine = [(nm, s, e) for th, nm, s, e in every if th == stepper_thread]
    others = [(nm, s, e) for th, nm, s, e in every if th != stepper_thread]
    out = []
    for g0, g1 in sorted(gap_list, key=lambda g: g[0] - g[1])[:n]:
        best, key = "untraced", (0.0, 0.0)
        for group in (mine, others):
            for nm, s, e in group:
                c = min(e, g1) - max(s, g0)
                if c > 0 and (c, s - e) > key:
                    best, key = nm, (c, s - e)
            if key[0] > 0:
                break
        out.append([best, (g1 - g0) / 1e9])
    return out
