"""Reduction of a profiler trace to device busy time, per-name sums and
named idle gaps.

A trace is reduced to plain tuples first (:func:`load_xspace`), so the
arithmetic below runs on recorded data without JAX:

* ``device``: ``{line name: [(name, start_ns, end_ns), ...]}`` of the first
  device plane (``/device:TPU:0``);
* ``host``: ``[(thread, name, start_ns, end_ns), ...]`` of the spans the
  benchmark opened with ``jax.profiler.TraceAnnotation`` (names starting
  with ``bench.``).

Device and host events share the trace's own clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]            # name, start_ns, end_ns
HostSpan = Tuple[str, str, float, float]    # thread, name, start_ns, end_ns

# Where XLA puts one event per executed program and one per operation on
# a TPU device plane.
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Trace:
    device: Dict[str, List[Event]]
    host: List[HostSpan]
    window: Interval                         # traced window, ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def modules(self) -> List[Event]:
        return self.device.get(MODULE_LINE, [])

    def ops(self) -> List[Event]:
        return self.device.get(OPS_LINE, [])

    def to_json(self) -> dict:
        return {"device": {k: [list(e) for e in v]
                           for k, v in self.device.items()},
                "host": [list(s) for s in self.host],
                "window": list(self.window)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(device={k: [tuple(e) for e in v]
                           for k, v in d["device"].items()},
                   host=[tuple(s) for s in d["host"]],
                   window=tuple(d["window"]))


def save_excerpt(trace: Trace, path: str, programs: int = 2) -> None:
    """The first ``programs`` whole programs that start inside the window,
    with every operation and benchmark span between the first's start and
    the last's end, each operation named by its HLO instruction alone (the
    text before `` = ``), as gzipped JSON: small enough to keep as test
    data."""
    import gzip
    import json

    lo, hi = trace.window
    mods = sorted(m for m in trace.modules() if lo <= m[1] < hi)[:programs]
    if not mods:
        raise ValueError("no program starts inside the window")
    win = (mods[0][1], max(m[2] for m in mods))
    dev = {k: [(n.split(" = ", 1)[0], s, e) for n, s, e in v
               if win[0] <= s and e <= win[1]]
           for k, v in trace.device.items()}
    host = [(th, n, max(s, win[0]), min(e, win[1]))
            for th, n, s, e in trace.host if e > win[0] and s < win[1]]
    with gzip.open(path, "wt") as f:
        json.dump(Trace(device=dev, host=host, window=win).to_json(), f)


def load_excerpt(path: str) -> Trace:
    import gzip
    import json

    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


def load_xspace(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir`` (as
    ``jax.profiler.stop_trace`` wrote it). The window is the benchmark's
    ``bench.window`` span, on the trace's clock."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane.pb under {log_dir}, "
                           f"found {files}")
    pd = ProfileData.from_file(files[0])
    device: Dict[str, List[Event]] = {}
    host: List[HostSpan] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not device:
            if "TPU:0" not in plane.name and "GPU:0" not in plane.name:
                continue
            for line in plane.lines:
                device[line.name] = [(e.name, e.start_ns, e.end_ns)
                                     for e in line.events]
        elif plane.name.startswith("/host:"):
            # one line per thread; threads may share a name, so the line's
            # place in the plane identifies it
            for i, line in enumerate(plane.lines):
                thread = f"{line.name}#{i}"
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append((thread, e.name, e.start_ns, e.end_ns))
    return Trace(device=device, host=host, window=find_window(host))


def find_window(host: Sequence[HostSpan], name: str = "bench.window"
                ) -> Interval:
    """The interval of the benchmark's ``bench.window`` span."""
    spans = [(s, e) for _, n, s, e in host if n == name]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {name} span, found {len(spans)}")
    return spans[0]


def clip(events: Iterable[Event], window: Interval) -> List[Event]:
    """Events cut to the window; those wholly outside are dropped."""
    lo, hi = window
    out = []
    for name, s, e in events:
        s2, e2 = max(s, lo), min(e, hi)
        if e2 > s2:
            out.append((name, s2, e2))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], window: Interval) -> float:
    """Length of the union of the events' intervals inside the window."""
    return sum(e - s for s, e in union(
        (s, e) for _, s, e in clip(events, window)))


def idle_share(events: Iterable[Event], window: Interval) -> float:
    """1 - busy / window."""
    width = window[1] - window[0]
    if width <= 0:
        raise ValueError(f"empty window {window}")
    return 1.0 - busy_ns(events, window) / width


def gaps(events: Iterable[Event], window: Interval) -> List[Interval]:
    """The idle intervals of the window: where no event runs."""
    lo, hi = window
    out, cur = [], lo
    for s, e in union((s, e) for _, s, e in clip(events, window)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def matching(events: Iterable[Event], needles: Sequence[str]) -> List[Event]:
    """Events whose name contains any of ``needles``."""
    return [ev for ev in events if any(n in ev[0] for n in needles)]


def total_ns(events: Iterable[Event]) -> float:
    return sum(e - s for _, s, e in events)


def inside(events: Iterable[Event], containers: Sequence[Event]
           ) -> List[Event]:
    """Events whose midpoint lies inside one of ``containers`` (e.g. the
    operations that ran within the decode programs)."""
    spans = union((s, e) for _, s, e in containers)
    starts = [s for s, _ in spans]
    import bisect
    out = []
    for ev in events:
        mid = (ev[1] + ev[2]) / 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < spans[i][1]:
            out.append(ev)
    return out


def short_name(name: str) -> str:
    """An HLO operation's event name without its result type and its
    instance number: ``%fusion.316 = bf16[...] fusion(...)`` -> ``fusion``,
    ``%expert_quant_matmul_grouped_pallas.11 = ...`` ->
    ``expert_quant_matmul_grouped_pallas``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, dot, tail = head.rpartition(".")
    return base if dot and tail.isdigit() else head


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that contain no other event of the list (the XLA Ops line
    nests a loop's body operations inside the loop's own event)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[1] < ev[2] and nxt[2] <= ev[2]:
            continue
        out.append(ev)
    return out


def top_by_name(events: Iterable[Event], n: int = 10
                ) -> List[Tuple[str, float]]:
    """The ``n`` operation kinds (:func:`short_name`) that took the most
    summed time, in seconds."""
    tot: Dict[str, float] = {}
    for name, s, e in events:
        k = short_name(name)
        tot[k] = tot.get(k, 0.0) + (e - s)
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def model_passes(module: Event, kernel_events: Sequence[Event],
                 layers: int) -> float:
    """How many passes over the layer stack a program execution made,
    counted by the expert-kernel calls inside it (one or more per layer
    and pass): a decode chunk makes ``decode_chunk`` passes, an admission
    wave one."""
    _, s, e = module
    n = sum(1 for _, ks, ke in kernel_events if s <= ks and ke <= e)
    return n / layers


def name_gaps(gap_list: Sequence[Interval], host: Sequence[HostSpan],
              stepper_thread: str, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps, each named by the benchmark span that
    covers most of it: the driving thread's spans first (``bench.step``:
    the host is inside the session's step; ``bench.no_request``: the stepper
    waits for an arrival), then any other thread's (``bench.submit``,
    ``bench.stream_wait``), else ``untraced``."""
    mine = [(nm, s, e) for th, nm, s, e in host if th == stepper_thread]
    others = [(nm, s, e) for th, nm, s, e in host if th != stepper_thread
              and nm != "bench.window"]
    out = []
    for g0, g1 in sorted(gap_list, key=lambda g: g[0] - g[1])[:n]:
        best, cover = "untraced", 0.0
        for group in (mine, others):
            for nm, s, e in group:
                c = min(e, g1) - max(s, g0)
                if c > cover:
                    best, cover = nm, c
            if cover > 0:
                break
        out.append([best, (g1 - g0) / 1e9])
    return out
