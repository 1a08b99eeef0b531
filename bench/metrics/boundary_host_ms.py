"""Host time of a chunk boundary outside its device syncs: for each
``dymoe.step`` span (the session's ``step()``) that dispatched a decode
chunk inside the window, its length less that of the ``dymoe.sync``
spans inside it, averaged over those boundaries. This is host work
(admission, dispatch, bookkeeping, handing jobs to the replay worker)
that the device may wait on."""
from bench.harness import program_trace as pt

LAYER = "session (serving/scheduler.py)"


def read(ctx):
    t = pt.traced(ctx)
    if t is None:
        return None
    lo, hi = t.window
    dispatched = [(d[0], d[2]) for d in pt.spans(t, "dispatch")
                  if lo <= d[2] < hi]
    syncs = pt.spans(t, "sync")
    vals = []
    for th, _, s, e, _ in pt.spans(t, "step"):
        if not any(dt == th and s <= ds < e for dt, ds in dispatched):
            continue
        inner = sum(y[3] - y[2] for y in syncs
                    if y[0] == th and s <= y[2] and y[3] <= e)
        vals.append((e - s - inner) / 1e6)
    return sum(vals) / len(vals) if vals else None
