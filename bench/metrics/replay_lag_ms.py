"""How long a decode chunk's tokens wait for the replay worker: from the
end of the chunk's boundary ``dymoe.sync`` (its tokens exist on the host
side's reach) to the end of its ``dymoe.replay`` job (telemetry fetched,
orchestrator replayed, tokens delivered to the client streams); the
median over the chunks whose replay ended inside the window."""
import statistics

from bench.harness import program_trace as pt

LAYER = "replay worker (serving/engine.py ReplayStream, orchestrator)"


def read(ctx):
    t = pt.traced(ctx)
    if t is None:
        return None
    lo, hi = t.window
    synced = {p[4]["chunk"]: p[3] for p in pt.spans(t, "sync")
              if "chunk" in p[4]}
    lags = [(p[3] - synced[p[4]["chunk"]]) / 1e6
            for p in pt.spans(t, "replay")
            if p[4].get("kind") == "chunk" and p[4].get("chunk") in synced
            and lo <= p[3] <= hi]
    return statistics.median(lags) if lags else None
