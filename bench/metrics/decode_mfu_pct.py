"""The whole decode step's share of the chip's peak bf16 FLOP/s: model
FLOPs of the tokens the decode chunks produced (``bench/harness/flops.py``:
weights of the experts a token selects, attention projections, router,
unembedding, and attention over the token's context), over the decode
programs' device time inside the window times the peak
(``bench/harness/peaks.py``).

Work and time are counted alike, chunk by chunk: each delivery of tokens
to a client is traced back to the decode chunk that produced it (the last
one to end before the delivery), and counts by the share of that chunk's
device time that lies inside the window, as the time does."""
import bisect

from bench.harness import flops, programs
from bench.harness import trace as tr

LAYER = "model step, decode chunk (models/model.py decode_many_batched)"


def decoded_flops(ctx, chunks) -> float:
    """FLOPs of every decoded token, weighted by its chunk's share of time
    inside the window. ``chunks``: every decode program of the trace."""
    window = ctx.trace.window
    # host clock (s) -> trace clock (ns): the window's span opened at t0
    offset = window[0] - ctx.t0 * 1e9
    chunks = sorted(chunks, key=lambda m: m[2])
    ends = [m[2] for m in chunks]
    share = [programs.clipped_fraction(m, window) for m in chunks]
    total = 0.0
    for r in ctx.records:
        j = 0
        for t, n in r.arrivals:
            i = bisect.bisect_right(ends, t * 1e9 + offset) - 1
            w = share[i] if i >= 0 else 0.0
            for _ in range(n):
                # the first token comes from the admission prefill
                if j >= 1 and w > 0:
                    total += w * flops.decode_token_flops(
                        ctx.spec, r.prompt_len + j)
                j += 1
    return total


def read(ctx):
    layers = ctx.spec["num_hidden_layers"]
    decode, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk)
    dev_s = tr.total_ns(tr.clip(decode, ctx.trace.window)) / 1e9
    chunks, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk,
                                  whole=True)
    work = decoded_flops(ctx, chunks)
    if dev_s <= 0 or work <= 0:
        return None
    return 100.0 * work / (dev_s * ctx.peaks["bf16_flops_per_s"])
