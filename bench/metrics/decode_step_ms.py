"""Device time of one decode step: the decode-chunk programs' device time
inside the window over the decode steps they ran there.

Reads the trace's ``XLA Modules`` line (programs) and the expert-kernel
calls of the ``XLA Ops`` line that tell decode chunks from admission waves
(``bench/harness/programs.py``)."""
from bench.harness import programs
from bench.harness import trace as tr

LAYER = "model step, decode chunk (models/model.py decode_many_batched)"


def read(ctx):
    layers = ctx.spec["num_hidden_layers"]
    decode, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk)
    steps = programs.decode_steps(decode, ctx.trace.window, ctx.decode_chunk)
    if steps <= 0:
        return None
    return tr.total_ns(tr.clip(decode, ctx.trace.window)) / steps / 1e6
