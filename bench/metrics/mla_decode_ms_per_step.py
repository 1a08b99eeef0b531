"""Device time of the latent-attention decode kernel per decode step: the
summed durations of its calls (``mla_decode_attention`` in the ``XLA Ops``
line) inside decode-chunk programs and the window, over the decode steps
run there (``bench/harness/programs.py``)."""
from bench.harness import mla, programs
from bench.harness import trace as tr

LAYER = "latent attention decode kernel (kernels/mla_decode/mla_decode.py)"


def read(ctx):
    decode, calls = mla.decode_kernel_calls(ctx)
    steps = programs.decode_steps(decode, ctx.trace.window, ctx.decode_chunk)
    if steps <= 0 or not calls:
        return None
    return tr.total_ns(calls) / steps / 1e6
