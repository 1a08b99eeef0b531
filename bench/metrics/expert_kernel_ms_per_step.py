"""Device time of the grouped expert kernel per decode step: the summed
durations of its calls (``expert_quant_matmul_grouped_pallas`` in the
``XLA Ops`` line) inside decode-chunk programs and the window, over the
decode steps run there."""
from bench.harness import programs
from bench.harness import trace as tr

LAYER = ("grouped expert kernel "
         "(kernels/quant_matmul/expert_quant_matmul.py)")
KERNEL = ("expert_quant_matmul_grouped_pallas",)


def read(ctx):
    layers = ctx.spec["num_hidden_layers"]
    decode, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk)
    steps = programs.decode_steps(decode, ctx.trace.window, ctx.decode_chunk)
    calls = tr.inside(tr.matching(ctx.trace.ops(), KERNEL), decode)
    if steps <= 0 or not calls:
        return None
    return tr.total_ns(tr.clip(calls, ctx.trace.window)) / steps / 1e6
