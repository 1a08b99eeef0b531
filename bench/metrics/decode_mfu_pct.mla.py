"""The whole decode step's share of the chip's peak bf16 FLOP/s, for a
DeepSeek-V3-block model: model FLOPs of the tokens the decode chunks
produced (``bench/harness/flops_mla.py``: latent-attention projections and
absorbed attention over the token's context, the dense layer, router,
shared and selected routed experts, unembedding), over the decode
programs' device time inside the window times the peak
(``bench/harness/peaks.py``). Work and time are counted alike, chunk by
chunk, as ``decode_mfu_pct`` counts them (``bench/harness/mla.py``
``decoded_tokens``)."""
from bench.harness import flops_mla, mla, programs
from bench.harness import trace as tr

LAYER = "model step, decode chunk (models/model.py decode_many_batched)"


def read(ctx):
    layers = ctx.spec["num_hidden_layers"]
    decode, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk)
    dev_s = tr.total_ns(tr.clip(decode, ctx.trace.window)) / 1e9
    chunks, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk,
                                  whole=True)
    work = sum(w * flops_mla.decode_token_flops(ctx.spec, c)
               for w, c in mla.decoded_tokens(ctx, chunks))
    if dev_s <= 0 or work <= 0:
        return None
    return 100.0 * work / (dev_s * ctx.peaks["bf16_flops_per_s"])
