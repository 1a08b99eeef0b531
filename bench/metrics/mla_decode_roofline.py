"""The latent-attention decode kernel's share of the chip's HBM bandwidth:
the latent cache bytes the decoded tokens needed read (each token's
context x (kv_lora_rank + qk_rope_head_dim) x 2 B x layers,
``bench/harness/mla.py``), each token weighted by its decode chunk's share
of time inside the window, over the kernel's device time inside decode
programs and the window times the peak bytes/s
(``bench/harness/peaks.py``). Bytes of dead rows, of the query and of the
output are not counted, so this is a lower bound."""
from bench.harness import mla, programs
from bench.harness import trace as tr

LAYER = "latent attention decode kernel (kernels/mla_decode/mla_decode.py)"


def read(ctx):
    _, calls = mla.decode_kernel_calls(ctx)
    ns = tr.total_ns(calls)
    chunks, _ = programs.classify(ctx.trace, ctx.spec["num_hidden_layers"],
                                  ctx.decode_chunk, whole=True)
    nbytes = sum(w * mla.decode_read_bytes(ctx.spec, c)
                 for w, c in mla.decoded_tokens(ctx, chunks))
    if ns <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (ns / 1e9 * ctx.peaks["hbm_bytes_per_s"])
