"""Bytes of the latent-attention decode kernel, and the decoded tokens a
reader counts them over.

At decode, each layer's ``mla_decode_attention`` call reads, for every
row, that row's latent cache over its context: per token of context one
``kv_lora_rank``-wide latent and one ``qk_rope_head_dim``-wide rope key in
bfloat16. Every layer, the dense ones included, has latent attention. The
query, the output and the slots past a row's context are not counted
(the kernel fetches no block beyond the context, and the rest is small),
so a share of bandwidth computed from this is a lower bound. Sizes come
from the configuration file's keys (``bench/configs/<name>.json``).
"""
from __future__ import annotations

import bisect
from typing import Iterator, Sequence, Tuple

from bench.harness import programs
from bench.harness import trace as tr

CACHE_BYTES = 2     # the latent cache is held in bfloat16
KERNEL = ("mla_decode_attention",)


def latent_bytes_per_token(spec: dict) -> int:
    """Latent cache bytes of one context token, summed over the layers."""
    return ((spec["kv_lora_rank"] + spec["qk_rope_head_dim"]) * CACHE_BYTES
            * spec["num_hidden_layers"])


def decode_read_bytes(spec: dict, context: int) -> int:
    """What one decoded token's kernel calls read, over every layer: its
    ``context`` (the prompt, the tokens decoded before it, and itself)."""
    return context * latent_bytes_per_token(spec)


def decoded_tokens(ctx, chunks: Sequence[tr.Event]
                   ) -> Iterator[Tuple[float, int]]:
    """(weight, context) of every token a decode chunk delivered to a
    client: each delivery is traced back to the chunk that produced it
    (the last of ``chunks`` to end before the delivery), and counts by the
    share of that chunk's device time inside the window, as the time a
    reader divides by is clipped to the window. The first token of a
    request comes from its admission prefill and is left out."""
    window = ctx.trace.window
    offset = window[0] - ctx.t0 * 1e9   # host clock (s) -> trace clock (ns)
    chunks = sorted(chunks, key=lambda m: m[2])
    ends = [m[2] for m in chunks]
    share = [programs.clipped_fraction(m, window) for m in chunks]
    for r in ctx.records:
        j = 0
        for t, n in r.arrivals:
            i = bisect.bisect_right(ends, t * 1e9 + offset) - 1
            w = share[i] if i >= 0 else 0.0
            for _ in range(n):
                if j >= 1 and w > 0:
                    yield w, r.prompt_len + j
                j += 1


def decode_kernel_calls(ctx) -> Tuple[list, list]:
    """(decode programs overlapping the window, the latent-attention
    kernel's calls inside them, clipped to the window)."""
    layers = ctx.spec["num_hidden_layers"]
    decode, _ = programs.classify(ctx.trace, layers, ctx.decode_chunk)
    calls = tr.inside(tr.matching(ctx.trace.ops(), KERNEL), decode)
    return decode, tr.clip(calls, ctx.trace.window)
