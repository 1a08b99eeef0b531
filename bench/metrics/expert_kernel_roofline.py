"""The grouped expert kernel's share of the chip's HBM bandwidth in
decode: the packed bytes (codes plus scales) of the (expert, precision)
groups its calls found live, over the kernel's device time times the
peak bytes/s (``bench/harness/peaks.py``).

Each decode chunk's bytes are the ``kernel_weight_bytes`` the session
counts on the chunk's ``dymoe.replay`` span; a chunk is matched to its
device program (``jit_decode_many_batched``) through ``chunk`` on its
``dymoe.dispatch`` span, and its bytes count by the share of that
program's device time inside the window, as its kernel time is clipped
to the window. Only live weights count, not activations, so this is a
lower bound on the kernel's bandwidth share."""
from bench.harness import program_trace as pt
from bench.harness import programs
from bench.harness import trace as tr

LAYER = ("grouped expert kernel "
         "(kernels/quant_matmul/expert_quant_matmul.py)")
KERNEL = ("expert_quant_matmul_grouped_pallas",)


def read(ctx):
    t = pt.traced(ctx)
    if t is None:
        return None
    counted = {p[4]["chunk"]: p[4]["kernel_weight_bytes"]
               for p in pt.spans(t, "replay")
               if p[4].get("kind") == "chunk"
               and "kernel_weight_bytes" in p[4]}
    kernels = tr.matching(t.ops(), KERNEL)
    mods = pt.decode_programs(t)
    nbytes = ns = 0.0
    for m, d in zip(mods, pt.chunk_of(mods, t)):
        if d is None or d[4]["chunk"] not in counted:
            continue
        nbytes += (programs.clipped_fraction(m, t.window)
                   * counted[d[4]["chunk"]])
        ns += tr.total_ns(tr.clip(tr.inside(kernels, [m]), t.window))
    if ns <= 0 or nbytes <= 0:
        return None
    return 100.0 * nbytes / (ns / 1e9 * ctx.peaks["hbm_bytes_per_s"])
