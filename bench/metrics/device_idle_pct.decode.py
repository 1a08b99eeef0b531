"""Share of the window in which no operation ran on the device: one minus
the union of the ``XLA Ops`` events' intervals over the traced window."""
from bench.harness import trace as tr

LAYER = "device"


def read(ctx):
    ops = ctx.trace.ops()
    if not ops:
        return None
    return 100.0 * tr.idle_share(ops, ctx.trace.window)
