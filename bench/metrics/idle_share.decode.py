"""Share (%) of the traced window in which no operation ran on the chip:
1 - union of the operation intervals / window, averaged over chips."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return tr.idle_pct(ctx.trace)
