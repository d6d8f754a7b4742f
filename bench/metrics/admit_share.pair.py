"""Share (%) of the chip's busy time spent in the admission program (the
trunk pass, its SVDs and the factor cache): the time in which some
operation of a ``_pf`` execution ran over the time in which any operation
ran, both unions of operation intervals from the ``XLA Ops`` line of the
trace (an operation that runs others holds their events, so sums would
count loops twice)."""
from bench import trace as tr

PROGRAM = r"_pf\b"


def read(ctx):
    if ctx.trace is None:
        return None
    total = tr.busy_s(ctx.trace)
    t = tr.busy_s(ctx.trace, module=PROGRAM)
    return 100.0 * t / total if total else None
