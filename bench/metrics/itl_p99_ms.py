"""99th percentile (nearest rank) of every gap between consecutive tokens
of a request, both inside the window, in milliseconds. Prefill waves stall
every decoding slot, so they land in this tail."""
from bench.common import percentile


def read(ctx):
    gaps = ctx.window.gaps()
    ctx.note(f"itl: {len(gaps)} gaps in the window")
    return 1e3 * percentile(gaps, 99) if len(gaps) >= 100 else None
