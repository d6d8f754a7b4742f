"""Host time of an engine step (ms) that is not spent waiting for the
chip: the mean, over the ``serve.step`` spans that end in the window, of
each span's duration less the part its ``serve.fetch`` spans (the
blocking device-to-host reads) cover. This is the host work between two
decode dispatches: admission, dispatch, sampling, commit and the
callbacks, page growth."""
from bench import spans


def read(ctx):
    if ctx.trace is None:
        return None
    steps = spans.ending(ctx.trace, "serve.step")
    if not steps:
        return None
    fetches = spans.named(ctx.trace, "serve.fetch")
    host = [s.end - s.start - spans.covered_ns(s, fetches) for s in steps]
    ctx.note(f"host_ms_per_step.decode: {len(steps)} steps, "
             f"{sum(s.end - s.start for s in steps) * 1e-9:.6f} s in steps, "
             f"{sum(host) * 1e-9:.6f} s of it not fetching")
    return 1e-6 * sum(host) / len(steps)
