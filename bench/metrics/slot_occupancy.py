"""Share (%) of the engine's slots holding a request, averaged over the
engine steps of the window. Under whole-footprint page reservation a slot
waits empty while the page pool cannot cover the next request."""


def read(ctx):
    steps = ctx.window.window_steps()
    n = ctx.cfg["engine"]["n_slots"]
    if not steps:
        return None
    return 100.0 * sum(s.live for s in steps) / (len(steps) * n)
