"""Generated tokens committed in the window over the window's seconds.
A token is committed when the engine hands it to the request's stream,
after the sampler's device-to-host copy."""


def read(ctx):
    w = ctx.window
    return w.tokens() / w.seconds if w.seconds > 0 else None
