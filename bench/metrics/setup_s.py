"""Set-up time: process start to the opening of the measured window
(weights made on the chip, programs compiled or read from the compile
cache, every shape the traffic reaches warmed, the ramp served)."""


def read(ctx):
    return ctx.setup_s
