"""admission_ms.<mix>: device milliseconds per tick whose innermost
scope is obs:admission (dedupe, contract gating and, as the program
stands, the masked ring enqueue), averaged over the devices."""
from bench.layers import scope_ms


def read(ctx):
    return scope_ms(ctx, ("obs:admission",))
