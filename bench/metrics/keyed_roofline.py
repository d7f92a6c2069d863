"""keyed_roofline.<mix>: per cent of the keyed stage's least time on the
chip (the deployment's work over bench/peaks.json) in its measured
device time per tick.  The least time is a lower bound on the bytes any
implementation moves (the deployment's ``work``), so the share cannot
pass 100%.  None where the program has no keyed stage."""
from bench import work
from bench.layers import scope_ms

KEYED = ("obs:keyed_update", "obs:keyed_fire")


def read(ctx):
    ms = scope_ms(ctx, KEYED)
    if not ms:
        return None
    least, bound = work.least_seconds(ctx.work, ctx.peak)
    ctx.log(f"keyed: least {least * 1e6:.3f} us a tick, bound by {bound}; "
            f"measured {ms:.4f} ms")
    return 100.0 * least / (ms / 1e3)
