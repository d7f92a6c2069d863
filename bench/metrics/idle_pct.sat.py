"""idle_pct.sat: per cent of the traced window in which no operation ran
on a device, averaged over the devices."""
from bench import trace_reduce as TR


def read(ctx):
    lo, hi = ctx.window
    if hi <= lo or not ctx.trace.devices:
        return None
    busy = sum(TR.busy_ns(d.ops, lo, hi) for d in ctx.trace.devices)
    return 100.0 * (1.0 - busy / len(ctx.trace.devices) / (hi - lo))
