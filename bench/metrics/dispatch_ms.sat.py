"""dispatch_ms.sat: host milliseconds per tick of the program's
fleet.dispatch span (FleetExecutor.step up to the enqueued step; the
wait for the device is the separate fleet.device_execute span)."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "fleet.dispatch")
