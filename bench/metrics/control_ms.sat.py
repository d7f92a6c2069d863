"""control_ms.sat: host milliseconds per tick of the program's
control.tick span (FleetController.tick)."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "control.tick")
