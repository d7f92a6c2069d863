"""control_pull_ms.sat: host milliseconds per tick of the program's
control.pull span: FleetController's one blocking pull of the sharded
counters, inside control.tick."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "control.pull")
