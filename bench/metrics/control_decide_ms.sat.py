"""control_decide_ms.sat: host milliseconds per tick of the program's
control.decide span: FleetController's detectors, budget policies and
actuation on the pulled counters, inside control.tick."""
from bench.layers import span_ms


def read(ctx):
    return span_ms(ctx, "control.decide")
