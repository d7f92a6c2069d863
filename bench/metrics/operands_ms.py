"""operands_ms.<mix>: host milliseconds per tick of the program's span
that puts the tick's host values on the device, inside the dispatch
span: stream.operands on one chip, fleet.operands on the fleet."""
from bench.layers import span_ms


def read(ctx):
    ms = span_ms(ctx, "stream.operands")
    return ms if ms is not None else span_ms(ctx, "fleet.operands")
