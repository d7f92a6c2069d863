"""call_ms.<mix>: host milliseconds per tick of the program's span
around the jit call alone, inside the dispatch span: stream.call on one
chip, fleet.call on the fleet."""
from bench.layers import span_ms


def read(ctx):
    ms = span_ms(ctx, "stream.call")
    return ms if ms is not None else span_ms(ctx, "fleet.call")
