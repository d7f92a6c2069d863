"""keyed_ms.<mix>: device milliseconds per tick of the keyed windows:
obs:keyed_update (selection, sort, table upsert) and obs:keyed_fire (the
due test, and on the ticks that fire a window the table scan), averaged
over the devices.  None where the program has no keyed stage."""
from bench.layers import scope_ms

KEYED = ("obs:keyed_update", "obs:keyed_fire")


def read(ctx):
    return scope_ms(ctx, KEYED)
