"""exchange_ms.sat: device milliseconds per tick of the escalation
exchange and core stage: obs:all_to_all_*, obs:fog_compact,
obs:core_compute and obs:core_commit, averaged over the devices."""
from bench.layers import scope_ms

SCOPES = ("obs:all_to_all_out", "obs:all_to_all_region",
          "obs:all_to_all_back", "obs:fog_compact", "obs:core_compute",
          "obs:core_commit")


def read(ctx):
    return scope_ms(ctx, SCOPES)
