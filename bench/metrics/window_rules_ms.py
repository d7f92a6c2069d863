"""window_rules_ms.<mix>: device milliseconds per tick of the windows
and rules stage: obs:fused_tick, or obs:window + obs:rules on the staged
path, averaged over the devices."""
from bench.layers import WINDOW_RULES, scope_ms


def read(ctx):
    return scope_ms(ctx, WINDOW_RULES)
