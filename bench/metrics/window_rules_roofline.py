"""window_rules_roofline.<mix>: per cent of the windows and rules
stage's least time on the chip (bench/work.py over bench/peaks.json) in
its measured device time per tick."""
from bench.layers import window_rules_roofline


def read(ctx):
    return window_rules_roofline(ctx)
