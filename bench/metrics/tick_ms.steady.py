"""tick_ms.steady: host milliseconds per tick from dispatch to the
tick's outputs on the host, the benchmark's own span, over the window."""


def read(ctx):
    d = [e - s for _, _, s, e in ctx.ticks]
    return 1e3 * sum(d) / len(d) if d else None
