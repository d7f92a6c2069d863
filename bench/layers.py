"""Shared arithmetic of the per-layer metric readers
(``bench/metrics/<name>.py``): device time per tick under named scopes,
averaged over the devices of the trace, and host span times per tick."""
from __future__ import annotations

from bench import trace_reduce as TR
from bench import work


def scope_ms(ctx, scopes) -> float | None:
    """Device milliseconds per traced tick under any of ``scopes``
    (innermost ``obs:*`` scope), averaged over the devices; None where
    no op of those scopes ran."""
    total, seen = 0.0, False
    lo, hi = ctx.window
    for d in ctx.trace.devices:
        per = TR.scope_ns(d.ops, lo, hi)
        for s in scopes:
            if s in per:
                total += per[s]
                seen = True
    if not seen or not ctx.traced_ticks:
        return None
    return total / len(ctx.trace.devices) / ctx.traced_ticks / 1e6


#: the windows-and-rules stage: the fused kernel's scope, or the staged
#: windows and rules
WINDOW_RULES = ("obs:fused_tick", "obs:window", "obs:rules")


def window_rules_roofline(ctx) -> float | None:
    """Per cent of the stage's least time on the chip (its work over the
    peak, ``bench/work.py``) in its measured device time per tick."""
    ms = scope_ms(ctx, WINDOW_RULES)
    if not ms:
        return None
    least, bound = work.least_seconds(ctx.work, ctx.peak)
    ctx.log(f"window_rules: least {least * 1e6:.3f} us a tick, bound by "
            f"{bound}; measured {ms:.4f} ms")
    return 100.0 * least / (ms / 1e3)


def span_ms(ctx, name: str) -> float | None:
    """Mean milliseconds of the program's host span ``name``."""
    d = [t1 - t0 for n, t0, t1, *_ in ctx.spans if n == name]
    return 1e3 * sum(d) / len(d) if d else None
