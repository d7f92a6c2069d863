"""offcpu_ms.<mix>: host milliseconds per tick that the dispatching
thread spent off the CPU inside the program's dispatch span
(stream.dispatch on one chip, fleet.dispatch on the fleet): the span's
wall time less the thread's CPU time in it, i.e. waiting for the GIL, a
lock or a blocking copy.  None where the spans carry no CPU time."""


def _offcpu_ms(ctx, name):
    d = [sp[2] - sp[1] - (sp.cpu1 - sp.cpu0) for sp in ctx.spans
         if sp[0] == name and hasattr(sp, "cpu1")]
    return 1e3 * sum(d) / len(d) if d else None


def read(ctx):
    ms = _offcpu_ms(ctx, "stream.dispatch")
    return ms if ms is not None else _offcpu_ms(ctx, "fleet.dispatch")
