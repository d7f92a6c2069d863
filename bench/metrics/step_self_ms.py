"""step_self_ms.<mix>: host milliseconds per tick inside the program's
step span (stream.step on one chip, fleet.step on the fleet) and in none
of its child spans, found by their parent ids: the step's own
bookkeeping, and on the fleet the argument checks and the default
``offered`` fill ahead of the dispatch.  None where the spans carry no
ids."""


def _self_ms(ctx, name):
    steps = {sp.id: sp for sp in ctx.spans
             if sp[0] == name and hasattr(sp, "parent")}
    inner = dict.fromkeys(steps, 0.0)
    for sp in ctx.spans:
        if getattr(sp, "parent", None) in inner:
            inner[sp.parent] += sp[2] - sp[1]
    d = [sp[2] - sp[1] - inner[i] for i, sp in steps.items()]
    return 1e3 * sum(d) / len(d) if d else None


def read(ctx):
    ms = _self_ms(ctx, "stream.step")
    return ms if ms is not None else _self_ms(ctx, "fleet.step")
