"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: per device, the busy intervals of its operations, the
device time under each ``obs:*`` named scope, and the idle gaps, each
labelled by the innermost host span open at that moment.

Device planes are the ``/device:TPU:<n>`` planes; their operations are
the events of the ``XLA Ops`` line, each named by the HLO instruction it
ran (``%fusion.12 = ...``).  An operation inside a control-flow op (the
body of a ``while``) shows as an event nested in that op's event, so
device time per scope counts each op's self time: its duration less the
events nested in it.  An operation belongs to the innermost
``obs:<stage>`` scope of its instruction's ``op_name`` metadata in the
compiled module's text (where XLA keeps the ``jax.named_scope`` path),
else to ``(none)``.  Host spans are the ``TraceAnnotation`` events of the
host plane whose names the caller lists (the harness's ``bench.*`` spans
and the program's ``Tracer`` spans).
"""
from __future__ import annotations

import dataclasses
import gzip
import re

_SCOPE = re.compile(r"obs:[A-Za-z0-9_]+")
_EVENT = re.compile(r"^%?([^\s=]+)\s*=")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=.*?op_name=\"([^\"]*)\"")
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Op:
    name: str                   # HLO instruction name
    scope: str
    start_ns: float
    end_ns: float
    self_ns: float = 0.0        # duration less the events nested in it


@dataclasses.dataclass
class Device:
    name: str
    ops: list[Op]


@dataclasses.dataclass
class Trace:
    devices: list[Device]
    host: list[tuple[str, float, float]]      # (name, start_ns, end_ns)


def scopes_of(hlo_texts) -> dict[str, str]:
    """Instruction name -> innermost ``obs:*`` scope of its ``op_name``,
    from compiled modules' text (``Compiled.as_text()``)."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                found = _SCOPE.findall(m.group(2))
                if found:
                    out[m.group(1)] = found[-1]
    return out


def nest(ops: list[Op]) -> None:
    """Set each op's self time: its duration less the time of the ops
    nested directly inside it (one device line, properly nested)."""
    stack: list[Op] = []
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        o.self_ns = o.end_ns - o.start_ns
        while stack and stack[-1].end_ns <= o.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= o.end_ns - o.start_ns
        stack.append(o)


def load(path: str, host_names, scopes: dict[str, str]) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``) into device ops and
    named host spans.  ``host_names``: a predicate on a host event's
    name; ``scopes``: instruction name -> scope (:func:`scopes_of`)."""
    from jax.profiler import ProfileData

    if str(path).endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(str(path))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    m = _EVENT.match(ev.name)
                    name = m.group(1) if m else ev.name
                    ops.append(Op(name, scopes.get(name, "(none)"),
                                  ev.start_ns, ev.start_ns + ev.duration_ns))
            nest(ops)
            devices.append(Device(plane.name, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if host_names(ev.name):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    devices.sort(key=lambda d: int(re.sub(r"\D", "", d.name) or 0))
    return Trace(devices, host)


def busy_intervals(ops: list[Op], lo: float, hi: float
                   ) -> list[tuple[float, float]]:
    """Union of the ops' intervals, clipped to [lo, hi], sorted."""
    spans = sorted((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops
                   if o.end_ns > lo and o.start_ns < hi)
    merged: list[list[float]] = []
    for a, b in spans:
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(ops: list[Op], lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(ops, lo, hi))


def _inside(o: Op, lo: float, hi: float) -> bool:
    return lo <= o.start_ns and o.end_ns <= hi


def scope_ns(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    """Device self time per innermost scope, of the ops inside
    [lo, hi]."""
    out: dict[str, float] = {}
    for o in ops:
        if _inside(o, lo, hi):
            out[o.scope] = out.get(o.scope, 0.0) + o.self_ns
    return out


def op_ns(ops: list[Op], lo: float, hi: float) -> dict[str, float]:
    """Device self time per ``scope/op`` name, of the ops inside
    [lo, hi]."""
    out: dict[str, float] = {}
    for o in ops:
        if _inside(o, lo, hi):
            key = f"{o.scope}/{o.name}"
            out[key] = out.get(key, 0.0) + o.self_ns
    return out


def label_at(host: list[tuple[str, float, float]], t: float) -> str:
    """Innermost (shortest) host span open at time t, or ``(no span)``."""
    best, width = "(no span)", float("inf")
    for name, a, b in host:
        if a <= t < b and b - a < width:
            best, width = name, b - a
    return best


def idle_gaps(ops: list[Op], host, lo: float, hi: float
              ) -> list[tuple[str, float]]:
    """Idle gaps of one device inside [lo, hi]: (host label, ns), each
    labelled at its midpoint."""
    gaps, t = [], lo
    for a, b in busy_intervals(ops, lo, hi) + [(hi, hi)]:
        if a > t:
            gaps.append((label_at(host, (a + t) / 2), a - t))
        t = max(t, b)
    return gaps
