"""Host-side span tracer with JAX profiler hooks and Chrome-trace export.

``Tracer`` records lightweight spans around the host phases of a stream
tick (dispatch -> device execute -> control).  Each span keeps its wall
times on the ``perf_counter`` clock, the thread's CPU seconds
(``time.thread_time``) at entry and exit, and its parent: the innermost
span open on the same thread when it started.  So a tick is a span tree
(``fleet.step`` > ``fleet.dispatch`` > ``fleet.operands``, ...), and a
span's wall time less its CPU time is the time its thread spent off the
CPU: waiting for the GIL, a lock or a blocking copy.

Each span doubles as a ``jax.profiler.TraceAnnotation``, so when a JAX
profiler capture is live (``with tracer.profile(logdir)``) the same
spans appear on the host timeline of the XLA trace, next to the device
ops, which carry their own stage names via ``jax.named_scope`` (the
layer table of PERF.md, section 3, lists them).

Two export paths:

* :meth:`Tracer.export_chrome_trace` — self-contained Chrome trace
  JSON (open in ``chrome://tracing`` or https://ui.perfetto.dev) from
  the host spans alone; zero dependencies, works headless.  After a
  :meth:`Tracer.profile` capture its timestamps are on the capture's
  clock (microseconds since the capture started), so the file overlays
  the device trace; CPU time rides in the format's ``tts``/``tdur``.
* :meth:`Tracer.profile` — wraps ``jax.profiler.trace``: the full XLA
  profile (device ops + these host annotations) lands in ``logdir`` as
  a TensorBoard/Perfetto trace.

Overhead discipline: a disabled tracer (``NULL_TRACER``) hands back a
pre-built null context per span and reads no clock — safe to leave in
the hot path; an enabled tracer costs four clock reads and one list
append per span.  Nothing here touches traced code: instrumentation
adds **zero** recompiles (the executor tests assert their trace bounds
with tracing on).
"""
from __future__ import annotations

import contextlib
import glob
import itertools
import json
import os
import threading
import time
from typing import NamedTuple

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.profiler import trace as _jax_trace

_NULL_CTX = contextlib.nullcontext()


class Span(NamedTuple):
    """One closed span.  The first five fields are the original record
    (seconds on the ``perf_counter`` clock); ``id`` numbers spans in the
    order they opened, ``parent`` is the ``id`` of the innermost span
    open on the same thread at entry (None at the root), and
    ``cpu0``/``cpu1`` are the thread's CPU seconds at entry and exit."""
    name: str
    t0: float
    t1: float
    tid: int
    args: dict
    id: int
    parent: int | None
    cpu0: float
    cpu1: float


class Tracer:
    """Accumulates named host spans; thread-safe appends.

    ``args`` ride along into the trace viewer's detail pane and into
    :meth:`stage_percentiles` grouping.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._spans: list[Span] = []
        self._lock = threading.Lock()
        self._open = threading.local()       # per-thread stack of open ids
        self._ids = itertools.count()
        # export origin on the perf_counter clock: tracer creation, or
        # the start of the last profile() capture
        self._origin = time.perf_counter()

    # -- recording ---------------------------------------------------------
    @contextlib.contextmanager
    def _span(self, name: str, args: dict):
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        sid = next(self._ids)
        parent = stack[-1] if stack else None
        stack.append(sid)
        # the wall clock is read right inside the annotation, so the
        # span and its mirror in a profiler capture start together; the
        # CPU clock (a system call, where the thread may be switched
        # out) is read outside both
        c0 = time.thread_time()
        annotation = TraceAnnotation(name)
        annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            t1 = time.perf_counter()
            annotation.__exit__(None, None, None)
            c1 = time.thread_time()
            stack.pop()
            with self._lock:
                self._spans.append(Span(name, t0, t1, threading.get_ident(),
                                        args, sid, parent, c0, c1))

    def span(self, name: str, **args):
        """Context manager: record ``name`` around the enclosed block
        (and mirror it into a live JAX profiler capture)."""
        if not self.enabled:
            return _NULL_CTX
        return self._span(name, args)

    def step_annotation(self, name: str, step_num: int):
        """``jax.profiler.StepTraceAnnotation`` for one tick: groups
        the tick's device ops under a step marker in the trace viewer
        (the profiler's per-step breakdown needs it)."""
        if not self.enabled:
            return _NULL_CTX
        return StepTraceAnnotation(name, step_num=step_num)

    def profile(self, logdir: str):
        """Capture a full XLA profile (device ops + host annotations)
        to ``logdir`` while the context is open.  View with
        TensorBoard's profile plugin or https://ui.perfetto.dev.
        Afterwards the Chrome export is on this capture's clock."""
        if not self.enabled:
            return _NULL_CTX
        return self._profile(logdir)

    @contextlib.contextmanager
    def _profile(self, logdir: str):
        before = set(_xplanes(logdir))
        unix_start = time.time_ns()
        with _jax_trace(logdir):
            unix_minus_perf = _unix_minus_perf()
            yield
        new = sorted(set(_xplanes(logdir)) - before, key=os.path.getmtime)
        if new:
            unix_start = _capture_start_ns(new[-1]) or unix_start
        self._origin = unix_start / 1e9 - unix_minus_perf

    def clear(self) -> None:
        with self._lock:
            self._spans = []

    # -- reading -----------------------------------------------------------
    @property
    def spans(self) -> list[Span]:
        """Closed spans in the order they closed (a child before its
        parent): :class:`Span` tuples whose first five fields are
        (name, t_start, t_end, thread_id, args)."""
        with self._lock:
            return list(self._spans)

    def stage_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Per-span-name duration percentiles (microseconds):
        ``{name: {count, mean_us, total_us, p50_us, p95_us, p99_us}}``
        — the host-side per-stage latency breakdown."""
        by_name: dict[str, list[float]] = {}
        for sp in self.spans:
            by_name.setdefault(sp.name, []).append((sp.t1 - sp.t0) * 1e6)
        out = {}
        for name, durs in sorted(by_name.items()):
            d = np.asarray(durs)
            stats = {"count": int(d.size),
                     "mean_us": float(d.mean()),
                     "total_us": float(d.sum())}
            for q in qs:
                stats[f"p{q}_us"] = float(np.percentile(d, q))
            out[name] = stats
        return out

    # -- export ------------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Chrome trace JSON object (``traceEvents`` complete events):
        ``ts``/``dur`` in microseconds since tracer creation, or since
        the start of the last :meth:`profile` capture, on that capture's
        clock; ``tts``/``tdur`` the thread's CPU microseconds."""
        events = []
        for sp in self.spans:
            events.append({
                "name": sp.name, "ph": "X", "pid": 1, "tid": sp.tid,
                "ts": (sp.t0 - self._origin) * 1e6,
                "dur": (sp.t1 - sp.t0) * 1e6,
                "tts": sp.cpu0 * 1e6,
                "tdur": (sp.cpu1 - sp.cpu0) * 1e6,
                "args": {k: _plain(v) for k, v in sp.args.items()},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Write :meth:`to_chrome_trace` to ``path``; returns ``path``.
        Open in ``chrome://tracing`` or https://ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


def _unix_minus_perf() -> float:
    """Unix seconds (the profiler's clock) less ``perf_counter``
    seconds."""
    return time.time_ns() / 1e9 - time.perf_counter()


def _xplanes(logdir: str) -> list[str]:
    return glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)


def _capture_start_ns(path: str) -> int | None:
    """Unix nanoseconds at which the capture in ``path`` started: the
    origin of its event times (``profile_start_time``)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key == "profile_start_time":
                return int(value)
    return None


def _plain(v):
    """JSON-safe span arg (numpy scalars -> python scalars)."""
    if isinstance(v, (np.generic,)):
        return v.item()
    if isinstance(v, np.ndarray):
        return v.tolist()
    return v


#: Shared disabled tracer: the executors' default — every hook on it is
#: a pre-built null context, so uninstrumented runs pay ~nothing.
NULL_TRACER = Tracer(enabled=False)
