"""Host-side span tracer with JAX profiler hooks and Chrome-trace export.

``Tracer`` records lightweight wall-clock spans around the host phases
of a stream tick (inject -> dispatch -> device execute -> control ->
drain).  Each span doubles as a ``jax.profiler.TraceAnnotation``, so
when a JAX profiler capture is live (``with tracer.profile(logdir)``)
the same spans appear on the host timeline of the XLA trace viewer —
host/device overlap and the dispatch-vs-execute split become *visible*
next to the device ops, which carry their own stage names via
``jax.named_scope`` (see ``stream.executor``/``stream.fleet``).

Two export paths:

* :meth:`Tracer.export_chrome_trace` — self-contained Chrome trace
  JSON (open in ``chrome://tracing`` or https://ui.perfetto.dev) from
  the host spans alone; zero dependencies, works headless.
* :meth:`Tracer.profile` — wraps ``jax.profiler.trace``: the full XLA
  profile (device ops + these host annotations) lands in ``logdir`` as
  a TensorBoard/Perfetto trace.

Overhead discipline: a disabled tracer (``NULL_TRACER``) costs one
attribute lookup and a pre-built null context per span — safe to leave
in the hot path; an enabled tracer costs two clock reads and one list
append per span.  Nothing here touches traced code: instrumentation
adds **zero** recompiles (the fleet tests assert their trace bounds
with tracing on).
"""
from __future__ import annotations

import contextlib
import json
import threading
import time

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.profiler import trace as _jax_trace

_NULL_CTX = contextlib.nullcontext()

#: Canonical ``jax.named_scope`` stage labels of the traced tick, in
#: hot-path order (single-device prefix, then the fleet-only stages).
#: ``obs.costmodel`` attributes HLO ops to these by compiled-metadata
#: ``op_name`` substring match; keep in sync with the executors.
DEVICE_STAGES = (
    "obs:ingest", "obs:watermark", "obs:window", "obs:lineage",
    "obs:rules", "obs:pipeline", "obs:metrics",
    "obs:fleet_watermark", "obs:edge_stages", "obs:exchange_core",
    "obs:all_to_all_out", "obs:fog_compact", "obs:all_to_all_region",
    "obs:core_compute", "obs:all_to_all_back", "obs:core_commit",
    "obs:latency",
)


class Tracer:
    """Accumulates named host spans; thread-safe appends.

    Spans nest naturally in Chrome trace rendering (same thread id,
    containing timestamps).  ``args`` ride along into the trace
    viewer's detail pane and into :meth:`stage_percentiles` grouping.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._spans: list[tuple[str, float, float, int, dict]] = []
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()

    # -- recording ---------------------------------------------------------
    @contextlib.contextmanager
    def _span(self, name: str, args: dict):
        t0 = time.perf_counter()
        try:
            with TraceAnnotation(name):
                yield self
        finally:
            t1 = time.perf_counter()
            with self._lock:
                self._spans.append((name, t0, t1,
                                    threading.get_ident(), args))

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
        TensorBoard's profile plugin or https://ui.perfetto.dev."""
        if not self.enabled:
            return _NULL_CTX
        return _jax_trace(logdir)

    def clear(self) -> None:
        with self._lock:
            self._spans = []

    # -- reading -----------------------------------------------------------
    @property
    def spans(self) -> list:
        """(name, t_start, t_end, thread_id, args) tuples, seconds on
        the ``perf_counter`` clock."""
        with self._lock:
            return list(self._spans)

    def stage_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Per-span-name duration percentiles (microseconds):
        ``{name: {count, mean_us, total_us, p50_us, p95_us, p99_us}}``
        — the host-side per-stage latency breakdown."""
        by_name: dict[str, list[float]] = {}
        for name, t0, t1, _, _ in self.spans:
            by_name.setdefault(name, []).append((t1 - t0) * 1e6)
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
        """Chrome trace JSON object (``traceEvents`` complete events,
        microsecond timestamps relative to tracer creation)."""
        events = []
        for name, t0, t1, tid, args in self.spans:
            events.append({
                "name": name, "ph": "X", "pid": 1, "tid": tid,
                "ts": (t0 - self._t0) * 1e6,
                "dur": (t1 - t0) * 1e6,
                "args": {k: _plain(v) for k, v in args.items()},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> str:
        """Write :meth:`to_chrome_trace` to ``path``; returns ``path``.
        Open in ``chrome://tracing`` or https://ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


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
