"""Fleet-wide observability: tracing, events, latency lineage, SLOs,
cost accounting, exporters.

The measurement substrate the perf roadmap is judged against — six
pieces, each usable alone:

* ``obs.trace`` — host-side span tracer (span tree per tick, wall and
  thread CPU time; Chrome-trace/Perfetto export) with JAX profiler
  hooks (``TraceAnnotation``/``StepTraceAnnotation``) so host phases
  and device stages line up on one timeline (PERF.md, section 3, lists
  the spans and the ``named_scope`` stages).
* ``obs.events`` — structured JSONL event log for the control plane:
  every decision (budget resize, health change, leave/join, remesh,
  backup replay, drains, SLO breach/recover) as one typed record with
  tick, wall time, shard, and cause, so an incident can be
  reconstructed post-hoc.
* ``obs.latency`` — bucketed latency histograms maintained *inside* the
  traced step (fixed-shape operands: no recompiles, trace-count bounds
  preserved): the step-latency histogram AND the per-stage event-time
  **lineage** banks (queueing / window residency / exchange hops /
  end-to-end), with host-side percentile extraction.
* ``obs.slo`` — declared latency/drop targets with multi-window
  burn-rate evaluation over the lineage banks; breach/recover
  transitions feed the event log and the control plane's policy signal.
* ``obs.costmodel`` — XLA HLO cost analysis of the traced tick
  (FLOPs/bytes, per-``named_scope``-stage attribution) + roofline
  utilization against declared machine peaks.
* ``obs.export`` — stable-schema snapshots of ``StreamMetrics`` /
  ``FleetMetrics`` + latency/lineage percentiles + per-stage timings,
  and the ``BENCH_<suite>.json`` artifact writer behind
  ``benchmarks/run.py --json``.
"""
from repro.obs.costmodel import (  # noqa: F401
    analyze,
    roofline,
    stage_table,
)
from repro.obs.events import EVENT_KINDS, EventLog  # noqa: F401
from repro.obs.export import (  # noqa: F401
    BENCH_SCHEMA_VERSION,
    bench_payload,
    metrics_snapshot,
    parse_derived,
    write_bench,
)
from repro.obs.latency import (  # noqa: F401
    DEFAULT_EDGES,
    LINEAGE_STAGES,
    histogram_init,
    histogram_merge,
    histogram_percentiles,
    histogram_update,
    histogram_update_batch,
    lineage_init,
    lineage_percentiles,
    lineage_update,
)
from repro.obs.slo import SLO, SloEvaluator, SloStatus  # noqa: F401
from repro.obs.trace import NULL_TRACER, Span, Tracer  # noqa: F401
