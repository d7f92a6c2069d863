"""Bucketed latency histogram carried *inside* the traced step.

Host-side percentile tracking (a python list of floats) can't ride a
donated jit step, and pulling every step's wall time to a host list
costs a sync per tick.  Instead the executors keep latency as an
**on-device bucketed histogram**: a fixed-shape int32 counts array
passed through the step as a donated operand, bucket-incremented by
the *previous* step's measured wall time (an f32 scalar operand).
Shapes never change, so instrumentation adds **zero** recompiles and
every existing trace-count bound survives; percentiles are extracted
host-side on demand (one transfer for the whole histogram).

Buckets are log-spaced (``DEFAULT_EDGES``: 1 µs .. 100 s, ~17% ratio
per bucket), so a reported percentile is exact to within one bucket
ratio — ample for p50/p95/p99 step-latency reporting, and the
resolution is a static constant, not data.

The same machinery carries the **event-time latency lineage**: every
micro-batch row is stamped with its ingest wall time (relative to the
executor's epoch, an f32 column in the ring row), and each tick
bucket-increments one histogram row per :data:`LINEAGE_STAGES` stage —
queueing delay, window residency, the two escalation hops, and
end-to-end — via :func:`histogram_update_batch` (a mask-validated
compare-and-reduce with no loop and no scatter: fixed shapes, donated
operand, zero added recompiles).  Latencies are quantized to the tick: every stage a
record passes inside one tick shares the tick's dispatch timestamp, so
sub-tick stage latencies land in bucket 0 ("< 1 tick") and the
distribution's signal is cross-tick residency — ring backpressure,
carry accumulation, stalls — which is exactly what an SLO watches.
Sub-tick decomposition is the cost model's job (``obs.costmodel``
attributes FLOPs/bytes to the named-scope stages of one tick).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

#: Log-spaced bucket upper edges in seconds: 1 µs .. 100 s, 121 edges
#: (122 buckets with the overflow bucket), ratio 10^(8/120) ~= 1.166.
DEFAULT_EDGES = np.logspace(-6.0, 2.0, 121)

#: Event-time lineage stages, in hot-path order.  ``queueing`` = ring
#: admission -> dequeue (per row); ``window`` = ring admission of a
#: window's *oldest* sample -> window emission (per emitted window);
#: ``hop1`` = admission -> fog-column receive (per escalation survivor,
#: measured on the receiving fog column); ``hop2`` = admission -> core
#: rank receive (per record crossing the region axis, measured at the
#: core); ``e2e`` = admission -> commit (per committed window — equals
#: ``window`` whenever the whole exchange completes inside the tick,
#: and diverges once execution overlaps ticks).  "Admission" here is
#: *post*-admission-lane: the ingest stamp is written at ring enqueue,
#: so rows the lane drops (dedupe, contract) never enter the lineage —
#: the queueing stage measures accepted-row residency, and rejected or
#: deduped traffic shows in the counters/EventLog instead.
LINEAGE_STAGES = ("queueing", "window", "hop1", "hop2", "e2e")


def histogram_init(edges: np.ndarray = DEFAULT_EDGES) -> jnp.ndarray:
    """Zeroed counts: one bucket per edge plus the overflow bucket."""
    return jnp.zeros((len(edges) + 1,), jnp.int32)


def bucket_counts(values, mask, edges: np.ndarray = DEFAULT_EDGES
                  ) -> jnp.ndarray:
    """Per-bucket counts ``[len(edges) + 1]`` int32 of the masked-in
    ``values`` (traced; any shape, flattened).  A value lands in the
    bucket ``searchsorted(edges, value, side="left")`` names: bucket
    ``i`` holds ``(edges[i-1], edges[i]]`` and the last bucket what lies
    above ``edges[-1]`` (NaN included).

    Loop- and scatter-free: one fused compare-and-reduce gives, per
    edge, how many masked-in values lie at or below it (the cumulative
    counts), and their differences are the buckets — where a
    ``searchsorted`` lowers to a ``while`` over every value and the
    increment to a scatter."""
    e = jnp.asarray(edges, jnp.float32)[:, None]
    # [edges, values]: the values run along the lanes, so each edge's
    # count is a lane-dense compare and a reduce along the minor axis
    v = jnp.reshape(jnp.asarray(values, jnp.float32), (1, -1))
    m = jnp.reshape(jnp.asarray(mask, bool), (1, -1))
    at_or_below = jnp.sum((m & (v <= e)).astype(jnp.int32), axis=1)
    total = jnp.sum(m.astype(jnp.int32))[None]
    cum = jnp.concatenate([at_or_below, total])
    return cum - jnp.concatenate([jnp.zeros((1,), jnp.int32), cum[:-1]])


def histogram_update(counts: jnp.ndarray, value,
                     edges: np.ndarray = DEFAULT_EDGES) -> jnp.ndarray:
    """Bucket-increment ``counts`` with one sample (traced; fixed
    shape).  Non-positive values are *skipped*, not bucketed — the
    executors feed the previous step's wall time, which is 0.0 before
    the first step (a missing measurement, not a fast step)."""
    value = jnp.asarray(value, jnp.float32)
    return counts + bucket_counts(value, value > 0.0,
                                  edges).astype(counts.dtype)


def histogram_update_batch(counts: jnp.ndarray, values, mask,
                           edges: np.ndarray = DEFAULT_EDGES
                           ) -> jnp.ndarray:
    """Bucket-increment ``counts`` with a batch of samples (traced;
    fixed shape): ``values`` [N] f32 seconds, ``mask`` [N] bool.

    Validity is the *explicit mask*, not positivity: a zero latency is
    a real measurement here (a record that entered and left inside one
    tick), so masked-in values are clamped up to the first bucket —
    same-tick samples count in bucket 0 ("<= 1 µs", i.e. "< 1 tick" at
    the lineage's tick-quantized resolution) instead of vanishing."""
    v = jnp.maximum(jnp.asarray(values, jnp.float32),
                    jnp.float32(edges[0] * 0.5))
    return counts + bucket_counts(v, mask, edges).astype(counts.dtype)


def histogram_merge(a, b):
    """Merge two histograms (or stacks of histograms) by summing
    counts.  Works on numpy and jnp alike; associative and commutative,
    and pooling per-shard histograms this way equals having bucketed
    every sample into one histogram — the property tests pin all
    three."""
    if isinstance(a, jnp.ndarray) or isinstance(b, jnp.ndarray):
        return jnp.asarray(a) + jnp.asarray(b)
    return np.asarray(a) + np.asarray(b)


def lineage_init(edges: np.ndarray = DEFAULT_EDGES) -> jnp.ndarray:
    """Zeroed per-stage lineage bank: ``[len(LINEAGE_STAGES), buckets]``
    int32 — one histogram row per stage, carried through the traced
    step as a single donated operand."""
    return jnp.zeros((len(LINEAGE_STAGES), len(edges) + 1), jnp.int32)


def lineage_update(bank: jnp.ndarray, samples: dict,
                   edges: np.ndarray = DEFAULT_EDGES) -> jnp.ndarray:
    """Batch-update stage rows of a lineage bank (traced).  ``samples``
    maps stage names (:data:`LINEAGE_STAGES`) to ``(values, mask)``
    pairs; stages absent this tick keep their counts unchanged.  A key
    may be a tuple of stage names whose rows take one measurement:
    it is bucketed once and added to each."""
    zero = jnp.zeros(bank.shape[-1:], bank.dtype)
    rows = {}
    for key, (values, mask) in samples.items():
        names = key if isinstance(key, tuple) else (key,)
        unknown = [n for n in names if n not in LINEAGE_STAGES]
        if unknown:
            raise ValueError(f"unknown lineage stages {unknown}; known: "
                             f"{LINEAGE_STAGES}")
        rows.update(dict.fromkeys(
            names, histogram_update_batch(zero, values, mask, edges)))
    return bank + jnp.stack([rows.get(n, zero) for n in LINEAGE_STAGES])


def lineage_percentiles(bank, qs=(50, 95, 99),
                        edges: np.ndarray = DEFAULT_EDGES) -> dict:
    """Host-side per-stage percentiles of a lineage bank.  ``bank`` is
    ``[..., n_stages, buckets]`` — leading axes (per-shard rows) are
    pooled by summation (:func:`histogram_merge` semantics)."""
    c = np.asarray(bank, np.int64)
    c = c.reshape(-1, c.shape[-2], c.shape[-1]).sum(axis=0)
    return {name: histogram_percentiles(c[i], qs, edges)
            for i, name in enumerate(LINEAGE_STAGES)}


def histogram_percentiles(counts, qs=(50, 95, 99),
                          edges: np.ndarray = DEFAULT_EDGES) -> dict:
    """Host-side percentile extraction: ``{"count": n, "p50_us": ...}``
    (microseconds).  A percentile is the upper edge of the bucket where
    the CDF crosses it (conservative: never under-reports; exact to one
    bucket ratio).  All-empty histograms report 0.0s."""
    c = np.asarray(counts, np.int64)
    total = int(c.sum())
    out = {"count": total}
    if total == 0:
        for q in qs:
            out[f"p{q}_us"] = 0.0
        return out
    cdf = np.cumsum(c)
    # value for bucket i is edges[i] (its upper edge); the overflow
    # bucket clamps to the last edge — off-scale-high, still monotone
    uppers = np.append(edges, edges[-1])
    for q in qs:
        idx = int(np.searchsorted(cdf, q / 100.0 * total))
        out[f"p{q}_us"] = float(uppers[min(idx, len(uppers) - 1)] * 1e6)
    return out
