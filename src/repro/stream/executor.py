"""Continuous micro-batch stream executor (ingest -> windows -> rules
-> pipeline).

This is the paper's edge analytics loop made concrete: producers post
sensor tuples into the memory-mapped queue (``data.ringbuffer``), the
edge RP consumes them in fixed-size micro-batches, computes windowed
aggregates (``stream.windows``), evaluates the data-driven IF-THEN
rules on the per-window features (``core.rules``), and pushes the
window records through a ``DataDrivenPipeline`` whose rule-gated core
stage is capacity-bounded — only flagged windows consume core compute.

Everything per step is one fixed-shape pure function, so the whole loop
compiles to **exactly one** XLA executable: after the first (warmup)
step there is no retracing, no recompilation, no host round-trip except
the producer handoff.  ``StreamExecutor.trace_count`` exposes the jit
cache size so benchmarks/tests can assert that.

Cross-batch window continuity: the executor carries the trailing
``window - stride`` samples between steps, so every step emits exactly
``micro_batch // stride`` *complete* windows and consecutive steps tile
the stream with no gap and no double-count (requires ``micro_batch %
stride == 0``).  The first windows of a run are partially masked (the
carry starts invalid) — their ``count`` reflects it.

Backpressure accounting mirrors the queue contract: items the ring
rejects are counted, never silently dropped; flagged windows beyond the
pipeline's ``core_capacity`` are counted as ``core_overflow`` (they
keep their edge results — the paper's graceful-degradation trade).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import rules as R
from repro.core.pipeline import DataDrivenPipeline
from repro.data import ringbuffer as rbuf
from repro.obs import costmodel as OC
from repro.obs import latency as OL
from repro.obs.trace import NULL_TRACER
from repro.stream import ingest as I
from repro.stream import windows as W


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static shape/policy knobs; all fields participate in the single
    jit trace, so changing any of them means a (single) recompile."""
    micro_batch: int               # samples dequeued per step (B)
    window: int                    # samples per window (W)
    stride: int                    # window start spacing (S), S <= W
    capacity: int = 4096           # ring-buffer capacity (items)
    lateness: float = 0.0          # watermark slack (event-time units)
    min_count: int = 1             # valid samples for a window to fire
    backend: str = "jnp"           # "jnp" | "pallas" window reduction
    interpret: bool = False        # Pallas interpret mode (CPU tests)
    fused: bool = False            # fused window+features+rules tick
    overlap_ingest: bool = False   # stage tick N+1 during tick N (run())
    ingest_int8: bool = False      # int8-quantize staged telemetry (lossy)
    admission: I.AdmissionPlan = I.AdmissionPlan()   # dedupe + contract lane

    def __post_init__(self):
        if not (0 < self.stride <= self.window):
            raise ValueError(f"need 0 < stride <= window, got {self}")
        if self.micro_batch % self.stride or self.micro_batch < self.stride:
            raise ValueError("micro_batch must be a positive multiple of "
                             f"stride, got {self}")
        if self.capacity < self.micro_batch:
            raise ValueError("capacity must hold one micro-batch")
        if self.ingest_int8 and not self.overlap_ingest:
            raise ValueError("ingest_int8 rides the overlapped ingest "
                             "stager: set overlap_ingest=True too")

    @property
    def windows_per_step(self) -> int:
        return self.micro_batch // self.stride

    @property
    def carry_len(self) -> int:
        return self.window - self.stride


class StreamMetrics(NamedTuple):
    """Monotone int32 counters, updated on-device every step."""
    steps: jnp.ndarray
    items_offered: jnp.ndarray     # producer -> enqueue attempts
    items_accepted: jnp.ndarray    # made it into the ring
    items_rejected: jnp.ndarray    # backpressure (ring full)
    items_dequeued: jnp.ndarray    # consumed by the executor
    items_late: jnp.ndarray        # dropped by the watermark
    items_replayed: jnp.ndarray    # backup-replay records (lateness-exempt)
    items_deduped: jnp.ndarray     # offered rows dropped as re-deliveries
    items_backfilled: jnp.ndarray  # backfill-mode records (lateness-exempt)
    windows_emitted: jnp.ndarray   # windows with >= min_count samples
    rules_fired: jnp.ndarray       # windows with consequence != NONE
    windows_escalated: jnp.ndarray # sent to the core tier
    windows_stored: jnp.ndarray    # store-at-edge consequence
    windows_dropped: jnp.ndarray   # quality-dropped
    core_overflow: jnp.ndarray     # flagged beyond core_capacity
    drift_counts: jnp.ndarray      # [D] per-field contract violations

    def as_dict(self) -> dict[str, int | list[int]]:
        """Host-side snapshot: one ``jax.device_get`` for the whole
        tuple (a single transfer, not one sync per counter), plain
        ints.  Array counters (per-shard [E] views, the per-field
        ``drift_counts``) come back as lists of ints."""
        host = jax.device_get(self)
        return {k: v.tolist() if getattr(v, "ndim", 0) else int(v)
                for k, v in zip(self._fields, host)}


def _zero_metrics(feature_dim: int) -> StreamMetrics:
    # distinct buffers per counter: the step donates its state, and XLA
    # rejects donating one aliased buffer through several arguments
    return StreamMetrics(*(jnp.zeros((), jnp.int32)
                           for _ in StreamMetrics._fields[:-1]),
                         drift_counts=jnp.zeros((feature_dim,), jnp.int32))


#: Ring rows are [ts | ingest_wall | features]: ``META_COLS`` leading
#: metadata columns before the D feature columns.  Column 0 is the
#: event timestamp; column 1 the *ingest wall time* (seconds since the
#: executor's epoch, f32) stamped at enqueue — the birth stamp the
#: event-time latency lineage measures every stage against.
META_COLS = 2


class StreamState(NamedTuple):
    rb: rbuf.RingBuffer            # [cap, META_COLS+D] rows (see above)
    carry: jnp.ndarray             # [W-S, META_COLS+D] trailing samples
    carry_valid: jnp.ndarray       # [W-S] bool
    max_ts: jnp.ndarray            # [] f32 running max event time
    metrics: StreamMetrics
    adm: I.AdmissionState          # dedupe-window ring ([0] when inert)


class StepOutput(NamedTuple):
    aggregates: jnp.ndarray        # [NW, D] mean window aggregate
    features: jnp.ndarray          # [NW, 5] rule features (signal col)
    window_count: jnp.ndarray      # [NW] valid samples per window
    consequence: jnp.ndarray       # [NW] rule consequence codes
    escalated: jnp.ndarray         # [NW] bool reached the core tier
    outputs: jnp.ndarray           # [NW, ...] pipeline outputs


class IngestResult(NamedTuple):
    """Front half of a stream step (ingest -> watermark -> windows ->
    rules), shared verbatim by the single-device and fleet executors so
    a fleet shard is *provably* the same machine as a lone device up to
    the escalation boundary."""
    rb: rbuf.RingBuffer
    carry: jnp.ndarray
    carry_valid: jnp.ndarray
    max_ts: jnp.ndarray
    aggregates: jnp.ndarray        # [NW, D]
    window_count: jnp.ndarray      # [NW]
    features: jnp.ndarray          # [NW, 5]
    consequence: jnp.ndarray       # [NW] engine codes (emit-masked)
    emit: jnp.ndarray              # [NW] bool count >= min_count
    record: jnp.ndarray            # [NW, 5 + D] features ++ aggregate
    n_in: jnp.ndarray
    n_accepted: jnp.ndarray
    n_dequeued: jnp.ndarray
    n_late: jnp.ndarray
    n_late_excluded: jnp.ndarray   # admitted, but late vs the fleet ref
    n_replayed: jnp.ndarray        # replay-mode records (never late-dropped)
    n_deduped: jnp.ndarray         # offered rows dropped by the dedupe window
    n_backfilled: jnp.ndarray      # backfill-mode records (never late-dropped)
    drift: jnp.ndarray             # [D] per-field contract violations
    adm: I.AdmissionState          # rotated dedupe window (post-record)
    q_lat: jnp.ndarray             # [B] f32 queueing delay per dequeued row
    q_mask: jnp.ndarray            # [B] bool which rows were dequeued
    w_birth: jnp.ndarray           # [NW] f32 oldest ingest stamp per window


def ingest_and_window(cfg: StreamConfig, engine: R.RuleEngine,
                      state: StreamState, items: jnp.ndarray,
                      ts: jnp.ndarray,
                      watermark_ts: jnp.ndarray | None = None,
                      offer_mask: jnp.ndarray | None = None,
                      excluded_ref: jnp.ndarray | None = None,
                      replay: jnp.ndarray | None = None,
                      mode: jnp.ndarray | None = None,
                      now: jnp.ndarray | float = 0.0
                      ) -> IngestResult:
    """enqueue -> dequeue -> watermark -> carry-continuous windows ->
    rule features, as one fixed-shape pure function.

    ``watermark_ts``: reference max event time for the late test.
    Defaults to this stream's own ``state.max_ts``; a fleet passes the
    *fleet-wide minimum* of per-shard maxima so lagging shards hold
    back window close everywhere.  The shard's own running max still
    only ever advances (a laggy fleet watermark never rolls it back).

    ``offer_mask``: optional [N] bool — which producer slots hold real
    items this tick (a stalled uplink offers nothing; shapes stay
    fixed).  ``excluded_ref``: optional fleet watermark reference used
    only for *accounting*: items admitted by ``watermark_ts`` but late
    by ``excluded_ref`` are counted in ``n_late_excluded`` — the
    catch-up records of a straggler-excluded shard, processed locally
    and flagged, never silently dropped.

    ``replay``: optional [] bool (a traced operand): this tick's
    *offered batch* is backup-replay traffic — another shard's
    buffered micro-batches re-executed here after the owner left the
    fleet.  Replayed records are exempt from the late test (they are
    old by construction; the whole point is to never drop them),
    counted in ``n_replayed`` instead of ``n_late``/
    ``n_late_excluded``, and they never advance this shard's *own*
    running max event time: a foreign stream must not perturb the
    local event-time clock, or the backup's own still-queued batches
    would arrive "late" against it.  The exemption is positional —
    the ring is FIFO, so rows the ring already held before this offer
    dequeue first and keep exact normal semantics; only the rows this
    tick's replay offer contributed are exempt.  (Replay offers do
    consume ring capacity like any offer: rows a full ring rejects
    surface in ``items_rejected``.)

    ``mode``: optional [] int32 traced operand generalizing ``replay``
    to the full ingest-mode lane (``stream.ingest``): ``MODE_LIVE``
    ticks behave exactly as before, ``MODE_REPLAY`` is the backup-
    replay semantics above, ``MODE_BACKFILL`` shares the lateness
    exemption and clock neutrality but accounts its records in
    ``n_backfilled`` — historical reprocessing as a first-class mode,
    not a churn side effect.  Passing both ``replay`` and ``mode`` is
    an error; ``replay=`` remains as the boolean shorthand.

    Before any row reaches the ring it passes the admission lane
    configured by ``cfg.admission`` (``stream.ingest.AdmissionPlan``):
    FNV event-id hashing + bounded-window idempotent dedupe
    (``kernels.dedupe_window``) and per-field contract validation,
    both as fixed-shape masked stages feeding the enqueue offer mask.
    Deduped rows surface in ``n_deduped`` (never in the ring), contract
    rejects in the per-field ``drift`` counters and the offered-minus-
    accepted backpressure accounting.  The default (inert) plan skips
    the lane statically — zero added ops, bit-for-bit the old path.

    ``now``: this tick's host wall time (seconds since the executor's
    epoch, a traced f32 scalar).  Every enqueued row is stamped with it
    (the lineage birth stamp: replayed rows get a *fresh* stamp at
    redelivery — the replay detour is accounted by the event log, not
    the lineage), and the lineage taps measure against it:

    * ``q_lat``/``q_mask`` — per dequeued row, ``now - ingest_stamp``
      (rows late-dropped by the watermark still spent that time queued,
      so the mask is *dequeued*, not *valid*);
    * ``w_birth`` — per window, the oldest valid sample's ingest stamp
      (the window-residency and end-to-end measurements' reference;
      all-invalid windows report 0 and are masked by ``emit``).
    """
    if replay is not None and mode is not None:
        raise ValueError("pass either replay= (bool shorthand) or "
                         "mode= (stream.ingest mode code), not both")
    if replay is not None:
        mode = jnp.where(jnp.asarray(replay, bool),
                         jnp.int32(I.MODE_REPLAY), jnp.int32(I.MODE_LIVE))
    n_in = items.shape[0]
    plan = cfg.admission
    held = state.rb.head - state.rb.tail       # rows queued before this offer
    now = jnp.asarray(now, jnp.float32)
    with jax.named_scope("obs:ingest"):
        rows_in = jnp.concatenate(
            [ts.astype(jnp.float32)[:, None],
             jnp.broadcast_to(now, (n_in, 1)),
             items.astype(jnp.float32)],
            axis=1)
        if offer_mask is None:
            n_offered = jnp.int32(n_in)
        else:
            n_offered = jnp.sum(offer_mask.astype(jnp.int32))
        if plan.inert:
            # statically no admission lane: the pre-existing enqueue
            # path verbatim (bit-for-bit, zero added ops)
            n_dedup = jnp.zeros((), jnp.int32)
            drift = jnp.zeros((items.shape[1],), jnp.int32)
            adm = state.adm
            if offer_mask is None:
                rb, n_acc = rbuf.enqueue(state.rb, rows_in)
            else:
                rb, n_acc = rbuf.enqueue(state.rb, rows_in, offer_mask)
        else:
            with jax.named_scope("obs:admission"):
                gate = I.admission_gate(plan, state.adm, ts, items,
                                        offer_mask)
                rb, n_acc = rbuf.enqueue(state.rb, rows_in, gate.admit)
                adm = I.admission_record(plan, state.adm, gate, n_acc)
            n_dedup = gate.n_deduped
            drift = gate.drift
        rb, rows, valid = rbuf.dequeue(rb, cfg.micro_batch)
    wm = state.max_ts if watermark_ts is None else watermark_ts
    dequeued = valid
    if mode is None:
        exempt = None
    else:
        # FIFO positional split: rows the ring held before this offer
        # dequeue first and keep exact normal semantics; only the rows
        # a replay/backfill offer contributed are lateness-exempt
        mode = jnp.asarray(mode, jnp.int32)
        reproc = mode >= I.MODE_REPLAY
        pos = jnp.arange(cfg.micro_batch, dtype=held.dtype)
        exempt = reproc & (pos >= held)
    with jax.named_scope("obs:watermark"):
        valid, n_late, max_ts = W.apply_watermark(
            rows[:, 0], valid, wm, cfg.lateness, exempt=exempt)
    max_ts = jnp.maximum(state.max_ts, max_ts)
    if mode is None:
        exempt = jnp.zeros(dequeued.shape, bool)
        n_rep = jnp.zeros((), jnp.int32)
        n_bf = jnp.zeros((), jnp.int32)
    else:
        n_ex = jnp.sum((exempt & dequeued).astype(jnp.int32))
        n_rep = jnp.where(mode == I.MODE_REPLAY, n_ex, 0)
        n_bf = jnp.where(mode == I.MODE_BACKFILL, n_ex, 0)
        # reprocessed rows never advance the local event-time clock: a
        # foreign/historical stream must not perturb it, or the host's
        # own still-queued batches would arrive "late" against it
        own_max = jnp.max(jnp.where(
            dequeued & ~exempt, rows[:, 0],
            jnp.asarray(jnp.finfo(jnp.float32).min)))
        max_ts = jnp.where(reproc,
                           jnp.maximum(state.max_ts, own_max),  # own rows
                           max_ts)                     # foreign clock apart
    if excluded_ref is None:
        n_lx = jnp.zeros((), jnp.int32)
    else:
        n_lx = jnp.sum((valid & ~exempt
                        & (rows[:, 0] < excluded_ref - cfg.lateness))
                       .astype(jnp.int32))

    # cross-batch continuity: prepend the carried W-S samples
    seq = jnp.concatenate([state.carry, rows], axis=0)
    seq_valid = jnp.concatenate([state.carry_valid, valid], axis=0)
    if cfg.fused:
        # fused tick: window reduction + rule features + lineage birth
        # + rule sweep in ONE pass over the block (the pallas backend
        # keeps it VMEM-resident — one HBM round trip instead of three
        # framings plus the rule ops; the jnp backend is the fused
        # path's traced oracle).  Bit-for-bit equal to the staged
        # scopes below — parity is pinned by tests/test_kernels.py and
        # the executor-equivalence tests.
        from repro.kernels.fused_tick import fused_tick as FT
        with jax.named_scope("obs:fused_tick"):
            agg, wcount, feats, w_birth, cons = FT(
                seq, seq_valid, cfg.window, cfg.stride,
                table=engine.table(), min_count=cfg.min_count,
                meta_cols=META_COLS, backend=cfg.backend,
                interpret=cfg.interpret)
            q_lat = now - rows[:, 1]
            emit = wcount >= cfg.min_count
    else:
        with jax.named_scope("obs:window"):
            sig = seq[:, META_COLS:]
            agg, wcount = W.sliding_window(
                sig, seq_valid, cfg.window, cfg.stride, reducer="mean",
                backend=cfg.backend, partial=False, interpret=cfg.interpret)
            feats, _ = W.window_features(sig, seq_valid, cfg.window,
                                         cfg.stride, partial=False)
        with jax.named_scope("obs:lineage"):
            # lineage taps: per-row queueing delay + per-window birth
            # stamp (oldest valid sample — the min reducer rides the
            # same window framing as the aggregate, one metadata column
            # instead of D)
            q_lat = now - rows[:, 1]
            w_birth, _ = W.sliding_window(
                seq[:, 1:2], seq_valid, cfg.window, cfg.stride,
                reducer="min", backend="jnp", partial=False)
            w_birth = w_birth[:, 0]

        with jax.named_scope("obs:rules"):
            emit = wcount >= cfg.min_count
            _, cons = engine.evaluate(feats)
            cons = jnp.where(emit, cons, R.C_NONE)
    record = jnp.concatenate([feats, agg], axis=1)         # [NW, 5 + D]
    return IngestResult(
        rb=rb,
        carry=seq[seq.shape[0] - cfg.carry_len:]
        if cfg.carry_len else seq[:0],
        carry_valid=seq_valid[seq_valid.shape[0] - cfg.carry_len:]
        if cfg.carry_len else seq_valid[:0],
        max_ts=max_ts, aggregates=agg, window_count=wcount, features=feats,
        consequence=cons, emit=emit, record=record,
        n_in=n_offered, n_accepted=n_acc,
        n_dequeued=jnp.sum(valid.astype(jnp.int32)) + n_late,
        n_late=n_late, n_late_excluded=n_lx, n_replayed=n_rep,
        n_deduped=n_dedup, n_backfilled=n_bf, drift=drift, adm=adm,
        q_lat=q_lat, q_mask=dequeued, w_birth=w_birth)


def advance_metrics(m: StreamMetrics, ing: IngestResult,
                    n_escalated: jnp.ndarray, n_stored: jnp.ndarray,
                    n_dropped: jnp.ndarray,
                    overflow: jnp.ndarray) -> StreamMetrics:
    """One step's worth of counter increments (shared fleet/single).

    Conservation per tick: ``n_in == n_accepted + rejected + deduped``
    (``items_rejected`` covers contract violations and ring
    backpressure; deduped re-deliveries are accounted apart — they are
    not an error, they are the admission lane doing its job)."""
    one = jnp.int32(1)
    return StreamMetrics(
        steps=m.steps + one,
        items_offered=m.items_offered + ing.n_in,
        items_accepted=m.items_accepted + ing.n_accepted,
        items_rejected=m.items_rejected
        + (ing.n_in - ing.n_accepted - ing.n_deduped),
        items_dequeued=m.items_dequeued + ing.n_dequeued,
        items_late=m.items_late + ing.n_late,
        items_replayed=m.items_replayed + ing.n_replayed,
        items_deduped=m.items_deduped + ing.n_deduped,
        items_backfilled=m.items_backfilled + ing.n_backfilled,
        windows_emitted=m.windows_emitted
        + jnp.sum(ing.emit.astype(jnp.int32)),
        rules_fired=m.rules_fired
        + jnp.sum((ing.consequence != R.C_NONE).astype(jnp.int32)),
        windows_escalated=m.windows_escalated + n_escalated,
        windows_stored=m.windows_stored + n_stored,
        windows_dropped=m.windows_dropped + n_dropped,
        core_overflow=m.core_overflow + overflow,
        drift_counts=m.drift_counts + ing.drift,
    )


class StreamExecutor:
    """Drives a continuous stream through ring buffer -> windows ->
    rules -> pipeline with a single traced step function.

    engine: rule engine evaluated on the [NW, 5] window features
    (``window_feature_names()`` gives the column order).
    pipeline: run on the [NW, 5 + D] window records (features
    concatenated with the mean aggregate) — stage fns can slice either.
    """

    def __init__(self, cfg: StreamConfig, engine: R.RuleEngine,
                 pipeline: DataDrivenPipeline):
        if cfg.fused and engine.table() is None:
            raise ValueError(
                "StreamConfig(fused=True) needs a tabular RuleEngine "
                "(threshold_rule-style rules only) — callable rules "
                "cannot run inside the fused kernel; use fused=False")
        self.cfg = cfg
        self.engine = engine
        self.pipeline = pipeline
        self._traces = 0
        self._budget = None            # dynamic core budget (traced operand)
        self.last_step_seconds = 0.0   # host wall time of the last dispatch
        # observability: host span tracer (default disabled — near-zero
        # cost) + on-device step-latency histogram + per-stage lineage
        # bank.  Both ride the step as fixed-shape donated operands (the
        # histogram fed the *previous* step's wall time), so percentile
        # tracking adds zero recompiles.
        self.tracer = NULL_TRACER
        self._lat_hist = OL.histogram_init()
        self._lineage = OL.lineage_init()
        self._t0 = time.perf_counter()     # lineage epoch (f32-friendly)
        # warmup exclusion: a step that (re)traced measured compile
        # time, not steady-state latency — its wall time is withheld
        # from the histogram (fed as 0.0, the "missing measurement"
        # sentinel) and counted instead
        self._skip_feed = False
        self.warmup_excluded = 0
        self._step_num = 0
        self._jstep = jax.jit(self._step, donate_argnums=(0, 4, 5))

    # -- state ------------------------------------------------------------
    def init_state(self, feature_dim: int) -> StreamState:
        cfg = self.cfg
        return StreamState(
            rb=rbuf.create(cfg.capacity, (META_COLS + feature_dim,)),
            carry=jnp.zeros((cfg.carry_len, META_COLS + feature_dim),
                            jnp.float32),
            carry_valid=jnp.zeros((cfg.carry_len,), bool),
            max_ts=jnp.asarray(jnp.finfo(jnp.float32).min),
            metrics=_zero_metrics(feature_dim),
            adm=I.admission_init(cfg.admission),
        )

    @property
    def trace_count(self) -> int:
        """Number of step traces so far — 1 after warmup, forever."""
        return self._traces

    def set_tracer(self, tracer) -> None:
        """Install an ``obs.Tracer`` for host-span instrumentation of
        ``step()`` (its span tree + JAX profiler step annotation).
        Tracing changes no traced shapes — zero recompiles."""
        self.tracer = tracer

    def latency_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Step-latency percentiles from the on-device histogram (one
        host transfer).  ``count`` is steps recorded so far — a step's
        wall time feeds the histogram on the *next* tick, and steps
        that (re)traced are excluded (their wall time is compile time,
        which used to pollute p99 by ~6 orders of magnitude; the
        ``warmup_excluded`` key counts them)."""
        out = OL.histogram_percentiles(self._lat_hist, qs)
        out["warmup_excluded"] = self.warmup_excluded
        return out

    def lineage_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Per-stage event-time latency percentiles (one host transfer
        of the lineage bank): ``{stage: {"count": n, "p50_us": ...}}``
        over :data:`repro.obs.latency.LINEAGE_STAGES`.  On a single
        device the exchange hops are empty (no escalation wire), and
        ``e2e`` equals window residency — everything commits in-tick.
        Resolution is one tick (see ``obs.latency``)."""
        return OL.lineage_percentiles(self._lineage, qs)

    def lower(self, state: StreamState, items, ts) -> jax.stages.Lowered:
        """Lower ONE tick at these operands without running it.
        ``state``/``items``/``ts`` may be arrays or
        ``jax.ShapeDtypeStruct``s (an ahead-of-time compile for a chip
        that is described, not attached); ``.compile()`` gives the
        executable, its HLO (``as_text()``) and ``memory_analysis()``.
        Nothing executes and no state is consumed."""
        return self._jstep.lower(
            state, items, ts,
            jnp.asarray(self._effective_budget(), jnp.int32),
            self._lat_hist, self._lineage,
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0.0, jnp.float32),
            jnp.asarray(I.MODE_LIVE, jnp.int32))

    def step_cost(self, state: StreamState, items: jnp.ndarray,
                  ts: jnp.ndarray) -> dict:
        """XLA cost analysis of ONE tick at these operand shapes
        (``obs.costmodel.cost_of``): total FLOPs/bytes plus a per-
        ``named_scope``-stage breakdown.  Lower + compile only —
        nothing executes, no state is consumed — and after warmup the
        compile hits jax's cache (same shapes as the traced step), so
        this is safe to call on a live executor."""
        return OC.cost_of(self.lower(state, jnp.asarray(items),
                                     jnp.asarray(ts)).compile())

    @property
    def core_budget(self) -> int | None:
        """Dynamic core budget, or None for the pipeline's static cap."""
        return self._budget

    def set_core_budget(self, budget: int) -> None:
        """Resize the effective core budget between steps.  The budget
        is a *traced operand* of the step, so resizes never recompile —
        the static ``pipeline.core_capacity`` stays the compaction
        shape (and the resize ceiling)."""
        if budget < 0:
            raise ValueError(f"core budget must be >= 0, got {budget}")
        self._budget = int(budget)

    def _effective_budget(self) -> int:
        cap = self.pipeline.core_capacity
        if self._budget is None:
            return cap if cap is not None else self.cfg.windows_per_step
        return self._budget if cap is None else min(self._budget, cap)

    # -- the single-trace step --------------------------------------------
    def _step(self, state: StreamState, items: jnp.ndarray,
              ts: jnp.ndarray, budget: jnp.ndarray,
              lat_hist: jnp.ndarray, lineage: jnp.ndarray,
              last_dt: jnp.ndarray, now: jnp.ndarray, mode: jnp.ndarray
              ) -> tuple[StreamState, StepOutput, jnp.ndarray, jnp.ndarray]:
        # the Python body runs exactly once per jit trace, so this
        # counts (re)traces without reaching into jit internals
        self._traces += 1
        ing = ingest_and_window(self.cfg, self.engine, state, items, ts,
                                mode=mode, now=now)

        # non-emitted windows (count < min_count) enter the pipeline
        # dead: no rules, no escalation, no core-capacity consumption
        with jax.named_scope("obs:pipeline"):
            result = self.pipeline.run(ing.record, live=ing.emit,
                                       core_budget=budget)
        escalated = result.escalated
        n_esc = jnp.sum(escalated.astype(jnp.int32))
        overflow = jnp.maximum(0, n_esc - budget)

        with jax.named_scope("obs:metrics"):
            metrics = advance_metrics(
                state.metrics, ing, n_esc,
                jnp.sum(result.stored.astype(jnp.int32)),
                jnp.sum(result.dropped.astype(jnp.int32)), overflow)
            lat_hist = OL.histogram_update(lat_hist, last_dt)
        with jax.named_scope("obs:lineage"):
            # window residency and end-to-end are one measurement here
            # (everything commits in-tick): bucketed once, added to both
            lineage = OL.lineage_update(lineage, {
                "queueing": (ing.q_lat, ing.q_mask),
                ("window", "e2e"): (now - ing.w_birth, ing.emit),
            })
        new_state = StreamState(
            rb=ing.rb, carry=ing.carry, carry_valid=ing.carry_valid,
            max_ts=ing.max_ts, metrics=metrics, adm=ing.adm,
        )
        return new_state, StepOutput(ing.aggregates, ing.features,
                                     ing.window_count, ing.consequence,
                                     escalated, result.outputs), \
            lat_hist, lineage

    # -- public API ---------------------------------------------------------
    def step(self, state: StreamState, items: jnp.ndarray,
             ts: jnp.ndarray, mode: int | jnp.ndarray = I.MODE_LIVE
             ) -> tuple[StreamState, StepOutput]:
        """One micro-batch tick: offer ``items [N, D]`` with event
        timestamps ``ts [N]``, consume one window batch.  N is the
        producer's batch size; keep it fixed across steps to stay on
        the single trace.

        ``mode``: this tick's ingest mode (``stream.ingest.MODE_*``).
        A traced int32 operand — switching a tick to replay or
        backfill never recompiles.  Backfill ticks feed historical
        batches through the same windows, lateness-exempt and
        clock-neutral, accounted in ``items_backfilled``; with a
        dedupe window configured, re-running a backfill is idempotent
        (``items_deduped`` absorbs the second pass).

        Timestamps ride the ring as float32 (one row per sample), so
        event-time resolution degrades past ~2^24 time units; scale
        long-running tick counters (e.g. seconds since stream start,
        not epoch nanoseconds) to stay inside that range.  The lineage
        ingest stamp (row column 1) is wall seconds since executor
        construction — the same f32 caveat applies after ~2^24 seconds
        (about six months of uptime; restart the epoch before then).

        ``last_step_seconds`` records the host wall time of the
        dispatch only (the bounds of the ``stream.dispatch`` span): jit
        dispatch is async, so device execution is not in it, except
        where the dispatch waits for the previous tick's buffers (the
        control plane feeds these into its straggler detector; real
        deployments substitute per-device telemetry).  The previous
        step's wall time also feeds the on-device latency histogram
        (``latency_percentiles()``) as a traced operand — except after a
        (re)trace, whose wall time is compile time: that sample is
        withheld (``warmup_excluded``) so one warmup tick can never
        masquerade as a million-microsecond p99.

        With a tracer installed a call is the span tree ``stream.step``
        > ``stream.dispatch`` > ``stream.operands`` (the scalar operands
        put on the device), ``stream.call`` (the jit call alone).
        """
        self._step_num += 1
        tracer = self.tracer
        with tracer.span("stream.step", step=self._step_num):
            feed = 0.0 if self._skip_feed else self.last_step_seconds
            if self._skip_feed and self.last_step_seconds > 0.0:
                self.warmup_excluded += 1
            traces_before = self._traces
            t0 = time.perf_counter()
            with tracer.step_annotation("stream_step", self._step_num), \
                    tracer.span("stream.dispatch", step=self._step_num):
                with tracer.span("stream.operands"):
                    budget = jnp.asarray(self._effective_budget(), jnp.int32)
                    last_dt = jnp.asarray(feed, jnp.float32)
                    now = jnp.asarray(time.perf_counter() - self._t0,
                                      jnp.float32)
                    mode = jnp.asarray(mode, jnp.int32)
                with tracer.span("stream.call"):
                    state, out, self._lat_hist, self._lineage = self._jstep(
                        state, items, ts, budget, self._lat_hist,
                        self._lineage, last_dt, now, mode)
            self.last_step_seconds = time.perf_counter() - t0
            self._skip_feed = self._traces > traces_before
        return state, out

    def run(self, state: StreamState,
            producer: Iterable[tuple[jnp.ndarray, jnp.ndarray]],
            ) -> tuple[StreamState, list[StepOutput]]:
        """Drain a producer iterable of (items, ts) micro-batches.

        Producer batches are ``(items, ts)`` or ``(items, ts, mode)``
        triples — a replay/backfill batch rides the same loop with its
        ingest mode attached (``stream.ingest.MODE_*``).

        With ``cfg.overlap_ingest`` the host stages batch N+1 (H2D
        transfer via ``runtime.overlap.IngestStager``, optionally
        int8-quantized) while the device still computes batch N — the
        classic ingest/compute overlap.  Staging changes delivery
        *timing* only: with ``ingest_int8=False`` the outputs are
        bitwise those of the direct loop (the staged path stays the
        oracle); int8 staging is lossy and opt-in.  The stager carries
        each batch's mode through its double buffer, so a replay batch
        is delivered *as* a replay batch — modes never silently decay
        to live under overlap."""
        outs = []
        if not self.cfg.overlap_ingest:
            for items, ts, *m in producer:
                state, out = self.step(state, items, ts,
                                       mode=m[0] if m else I.MODE_LIVE)
                outs.append(out)
            return state, outs
        from repro.runtime.overlap import IngestStager
        stager = IngestStager(int8=self.cfg.ingest_int8)
        for items, ts, *m in producer:
            staged = stager.stage(items, ts, m[0] if m else I.MODE_LIVE)
            if staged is not None:
                state, out = self.step(state, *staged[:2], mode=staged[2])
                outs.append(out)
        staged = stager.flush()
        if staged is not None:
            state, out = self.step(state, *staged[:2], mode=staged[2])
            outs.append(out)
        return state, outs
