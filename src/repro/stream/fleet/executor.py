"""Sharded edge-fleet stream runtime: a 2-D ``(region, edge)`` mesh,
one traced step.

``FleetExecutor`` runs S = R x E independent edge shards — each with
its own ring buffer, window carry, and watermark — as **one**
``shard_map`` step over a ``("region", "edge")`` mesh (``num_regions=1``
is the flat fleet, bit for bit):

    per-shard:  enqueue -> dequeue -> watermark -> windows -> rules
                -> edge pipeline stages            (no cross-talk)
    region:     escalation candidates pre-aggregate on the inner
                ``edge`` axis — one intra-region all-to-all to the
                region's fog columns under a per-region ``fog_budget``
                (shed candidates keep their edge results)
    fleet:      only region survivors cross the ``region`` axis (one
                budget-sized all-to-all) to the core sub-mesh in
                region 0 -> fleet-budgeted core stage -> the same two
                hops back -> commit

The whole tick compiles to a single XLA executable (``trace_count``
stays 1 after warmup, same discipline as ``StreamExecutor``): per-shard
work is the *same code* as the single-device executor
(``ingest_and_window``), so a fleet of S shards is bit-identical to S
lone devices except where the fleet semantics intentionally differ —

* the watermark reference is the fleet-wide **min** of per-shard max
  event times (a lagging shard holds back lateness-dropping on every
  shard), layered per region then across regions
  (``federation.tiered_watermark``),
* core capacity is a **fleet-level budget**: the first ``core_budget``
  escalated windows per step (deterministic region-major global-slot
  order) get core compute wherever they came from; the rest keep their
  edge results, and
* each region additionally caps what it forwards at its own
  ``fog_budget`` — cross-region traffic is O(fog budget), not O(E),
  which is what lets fleet width scale (see ``stream/fleet/routing``).

Fleet **churn** (devices leave and join) is handled at two granularities:

* **membership mask** — ``active`` is a per-shard traced operand
  (alongside ``healthy``/``offered``/``budget``): a shard leaving or a
  spare joining *within* the current mesh width recompiles nothing.
  An inactive shard contributes no watermark, no escalations, and no
  fleet psums; whatever already sits in its ring keeps draining
  locally against its own watermark, surfacing on its own rows only.
  The core sub-mesh (ranks ``0..num_core-1``) must stay active — a
  core rank leaving is a device-set change, i.e. a :meth:`remesh`.
* **re-mesh** — when the device set actually changes,
  :meth:`FleetExecutor.remesh` rebuilds the mesh over the survivors
  (``runtime.elastic.remesh`` on the ``("region", "edge")`` axes,
  resizing one axis per call),
  places the migrated state on the new mesh (surviving rows migrate; a
  departed shard's unconsumed ring rows come back to the host as the
  backup-replay payload and its counters fold into a surviving row),
  and costs exactly one re-trace
  (``trace_count <= 1 + retraces + remeshes``).

Backup replay rides the ``mode`` per-shard operand (``stream.ingest``'s
``MODE_LIVE | MODE_REPLAY | MODE_BACKFILL``): a tick whose batch is
another (departed) shard's buffered micro-batches — or a historical
backfill — is exempt from the late test, counted in ``items_replayed``
/ ``items_backfilled``, and never advances the host shard's own
event-time clock.  Every shard's ingest runs through the same
admission lane as the single-device executor (``stream.ingest``):
per-shard dedupe windows, contract gating, and drift counters are
rows of the sharded state, so a redelivered backup batch dedupes on
the backup exactly as it would have on the departed shard.
"""
from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.runtime import elastic

from repro.core import rules as R
from repro.obs import costmodel as OC
from repro.obs import latency as OL
from repro.obs.trace import NULL_TRACER
from repro.core.pipeline import DataDrivenPipeline
from repro.data import ringbuffer as rbuf
from repro.stream import ingest as SI
from repro.stream.executor import (COUNTERS, META_COLS, StepOutput,
                                   StreamConfig, StreamMetrics, StreamState,
                                   _zero_metrics, advance_metrics,
                                   ingest_and_window)
from repro.stream.fleet import federation as F
from repro.stream.fleet import routing as FR


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet topology + budget knobs.  Topology fields are static (part
    of the single trace, like ``StreamConfig``); ``core_budget`` and
    ``fog_budget`` are only the *initial* values of the dynamic budgets
    — the control plane resizes them between ticks without recompiling,
    up to the static shape ceilings (``core_budget_max`` /
    ``fog_budget_max``; growing past one costs exactly one re-trace).

    The fleet is a 2-D ``(region, edge)`` mesh: ``num_shards`` total
    edge devices in ``num_regions`` equal regions (region-major flat
    numbering: shard ``s`` = region ``s // edges_per_region``, edge
    column ``s % edges_per_region``).  ``num_regions=1`` (the default)
    is the flat fleet — same semantics, bit for bit.  The core
    sub-mesh lives in region 0 at edge columns ``0..num_core-1``
    (flat shards ``0..num_core-1``, exactly as before); every region's
    matching columns double as its *fog* tier, pre-aggregating the
    region's escalations under a per-region ``fog_budget`` before
    anything crosses the region axis."""
    stream: StreamConfig           # per-shard stream config
    num_shards: int                # total edge devices (all regions)
    num_core: int = 1              # core sub-mesh = region-0 cols 0..K-1
    core_budget: int = 8           # initial fleet-level escalations / step
    core_budget_max: int | None = None   # static slot ceiling (shape)
    axis_name: str = "edge"
    num_regions: int = 1           # R regions on the outer mesh axis
    fog_budget: int | None = None  # initial per-region escalation budget
    #                                (None = non-binding: a region may
    #                                escalate everything, the flat
    #                                semantics)
    fog_budget_max: int | None = None    # static per-region ceiling
    region_axis: str = "region"

    def __post_init__(self):
        if self.num_shards < 1:
            raise ValueError(f"need >= 1 shard, got {self.num_shards}")
        if self.num_regions < 1 or self.num_shards % self.num_regions:
            raise ValueError(
                f"num_shards ({self.num_shards}) must split into "
                f"num_regions ({self.num_regions}) equal regions")
        if not (1 <= self.num_core <= self.edges_per_region):
            raise ValueError(
                "need 1 <= num_core <= edges_per_region (the core "
                "sub-mesh is region 0's leading edge columns), got "
                f"{self.num_core} / {self.edges_per_region}")
        if self.core_budget < 0:
            raise ValueError(f"core_budget must be >= 0, got {self}")
        if self.core_budget_max is not None \
                and self.core_budget_max < self.core_budget:
            raise ValueError(f"core_budget_max < core_budget: {self}")
        if self.fog_budget is not None and self.fog_budget < 0:
            raise ValueError(f"fog_budget must be >= 0, got {self}")
        if self.fog_budget_max is not None and self.fog_budget is not None \
                and self.fog_budget_max < self.fog_budget:
            raise ValueError(f"fog_budget_max < fog_budget: {self}")
        if self.axis_name == self.region_axis:
            raise ValueError(f"mesh axes must be distinct, got {self}")

    @property
    def edges_per_region(self) -> int:
        """Edge devices per region (the inner mesh axis width)."""
        return self.num_shards // self.num_regions

    @property
    def core_slots(self) -> int:
        """Static shape ceiling of the dynamic core budget."""
        return self.core_budget if self.core_budget_max is None \
            else self.core_budget_max

    @property
    def fog_slots(self) -> int:
        """Static shape ceiling of the per-region fog budget.  With no
        fog budget configured it is the region's worst-case demand
        (every window of every edge escalating) — non-binding, so the
        region tier degenerates to the flat fleet exactly."""
        if self.fog_budget_max is not None:
            return self.fog_budget_max
        if self.fog_budget is not None:
            return self.fog_budget
        return self.edges_per_region * self.stream.windows_per_step

    @property
    def initial_fog_budget(self) -> int:
        """Per-region budget in force before any control-plane resize."""
        return self.fog_slots if self.fog_budget is None \
            else self.fog_budget

    @property
    def route_capacity(self) -> int:
        """Per-(src, dest) slot count of the intra-region (hop 1)
        all-to-all buffer.  Global slots fan out round-robin over the
        fog columns, so one shard never sends more than
        ceil(NW / num_core) records to one column — no send-side shed,
        ever."""
        return -(-self.stream.windows_per_step // self.num_core)

    @property
    def cross_capacity(self) -> int:
        """Per-(region, region) slot count of the cross-region (hop 2)
        all-to-all buffer: ``ceil(fog_slots / num_core)``.  Derived
        from the fog-budget ceiling, NOT from the region width — the
        reason cross-region traffic stops scaling with fleet width."""
        return max(1, -(-self.fog_slots // self.num_core))

    def exchange(self) -> FR.TieredExchange:
        """Static geometry of the two-hop exchange (byte accounting
        for the region bench)."""
        return FR.TieredExchange(
            num_regions=self.num_regions,
            edges_per_region=self.edges_per_region,
            num_core=self.num_core, edge_capacity=self.route_capacity,
            cross_capacity=self.cross_capacity)


class FleetMetrics(NamedTuple):
    """Per-shard stream counters + all-reduced fleet counters +
    escalation-exchange counters.  In the global (host) view, ``shard``
    leaves are [S] arrays (S = total shards, region-major); ``fleet``
    leaves are [S] replicated; ``region_watermark`` is replicated
    *within* each region (row ``s`` holds region ``s //
    edges_per_region``'s value)."""
    shard: StreamMetrics            # this shard's local counters
    fleet: StreamMetrics            # psum over both mesh axes
    escalations_sent: jnp.ndarray   # this shard's fog-budget survivors
    fog_shed: jnp.ndarray           # this shard's candidates shed by the
    #                                 region (fog) budget
    core_received: jnp.ndarray      # records landed here as core rank
    core_processed: jnp.ndarray     # of those, got core compute
    fleet_core_overflow: jnp.ndarray  # fleet survivors beyond budget
    late_excluded: jnp.ndarray      # records admitted past the fleet wm
    watermark: jnp.ndarray          # fleet watermark used last tick (f32)
    region_watermark: jnp.ndarray   # this shard's region's watermark (f32)

    def as_dict(self) -> dict:
        """Host-side snapshot: a single ``jax.device_get`` for the
        whole tree.  Per-shard counters come back as lists (one int per
        shard); fleet counters as plain ints."""
        host = jax.device_get(self)

        def _shard(v):
            return v.tolist() if getattr(v, "ndim", 0) else int(v)

        def _fleet(v):
            # fleet leaves are replicated over the leading [S] axis;
            # scalar counters collapse to one int, array counters (the
            # [S, D] drift leaf) to their first row
            v = np.asarray(v)
            return v[0].tolist() if v.ndim > 1 else int(v.reshape(-1)[0])

        return {
            "shard": {k: _shard(v) for k, v in
                      zip(COUNTERS, host.shard)},
            "fleet": {k: _fleet(v) for k, v in
                      zip(COUNTERS, host.fleet)},
            "escalations_sent": _shard(host.escalations_sent),
            "fog_shed": _shard(host.fog_shed),
            "core_received": _shard(host.core_received),
            "core_processed": _shard(host.core_processed),
            "fleet_core_overflow": _fleet(host.fleet_core_overflow),
            "late_excluded": _shard(host.late_excluded),
            "watermark": float(np.asarray(host.watermark).reshape(-1)[0]),
            "region_watermark": [
                float(x) for x in
                np.asarray(host.region_watermark).reshape(-1)],
        }


class FleetState(NamedTuple):
    """Global fleet state: every leaf carries a leading [S] shard axis
    (region-major flat numbering, sharded over both mesh axes;
    ``shard_map`` hands each device its row)."""
    shard: StreamState              # per-shard rb/carry/watermark/metrics
    fleet: StreamMetrics            # all-reduced counters (replicated)
    escalations_sent: jnp.ndarray
    fog_shed: jnp.ndarray           # per-shard fog-budget shed counter
    core_received: jnp.ndarray
    core_processed: jnp.ndarray
    fleet_core_overflow: jnp.ndarray
    late_excluded: jnp.ndarray      # per-shard catch-up record counter
    watermark: jnp.ndarray          # [S] f32, fleet reference (replicated)
    region_watermark: jnp.ndarray   # [S] f32, replicated within region

    @property
    def metrics(self) -> FleetMetrics:
        return FleetMetrics(self.shard.metrics, self.fleet,
                            self.escalations_sent, self.fog_shed,
                            self.core_received, self.core_processed,
                            self.fleet_core_overflow, self.late_excluded,
                            self.watermark, self.region_watermark)


class FleetExecutor:
    """E sharded stream executors + core escalation, one XLA executable.

    engine/pipeline: same contract as ``StreamExecutor``; the pipeline
    must end in a single core-placement stage (the canonical two-tier
    shape) — its edge prefix runs per shard, its core stage runs on the
    core sub-mesh over gathered records.  The pipeline's per-device
    ``core_capacity`` is ignored here: ``cfg.core_budget`` is the
    fleet-level replacement.
    """

    def __init__(self, cfg: FleetConfig, engine: R.RuleEngine,
                 pipeline: DataDrivenPipeline, mesh: Mesh | None = None):
        if cfg.stream.keyed is not None:
            raise ValueError("a keyed plan runs on one StreamExecutor; "
                             "the fleet has no keyed path")
        ci = pipeline.core_index
        if ci is None or ci != len(pipeline.stages) - 1:
            raise ValueError("fleet pipeline needs exactly one core stage, "
                             "as the last stage")
        if cfg.stream.fused and engine.table() is None:
            raise ValueError(
                "FleetConfig.stream has fused=True but the RuleEngine is "
                "not tabular (threshold_rule-style rules only) — callable "
                "rules cannot run inside the fused kernel; use fused=False")
        self.cfg = cfg
        self.engine = engine
        self.pipeline = pipeline
        if mesh is None:
            devs = jax.devices()
            if len(devs) < cfg.num_shards:
                raise ValueError(f"need {cfg.num_shards} devices for the "
                                 f"fleet mesh, have {len(devs)}")
            mesh = Mesh(
                np.asarray(devs[:cfg.num_shards]).reshape(
                    cfg.num_regions, cfg.edges_per_region),
                (cfg.region_axis, cfg.axis_name))
        if mesh.shape.get(cfg.region_axis) != cfg.num_regions \
                or mesh.shape.get(cfg.axis_name) != cfg.edges_per_region:
            raise ValueError(
                f"mesh shape {dict(mesh.shape)} does not match config "
                f"({cfg.region_axis}={cfg.num_regions}, "
                f"{cfg.axis_name}={cfg.edges_per_region})")
        self.mesh = mesh
        self._traces = 0
        self._remeshes = 0
        self._budget = cfg.core_budget       # dynamic, a traced operand
        self._slots = cfg.core_slots         # static shape ceiling
        # per-region fog budgets: dynamic [R] traced operand + one
        # static per-region slot ceiling (shape)
        self._region_budget = np.full(cfg.num_regions,
                                      cfg.initial_fog_budget, np.int32)
        self._fog_slots = cfg.fog_slots
        self._healthy = np.ones(cfg.num_shards, bool)
        self._active = np.ones(cfg.num_shards, bool)
        self.last_step_seconds = 0.0
        # observability: host span tracer (default disabled) + on-device
        # step-latency histogram (fixed-shape donated operand fed the
        # previous tick's wall time — zero recompiles, updated inside
        # the same jit as the fleet step, outside the shard_map)
        self.tracer = NULL_TRACER
        # (both banks move onto the mesh in init_state, with the state)
        self._lat_hist = OL.histogram_init()
        # event-time latency lineage: one [n_stages, buckets] histogram
        # bank PER SHARD ([S, n_stages, buckets], sharded like the
        # state), updated inside the shard_map from the rows' ingest
        # stamps — fixed shape, donated, zero added recompiles.  The
        # leading shard axis is what per-shard / per-region breakdowns
        # pool over (histogram_merge semantics)
        self._lineage = jnp.tile(OL.lineage_init()[None],
                                 (cfg.num_shards, 1, 1))
        self._t0 = time.perf_counter()     # lineage epoch (f32 stamps)
        # warmup exclusion: a tick that traced measures compile+execute
        # wall time — withhold it from the NEXT tick's histogram feed
        # (see step())
        self._skip_feed = False
        self.warmup_excluded = 0
        self._step_num = 0
        # when True (default), step() blocks on the output so
        # last_step_seconds measures device execution — the control
        # plane's default wall-time straggler signal.  Deployments with
        # real per-device telemetry (they pass step_times to
        # FleetController.tick) can set it False to keep async dispatch
        # and host/device overlap; last_step_seconds then reads
        # dispatch time only.
        self.measure_steps = True
        self._build()

    def _shard_spec(self) -> P:
        """Spec of every [S]-leading leaf: the shard axis splits over
        both mesh axes, region-major."""
        return P((self.cfg.region_axis, self.cfg.axis_name))

    def _place(self, tree, spec: P):
        """Put a pytree on the current mesh with the sharding the step
        hands it back with.  Tick 0 then sees the same input shardings
        as every later tick: jit keys its trace on each argument's mesh,
        so an unplaced first operand would cost a second trace."""
        return jax.device_put(tree, NamedSharding(self.mesh, spec))

    def _build(self) -> None:
        """(Re)build the jitted fleet step for the current static slot
        ceilings, mesh, and shard count.  Called once at init and again
        only when the control plane grows a budget past its ceiling
        (``self._slots`` / ``self._fog_slots``) or :meth:`remesh`
        changes the device set — each rebuild costs exactly one
        re-trace on the next step."""
        cfg = self.cfg
        # [S]-leading leaves shard over both mesh axes (region-major);
        # the per-region fog budgets [R] shard over the region axis only
        spec = self._shard_spec()
        rspec = P(cfg.region_axis)
        sharded = jax.shard_map(self._fleet_step, mesh=self.mesh,
                                in_specs=(spec, spec, spec, spec, spec,
                                          spec, spec, P(), rspec, spec,
                                          P()),
                                out_specs=(spec, spec, spec))

        def _traced(state, items, ts, offered, mode, healthy, active,
                    budget, region_budget, lat_hist, lineage, last_dt,
                    now):
            # outer jit body runs once per trace (shard_map may re-trace
            # its inner fn during lowering; don't count those)
            self._traces += 1
            new_state, out, lineage = sharded(
                state, items, ts, offered, mode, healthy, active,
                budget, region_budget, lineage, now)
            # step-latency histogram: replicated, updated outside the
            # shard_map (one tick = one host-measured wall time)
            with jax.named_scope("obs:latency"):
                lat_hist = OL.histogram_update(lat_hist, last_dt)
            return (new_state, out), lat_hist, lineage

        # outputs pinned to the shardings _place gives the carried
        # operands: left free, XLA may hand back an equivalent spec
        # (size-1 mesh axes dropped), which jit keys as a new executable
        sharded_out = NamedSharding(self.mesh, spec)
        self._jstep = jax.jit(
            _traced, donate_argnums=(0, 9, 10),
            out_shardings=(sharded_out, NamedSharding(self.mesh, P()),
                           sharded_out))

    # -- control-plane knobs (host-side, between ticks) --------------------
    @property
    def core_budget(self) -> int:
        """Current dynamic fleet core budget."""
        return self._budget

    @property
    def core_slots(self) -> int:
        """Current static slot ceiling of the budget (shape)."""
        return self._slots

    def set_core_budget(self, budget: int) -> None:
        """Resize the fleet core budget between ticks.  Budgets within
        the current slot ceiling change only a traced operand (zero
        recompiles); growing past it rebuilds the step for the larger
        shape — at most one re-trace per resize, which the benchmarks
        and regression tests assert."""
        budget = int(budget)
        if budget < 0:
            raise ValueError(f"core_budget must be >= 0, got {budget}")
        if budget > self._slots:
            self._slots = budget
            self._build()
        self._budget = budget

    @property
    def region_budget(self) -> np.ndarray:
        """Current dynamic per-region fog budgets ([R] ints)."""
        return self._region_budget.copy()

    @property
    def fog_slots(self) -> int:
        """Current static per-region fog slot ceiling (shape)."""
        return self._fog_slots

    def set_region_budget(self, budgets) -> None:
        """Resize the per-region fog budgets between ticks.  A scalar
        applies to every region; an [R] array sets them individually.
        Values within the current fog slot ceiling change only a traced
        operand (zero recompiles); growing the *maximum* past the
        ceiling rebuilds the step for the larger hop-2 buffer — at most
        one re-trace per resize, same discipline as
        :meth:`set_core_budget`."""
        budgets = np.broadcast_to(
            np.asarray(budgets, np.int32),
            (self.cfg.num_regions,)).copy()
        if (budgets < 0).any():
            raise ValueError(f"fog budgets must be >= 0, got {budgets}")
        top = int(budgets.max())
        if top > self._fog_slots:
            self._fog_slots = top
            self._build()
        self._region_budget = budgets

    def set_health(self, healthy: np.ndarray) -> None:
        """Install the per-shard health mask used by the *next* tick's
        watermark (False = excluded from the fleet ``pmin``).  Comes
        from the control plane's straggler detectors."""
        healthy = np.asarray(healthy, bool)
        if healthy.shape != (self.cfg.num_shards,):
            raise ValueError(f"health mask must be [{self.cfg.num_shards}]"
                             f", got {healthy.shape}")
        self._healthy = healthy.copy()

    @property
    def health(self) -> np.ndarray:
        return self._healthy.copy()

    def set_active(self, active: np.ndarray) -> None:
        """Install the per-shard membership mask for the *next* tick
        (False = the device left the fleet).  A membership flip within
        the current mesh width is a traced operand — it recompiles
        nothing.  Inactive shards contribute no watermark, no
        escalations, and no fleet psums.

        The core sub-mesh (ranks ``0..num_core-1``) must stay active:
        escalated records land there by global-slot arithmetic, so a
        core rank leaving is a real device-set change — use
        :meth:`remesh` for that."""
        active = np.asarray(active, bool)
        if active.shape != (self.cfg.num_shards,):
            raise ValueError(f"active mask must be [{self.cfg.num_shards}]"
                             f", got {active.shape}")
        if not active[:self.cfg.num_core].all():
            raise ValueError(
                f"core sub-mesh ranks 0..{self.cfg.num_core - 1} must stay "
                f"active (got {active}); a core rank leaving changes the "
                f"device set — use remesh()")
        self._active = active.copy()

    @property
    def active(self) -> np.ndarray:
        return self._active.copy()

    @property
    def remeshes(self) -> int:
        """Device-set rebuilds so far — each costs one re-trace."""
        return self._remeshes

    def set_tracer(self, tracer) -> None:
        """Install an ``obs.Tracer``: the host span tree of ``step()``
        (see there) + a JAX profiler step annotation per tick.
        Changes no traced shapes — zero recompiles."""
        self.tracer = tracer

    def latency_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Fleet-tick latency percentiles from the on-device histogram
        (one host transfer).  ``count`` trails ``metrics.steps`` by one
        — a tick's wall time feeds the histogram on the next tick — and
        additionally excludes warmup: a tick that traced (compiled)
        measured compile+execute, so its wall time is withheld
        (``warmup_excluded`` counts the withheld samples).  The
        histogram survives :meth:`remesh` (it is per-executor, not
        per-shard state)."""
        out = OL.histogram_percentiles(self._lat_hist, qs)
        out["warmup_excluded"] = self.warmup_excluded
        return out

    def lineage_percentiles(self, by: str | None = None,
                            qs=(50, 95, 99)):
        """Per-stage event-time latency percentiles
        (:data:`obs.latency.LINEAGE_STAGES`) from the on-device lineage
        banks (one host transfer).

        ``by=None`` pools every shard's bank into one fleet-wide dict;
        ``by="shard"`` returns a list of S dicts (region-major flat
        numbering); ``by="region"`` pools each region's shards and
        returns a list of R dicts.  Pooling is histogram summation —
        associative/commutative and equal to having bucketed every
        sample into one histogram, so the three views are consistent.

        Note the stages measure where latency is *experienced*: hop1
        populates on each region's fog columns, hop2 only on region 0's
        core ranks — per-region hop2 rows outside region 0 are empty by
        construction."""
        bank = np.asarray(jax.device_get(self._lineage), np.int64)
        if by is None:
            return OL.lineage_percentiles(bank, qs)
        if by == "shard":
            return [OL.lineage_percentiles(bank[i], qs)
                    for i in range(bank.shape[0])]
        if by == "region":
            rr = self.cfg.num_regions
            pooled = bank.reshape((rr, -1) + bank.shape[1:]).sum(axis=1)
            return [OL.lineage_percentiles(pooled[i], qs)
                    for i in range(rr)]
        raise ValueError(f"by must be None, 'shard' or 'region', got {by!r}")

    def lineage_counts(self) -> np.ndarray:
        """Cumulative fleet-pooled lineage bank as a host
        ``[n_stages, buckets]`` int64 array — the SLO evaluator's input
        (one transfer, summed over shards)."""
        return np.asarray(jax.device_get(self._lineage),
                          np.int64).sum(axis=0)

    def lower(self, state: FleetState, items, ts) -> jax.stages.Lowered:
        """Lower ONE fleet tick at these operands without running it
        (every shard offering its whole batch, live mode).  ``state``/
        ``items``/``ts`` may be arrays or ``jax.ShapeDtypeStruct``s
        sharded over a mesh of described devices (an ahead-of-time
        compile); ``.compile()`` gives the executable, its HLO
        (``as_text()``) and ``memory_analysis()``."""
        return self._jstep.lower(
            state, items, ts, jnp.ones(ts.shape, bool),
            jnp.zeros(self.cfg.num_shards, jnp.int32),
            jnp.asarray(self._healthy), jnp.asarray(self._active),
            jnp.asarray(self._budget, jnp.int32),
            jnp.asarray(self._region_budget, jnp.int32),
            self._lat_hist, self._lineage,
            jnp.asarray(0.0, jnp.float32), jnp.asarray(0.0, jnp.float32))

    def step_cost(self, state: FleetState, items: jnp.ndarray,
                  ts: jnp.ndarray) -> dict:
        """XLA cost analysis of ONE fleet tick at these operand shapes
        (``obs.costmodel.cost_of``): whole-executable FLOPs/bytes plus
        the per-``named_scope``-stage breakdown (exchange hops, core
        compute, commit...).  Lower + compile only — nothing executes —
        and after warmup the compile hits jax's cache."""
        return OC.cost_of(self.lower(state, jnp.asarray(items),
                                     jnp.asarray(ts)).compile())

    # -- state ------------------------------------------------------------
    def init_state(self, feature_dim: int) -> FleetState:
        """Fresh fleet state on the mesh, with the shardings the step
        returns.  The executor's latency histogram and lineage banks
        ride the step beside the state, so they move onto the mesh here
        too (their counts carry over)."""
        self._lat_hist = self._place(self._lat_hist, P())
        self._lineage = self._place(self._lineage, self._shard_spec())
        return self._place(self._fresh_state(feature_dim),
                           self._shard_spec())

    def _fresh_state(self, feature_dim: int) -> FleetState:
        """Zeroed fleet state, not yet placed on the mesh."""
        cfg, E = self.cfg.stream, self.cfg.num_shards

        def tile(x):
            return jnp.tile(x[None], (E,) + (1,) * x.ndim)

        shard = StreamState(
            rb=rbuf.create(cfg.capacity, (META_COLS + feature_dim,)),
            carry=jnp.zeros((cfg.carry_len, META_COLS + feature_dim),
                            jnp.float32),
            carry_valid=jnp.zeros((cfg.carry_len,), bool),
            max_ts=jnp.asarray(jnp.finfo(jnp.float32).min),
            metrics=_zero_metrics(feature_dim),
            adm=SI.admission_init(cfg.admission),
        )
        # distinct buffers per counter: the step donates its state, and
        # XLA rejects donating one aliased buffer through several args
        def zero():
            return jnp.zeros((E,), jnp.int32)

        return FleetState(
            shard=jax.tree.map(tile, shard),
            fleet=StreamMetrics(
                *(zero() for _ in COUNTERS[:-1]),
                drift_counts=jnp.zeros((E, feature_dim), jnp.int32)),
            escalations_sent=zero(), fog_shed=zero(), core_received=zero(),
            core_processed=zero(), fleet_core_overflow=zero(),
            late_excluded=zero(),
            watermark=jnp.full((E,), jnp.finfo(jnp.float32).min,
                               jnp.float32),
            region_watermark=jnp.full((E,), jnp.finfo(jnp.float32).min,
                                      jnp.float32),
        )

    @property
    def trace_count(self) -> int:
        """Number of fleet-step traces so far — 1 after warmup."""
        return self._traces

    # -- the single-trace fleet tick ---------------------------------------
    def _fleet_step(self, state: FleetState, items: jnp.ndarray,
                    ts: jnp.ndarray, offered: jnp.ndarray,
                    mode: jnp.ndarray, healthy: jnp.ndarray,
                    active: jnp.ndarray, budget: jnp.ndarray,
                    region_budget: jnp.ndarray, lineage: jnp.ndarray,
                    now: jnp.ndarray
                    ) -> tuple[FleetState, StepOutput, jnp.ndarray]:
        cfg = self.cfg
        s = jax.tree.map(lambda x: x[0], state)        # this shard's block
        h = healthy[0]                                 # this shard's flag
        a = active[0]                                  # membership flag
        m = mode[0]                                    # ingest mode (live /
        #                                                replay / backfill)
        rb = region_budget[0]                          # this region's fog
        #                                                budget
        lin = lineage[0]                               # [n_stages, buckets]

        # fleet watermark: min of per-shard maxima (as of the previous
        # step) over *healthy, active* shards — a lagging-but-healthy
        # shard holds back lateness fleet-wide; a flagged straggler or
        # a departed shard doesn't.  Tiered: the layered
        # healthy&active -> active -> plain fallback runs per region
        # over the edge axis (the fog tier's close reference, kept in
        # region_watermark), then again over the region axis for the
        # fleet reference — with one region or a fully healthy fleet
        # this equals the flat fleet's min exactly.  An
        # excluded-but-present shard falls back to its own running max
        # (exact single-device semantics): it keeps processing its
        # backlog — the catch-up path — and every record it admits past
        # the fleet reference is counted in late_excluded, never
        # silently lost.  Clamped against the previous reference:
        # re-admitting a shard that still trails must not roll the
        # published watermark back (watermarks are monotone; the
        # control plane delays re-admission until the shard's records
        # would survive this reference, so the clamp never converts
        # into silent drops).
        with jax.named_scope("obs:fleet_watermark"):
            wm_raw, rwm_raw = F.tiered_watermark(
                s.shard.max_ts, cfg.region_axis, cfg.axis_name, healthy=h,
                active=a)
            wm = jnp.maximum(wm_raw, s.watermark)
            rwm = jnp.maximum(rwm_raw, s.region_watermark)
            eff_wm = jnp.where(h & a, wm, s.shard.max_ts)
        ing = ingest_and_window(cfg.stream, self.engine, s.shard,
                                items[0], ts[0], watermark_ts=eff_wm,
                                offer_mask=offered[0], excluded_ref=wm,
                                mode=m, now=now)

        # edge pipeline stages + rule gating, purely local; a departed
        # shard never escalates (membership masks the core exchange)
        with jax.named_scope("obs:edge_stages"):
            partial, core_live = self.pipeline.run_edge(ing.record,
                                                        live=ing.emit)
            core_live = core_live & a

        # escalation: the two-hop tiered exchange — intra-region
        # all-to-all to the fog columns under the per-region fog
        # budget, then only region survivors cross the region axis to
        # the core sub-mesh.  Both budgets are traced operands; their
        # static shape ceilings (self._slots / self._fog_slots) are
        # baked into the trace
        with jax.named_scope("obs:exchange_core"):
            core_out, core_feats, processed, stats, taps = \
                F.federate_escalations_tiered(
                    partial.outputs, core_live, self.pipeline.run_core,
                    region_axis=cfg.region_axis, edge_axis=cfg.axis_name,
                    num_regions=cfg.num_regions,
                    edges_per_region=cfg.edges_per_region,
                    num_core=cfg.num_core, region_budget=rb,
                    core_budget=budget, edge_capacity=cfg.route_capacity,
                    cross_capacity=max(
                        1, -(-self._fog_slots // cfg.num_core)),
                    core_slots=self._slots, birth=ing.w_birth)
        with jax.named_scope("obs:core_commit"):
            result = self.pipeline.commit_core(partial, core_live, core_out,
                                               core_feats, processed)

        # event-time lineage: each stage's cross-tick residency, bucket-
        # incremented into this shard's bank.  queueing/window/e2e come
        # from this shard's rows; hop1 populates on fog columns (stamps
        # received over the intra-region all-to-all), hop2 on region 0's
        # core ranks (stamps that crossed the region axis) — the lineage
        # lands where the latency is *experienced*, so pooling per
        # region shows each tier's receive-side distribution
        with jax.named_scope("obs:lineage"):
            # window and e2e are one measurement (the shard commits
            # in-tick): bucketed once, added to both rows
            lin = OL.lineage_update(lin, {
                "queueing": (ing.q_lat, ing.q_mask),
                ("window", "e2e"): (now - ing.w_birth, ing.emit),
                "hop1": (now - taps.hop1_birth, taps.hop1_mask),
                "hop2": (now - taps.hop2_birth, taps.hop2_mask),
            })

        n_esc = jnp.sum(core_live.astype(jnp.int32))
        overflow = jnp.sum((core_live & ~processed).astype(jnp.int32))
        with jax.named_scope("obs:metrics"):
            metrics = advance_metrics(
                s.shard.metrics, ing, n_esc,
                jnp.sum(result.stored.astype(jnp.int32)),
                jnp.sum(result.dropped.astype(jnp.int32)), overflow)
        new_shard = StreamState(rb=ing.rb, carry=ing.carry,
                                carry_valid=ing.carry_valid,
                                max_ts=ing.max_ts, metrics=metrics,
                                adm=ing.adm)
        # fleet totals sum over *members* only: a departed shard's rows
        # drop out of the psum while it is away and return on rejoin
        contrib = jax.tree.map(lambda v: jnp.where(a, v, jnp.zeros_like(v)),
                               metrics)
        new_state = FleetState(
            shard=new_shard,
            fleet=F.allreduce_metrics(contrib,
                                      (cfg.region_axis, cfg.axis_name)),
            escalations_sent=s.escalations_sent + stats.escalations_sent,
            fog_shed=s.fog_shed + stats.fog_shed,
            core_received=s.core_received + stats.core_received,
            core_processed=s.core_processed + stats.core_processed,
            fleet_core_overflow=s.fleet_core_overflow
            + stats.fleet_overflow,
            late_excluded=s.late_excluded + ing.n_late_excluded,
            watermark=wm.astype(jnp.float32),
            region_watermark=rwm.astype(jnp.float32),
        )
        out = StepOutput(ing.aggregates, ing.features, ing.window_count,
                         ing.consequence, result.escalated, result.outputs)
        expand = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        return expand(new_state), expand(out), lin[None]

    # -- public API ---------------------------------------------------------
    def step(self, state: FleetState, items: jnp.ndarray,
             ts: jnp.ndarray, offered: jnp.ndarray | None = None,
             replay: jnp.ndarray | None = None,
             mode: jnp.ndarray | None = None
             ) -> tuple[FleetState, StepOutput]:
        """One fleet tick: offer ``items [E, N, D]`` with event
        timestamps ``ts [E, N]`` (one producer batch per shard),
        consume one window batch per shard.  Returned ``StepOutput``
        leaves carry a leading [E] shard axis.

        ``offered``: optional [E, N] bool — which producer slots hold
        real items (a stalled shard's uplink offers nothing while its
        batches buffer upstream; shapes stay fixed, so the single
        trace survives fleet degradation).  ``mode``: optional [E]
        int32 of ``stream.ingest.MODE_*`` — which shards' batches are
        reprocessing traffic this tick (``MODE_REPLAY`` for a departed
        peer's buffered micro-batches re-executed here, ``MODE_BACKFILL``
        for historical re-ingestion: both lateness-exempt, counted in
        ``items_replayed`` / ``items_backfilled``, never touching the
        host shard's own event-time clock).  ``replay``: legacy [E]
        bool shorthand for ``MODE_REPLAY`` (mutually exclusive with
        ``mode``).  The current health mask (``set_health``),
        membership mask (``set_active``), and dynamic core budget
        (``set_core_budget``) ride along as traced operands.

        ``last_step_seconds`` records the host wall time of the call
        *including device execution* (the output is blocked on before
        the clock stops): jit dispatch is async, so an unsynchronized
        reading would time the host dispatch only and feed the control
        plane's wall-time straggler detector a signal a slow device
        never inflates.  Callers with real per-device telemetry can set
        ``measure_steps = False`` to skip the sync and keep host/device
        overlap.

        With a tracer installed a call is the span tree ``fleet.step``
        > ``fleet.dispatch`` > ``fleet.operands`` (the host values put
        on the device), ``fleet.call`` (the jit call alone); then
        ``fleet.device_execute`` (the wait for the device) under
        ``fleet.step``."""
        self._step_num += 1
        tracer = self.tracer
        with tracer.span("fleet.step", step=self._step_num):
            if offered is None:
                offered = jnp.ones(items.shape[:2], bool)
            if replay is not None and mode is not None:
                raise ValueError("pass either replay (bool shorthand) or "
                                 "mode (MODE_* codes), not both")
            if replay is not None:
                mode = np.where(np.asarray(replay, bool),
                                SI.MODE_REPLAY, SI.MODE_LIVE).astype(np.int32)
            if mode is None:
                mode = np.zeros(self.cfg.num_shards, np.int32)
            elif np.asarray(mode).any():
                # batch-granular reprocessing precondition, enforced
                # (silent window corruption otherwise, see README "Shard
                # churn"): a per-tick-drained ring (N <= micro_batch; N
                # is fixed by the trace, so replayed/backfilled rows can
                # never linger in the ring past their lateness-exempt
                # tick).  Sliding-carry configs are legal too, PROVIDED
                # the control plane performed the mid-ring carry handoff
                # (``FleetController.begin_replay_carry`` /
                # ``end_replay_carry``): the departed stream's window
                # carry rides on the backup's slot for the replay ticks,
                # so the backup's own samples never smear into replayed
                # windows.
                if items.shape[1] > self.cfg.stream.micro_batch:
                    raise ValueError(
                        f"replay/backfill needs a per-tick-drained ring: "
                        f"offer size {items.shape[1]} > micro_batch "
                        f"{self.cfg.stream.micro_batch} leaves reprocessed "
                        "rows queued past their lateness-exempt tick")
            # warmup exclusion: the previous tick's wall time is the
            # histogram feed — unless that tick traced, in which case it
            # measured compile+execute and would pollute the tail (the
            # p99-vs-p95 cliff the BENCH baselines showed).  Feed 0.0
            # instead (histogram_update skips non-positive) and count it
            feed = 0.0 if self._skip_feed else self.last_step_seconds
            if self._skip_feed and self.last_step_seconds > 0.0:
                self.warmup_excluded += 1
            traces_before = self._traces
            t0 = time.perf_counter()
            with tracer.step_annotation("fleet_tick", self._step_num):
                with tracer.span("fleet.dispatch", step=self._step_num):
                    with tracer.span("fleet.operands", step=self._step_num):
                        ops = (jnp.asarray(offered, bool),
                               jnp.asarray(mode, jnp.int32),
                               jnp.asarray(self._healthy),
                               jnp.asarray(self._active),
                               jnp.asarray(self._budget, jnp.int32),
                               jnp.asarray(self._region_budget, jnp.int32))
                        last_dt = jnp.asarray(feed, jnp.float32)
                        now = jnp.asarray(time.perf_counter() - self._t0,
                                          jnp.float32)
                    with tracer.span("fleet.call", step=self._step_num):
                        out, self._lat_hist, self._lineage = self._jstep(
                            state, items, ts, *ops, self._lat_hist,
                            self._lineage, last_dt, now)
                if self.measure_steps:
                    with tracer.span("fleet.device_execute",
                                     step=self._step_num):
                        jax.block_until_ready(out)
            self.last_step_seconds = time.perf_counter() - t0
            self._skip_feed = self._traces > traces_before
        return out

    # -- true re-mesh (the device set changed) ------------------------------
    def remesh(self, state: FleetState, devices: list, *,
               keep: list | None = None, num_core: int | None = None,
               num_regions: int | None = None,
               fold_counters: dict | None = None
               ) -> tuple[FleetState, dict]:
        """Rebuild the fleet over a *changed device set* and migrate the
        state — churn beyond what the ``active`` mask can absorb.

        The new mesh is ``runtime.elastic.remesh`` over ``devices`` on
        the 2-D ``(region, edge)`` axes, resizing ONE axis per call:
        by default the region count is preserved (``fixed_axis =
        region_axis``) and the edge axis absorbs the device-count
        change; pass ``num_regions`` to resize the region axis instead
        (the edge width must then stay ``len(devices) // num_regions ==
        edges_per_region``; resizing both axes at once is two remesh
        calls).  The re-laid-out state, latency histogram and lineage
        banks are placed on the new mesh with the step's shardings.
        Costs exactly one re-trace on the next step (``trace_count <= 1
        + retraces + remeshes`` — the re-trace discipline the tests and
        benchmarks assert).

        ``keep``: for each NEW slot (region-major flat numbering), the
        OLD shard index whose state row (ring buffer, window carry,
        watermark, counters) it inherits, or ``None`` for a freshly
        initialized row (a joiner).  Defaults to identity truncation on
        shrink / identity plus fresh tail slots on grow.  ``num_core``
        defaults to the old value clamped to the new per-region width.
        ``fold_counters``: optional {departed old index -> surviving
        old index} — the departed shard's monotone counters (its
        ``StreamMetrics`` row, ``late_excluded``, escalation/fog
        counters) are added into the surviving row so fleet totals
        survive the shrink.

        Returns ``(new_state, departed)`` where ``departed`` maps each
        dropped old shard index to its *unconsumed* ring rows (host
        ``[k, 2+D]`` array, ``ts`` in column 0, the ingest stamp in
        column 1) — the backup-replay payload: route it to the backup's
        uplink (e.g. ``FaultInjector.requeue``) so nothing the departed
        shard had accepted is ever dropped.  Replayed rows get *fresh*
        ingest stamps at redelivery, so the replay detour shows in the
        EventLog, not the lineage.

        A re-mesh *renumbers* slots: old shard ``keep[j]`` is new slot
        ``j``.  Host-side bookkeeping addressed in the old numbering
        must be carried across: a live ``FaultInjector`` translates its
        schedule and queues with ``FaultInjector.translate(keep, tick)``
        (which errors loudly when a departed-and-unreassigned shard
        still holds pending batches or open/future schedule windows —
        never silent loss), and a ``backups`` plan must be re-derived
        in the new numbering (e.g. a fresh ``FleetController.leave``).
        Alternatively drain the injector first, or seed a fresh one
        against the new topology with the returned payload via
        ``requeue``.

        Region *identity* survives an edge-width resize (the default
        ``fixed_axis = region_axis`` path): region ``i`` is still
        region ``i``, so per-region watermarks, fog budgets, and the
        grown fog slot ceiling all carry over — the control plane's
        hysteresis does not restart and no spurious
        ``fog_budget_resize`` follows the resize.  A region-*count*
        change re-forms regions, so that per-region state re-derives
        from scratch."""
        cfg = self.cfg
        old_e = cfg.num_shards
        old_shape = {cfg.region_axis: cfg.num_regions,
                     cfg.axis_name: cfg.edges_per_region}
        axes = (cfg.region_axis, cfg.axis_name)
        if num_regions is None or num_regions == cfg.num_regions:
            # edge resize: the region count is the preserved axis
            new_mesh = elastic.remesh(old_shape, list(devices), axes,
                                      fixed_axis=cfg.region_axis)
        else:
            # region resize: the per-region edge width is preserved
            new_mesh = elastic.remesh(old_shape, list(devices), axes,
                                      fixed_axis=cfg.axis_name)
            if new_mesh.shape[cfg.region_axis] != num_regions:
                raise ValueError(
                    f"{len(list(devices))} devices at edge width "
                    f"{cfg.edges_per_region} form "
                    f"{new_mesh.shape[cfg.region_axis]} regions, not "
                    f"num_regions={num_regions} — resize one axis per "
                    f"call")
        new_r = new_mesh.shape[cfg.region_axis]
        new_ee = new_mesh.shape[cfg.axis_name]
        new_e = new_r * new_ee
        if keep is None:
            keep = [i if i < old_e else None for i in range(new_e)]
        if len(keep) != new_e:
            raise ValueError(f"keep must name {new_e} slots, got {keep}")
        kept = [k for k in keep if k is not None]
        if len(set(kept)) != len(kept) \
                or any(not (0 <= k < old_e) for k in kept):
            raise ValueError(f"keep must be distinct old indices < "
                             f"{old_e} (or None), got {keep}")

        host = jax.tree.map(np.array, jax.device_get(state))
        departed_idx = [i for i in range(old_e) if i not in kept]
        departed = {}
        rb = host.shard.rb
        for i in departed_idx:
            head, tail = int(rb.head[i]), int(rb.tail[i])
            cap = rb.buf.shape[1]
            idx = (tail + np.arange(head - tail)) % cap
            departed[i] = rb.buf[i][idx]           # [pending, 2+D] rows
        fold_counters = fold_counters or {}
        if any(src not in departed_idx or dst not in kept
               for src, dst in fold_counters.items()):
            raise ValueError(f"fold_counters must map departed -> kept "
                             f"old indices, got {fold_counters} with "
                             f"departed={departed_idx}")
        for src, dst in fold_counters.items():
            for arr in (list(host.shard.metrics[:-1])   # less keyed
                        + [host.escalations_sent, host.fog_shed,
                           host.core_received, host.core_processed,
                           host.late_excluded]):
                arr[dst] += arr[src]

        feature_dim = rb.buf.shape[-1] - META_COLS
        old_r = cfg.num_regions
        self.cfg = dataclasses.replace(
            cfg, num_shards=new_e, num_regions=new_r,
            num_core=min(cfg.num_core, new_ee) if num_core is None
            else num_core)
        self.mesh = new_mesh
        fresh = jax.device_get(self._fresh_state(feature_dim))
        new_host = jax.tree.map(
            lambda o, f: np.stack(
                [np.asarray(o[k]) if k is not None else np.asarray(f[j])
                 for j, k in enumerate(keep)]),
            host, fresh)
        if new_r == old_r:
            # edge-width resize: region IDENTITY is preserved (region i
            # is still region i, only its member set changed), so the
            # per-region watermark carries over — its monotone clamp is
            # per region identity, and resetting it here used to let a
            # lagging joiner roll a region's reference back.  Every new
            # slot reads its region's migrated value regardless of
            # which old shard (or fresh row) fills it.
            old_rwm = host.region_watermark.reshape(old_r, -1)[:, 0]
            new_host = new_host._replace(
                region_watermark=np.repeat(old_rwm, new_ee).astype(
                    np.float32))
            # fog budgets survive verbatim (the [R] vector is unchanged)
            # and the slot ceiling only ever grows: shrinking it would
            # clamp control-plane-grown budgets, firing spurious
            # fog_budget_resize events on the next tick.  A non-binding
            # config (no fog budget opted in) must keep tracking the
            # new worst-case demand, or an edge-width grow would start
            # shedding where flat semantics promise it never does.
            self._fog_slots = max(self._fog_slots, self.cfg.fog_slots)
            if self.cfg.fog_budget is None \
                    and self.cfg.fog_budget_max is None:
                self._region_budget = np.maximum(
                    self._region_budget,
                    np.int32(self.cfg.initial_fog_budget))
        else:
            # region-count change: regions are re-formed by the
            # renumbering, so the per-region watermark restarts from
            # scratch (it re-derives on the next tick; its monotone
            # clamp is per region *identity*, which this resize does
            # not preserve).  The fleet reference keeps its migrated
            # (replicated) value — fleet identity does persist
            new_host = new_host._replace(region_watermark=np.full(
                new_e, np.finfo(np.float32).min, np.float32))
            # fog budgets re-derive for the new region set: the ceiling
            # tracks the new config, surviving regions keep their
            # budget clamped to it, new regions start at the initial
            self._fog_slots = self.cfg.fog_slots
            rbud = np.full(new_r, min(self.cfg.initial_fog_budget,
                                      self._fog_slots), np.int32)
            lap = min(old_r, new_r)
            rbud[:lap] = np.minimum(self._region_budget[:lap],
                                    self._fog_slots)
            self._region_budget = rbud

        self._healthy = np.asarray(
            [self._healthy[k] if k is not None else True for k in keep])
        self._active = np.asarray(
            [self._active[k] if k is not None else True for k in keep])
        # the latency histogram survives the remesh, but its buffer is
        # committed to the OLD device set — move it to the new mesh
        self._lat_hist = self._place(jax.device_get(self._lat_hist), P())
        # the lineage banks are per-shard state: fold departed rows into
        # their counter-fold survivor (histogram merge — totals survive
        # the shrink), then renumber by keep (joiners start zeroed)
        lin = np.array(np.asarray(jax.device_get(self._lineage)))
        for src, dst in fold_counters.items():
            lin[dst] = OL.histogram_merge(lin[dst], lin[src])
        self._lineage = self._place(np.stack(
            [lin[k] if k is not None else np.zeros_like(lin[0])
             for k in keep]), self._shard_spec())
        self._remeshes += 1
        self._build()                          # one re-trace, next step
        return self._place(new_host, self._shard_spec()), departed
