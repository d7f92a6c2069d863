"""Cross-shard federation primitives for the edge fleet (run under
``shard_map`` on the ``"edge"`` mesh axis).

Three fleet-wide agreements turn E independent edge shards into one
system:

* **Watermark**: the fleet watermark is the *minimum* of the per-shard
  running max event times (the stream-SQL rule: a window may only
  close once *every* shard has seen past it, so a lagging shard holds
  back lateness-dropping fleet-wide).
* **Escalation routing**: every rule-escalated window record gets a
  deterministic *global slot* (shard-major order, via one all_gather
  of per-shard counts) and rides a **single all-to-all** to core rank
  ``slot % num_core`` — the paper's multi-hop post() as one collective,
  same machinery as ``core.routing`` MoE dispatch.
* **Core budget**: the core sub-mesh processes the first
  ``core_budget`` global slots per step, *fleet-level*, enforced after
  the all-to-all from the same all_gathered counts (no flag channel on
  the wire).  Overflow windows keep their edge results — the paper's
  graceful-degradation trade, now a fleet-wide budget instead of a
  per-device capacity.

Everything here is a pure fixed-shape function: the whole fleet tick
(per-shard ingest -> windows -> rules, federation, core processing,
result scatter-back) stays inside one jit trace / one XLA executable.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import routing as RT
from repro.stream.fleet.routing import (fog_recv_occupancy,
                                        region_survivor_counts)


def fleet_watermark(max_ts: jnp.ndarray, axis_name,
                    healthy: jnp.ndarray | None = None,
                    active: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fleet watermark = min over shards of the per-shard max event
    time.  Lagging shards hold back window close everywhere.

    ``healthy``: optional per-shard bool (this shard's flag, a traced
    operand from the host control plane).  Flagged shards are excluded
    from the min — a stalled shard can no longer freeze window close
    fleet-wide; its own late records are counted (``late_excluded``)
    and processed against its local watermark, never silently dropped.

    ``active``: optional per-shard bool membership flag (also a traced
    operand).  A shard that left the mesh contributes *nothing*: its
    frozen max must never hold the reference back, and unlike an
    unhealthy shard it has no catch-up path of its own.  The fallback
    is layered — min over healthy&active shards; if none, min over
    active shards; if the whole fleet is inactive (a host bookkeeping
    bug, not a reachable steady state), the plain min is the only
    consistent reference left."""
    if healthy is None and active is None:
        return jax.lax.pmin(max_ts, axis_name)
    ones = jnp.ones((), bool)
    h = ones if healthy is None else healthy.astype(bool)
    a = ones if active is None else active.astype(bool)
    ha = h & a
    # one stacked pmin, not five collectives: [healthy&active min,
    # active min, plain min, 0-iff-any-healthy&active, 0-iff-any-active]
    # — the mask paths must not break the fleet tick's
    # one-collective-per-exchange discipline
    big = jnp.asarray(jnp.finfo(jnp.float32).max, max_ts.dtype)
    f = max_ts.dtype
    vec = jnp.stack([jnp.where(ha, max_ts, big), jnp.where(a, max_ts, big),
                     max_ts, 1.0 - ha.astype(f), 1.0 - a.astype(f)])
    m = jax.lax.pmin(vec, axis_name)
    return jnp.where(m[3] < 0.5, m[0], jnp.where(m[4] < 0.5, m[1], m[2]))


def tiered_watermark(max_ts: jnp.ndarray, region_axis, edge_axis,
                     healthy: jnp.ndarray | None = None,
                     active: jnp.ndarray | None = None
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Layered fleet watermark over a 2-D ``(region, edge)`` mesh:
    returns ``(fleet_wm, region_wm)``.

    The region watermark applies :func:`fleet_watermark`'s layered
    healthy&active -> active -> plain fallback over the *edge* axis
    only — the fog tier's close reference, replicated within a region.
    The fleet watermark then layers the same fallback over the *region*
    axis: regions with any healthy&active member first; if none
    anywhere, regions with any active member; a fully-inactive fleet
    falls back to the plain min.  With one region this reduces exactly
    to :func:`fleet_watermark`, and with every shard healthy & active
    both tiers collapse to the flat fleet's plain min — the oracle
    equality the region tests pin.

    Two stacked pmins total (one per mesh axis) — the same
    one-collective-per-exchange discipline as the flat path."""
    ones = jnp.ones((), bool)
    h = ones if healthy is None else healthy.astype(bool)
    a = ones if active is None else active.astype(bool)
    ha = h & a
    big = jnp.asarray(jnp.finfo(jnp.float32).max, max_ts.dtype)
    f = max_ts.dtype
    vec = jnp.stack([jnp.where(ha, max_ts, big), jnp.where(a, max_ts, big),
                     max_ts, 1.0 - ha.astype(f), 1.0 - a.astype(f)])
    m = jax.lax.pmin(vec, edge_axis)
    region_wm = jnp.where(m[3] < 0.5, m[0], jnp.where(m[4] < 0.5, m[1],
                                                      m[2]))
    # region tier: m[3] is 0 iff this region has any healthy&active
    # member, m[4] 0 iff any active member — the per-region occupancy
    # flags ride the second pmin alongside the candidate minima
    fvec = jnp.stack([jnp.where(m[3] < 0.5, region_wm, big),
                      jnp.where(m[4] < 0.5, region_wm, big),
                      region_wm, m[3], m[4]])
    fm = jax.lax.pmin(fvec, region_axis)
    fleet_wm = jnp.where(fm[3] < 0.5, fm[0], jnp.where(fm[4] < 0.5, fm[1],
                                                       fm[2]))
    return fleet_wm, region_wm


def layered_min_ref(max_ts, healthy=None, active=None):
    """Host-side numpy reference of one layered watermark level (the
    healthy&active -> active -> plain fallback) — the oracle the
    hypothesis properties compare the device code against."""
    max_ts = np.asarray(max_ts, np.float64)
    h = np.ones(max_ts.shape, bool) if healthy is None \
        else np.asarray(healthy, bool)
    a = np.ones(max_ts.shape, bool) if active is None \
        else np.asarray(active, bool)
    ha = h & a
    if ha.any():
        return float(max_ts[ha].min())
    if a.any():
        return float(max_ts[a].min())
    return float(max_ts.min())


def tiered_watermark_ref(max_ts, healthy=None, active=None):
    """Host-side numpy reference of :func:`tiered_watermark`:
    ``max_ts``/masks are [R, E]; returns ``(fleet_wm, [R] region_wms)``.
    """
    max_ts = np.asarray(max_ts, np.float64)
    r, _ = max_ts.shape
    h = np.ones(max_ts.shape, bool) if healthy is None \
        else np.asarray(healthy, bool)
    a = np.ones(max_ts.shape, bool) if active is None \
        else np.asarray(active, bool)
    region = np.asarray([layered_min_ref(max_ts[i], h[i], a[i])
                         for i in range(r)])
    has_ha = (h & a).any(axis=1)
    has_a = a.any(axis=1)
    if has_ha.any():
        fleet = region[has_ha].min()
    elif has_a.any():
        fleet = region[has_a].min()
    else:
        fleet = region.min()
    return float(fleet), region


class FederationStats(NamedTuple):
    """Per-step escalation-exchange counters (int32 scalars)."""
    escalations_sent: jnp.ndarray   # this shard's records routed out
    core_received: jnp.ndarray      # records landing on this core rank
    core_processed: jnp.ndarray     # of those, under the fleet budget
    fleet_escalations: jnp.ndarray  # fleet total this step (replicated)
    fleet_overflow: jnp.ndarray     # fleet total beyond budget (replicated)


def federate_escalations(records: jnp.ndarray, escalate: jnp.ndarray,
                         run_core: Callable, *, axis_name,
                         num_shards: int, num_core: int, core_budget,
                         capacity: int, core_slots: int | None = None
                         ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                                    FederationStats]:
    """Route escalated records to the core sub-mesh, process under the
    fleet budget, scatter results back — one all-to-all each way.

    records: [N, R] this shard's window records (edge-stage outputs);
    escalate: [N] bool; run_core: compact [C, R] -> ([C, R] outputs,
    [C, F] features) — the pipeline's core stage.  ``capacity`` is the
    per-(src, dest) slot count of the exchange buffer (>=
    ceil(N / num_core) guarantees no send-side shed).

    ``core_budget`` may be a *traced* int32 scalar: the budget test and
    the overflow counter are data, not shape.  ``core_slots`` (static,
    defaults to ``core_budget`` which must then be a Python int) is the
    shape ceiling — the per-core-rank compact batch holds
    ``ceil(core_slots / num_core)`` rows, so any budget value in
    ``[0, core_slots]`` runs on the same trace and an elastic resize
    between ticks recompiles nothing.

    Returns ([N, R] core outputs, [N, F] core features, [N] bool
    processed, stats).  ``processed`` marks the records that actually
    got core compute; the rest keep their edge results.
    """
    if core_slots is None:
        core_slots = int(core_budget)
    core_budget = jnp.asarray(core_budget, jnp.int32)
    n, r = records.shape
    esc = escalate.astype(bool)
    my_count = jnp.sum(esc.astype(jnp.int32))
    # one tiny all_gather of counts gives every shard the full global
    # slot layout: send plan, receive validity, and the budget test are
    # all pure index arithmetic from here on
    counts = jax.lax.all_gather(my_count, axis_name)       # [E]
    ridx = jax.lax.axis_index(axis_name).astype(jnp.int32)
    offset = jnp.sum(jnp.where(jnp.arange(num_shards) < ridx, counts, 0))
    plan, g = RT.escalation_plan(esc, offset, num_shards, num_core, capacity)

    # bucket num_shards is the plan's shed row (non-escalated items);
    # it never rides the wire
    with jax.named_scope("obs:all_to_all_out"):
        send = RT.scatter_to_buckets(records, plan, num_shards + 1,
                                     capacity)[:num_shards]
        recv = RT.all_to_all_route(send, axis_name)        # [E, cap, R]

    under, occupied, _ = RT.escalation_recv_slots(
        counts, ridx, num_core, capacity, core_budget)
    # compact the under-budget records: flat (src, slot) order is
    # ascending global slot, so "first core_budget fleet-wide" is
    # exactly what survives, deterministically
    c_core = max(1, -(-core_slots // num_core))
    with jax.named_scope("obs:core_compute"):
        full_out, full_feats, done_mask = RT.compact_apply(
            run_core, recv.reshape(num_shards * capacity, r),
            under.reshape(-1), c_core)
    f = full_feats.shape[1]
    done = done_mask.astype(records.dtype)

    with jax.named_scope("obs:all_to_all_back"):
        payload = jnp.concatenate(
            [full_out, full_feats, done[:, None]],
            axis=1).reshape(num_shards, capacity, r + f + 1)
        back = RT.all_to_all_route(payload, axis_name)     # [E, cap, R+F+1]
        resp = RT.gather_from_buckets(back, plan)          # [N, R+F+1]
    core_out = resp[:, :r]
    core_feats = resp[:, r:r + f]
    processed = (resp[:, -1] > 0.5) & plan.keep

    total = jnp.sum(counts)
    stats = FederationStats(
        escalations_sent=my_count,
        core_received=jnp.sum(occupied.astype(jnp.int32)),
        core_processed=jnp.sum(done_mask.astype(jnp.int32)),
        fleet_escalations=total,
        fleet_overflow=jnp.maximum(0, total - core_budget),
    )
    return core_out, core_feats, processed, stats


class LineageTaps(NamedTuple):
    """Per-hop lineage measurement points of the tiered exchange (this
    shard's view, inside the shard_map).  Stamps are the records'
    ingest wall times (birth, seconds since the executor epoch); masks
    select the buffer cells actually occupied.  ``hop1`` populates only
    on fog columns (edge columns ``0..num_core-1``); ``hop2`` only on
    region 0's core ranks.  Latency = the tick's ``now`` minus the
    stamp — tick-quantized like every lineage stage."""
    hop1_birth: jnp.ndarray        # [E * edge_capacity] f32 stamps
    hop1_mask: jnp.ndarray         # [E * edge_capacity] bool occupancy
    hop2_birth: jnp.ndarray        # [R * cross_capacity] f32 stamps
    hop2_mask: jnp.ndarray         # [R * cross_capacity] bool occupancy


class TieredStats(NamedTuple):
    """Per-step counters of the two-hop (edge -> fog -> cloud)
    escalation exchange (int32 scalars)."""
    escalations_sent: jnp.ndarray    # this shard's fog-budget survivors
    fog_shed: jnp.ndarray            # this shard's candidates shed by the
    #                                  region (fog) budget
    core_received: jnp.ndarray       # records landing on this core rank
    core_processed: jnp.ndarray      # of those, under the fleet budget
    region_escalations: jnp.ndarray  # region candidate total (replicated
    #                                  within the region)
    fleet_escalations: jnp.ndarray   # fleet survivor total (replicated)
    fleet_overflow: jnp.ndarray      # fleet survivors beyond the core
    #                                  budget (replicated)


def federate_escalations_tiered(
        records: jnp.ndarray, escalate: jnp.ndarray, run_core: Callable, *,
        region_axis, edge_axis, num_regions: int, edges_per_region: int,
        num_core: int, region_budget, core_budget, edge_capacity: int,
        cross_capacity: int, core_slots: int, birth: jnp.ndarray | None = None
        ):
    """Two-hop escalation exchange over the ``(region, edge)`` mesh:
    fog pre-aggregation on the edge axis, then only region survivors
    cross the region axis to the core sub-mesh.

    Slot discipline (the flat path's determinism, one tier up):

    1. one all_gather of counts on the **edge** axis gives every shard
       its region's candidate layout; candidates get *region-local*
       slots (edge-major) and the first ``region_budget`` survive the
       fog budget — shed candidates keep their edge results, counted in
       ``fog_shed``, and never ride any wire;
    2. one all_gather of survivor totals on the **region** axis turns
       region-local slots into *global* slots (region-major — with a
       non-binding fog budget these are exactly the flat fleet's
       shard-major slots, which is the bit-for-bit oracle equality);
    3. hop 1: survivors ride one intra-region all-to-all to fog column
       ``g % num_core`` (edge columns ``0..num_core-1``), buffer
       ``[E, edge_capacity, row]``;
    4. each fog column compacts its received survivors (flat receive
       order is ascending global slot) into ``[cross_capacity, row]``
       — ``cross_capacity`` derives from the fog-budget ceiling, NOT
       from E, so hop 2 stops scaling with fleet width;
    5. hop 2: one all-to-all on the region axis delivers every region's
       compact batch to region 0 (the cloud), where receive validity
       and the ``core_budget`` test are recomputed arithmetically from
       the gathered survivor totals — no flag channel on the wire —
       and the results ride the same two hops back.

    ``region_budget`` and ``core_budget`` may be traced int32 scalars
    (``region_budget`` is this region's own budget — per-region values
    enter as a sharded operand); ``edge_capacity``, ``cross_capacity``
    and ``core_slots`` are the static shape ceilings.  Any budget
    values within the ceilings run on the same trace.

    ``birth``: optional [N] f32 ingest stamps (lineage).  When given,
    the stamp rides the wire as one extra trailing record column (the
    only wire-format change: zero extra collectives), ``run_core`` is
    fed the *un*-widened records, and the return grows a fifth element
    — :class:`LineageTaps` with the stamps + occupancy masks observed
    at each hop's receive side.

    Returns ([N, R] core outputs, [N, F] core features, [N] bool
    processed, :class:`TieredStats`[, :class:`LineageTaps`]).
    """
    ee, rr = edges_per_region, num_regions
    n, r = records.shape
    if birth is not None:
        # the stamp is wire metadata, not a record column: widen the
        # wire rows, strip before the core fn so its input width (and
        # therefore its output shapes) are unchanged
        records = jnp.concatenate(
            [records, jnp.asarray(birth, records.dtype)[:, None]], axis=1)
        core_fn = lambda b: run_core(b[:, :r])          # noqa: E731
    else:
        core_fn = run_core
    rw = records.shape[1]                               # wire row width
    region_budget = jnp.asarray(region_budget, jnp.int32)
    core_budget = jnp.asarray(core_budget, jnp.int32)
    esc = escalate.astype(bool)
    my_count = jnp.sum(esc.astype(jnp.int32))
    counts = jax.lax.all_gather(my_count, edge_axis)           # [E]
    eidx = jax.lax.axis_index(edge_axis).astype(jnp.int32)
    ridx = jax.lax.axis_index(region_axis).astype(jnp.int32)
    off_e = jnp.sum(jnp.where(jnp.arange(ee) < eidx, counts, 0))

    # fog budget: candidates hold region-local slots off_e + k (edge-
    # major); the first region_budget survive.  Slots are dense, so a
    # shard's shed candidates are always a suffix of its own — the
    # survivor prefix keeps candidate-local indices unchanged
    e32 = esc.astype(jnp.int32)
    q = off_e + jnp.cumsum(e32) - e32                          # [N] slots
    surv = esc & (q < region_budget)
    surv_counts = region_survivor_counts(counts, region_budget)  # [E]
    my_surv = jnp.sum(surv.astype(jnp.int32))
    region_total = jnp.sum(counts)
    region_surv = jnp.sum(surv_counts)       # = min(total, budget)

    # global slots: region-major over per-region survivor totals
    rs_all = jax.lax.all_gather(region_surv, region_axis)      # [R]
    roff = jnp.sum(jnp.where(jnp.arange(rr) < ridx, rs_all, 0))

    # hop 1: intra-region all-to-all to fog column g % num_core.  The
    # survivor prefix property above means escalation_plan's
    # survivor-local cumsum equals the candidate-local one, so the
    # plan's global slots are exactly roff + q
    plan1, _ = RT.escalation_plan(surv, roff + off_e, ee, num_core,
                                  edge_capacity)
    with jax.named_scope("obs:all_to_all_out"):
        send1 = RT.scatter_to_buckets(records, plan1, ee + 1,
                                      edge_capacity)[:ee]
        recv1 = RT.all_to_all_route(send1, edge_axis)  # [E, cap1, R]

    # fog-column receive validity: survivor counts + this region's
    # global offset give the occupied (src edge, slot) cells
    # arithmetically — no flag channel on the wire, same as the flat
    # path.  Every cell is under the fog budget by construction
    occ1 = fog_recv_occupancy(surv_counts, eidx, roff, num_core,
                              edge_capacity)

    # compact this fog column's survivors: flat (src edge, slot) order
    # is ascending global slot, so the compact batch is globally
    # ordered and bounded by ceil(fog ceiling / num_core)
    with jax.named_scope("obs:fog_compact"):
        occ_flat = occ1.reshape(ee * edge_capacity)
        plan2 = RT.make_plan(jnp.where(occ_flat, 0, 1).astype(jnp.int32),
                             2, cross_capacity)
        compact = RT.scatter_to_buckets(
            recv1.reshape(ee * edge_capacity, rw), plan2, 2,
            cross_capacity)[0]                         # [cap2, RW]

    # hop 2: one cross-region all-to-all; only chunk 0 (to the cloud
    # region) carries payload — the buffer is budget-sized, not E-sized
    with jax.named_scope("obs:all_to_all_region"):
        send2 = jnp.zeros((rr, cross_capacity, rw),
                          records.dtype).at[0].set(compact)
        recv2 = RT.all_to_all_route(send2, region_axis)  # [R, cap2, RW]

    # cloud-side validity + fleet core budget: the same receive-slot
    # arithmetic one tier up — per-region survivor totals play the
    # per-shard counts' role.  Gated on region 0: other regions hold
    # zero-filled buffers that must not claim phantom occupancy
    at_core = ridx == 0
    under2, occ2, _ = RT.escalation_recv_slots(rs_all, eidx, num_core,
                                               cross_capacity, core_budget)
    under2 = under2 & at_core
    occ2 = occ2 & at_core
    c_core = max(1, -(-core_slots // num_core))
    with jax.named_scope("obs:core_compute"):
        full_out, full_feats, done_mask = RT.compact_apply(
            core_fn, recv2.reshape(rr * cross_capacity, rw),
            under2.reshape(-1), c_core)
    f = full_feats.shape[1]
    done = done_mask.astype(records.dtype)

    # the way back: results retrace both hops (cloud -> fog column ->
    # origin shard), un-compacting with the same plans
    with jax.named_scope("obs:all_to_all_back"):
        payload = jnp.concatenate(
            [full_out, full_feats, done[:, None]],
            axis=1).reshape(rr, cross_capacity, r + f + 1)
        back2 = RT.all_to_all_route(payload, region_axis)
        resp_region = back2[0]                   # [cap2, R+F+1] from cloud
        pad = jnp.zeros((2, cross_capacity, r + f + 1),
                        payload.dtype).at[0].set(resp_region)
        flat_back = RT.gather_from_buckets(pad, plan2)
        back1 = RT.all_to_all_route(
            flat_back.reshape(ee, edge_capacity, r + f + 1), edge_axis)
        resp = RT.gather_from_buckets(back1, plan1)          # [N, R+F+1]
    core_out = resp[:, :r]
    core_feats = resp[:, r:r + f]
    processed = (resp[:, -1] > 0.5) & plan1.keep

    fleet_surv = jnp.sum(rs_all)
    stats = TieredStats(
        escalations_sent=my_surv,
        fog_shed=my_count - my_surv,
        core_received=jnp.sum(occ2.astype(jnp.int32)),
        core_processed=jnp.sum(done_mask.astype(jnp.int32)),
        region_escalations=region_total,
        fleet_escalations=fleet_surv,
        fleet_overflow=jnp.maximum(0, fleet_surv - core_budget),
    )
    if birth is None:
        return core_out, core_feats, processed, stats
    taps = LineageTaps(
        hop1_birth=recv1.reshape(ee * edge_capacity, rw)[:, -1],
        hop1_mask=occ1.reshape(-1),
        hop2_birth=recv2.reshape(rr * cross_capacity, rw)[:, -1],
        hop2_mask=occ2.reshape(-1),
    )
    return core_out, core_feats, processed, stats, taps


def allreduce_metrics(metrics, axis_name):
    """All-reduce a NamedTuple of counters over the fleet axis.  Scalar
    leaves ride ONE stacked psum (not one collective per counter);
    array-valued leaves (the [D] per-field ``drift_counts``) can't join
    the stack — shapes differ — so each gets its own psum.  A field
    left None (``StreamMetrics.keyed`` without a keyed plan) stays so."""
    leaves = list(metrics)
    scalar = [i for i, v in enumerate(leaves)
              if v is not None and jnp.ndim(v) == 0]
    out = list(leaves)
    if scalar:
        tot = jax.lax.psum(jnp.stack([leaves[i] for i in scalar]),
                           axis_name)
        for j, i in enumerate(scalar):
            out[i] = tot[j]
    for i in range(len(leaves)):
        if i not in scalar and leaves[i] is not None:
            out[i] = jax.lax.psum(leaves[i], axis_name)
    return type(metrics)(*out)
