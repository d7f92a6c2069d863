"""Unified ingest admission lane: dedupe window, data contracts,
drift counters, and first-class ingest modes (live | replay | backfill).

Real edge fleets re-send.  Producers retry on flaky uplinks, a backup
replays a departed shard's queue, an operator backfills a historical
span — and the paper's pipeline assumes each record arrives exactly
once.  This module is the ONE admission path every executor ingest
lane flows through (``StreamExecutor`` staged, ``fused_tick``, the
``IngestStager`` overlap loop, and the fleet's per-shard tick all call
``stream.executor.ingest_and_window``, which runs this lane between
the wire and the ring buffer):

1. **stamp** — the wire row is ``[event_ts | ingest_wall | features]``
   (``executor.META_COLS``); the admission identity deliberately
   *excludes* the local ``ingest_wall`` stamp, so a re-delivery with a
   fresh stamp still hashes identically;
2. **idempotent dedupe** — FNV-1a event-id hashing over a bounded
   window of the last ``K`` accepted rows (``kernels.dedupe_window``),
   a fixed-shape masked stage: the window ring is a traced ``uint32[K]``
   operand carried in ``StreamState`` exactly like the latency banks,
   so consulting or rotating it never recompiles;
3. **contract validation** — static per-field bounds + finiteness as a
   masked gating stage feeding the existing live-mask, with per-field
   ``drift_counts`` (a violation is evidence the producer's schema
   drifted, so it is *counted per field*, not just dropped);
4. **mode** — an explicit per-tick ingest mode (``MODE_LIVE`` |
   ``MODE_REPLAY`` | ``MODE_BACKFILL``) as a traced int32 operand,
   generalizing the churn replay's lateness-exempt machinery: replay
   and backfill rows are exempt from the late test and never advance
   the local event-time clock, and are accounted separately
   (``items_replayed`` / ``items_backfilled``).

Accounting is conservation-exact per tick::

    items_offered == items_accepted + items_rejected + items_deduped

where ``items_rejected`` covers both contract violations and ring
backpressure, and only rows that actually *entered* the ring are
recorded in the dedupe window (a row bounced by backpressure must be
re-sendable).  Exactly-once under re-delivery follows: a duplicated
stream admits each event once, so any executor path equals the
dedup'd healthy oracle bit-for-bit (``tests/test_ingest.py``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp

from repro.kernels.dedupe_window import (dedupe_window, row_hash,
                                         seen_record)

#: Ingest modes (traced int32 operand — switching modes never
#: recompiles).  ``MODE_REPLAY`` is backup replay of another shard's
#: queue after churn; ``MODE_BACKFILL`` is operator-driven historical
#: reprocessing.  Both are lateness-exempt and clock-neutral; they
#: differ only in accounting.
MODE_LIVE = 0
MODE_REPLAY = 1
MODE_BACKFILL = 2

MODE_NAMES = {MODE_LIVE: "live", MODE_REPLAY: "replay",
              MODE_BACKFILL: "backfill"}


@dataclasses.dataclass(frozen=True)
class DataContract:
    """Static per-field admission bounds (trace constants).

    ``lo`` / ``hi``: optional per-field closed bounds, one entry per
    feature column (length D tuples — hashable, so the contract can
    live on the frozen ``StreamConfig``).  ``require_finite`` rejects
    NaN/Inf payloads.  A row violating ANY field is rejected whole
    (the row never enters the ring); every violated field increments
    that field's drift counter.
    """
    lo: tuple | None = None
    hi: tuple | None = None
    require_finite: bool = True

    def __post_init__(self):
        if self.lo is not None and self.hi is not None \
                and len(self.lo) != len(self.hi):
            raise ValueError(f"lo/hi length mismatch: {len(self.lo)} "
                             f"vs {len(self.hi)}")

    def violations(self, feats: jnp.ndarray) -> jnp.ndarray:
        """[N, D] features -> [N, D] bool per-field violation matrix."""
        viol = jnp.zeros(feats.shape, bool)
        if self.require_finite:
            viol |= ~jnp.isfinite(feats)
        if self.lo is not None:
            viol |= feats < jnp.asarray(self.lo, feats.dtype)[None, :]
        if self.hi is not None:
            viol |= feats > jnp.asarray(self.hi, feats.dtype)[None, :]
        return viol


@dataclasses.dataclass(frozen=True)
class AdmissionPlan:
    """Static admission policy, carried on ``StreamConfig``.

    ``dedupe_window``: K, the number of most-recently-accepted event
    ids remembered (0 disables dedupe — the default, which keeps every
    pre-existing config bit-for-bit on its old path).  Size it to
    cover the producer's redelivery horizon: at least one micro-batch,
    typically a few (see the stream README's sizing note).
    ``contract``: optional :class:`DataContract`.
    """
    dedupe_window: int = 0
    contract: DataContract | None = None

    def __post_init__(self):
        if self.dedupe_window < 0:
            raise ValueError(
                f"dedupe_window must be >= 0, got {self.dedupe_window}")

    @property
    def inert(self) -> bool:
        """No dedupe, no contract: the lane is statically a no-op and
        the executors skip it entirely (zero added ops on the trace)."""
        return self.dedupe_window == 0 and self.contract is None


class AdmissionState(NamedTuple):
    """Traced dedupe-window state, carried in ``StreamState`` (donated
    with it, migrated through a re-mesh with it — a backup keeps its
    dedupe memory across churn)."""
    seen: jnp.ndarray          # [K] uint32 accepted-hash ring
    seen_pos: jnp.ndarray      # [] int32 next write slot


class AdmissionGate(NamedTuple):
    """One tick's admission verdict, computed *before* the ring sees
    the batch."""
    admit: jnp.ndarray         # [N] bool — offer these rows to the ring
    hashes: jnp.ndarray        # [N] uint32 event ids
    n_deduped: jnp.ndarray     # [] int32 offered rows dropped as dups
    n_contract: jnp.ndarray    # [] int32 offered rows failing contract
    drift: jnp.ndarray         # [D] int32 per-field violation counts


def admission_init(plan: AdmissionPlan) -> AdmissionState:
    """Fresh (empty) dedupe window for ``plan``."""
    return AdmissionState(
        seen=jnp.zeros((plan.dedupe_window,), jnp.uint32),
        seen_pos=jnp.zeros((), jnp.int32))


def admission_gate(plan: AdmissionPlan, adm: AdmissionState,
                   ts: jnp.ndarray, items: jnp.ndarray,
                   offer_mask: jnp.ndarray | None,
                   row_dtype: str = "float32") -> AdmissionGate:
    """stamp -> dedupe -> contract, as fixed-shape masked ops.

    The event identity is ``hash(event_ts ++ features)`` — the
    producer's wire content, NOT the local ingest stamp, so a
    redelivery stamped at a later wall time still dedupes.  The wire
    words are ``row_dtype``'s, as the ring holds them: float32 bits, or
    exact int32 words.  Dedupe
    runs first; contract-rejected rows are never recorded in the
    window, so a re-send of a rejected row is judged *fresh* again and
    rejected again by the contract — by design, every delivery of a
    violating row is fresh evidence of producer drift and bumps the
    per-field counters.
    """
    n = items.shape[0]
    offered = jnp.ones((n,), bool) if offer_mask is None \
        else jnp.asarray(offer_mask, bool)
    wire = jnp.concatenate(
        [jnp.asarray(ts, row_dtype)[:, None],
         jnp.asarray(items, row_dtype)], axis=1)
    hashes = row_hash(wire)
    fresh, dup = dedupe_window(hashes, offered, adm.seen)
    if plan.contract is None:
        viol = jnp.zeros(items.shape, bool)
    else:
        viol = plan.contract.violations(jnp.asarray(items, jnp.float32))
    ok = ~jnp.any(viol, axis=1)
    admit = fresh & ok
    return AdmissionGate(
        admit=admit, hashes=hashes,
        n_deduped=jnp.sum(dup.astype(jnp.int32)),
        n_contract=jnp.sum((fresh & ~ok).astype(jnp.int32)),
        drift=jnp.sum(viol & fresh[:, None], axis=0, dtype=jnp.int32))


def admission_record(plan: AdmissionPlan, adm: AdmissionState,
                     gate: AdmissionGate, n_acc: jnp.ndarray
                     ) -> AdmissionState:
    """Fold the rows the ring actually accepted into the dedupe window.

    ``n_acc`` is the enqueue acceptance count; acceptance is a prefix
    of the admitted rows in offer order (the ring's stable-compaction
    contract), so the accepted mask is exact — rows bounced by
    backpressure stay unrecorded and a later re-send of them admits.
    """
    if plan.inert:
        return adm
    rank = jnp.cumsum(gate.admit.astype(jnp.int32)) - 1
    accepted = gate.admit & (rank < n_acc)
    seen, pos = seen_record(adm.seen, adm.seen_pos, gate.hashes, accepted)
    return AdmissionState(seen=seen, seen_pos=pos)
