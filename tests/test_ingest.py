"""Unified ingest admission lane: the exactly-once oracle tier.

* ``kernels/dedupe_window`` jnp ops vs the pure-numpy reference,
  bit-for-bit (uint32 hashes), across ring wrap, masked offers, and
  over-full batches.
* Conservation: ``items_offered == items_accepted + items_rejected +
  items_deduped`` under duplicated re-delivery, contract rejects, and
  backpressure — on one trace.
* The bitwise oracle: a dup-laden stream through the dedupe lane
  equals the same stream with duplicates offer-masked away, ring state
  and window outputs bit-for-bit; and the SAME admission feed through
  the staged, fused, and overlapped executor paths is bitwise
  identical (all paths consume one lane).
* Backfill: lateness-exempt, clock-neutral, idempotent under re-run.
* Fleet (subprocess, 8 forced host devices): a leave -> requeue ->
  replay arc where the requeue re-delivers already-replayed batches —
  the double-delivery hole the dedupe lane closes — with EXACT
  ``items_replayed`` / ``items_deduped`` accounting and per-stream
  outputs equal to the healthy-fleet oracle.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pipeline as pipe
from repro.core import rules
from repro.kernels.dedupe_window import (EMPTY_HASH, dedupe_window,
                                         dedupe_window_ref, row_hash,
                                         row_hash_ref, seen_record,
                                         seen_record_ref)
from repro.stream import (AdmissionPlan, DataContract, MODE_BACKFILL,
                          MODE_LIVE, MODE_REPLAY, StreamConfig,
                          StreamExecutor)
from repro.stream import executor as X
from repro.stream import ingest as SI


def _make(admission=None, fused=False, overlap=False, d=3, micro_batch=32,
          window=16, stride=8, capacity=256, lateness=8.0):
    cfg = StreamConfig(micro_batch=micro_batch, window=window,
                       stride=stride, capacity=capacity, lateness=lateness,
                       fused=fused, overlap_ingest=overlap,
                       admission=admission or AdmissionPlan())
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE,
                             priority=1)])

    def edge_fn(p, b):
        return b, b[:, :5]

    def core_fn(p, b):
        return b + 100.0, b[:, :5]

    p = pipe.two_tier_pipeline(edge_fn, core_fn, engine, core_capacity=2)
    ex = StreamExecutor(cfg, engine, p)
    return ex, ex.init_state(d)


# ---- dedupe-window kernel vs the numpy oracle ----------------------------

@pytest.mark.parametrize("n,k", [
    (1, 1),       # minimal
    (5, 0),       # window disabled: everything offered is fresh
    (8, 4),       # window smaller than the batch
    (16, 16),     # exact fit
    (40, 3),      # over-full batch: ring keeps only the last K
    (7, 32),      # window larger than several batches (wrap later)
])
def test_dedupe_kernel_matches_ref(rng, n, k):
    seen_o = jnp.full((k,), EMPTY_HASH, jnp.uint32)
    pos_o = jnp.zeros((), jnp.int32)
    seen_r = np.full((k,), np.uint32(EMPTY_HASH), np.uint32)
    pos_r = 0
    prev = None
    for _ in range(5):
        rows = rng.standard_normal((n, 4)).astype(np.float32)
        if prev is not None and n >= 2:
            rows[0] = prev[-1]          # cross-batch re-delivery
            rows[-1] = rows[n // 2]     # intra-batch duplicate
        prev = rows
        offered = rng.random(n) < 0.8
        h_o = row_hash(jnp.asarray(rows))
        h_r = row_hash_ref(rows)
        np.testing.assert_array_equal(np.asarray(h_o), h_r)
        fresh_o, dup_o = dedupe_window(h_o, jnp.asarray(offered), seen_o)
        fresh_r, dup_r = dedupe_window_ref(h_r, offered, seen_r)
        np.testing.assert_array_equal(np.asarray(fresh_o), fresh_r)
        np.testing.assert_array_equal(np.asarray(dup_o), dup_r)
        # simulated backpressure: only a prefix of the fresh rows (in
        # offer order) is accepted — exactly the enqueue contract
        n_acc = int(rng.integers(0, int(fresh_r.sum()) + 1))
        rank = np.cumsum(fresh_r) - 1
        accepted = fresh_r & (rank < n_acc)
        seen_o, pos_o = seen_record(seen_o, pos_o, h_o,
                                    jnp.asarray(accepted))
        seen_r, pos_r = seen_record_ref(seen_r, pos_r, h_r, accepted)
        np.testing.assert_array_equal(np.asarray(seen_o), seen_r)
        assert int(pos_o) == int(pos_r)


def _case_collision_heavy(rng):
    """Rows drawn from 4 distinct rows: nearly every hash repeats, some
    already sit in the ring."""
    base = rng.standard_normal((4, 4)).astype(np.float32)
    h = row_hash_ref(base[rng.integers(0, 4, 256)])
    seen = np.full((32,), np.uint32(EMPTY_HASH), np.uint32)
    seen[:2] = row_hash_ref(base[:1])[0], h[7]
    return h, rng.random(256) < 0.7, seen


def _case_unoffered_first(rng):
    """A non-offered slot holding the same id as a later offered one must
    not make that later row a duplicate."""
    rows = rng.standard_normal((6, 4)).astype(np.float32)
    rows[4] = rows[1]
    rows[5] = rows[3]
    offered = np.array([1, 0, 1, 0, 1, 1], bool)
    seen = np.full((2,), np.uint32(EMPTY_HASH), np.uint32)
    return row_hash_ref(rows), offered, seen


def _case_seen_and_repeated(rng):
    """An id in the ring that the batch also repeats: every offered copy
    is a duplicate, the first included."""
    rows = rng.standard_normal((12, 4)).astype(np.float32)
    rows[[2, 5, 9]] = rows[0]
    h = row_hash_ref(rows)
    seen = np.full((8,), np.uint32(EMPTY_HASH), np.uint32)
    seen[3] = h[0]
    return h, np.ones(12, bool), seen


def _case_empty_slots(rng):
    """A ring mostly of ``EMPTY_HASH`` slots, several of them, among real
    ids: empty slots match no row (row ids are never ``EMPTY_HASH``)."""
    rows = rng.standard_normal((20, 4)).astype(np.float32)
    h = row_hash_ref(rows)
    seen = np.full((16,), np.uint32(EMPTY_HASH), np.uint32)
    seen[[1, 6, 11]] = h[[3, 8, 15]]
    return h, rng.random(20) < 0.9, seen


def _case_deployment_ring(rng):
    """N = 4,096 against a full K = 1,024 ring, with ring hits and
    in-batch repeats."""
    rows = rng.standard_normal((4096, 4)).astype(np.float32)
    rows[rng.integers(0, 4096, 300)] = rows[rng.integers(0, 4096, 300)]
    h = row_hash_ref(rows)
    seen = row_hash_ref(rng.standard_normal((1024, 4)).astype(np.float32))
    seen[rng.permutation(1024)[:100]] = h[rng.integers(0, 4096, 100)]
    return h, rng.random(4096) < 0.95, seen


@pytest.mark.parametrize("case", [
    _case_collision_heavy, _case_unoffered_first, _case_seen_and_repeated,
    _case_empty_slots, _case_deployment_ring],
    ids=lambda c: c.__name__.removeprefix("_case_"))
def test_dedupe_sorted_membership_matches_ref(rng, case):
    """The sort-based membership test against the oracle on the inputs
    its ordering argument depends on."""
    h, offered, seen = case(rng)
    fresh_o, dup_o = dedupe_window(jnp.asarray(h), jnp.asarray(offered),
                                   jnp.asarray(seen))
    fresh_r, dup_r = dedupe_window_ref(h, offered, seen)
    np.testing.assert_array_equal(np.asarray(fresh_o), fresh_r)
    np.testing.assert_array_equal(np.asarray(dup_o), dup_r)
    if case is _case_unoffered_first:
        np.testing.assert_array_equal(fresh_r, offered)
    if case is _case_seen_and_repeated:
        assert dup_r[[0, 2, 5, 9]].all()


def test_dedupe_has_no_quadratic_intermediate():
    """The membership test lowers without an ``[N, N]`` or ``[N, K]``
    tensor at N = 4,096, K = 1,024: the quadratic compare stays gone."""
    text = jax.jit(dedupe_window).lower(
        jnp.zeros((4096,), jnp.uint32), jnp.ones((4096,), bool),
        jnp.zeros((1024,), jnp.uint32)).as_text()
    assert "sort" in text
    for shape in ("4096x4096", "4096x1024", "1024x4096"):
        assert shape not in text


def test_row_hash_ignores_nothing(rng):
    """Any single-bit feature change, and any timestamp change, gives a
    different event id; a verbatim re-send gives the same one."""
    rows = rng.standard_normal((4, 5)).astype(np.float32)
    h = row_hash_ref(rows)
    assert (h != np.uint32(EMPTY_HASH)).all()
    np.testing.assert_array_equal(row_hash_ref(rows.copy()), h)
    bump = rows.copy()
    bump[2, 3] = np.nextafter(bump[2, 3], np.inf, dtype=np.float32)
    assert row_hash_ref(bump)[2] != h[2]
    assert (row_hash_ref(bump)[[0, 1, 3]] == h[[0, 1, 3]]).all()


# ---- conservation + contract gating --------------------------------------

def test_admission_conservation_under_duplicates(rng):
    plan = AdmissionPlan(dedupe_window=128,
                         contract=DataContract(lo=(-4.0,) * 3,
                                               hi=(4.0,) * 3))
    ex, state = _make(admission=plan)
    t0 = 0.0
    last = None
    for step in range(8):
        items = rng.standard_normal((32, 3)).astype(np.float32)
        ts = np.asarray(t0 + np.arange(32), np.float32)
        if step % 3 == 2 and last is not None:
            items, ts = last               # verbatim re-delivery tick
        else:
            t0 += 32
            if step == 4:
                items[:5, 1] = np.nan      # contract violations
            last = (items, ts)
        state, _ = ex.step(state, jnp.asarray(items), jnp.asarray(ts))
    m = state.metrics.as_dict()
    assert m["items_offered"] == 8 * 32
    assert m["items_offered"] == (m["items_accepted"] + m["items_rejected"]
                                  + m["items_deduped"])
    # two full re-delivery ticks, EXCEPT the 5 NaN rows of step 4: a
    # rejected row is never recorded as seen (it stays re-sendable), so
    # its re-delivery at step 5 is rejected again, not deduped
    assert m["items_deduped"] == 2 * 32 - 5
    assert m["items_rejected"] >= 2 * 5    # NaN rows, twice (+ range hits)
    assert m["drift_counts"][1] >= 2 * 5   # attributed to field 1
    assert ex.trace_count == 1


def test_contract_per_field_drift(rng):
    plan = AdmissionPlan(contract=DataContract(lo=(-100.0, -100.0, 0.0),
                                               hi=(100.0, 100.0, 100.0)))
    ex, state = _make(admission=plan)
    items = rng.standard_normal((32, 3)).astype(np.float32)
    items[:, 2] = np.abs(items[:, 2])      # field 2 in contract
    items[:3, 0] = np.inf                  # 3 non-finite in field 0
    items[:7, 2] = -1.0                    # 7 range violations in field 2
    ts = np.arange(32, dtype=np.float32)
    state, _ = ex.step(state, jnp.asarray(items), jnp.asarray(ts))
    m = state.metrics.as_dict()
    # drift counts FIELD violations (rows 0-2 violate both fields -> 10
    # violations); items_rejected counts ROWS (the union -> 7 rows)
    assert m["drift_counts"] == [3, 0, 7]
    assert m["items_rejected"] == 7
    assert m["items_accepted"] == 32 - 7


# ---- the bitwise oracle ---------------------------------------------------

def test_dedupe_equals_offer_masked_oracle(rng):
    """Lane A: dup-laden offers through the dedupe window.  Lane B: the
    same offers with the duplicate rows masked out of the offer (the
    dedup'd healthy oracle).  Ring state, carry, window outputs, and
    the recorded seen-window must agree bit-for-bit every tick."""
    plan = AdmissionPlan(dedupe_window=128)
    ex, sa = _make(admission=plan)
    _, sb = _make(admission=plan)
    cfg = ex.cfg
    engine = ex.engine
    seen = np.full((128,), np.uint32(EMPTY_HASH), np.uint32)
    pos = 0
    t0, last = 0.0, None
    for step in range(7):
        items = rng.standard_normal((32, 3)).astype(np.float32)
        ts = np.asarray(t0 + np.arange(32), np.float32)
        if step % 2 == 1 and last is not None:
            # half-dup tick: first 16 rows re-sent, rest fresh
            items[:16], ts[:16] = last[0][:16], last[1][:16]
        t0 += 32
        last = (items.copy(), ts.copy())
        # ground-truth fresh mask via the numpy oracle
        h = row_hash_ref(np.concatenate([ts[:, None], items], axis=1))
        fresh, _ = dedupe_window_ref(h, np.ones(32, bool), seen)
        seen, pos = seen_record_ref(seen, pos, h, fresh)
        ia = X.ingest_and_window(cfg, engine, sa, jnp.asarray(items),
                                 jnp.asarray(ts), now=0.0)
        ib = X.ingest_and_window(cfg, engine, sb, jnp.asarray(items),
                                 jnp.asarray(ts),
                                 offer_mask=jnp.asarray(fresh), now=0.0)
        for leaf in ("aggregates", "window_count", "features",
                     "consequence", "emit", "carry", "carry_valid",
                     "max_ts", "n_accepted", "n_dequeued", "n_late"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ia, leaf)),
                np.asarray(getattr(ib, leaf)), err_msg=leaf)
        np.testing.assert_array_equal(np.asarray(ia.rb.buf),
                                      np.asarray(ib.rb.buf))
        assert int(ia.rb.head) == int(ib.rb.head)
        assert int(ia.rb.tail) == int(ib.rb.tail)
        # both lanes recorded the same accepted hashes
        np.testing.assert_array_equal(np.asarray(ia.adm.seen),
                                      np.asarray(ib.adm.seen))
        np.testing.assert_array_equal(np.asarray(ia.adm.seen), seen)
        assert int(ia.n_deduped) == int((~fresh).sum())
        assert int(ib.n_deduped) == 0
        sa = X.StreamState(rb=ia.rb, carry=ia.carry,
                           carry_valid=ia.carry_valid, max_ts=ia.max_ts,
                           metrics=sa.metrics, adm=ia.adm)
        sb = X.StreamState(rb=ib.rb, carry=ib.carry,
                           carry_valid=ib.carry_valid, max_ts=ib.max_ts,
                           metrics=sb.metrics, adm=ib.adm)


def _admission_feed(rng, steps=9, batch=32, d=3):
    """A feed exercising every lane stage: duplicates, contract
    violations, a backfill tick, and a replay re-send."""
    feed, t0, last = [], 0.0, None
    for step in range(steps):
        items = rng.standard_normal((batch, d)).astype(np.float32)
        ts = np.asarray(t0 + np.arange(batch), np.float32)
        mode = MODE_LIVE
        if step == 3 and last is not None:         # replay re-send
            items, ts = last
            mode = MODE_REPLAY
        elif step == 5:                            # contract violations
            items[:4, 0] = np.nan
            t0 += batch
        elif step == 6:                            # historical backfill
            items = rng.standard_normal((batch, d)).astype(np.float32)
            ts = np.asarray(np.arange(batch), np.float32) - 10_000.0
            mode = MODE_BACKFILL
        else:
            t0 += batch
        last = (items.copy(), ts.copy())
        feed.append((jnp.asarray(items), jnp.asarray(ts), mode))
    return feed


def test_all_executor_paths_share_the_lane(rng):
    """The same dup/contract/backfill feed through the staged, fused,
    and overlapped executors: outputs bitwise identical, admission
    counters identical — one lane, three consumers."""
    plan = AdmissionPlan(dedupe_window=128,
                         contract=DataContract(require_finite=True))
    feed = _admission_feed(rng)
    results = {}
    for name, kw in (("staged", {}), ("fused", {"fused": True}),
                     ("overlap", {"overlap": True})):
        ex, state = _make(admission=plan, **kw)
        state, outs = ex.run(state, feed)
        assert ex.trace_count == 1, (name, ex.trace_count)
        results[name] = (state, outs)
    ref_state, ref_outs = results["staged"]
    ref_m = ref_state.metrics.as_dict()
    assert ref_m["items_deduped"] == 32          # the replay re-send
    assert ref_m["items_backfilled"] == 32
    assert ref_m["items_replayed"] == 0          # all 32 deduped first
    assert ref_m["items_rejected"] == 4
    assert ref_m["drift_counts"] == [4, 0, 0]
    assert ref_m["items_late"] == 0
    for name in ("fused", "overlap"):
        state, outs = results[name]
        assert len(outs) == len(ref_outs), name
        for i, (a, b) in enumerate(zip(outs, ref_outs)):
            for leaf in X.StepOutput._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(a, leaf)),
                    np.asarray(getattr(b, leaf)),
                    err_msg=f"{name} tick {i} {leaf}")
        assert state.metrics.as_dict() == ref_m, name


def test_backfill_exactly_once(rng):
    plan = AdmissionPlan(dedupe_window=256)
    ex, state = _make(admission=plan)
    # live traffic establishes the clock
    for step in range(3):
        items = rng.standard_normal((32, 3)).astype(np.float32)
        ts = np.asarray(step * 32 + np.arange(32), np.float32)
        state, _ = ex.step(state, jnp.asarray(items), jnp.asarray(ts))
    clock = float(state.max_ts)
    old = rng.standard_normal((32, 3)).astype(np.float32)
    old_ts = np.asarray(np.arange(32), np.float32) - 5000.0
    state, _ = ex.step(state, jnp.asarray(old), jnp.asarray(old_ts),
                       mode=MODE_BACKFILL)
    m = state.metrics.as_dict()
    assert m["items_backfilled"] == 32
    assert m["items_late"] == 0                  # lateness-exempt
    assert float(state.max_ts) == clock          # clock-neutral
    # re-running the whole backfill is a no-op: exactly-once
    state, _ = ex.step(state, jnp.asarray(old), jnp.asarray(old_ts),
                       mode=MODE_BACKFILL)
    m2 = state.metrics.as_dict()
    assert m2["items_backfilled"] == 32          # not double-counted
    assert m2["items_deduped"] - m["items_deduped"] == 32
    assert ex.trace_count == 1                   # mode is an operand


def test_overlap_never_launders_modes(rng):
    """A replay/backfill batch staged through the ingest overlap double
    buffer must be delivered WITH its mode: the overlapped run equals
    the direct run bitwise, including the mode-split counters."""
    plan = AdmissionPlan(dedupe_window=128)
    feed = _admission_feed(rng)
    ex_d, sd = _make(admission=plan)
    sd, outs_d = ex_d.run(sd, feed)
    ex_o, so = _make(admission=plan, overlap=True)
    so, outs_o = ex_o.run(so, feed)
    assert len(outs_o) == len(outs_d)
    for a, b in zip(outs_o, outs_d):
        for leaf in X.StepOutput._fields:
            np.testing.assert_array_equal(np.asarray(getattr(a, leaf)),
                                          np.asarray(getattr(b, leaf)),
                                          err_msg=leaf)
    md, mo = sd.metrics.as_dict(), so.metrics.as_dict()
    assert mo == md
    assert mo["items_replayed"] + mo["items_deduped"] > 0
    assert mo["items_backfilled"] == 32


def test_inert_plan_is_statically_free(rng):
    """The default AdmissionPlan adds zero ops: step cost (flops/bytes)
    identical to a config that never heard of the lane."""
    ex, state = _make()
    assert ex.cfg.admission.inert
    assert state.adm.seen.shape == (0,)
    items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
    ts = jnp.asarray(np.arange(32), jnp.float32)
    state, _ = ex.step(state, items, ts)
    m = state.metrics.as_dict()
    assert m["items_deduped"] == 0 and m["items_backfilled"] == 0
    assert ex.trace_count == 1


def test_plan_validation():
    with pytest.raises(ValueError, match="dedupe_window"):
        AdmissionPlan(dedupe_window=-1)
    with pytest.raises(ValueError, match="lo"):
        DataContract(lo=(0.0,), hi=(1.0, 2.0))
    ex, state = _make()
    items = jnp.zeros((32, 3), jnp.float32)
    ts = jnp.arange(32, dtype=jnp.float32)
    with pytest.raises(ValueError, match="not both"):
        X.ingest_and_window(ex.cfg, ex.engine, state, items, ts,
                            replay=jnp.asarray(True),
                            mode=jnp.asarray(MODE_REPLAY, jnp.int32))


# ---- fleet: the leave -> requeue -> replay double-delivery hole ----------

_FLEET_SCRIPT = textwrap.dedent("""
    import collections
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.runtime.elastic import ElasticBudget
    from repro.stream import AdmissionPlan, StreamConfig
    from repro.stream.fleet import (Churn, FaultInjector, FaultSchedule,
                                    FleetConfig, FleetExecutor)
    from repro.stream.fleet.control import FleetController

    D, BATCH, E = 3, 32, 8
    edge_fn = lambda p, b: (b * 1.5, b[:, :5])
    core_fn = lambda p, b: (b + 100.0, b[:, :5])
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE,
                             priority=2)])
    # tumbling windows (batch-granular replay), dedupe window wide
    # enough to remember every batch a backup could see twice
    scfg = StreamConfig(micro_batch=BATCH, window=16, stride=16,
                        capacity=4 * BATCH, lateness=4.0,
                        admission=AdmissionPlan(dedupe_window=8 * BATCH))

    def make_fleet():
        return FleetExecutor(
            FleetConfig(stream=scfg, num_shards=E, num_core=2,
                        core_budget=64),
            engine, pipe.two_tier_pipeline(edge_fn, core_fn, engine))

    T, SHARD, LEAVE, JOIN = 14, 3, 4, 9
    rng = np.random.default_rng(0)
    stream = []
    for t in range(T):
        items = rng.standard_normal((E, BATCH, D)).astype(np.float32)
        items[:, :, 0] += (t % 3 == 0) * 1.5
        ts = np.tile(t * BATCH + np.arange(BATCH, dtype=np.float32),
                     (E, 1))
        stream.append((items, ts))

    def collect(out, e, store):
        emit = np.asarray(out.window_count[e]) > 0
        if emit.any():
            store["agg"].append(np.asarray(out.aggregates[e])[emit])
            store["cons"].append(np.asarray(out.consequence[e])[emit])

    def cat(store):
        return {k: np.concatenate(v) if v else np.zeros((0,))
                for k, v in store.items()}

    # healthy oracle (same dedupe config, no churn, no duplicates)
    orc = make_fleet()
    ostate = orc.init_state(D)
    oracle = [collections.defaultdict(list) for _ in range(E)]
    for t in range(T):
        items, ts = stream[t]
        ostate, out = orc.step(ostate, jnp.asarray(items),
                               jnp.asarray(ts))
        for e in range(E):
            collect(out, e, oracle[e])
    oracle = [cat(o) for o in oracle]

    fx = make_fleet()
    ctl = FleetController(
        fx, budget_policy=ElasticBudget(min_budget=64, max_budget=64))
    sched = FaultSchedule(churn=[Churn(shard=SHARD, leave=LEAVE,
                                       join=JOIN)])
    inj = FaultInjector(sched)
    state = fx.init_state(D)
    churned = [collections.defaultdict(list) for _ in range(E)]
    backups = {}
    dup_rows = 0
    t = 0
    while t < T or inj.pending:
        if t == LEAVE:
            backup = ctl.leave(SHARD)
            assert backup is not None and backup != SHARD
            backups = {SHARD: backup}
        if t == LEAVE + 2:
            # THE HOLE: a requeue (e.g. a remesh payload assembled from
            # the departed ring) re-delivers batches that the replay
            # queue has already drained onto the backup — the same
            # rows, double-counted without the dedupe lane.  Re-push
            # the departed stream's first two churned batches verbatim.
            for tt in (LEAVE, LEAVE + 1):
                items, ts = stream[tt]
                rows = np.concatenate(
                    [ts[SHARD][:, None],
                     np.zeros((BATCH, 1), np.float32),   # stamp: dropped
                     items[SHARD]], axis=1)
                inj.requeue(SHARD, rows, BATCH)
                dup_rows += BATCH
        if t == JOIN:
            ctl.join(SHARD)
        drain = t >= T
        base = stream[t] if not drain else (
            np.zeros((E, BATCH, D), np.float32),
            np.zeros((E, BATCH), np.float32))
        items, ts, offered, replay = inj.inject(t, *base,
                                                fresh=not drain,
                                                backups=backups)
        origin = inj.origin.copy()
        state, out = fx.step(state, jnp.asarray(items), jnp.asarray(ts),
                             offered=jnp.asarray(offered),
                             replay=jnp.asarray(replay))
        ctl.tick(state, step_times=sched.stall_time(t, E))
        for e in range(E):
            if origin[e] >= 0:
                collect(out, e, churned[int(origin[e])])
        t += 1
    assert inj.pending == 0
    churned = [cat(c) for c in churned]
    md = state.metrics.as_dict()

    # exactly-once: the backup replayed one batch per churn tick
    # (LEAVE..JOIN-1 minus the two queue slots burned on the requeued
    # duplicates, which land entirely in items_deduped) — every unique
    # row counted exactly once, every doubled row deduped on arrival
    b = int(backup)
    unique_rep = (JOIN - LEAVE) * BATCH - dup_rows
    assert sum(md["shard"]["items_deduped"]) == dup_rows, \\
        (md["shard"]["items_deduped"], dup_rows)
    assert md["shard"]["items_deduped"][b] == dup_rows
    assert sum(md["shard"]["items_replayed"]) == unique_rep, \\
        (md["shard"]["items_replayed"], unique_rep)
    assert md["shard"]["items_replayed"][b] == unique_rep
    assert md["shard"]["items_late"] == [0] * E
    # conservation, fleet-wide
    f = md["fleet"]
    assert f["items_offered"] == (f["items_accepted"]
                                  + f["items_rejected"]
                                  + f["items_deduped"])

    # per-stream outputs equal the healthy oracle despite the
    # double-delivery: the dedupe lane absorbed the requeue overlap
    for e in range(E):
        assert churned[e]["agg"].shape == oracle[e]["agg"].shape, e
        np.testing.assert_allclose(churned[e]["agg"], oracle[e]["agg"],
                                   rtol=1e-6, atol=1e-6, err_msg=str(e))
        np.testing.assert_array_equal(churned[e]["cons"],
                                      oracle[e]["cons"], err_msg=str(e))
    assert fx.trace_count == 1, fx.trace_count
    print("REQUEUE_DEDUPE_OK")
""")


def test_fleet_requeue_double_delivery_dedupes(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = tmp_path / "fleet_requeue_dedupe.py"
    script.write_text(_FLEET_SCRIPT)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "REQUEUE_DEDUPE_OK" in out.stdout
