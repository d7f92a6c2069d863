"""Sharded edge-fleet streaming: fleet items/sec and step latency vs E.

Drives ``FleetExecutor`` — E edge shards as one ``shard_map`` step with
core escalation over a single all-to-all — for E in {1, 4, 8}, as far
as the devices reach, and reports sustained fleet throughput, median and
p99 per-step latency, and the jit trace count (asserted == 1: the whole
fleet tick is one XLA executable).  Emits the same CSV row schema as
``benchmarks/streaming.py``, including the event-time lineage rows
(per-stage ``fleet/E*_lat_*`` percentiles), the warmup-excluded device
step histogram, and the ``fleet/E*_cost`` roofline coordinates from
``obs.costmodel``; a ``fused=1`` lane re-runs the widest shape with
the per-shard fused-tick kernel (``fleet/E<widest>_fused_*`` rows,
counters asserted equal to the staged lane's).

``--faults`` runs the degraded-fleet smoke instead: a
``FleetController`` drives the elastic core budget and the
straggler-aware watermark through a scripted mid-run stall
(``FaultSchedule``), reporting step latency under degradation, the
budget trajectory, the ``late_excluded`` accounting, and the re-trace
bound (``trace_count <= 1 + resizes``, asserted).

``--churn`` runs the membership-churn smoke: a shard leaves the fleet
mid-run, its stream replays on the ``reassignment``-chosen backup, a
joiner takes the slot back, and the fleet then truly re-meshes to one
fewer device.  Asserted end-to-end: per-stream output equals a
healthy-fleet oracle, zero records dropped, ``items_replayed`` matches
an exact host-side recomputation, and ``trace_count <= 1 + retraces +
remeshes`` (the leave/join itself stays on ONE trace — membership is
an operand).

``--regions`` runs the hierarchical-federation smoke: the same
devices arranged as ``(R, E)`` region meshes for R in {1, 2, 4}, at
E >= 2 shards per region, under a fixed per-region fog budget, measuring step latency per shape and
accounting the two-hop exchange volume.  Asserted: cross-region bytes
derive from the fog *budget* and are independent of the region width E
(the flat single-hop exchange grows with E), and every shape runs its
whole measured window on ONE trace.

Every suite runs in the calling process on the devices JAX finds: the
fleet is as wide as ``min(8, jax.device_count())``.  The default suite
runs the shard counts of ``SHARD_COUNTS`` that fit; ``--faults``,
``--churn`` and ``--regions`` need at least 4 devices and are skipped,
with a message, on fewer.  On a CPU host, ask for 8 virtual devices in
the environment before JAX starts:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import sys

D = 16            # sensor feature width
BATCH = 256       # items per shard per micro-batch
STEPS = 100
WARMUP = 5
SHARD_COUNTS = (1, 4, 8)
MAX_SHARDS = SHARD_COUNTS[-1]
MIN_SHARDS = 4    # the faults/churn/regions suites' narrowest fleet


def bench(faults: bool = False, churn: bool = False,
          regions: bool = False):
    import jax
    width = min(MAX_SHARDS, jax.device_count())
    if faults or churn or regions:
        if width < MIN_SHARDS:
            print(f"# fleet: skipping the suite: it needs {MIN_SHARDS} "
                  f"devices, JAX found {jax.device_count()} (on a CPU host "
                  f"set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
                  f"before starting)", file=sys.stderr)
            return
    if churn:
        _churn(width)
    elif faults:
        _faults(width)
    elif regions:
        _regions(width)
    else:
        _widths(width)


def _widths(width: int):
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import row
    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.obs import costmodel as CM
    from repro.stream import StreamConfig
    from repro.stream.fleet import FleetConfig, FleetExecutor

    def edge_fn(p, batch):
        return batch, batch[:, :5]

    def core_fn(p, batch):
        h = batch
        for _ in range(8):
            h = jnp.tanh(h @ p)
        return h, batch[:, :5]

    core_p = jnp.asarray(
        np.random.default_rng(0).standard_normal((5 + D, 5 + D)) * 0.1,
        jnp.float32)
    scfg = StreamConfig(micro_batch=BATCH, window=64, stride=32,
                        capacity=4 * BATCH, lateness=64.0)
    counts = [e for e in SHARD_COUNTS if e <= width]
    for e in counts:
        engine = rules.RuleEngine([
            rules.threshold_rule("hot_mean", 0, ">=", 0.25,
                                 rules.C_SEND_CORE, priority=1),
            rules.threshold_rule("sparse", 4, "<", 8.0,
                                 rules.C_STORE_EDGE, priority=2),
        ])
        p = pipe.two_tier_pipeline(edge_fn, core_fn, engine,
                                   core_params=core_p)
        cfg = FleetConfig(stream=scfg, num_shards=e,
                          num_core=max(1, e // 4), core_budget=2 * e)
        ex = FleetExecutor(cfg, engine, p)
        state = ex.init_state(D)

        rng = np.random.default_rng(7)
        lat, t0 = [], 0.0
        for i in range(WARMUP + STEPS):
            base = rng.standard_normal((e, BATCH, D)).astype(np.float32)
            if (i // 20) % 2:
                base[:, :, 0] += 0.5       # alternating hot regime
            items = jnp.asarray(base)
            ts = jnp.asarray(
                np.tile(t0 + np.arange(BATCH, dtype=np.float32), (e, 1)))
            t0 += BATCH
            t = time.perf_counter()
            state, out = ex.step(state, items, ts)
            jax.block_until_ready(out)
            if i >= WARMUP:
                lat.append(time.perf_counter() - t)
        lat = np.asarray(lat)
        m = state.metrics.as_dict()
        items_s = e * BATCH / np.median(lat)
        assert ex.trace_count == 1, f"retraced: {ex.trace_count}"
        row(f"fleet/E{e}_step", float(np.median(lat) * 1e6),
            f"items_per_s={items_s:.0f};fused=0")
        row(f"fleet/E{e}_p99", float(np.percentile(lat, 99) * 1e6),
            f"esc={m['fleet']['windows_escalated']}"
            f"/{m['fleet']['windows_emitted']}"
            f";overflow={m['fleet_core_overflow']}"
            f";traces={ex.trace_count}")
        if e == counts[-1]:
            staged_fleet_counters = (m["fleet"]["windows_escalated"],
                                     m["fleet"]["windows_emitted"])
        # the in-step device histogram's view of the same run (warmup/
        # compile ticks are EXCLUDED — warmup_excluded counts them — so
        # its tail tracks steady-state, not the one compile)
        h = ex.latency_percentiles()
        row(f"fleet/E{e}_hist", h["p50_us"],
            f"hist_p95_us={h['p95_us']:.1f}"
            f";hist_p99_us={h['p99_us']:.1f};hist_count={h['count']}"
            f";warmup_excluded={h['warmup_excluded']}")
        # event-time lineage: per-stage percentiles of the same run
        # (tick-quantized; in the flat R=1 mesh both hops run in the
        # single region, so hop1/hop2 counts both equal escalations)
        lin = ex.lineage_percentiles()
        for stage in ("queueing", "window", "hop1", "hop2", "e2e"):
            s = lin[stage]
            row(f"fleet/E{e}_lat_{stage}", s["p50_us"],
                f"p95_us={s['p95_us']:.1f};p99_us={s['p99_us']:.1f}"
                f";count={s['count']}")
        # device cost + roofline coordinates of ONE fleet tick (XLA's
        # own post-fusion cost model over the whole sharded executable;
        # utilization columns read $REPRO_PEAK_FLOPS/$REPRO_PEAK_BW,
        # 0.0 = peak undeclared)
        cost = ex.step_cost(
            state, rng.standard_normal((e, BATCH, D)).astype(np.float32),
            np.tile(t0 + np.arange(BATCH, dtype=np.float32), (e, 1)))
        rl = CM.roofline(cost["flops"], cost["bytes_accessed"],
                         float(np.median(lat)))
        row(f"fleet/E{e}_cost", float(np.median(lat) * 1e6),
            f"flops={cost['flops']:.0f}"
            f";bytes={cost['bytes_accessed']:.0f}"
            f";gflops={rl['gflops']:.4f};gbs={rl['gbs']:.4f}"
            f";ai={rl['ai']:.4f};flops_util={rl['flops_util']:.6f}"
            f";bw_util={rl['bw_util']:.6f}")

    # fused tick lane: the widest shape again with every shard's ingest
    # running the fused window+features+rules kernel
    # (StreamConfig(fused=True) — the per-shard path inside the same
    # shard_map step).  Counters must come out bitwise the staged
    # lane's (parity is pinned record-level in tests; the fleet-level
    # escalation totals are re-asserted here so the bench itself would
    # catch a divergence), so only throughput/latency re-report.
    e = counts[-1]
    engine = rules.RuleEngine([
        rules.threshold_rule("hot_mean", 0, ">=", 0.25,
                             rules.C_SEND_CORE, priority=1),
        rules.threshold_rule("sparse", 4, "<", 8.0,
                             rules.C_STORE_EDGE, priority=2),
    ])
    p = pipe.two_tier_pipeline(edge_fn, core_fn, engine,
                               core_params=core_p)
    fcfg = StreamConfig(micro_batch=BATCH, window=64, stride=32,
                        capacity=4 * BATCH, lateness=64.0, fused=True)
    cfg = FleetConfig(stream=fcfg, num_shards=e,
                      num_core=max(1, e // 4), core_budget=2 * e)
    ex = FleetExecutor(cfg, engine, p)
    state = ex.init_state(D)
    rng = np.random.default_rng(7)
    lat, t0 = [], 0.0
    for i in range(WARMUP + STEPS):
        base = rng.standard_normal((e, BATCH, D)).astype(np.float32)
        if (i // 20) % 2:
            base[:, :, 0] += 0.5
        items = jnp.asarray(base)
        ts = jnp.asarray(
            np.tile(t0 + np.arange(BATCH, dtype=np.float32), (e, 1)))
        t0 += BATCH
        t = time.perf_counter()
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
        if i >= WARMUP:
            lat.append(time.perf_counter() - t)
    lat = np.asarray(lat)
    m = state.metrics.as_dict()
    fused_counters = (m["fleet"]["windows_escalated"],
                      m["fleet"]["windows_emitted"])
    assert fused_counters == staged_fleet_counters, \
        (fused_counters, staged_fleet_counters)
    assert ex.trace_count == 1, f"retraced: {ex.trace_count}"
    row(f"fleet/E{e}_fused_step", float(np.median(lat) * 1e6),
        f"items_per_s={e * BATCH / np.median(lat):.0f};fused=1")
    row(f"fleet/E{e}_fused_p99", float(np.percentile(lat, 99) * 1e6),
        f"esc={m['fleet']['windows_escalated']}"
        f"/{m['fleet']['windows_emitted']}"
        f";overflow={m['fleet_core_overflow']}"
        f";traces={ex.trace_count};fused=1")


def _hot_fixture():
    """The degraded and churned suites' shared workload: tanh core
    stage, hot-mean escalation rule, tumbling 64/64 stream config
    (tumbling: a stall gap or a foreign-slot replay cannot smear
    window boundaries).  One copy, so --faults and --churn measure the
    same pipeline.  Returns (engine, scfg, make_pipeline)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig

    def edge_fn(p, batch):
        return batch, batch[:, :5]

    def core_fn(p, batch):
        h = batch
        for _ in range(8):
            h = jnp.tanh(h @ p)
        return h, batch[:, :5]

    core_p = jnp.asarray(
        np.random.default_rng(0).standard_normal((5 + D, 5 + D)) * 0.1,
        jnp.float32)
    engine = rules.RuleEngine([
        rules.threshold_rule("hot_mean", 0, ">=", 0.25,
                             rules.C_SEND_CORE, priority=1)])
    scfg = StreamConfig(micro_batch=BATCH, window=64, stride=64,
                        capacity=4 * BATCH, lateness=64.0)

    def make_pipeline():
        return pipe.two_tier_pipeline(edge_fn, core_fn, engine,
                                      core_params=core_p)

    return engine, scfg, make_pipeline


def _faults(E: int):
    """Degraded-fleet smoke: stall one shard mid-run under an elastic
    budget and report what the control plane did about it."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import row
    from repro.obs import EventLog, Tracer
    from repro.runtime.elastic import ElasticBudget
    from repro.runtime.straggler import StragglerDetector
    from repro.stream.fleet import (Fault, FaultInjector, FaultSchedule,
                                    FleetConfig, FleetController,
                                    FleetExecutor)

    steps = 60
    stall = Fault(shard=2, start=20, end=32)
    sched = FaultSchedule([stall])
    engine, scfg, make_pipeline = _hot_fixture()
    ex = FleetExecutor(
        FleetConfig(stream=scfg, num_shards=E, num_core=2,
                    core_budget=4, core_budget_max=16),
        engine, make_pipeline())
    # observability rides the measured run: host spans + control-plane
    # event log (JSONL to $REPRO_OBS_EVENTS if set), instrumentation on
    # while the trace bound below is asserted
    tracer = Tracer()
    log = EventLog(os.environ.get("REPRO_OBS_EVENTS"))
    ex.set_tracer(tracer)
    ctl = FleetController(
        ex,
        budget_policy=ElasticBudget(min_budget=2, max_budget=64,
                                    patience=2),
        wall_detector=StragglerDetector(E, window=3, threshold=3.0,
                                        patience=2),
        event_log=log, tracer=tracer)
    state = ex.init_state(D)

    rng = np.random.default_rng(7)
    inj = FaultInjector(sched, event_log=log)
    lat, budgets, t0 = [], [], 0.0
    for i in range(steps):
        base = rng.standard_normal((E, BATCH, D)).astype(np.float32)
        if (i // 10) % 2:
            base[:, :, 0] += 0.5           # alternating hot regime
        ts = np.tile(t0 + np.arange(BATCH, dtype=np.float32), (E, 1))
        t0 += BATCH
        with tracer.span("inject", tick=i):
            base, ts, offered, _ = inj.inject(i, base, ts)
        t = time.perf_counter()
        state, out = ex.step(state, jnp.asarray(base), jnp.asarray(ts),
                             offered=jnp.asarray(offered))
        jax.block_until_ready(out)
        if i >= WARMUP:
            lat.append(time.perf_counter() - t)
        budgets.append(ctl.tick(state,
                                step_times=sched.stall_time(i, E)).budget)
    # unmeasured drain: flush the stalled shard's buffered tail so the
    # run ends with every record processed, not quietly abandoned
    i = steps
    while inj.pending:
        base, ts, offered, _ = inj.inject(
            i, np.zeros((E, BATCH, D), np.float32),
            np.zeros((E, BATCH), np.float32), fresh=False)
        state, out = ex.step(state, jnp.asarray(base), jnp.asarray(ts),
                             offered=jnp.asarray(offered))
        ctl.tick(state, step_times=sched.stall_time(i, E))
        i += 1
    lat = np.asarray(lat)
    m = state.metrics.as_dict()
    assert ex.trace_count <= ctl.max_trace_count <= 1 + ctl.resizes, \
        f"trace bound broken: {ex.trace_count} > 1 + {ctl.resizes}"
    assert sum(m["late_excluded"]) > 0, "stall never hit the catch-up path"
    assert sum(m["shard"]["items_late"]) == 0, "catch-up dropped records"
    row("fleet/faults_step", float(np.median(lat) * 1e6),
        f"items_per_s={E * BATCH / np.median(lat):.0f}")
    row("fleet/faults_p99", float(np.percentile(lat, 99) * 1e6),
        f"budget={min(budgets)}..{max(budgets)}"
        f";resizes={ctl.resizes}"
        f";late_excluded={sum(m['late_excluded'])}"
        f";esc={m['fleet']['windows_escalated']}"
        f";overflow={m['fleet_core_overflow']}"
        f";traces={ex.trace_count}")
    # the observability surface of the same degraded run: the event log
    # must reconstruct (causally ordered), and the in-step device
    # histogram yields percentiles without having cost a retrace
    # (warmup/resize-retrace ticks excluded — warmup_excluded counts)
    EventLog.validate(log.records)
    h = ex.latency_percentiles()
    row("fleet/faults_hist", h["p50_us"],
        f"hist_p95_us={h['p95_us']:.1f}"
        f";hist_p99_us={h['p99_us']:.1f};hist_count={h['count']}"
        f";warmup_excluded={h['warmup_excluded']}")
    # the stall's event-time signature: queueing latency is where a
    # stalled shard's buffered tail shows up once it drains
    lin = ex.lineage_percentiles()
    row("fleet/faults_lat_queueing", lin["queueing"]["p50_us"],
        f"p95_us={lin['queueing']['p95_us']:.1f}"
        f";p99_us={lin['queueing']['p99_us']:.1f}"
        f";count={lin['queueing']['count']}"
        f";e2e_p99_us={lin['e2e']['p99_us']:.1f}")
    row("fleet/faults_events", float(len(log)),
        f"resizes={len(log.of_kind('budget_resize'))}"
        f";health={len(log.of_kind('health_change'))}"
        f";stalls={len(log.of_kind('stall_buffer'))}"
        f";drains={len(log.of_kind('backlog_drain'))}")
    log.close()


def _churn(E: int):
    """Membership-churn smoke: a shard leaves mid-run, its stream
    replays on the reassignment-chosen backup, a joiner restores the
    slot, and the fleet then truly re-meshes — all verified against a
    healthy-fleet oracle, with latency reported per phase."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import row
    from repro.obs import EventLog, Tracer
    from repro.runtime.elastic import ElasticBudget
    from repro.stream.fleet import (Churn, FaultInjector, FaultSchedule,
                                    FleetConfig, FleetController,
                                    FleetExecutor)

    steps = 60
    event = Churn(shard=3, leave=20, join=34)
    sched = FaultSchedule(churn=[event])
    engine, scfg, make_pipeline = _hot_fixture()
    budget = 4 * E                     # ample + pinned: the oracle has no
                                       # controller, so an elastic resize
                                       # would be a semantic difference

    def make_fleet():
        return FleetExecutor(
            FleetConfig(stream=scfg, num_shards=E, num_core=2,
                        core_budget=budget),
            engine, make_pipeline())

    def feed(i):
        r = np.random.default_rng(1000 + i)
        base = r.standard_normal((E, BATCH, D)).astype(np.float32)
        if (i // 10) % 2:
            base[:, :, 0] += 0.5       # alternating hot regime
        ts = np.tile(i * BATCH + np.arange(BATCH, dtype=np.float32),
                     (E, 1))
        return base, ts

    def collect(out, e, store):
        emit = np.asarray(out.window_count[e]) > 0
        if emit.any():
            store.append(np.asarray(out.aggregates[e])[emit])

    orc = make_fleet()
    ostate = orc.init_state(D)
    oracle = [[] for _ in range(E)]
    for i in range(steps):
        base, ts = feed(i)
        ostate, out = orc.step(ostate, jnp.asarray(base), jnp.asarray(ts))
        for e in range(E):
            collect(out, e, oracle[e])

    ex = make_fleet()
    # the churned (measured) run carries the full observability surface;
    # the oracle stays bare so the equality check compares pipelines,
    # not instrumentation
    tracer = Tracer()
    log = EventLog(os.environ.get("REPRO_OBS_EVENTS"))
    ex.set_tracer(tracer)
    ctl = FleetController(
        ex, budget_policy=ElasticBudget(min_budget=budget,
                                        max_budget=budget),
        event_log=log, tracer=tracer)
    state = ex.init_state(D)
    inj = FaultInjector(sched, event_log=log)
    churned = [[] for _ in range(E)]
    backups, lat, rep_expected = {}, [], 0
    for i in range(steps):
        if i == event.leave:
            backup = ctl.leave(event.shard)
            assert backup is not None
            backups = {event.shard: backup}
        if i == event.join:
            ctl.join(event.shard)
        base, ts = feed(i)
        base, ts, offered, replay = inj.inject(i, base, ts,
                                               backups=backups)
        origin = inj.origin.copy()
        rep_expected += int(offered[replay].sum())
        t = time.perf_counter()
        state, out = ex.step(state, jnp.asarray(base), jnp.asarray(ts),
                             offered=jnp.asarray(offered),
                             replay=jnp.asarray(replay))
        if i >= WARMUP:
            lat.append(time.perf_counter() - t)
        ctl.tick(state, step_times=sched.stall_time(i, E))
        for e in range(E):
            if origin[e] >= 0:
                collect(out, e, churned[int(origin[e])])
    # unmeasured drain: flush the backup's displaced backlog
    i = steps
    while inj.pending:
        base, ts, offered, replay = inj.inject(
            i, np.zeros((E, BATCH, D), np.float32),
            np.zeros((E, BATCH), np.float32), fresh=False,
            backups=backups)
        origin = inj.origin.copy()
        state, out = ex.step(state, jnp.asarray(base), jnp.asarray(ts),
                             offered=jnp.asarray(offered),
                             replay=jnp.asarray(replay))
        ctl.tick(state, step_times=sched.stall_time(i, E))
        for e in range(E):
            if origin[e] >= 0:
                collect(out, e, churned[int(origin[e])])
        i += 1
    m = state.metrics.as_dict()
    # churn end-to-end, asserted: oracle equality per stream, nothing
    # dropped, replayed == exact recomputation, ONE trace for the whole
    # leave -> replay -> join arc
    for e in range(E):
        a = np.concatenate(churned[e]) if churned[e] else np.zeros((0,))
        b = np.concatenate(oracle[e]) if oracle[e] else np.zeros((0,))
        assert a.shape == b.shape, (e, a.shape, b.shape)
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6,
                                   err_msg=f"stream {e}")
    assert sum(m["shard"]["items_replayed"]) == rep_expected > 0, \
        (m["shard"]["items_replayed"], rep_expected)
    assert sum(m["shard"]["items_late"]) == 0, "churn dropped records"
    assert ex.trace_count == 1, f"membership retraced: {ex.trace_count}"

    # true re-mesh: the departed device never comes back — shrink to E - 1
    devs = [d for j, d in enumerate(jax.devices()[:E]) if j != event.shard]
    keep = [j for j in range(E) if j != event.shard]
    state, payload = ctl.remesh(state, devs, keep=keep)
    base, ts = feed(steps)
    t = time.perf_counter()
    state, out = ex.step(state, jnp.asarray(base[keep]),
                         jnp.asarray(ts[keep]))
    remesh_lat = time.perf_counter() - t
    ctl.tick(state, step_times=np.full(E - 1, 0.1))
    assert ex.trace_count == 2 <= ctl.max_trace_count, \
        (ex.trace_count, ctl.max_trace_count)

    lat = np.asarray(lat)
    row("fleet/churn_step", float(np.median(lat) * 1e6),
        f"items_per_s={E * BATCH / np.median(lat):.0f}")
    row("fleet/churn_p99", float(np.percentile(lat, 99) * 1e6),
        f"replayed={sum(m['shard']['items_replayed'])}"
        f";late_excluded={sum(m['late_excluded'])}"
        f";traces={ex.trace_count}"
        f";remeshes={ex.remeshes}")
    row("fleet/churn_remesh_step", float(remesh_lat * 1e6),
        f"shards={E}->{E - 1};retrace=1")
    # the whole leave -> replay -> join -> remesh arc as an event log:
    # parseable, causally ordered, every membership decision accounted
    EventLog.validate(log.records)
    assert len(log.of_kind("leave")) == 1
    assert len(log.of_kind("backup_assign")) == 1
    assert len(log.of_kind("join")) == 1
    assert len(log.of_kind("remesh")) == 1
    h = ex.latency_percentiles()
    row("fleet/churn_events", float(len(log)),
        f"replay_q={len(log.of_kind('replay_queue'))}"
        f";replay_d={len(log.of_kind('replay_delivery'))}"
        f";slot_drains={len(log.of_kind('slot_drain'))}"
        f";hist_p99_us={h['p99_us']:.1f}")
    log.close()


def _regions(S: int):
    """Hierarchical-federation smoke: the same device budget arranged
    as (R, E) region meshes, with the two-hop exchange volume accounted
    against the flat single-hop baseline."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import row
    from repro.obs import Tracer
    from repro.stream.fleet import FleetConfig, FleetExecutor

    steps = 40
    FOG = 8                             # fixed per-region fog budget
    engine, scfg, make_pipeline = _hot_fixture()
    rw = 5 + D                          # escalation record row width

    # the O-claim is pure exchange geometry (no devices needed): at a
    # fixed fog budget, widening a region leaves the cross-region hop
    # untouched while the flat single-hop exchange keeps growing
    def geom(r, eper):
        return FleetConfig(stream=scfg, num_shards=r * eper,
                           num_core=2, core_budget=2 * MAX_SHARDS,
                           num_regions=r, fog_budget=FOG).exchange()

    widths = (2, 4, 8, 16)
    cross = [geom(2, e).cross_region_bytes(rw) for e in widths]
    flat = [geom(2, e).flat_exchange_bytes(rw) for e in widths]
    assert len(set(cross)) == 1, f"cross-region bytes grew with E: {cross}"
    assert all(b > a for a, b in zip(flat, flat[1:])), flat
    # ... and scales with the budget it is derived from
    big = FleetConfig(stream=scfg, num_shards=8, num_core=2,
                      core_budget=2 * MAX_SHARDS, num_regions=2,
                      fog_budget=4 * FOG).exchange()
    assert big.cross_region_bytes(rw) > cross[0]

    # a region of one shard has no intra-region hop to save: the two-hop
    # exchange only pays off from two shards per region up
    for r in (r for r in (1, 2, 4) if S // r >= 2):
        eper = S // r
        cfg = FleetConfig(stream=scfg, num_shards=S,
                          num_core=min(2, eper), core_budget=2 * S,
                          num_regions=r, fog_budget=FOG)
        ex = FleetExecutor(cfg, engine, make_pipeline())
        ex.set_tracer(Tracer())        # trace bound holds with obs ON
        state = ex.init_state(D)
        rng = np.random.default_rng(7)
        lat, t0 = [], 0.0
        for i in range(WARMUP + steps):
            base = rng.standard_normal((S, BATCH, D)).astype(np.float32)
            if (i // 10) % 2:
                base[:, :, 0] += 0.5   # alternating hot regime
            ts = np.tile(t0 + np.arange(BATCH, dtype=np.float32), (S, 1))
            t0 += BATCH
            t = time.perf_counter()
            state, out = ex.step(state, jnp.asarray(base),
                                 jnp.asarray(ts))
            jax.block_until_ready(out)
            if i >= WARMUP:
                lat.append(time.perf_counter() - t)
        lat = np.asarray(lat)
        m = state.metrics.as_dict()
        assert ex.trace_count == 1, f"retraced: {ex.trace_count}"
        exch = cfg.exchange()
        xb, ib = exch.cross_region_bytes(rw), exch.intra_region_bytes(rw)
        fb = exch.flat_exchange_bytes(rw)
        assert xb <= fb, (xb, fb)
        row(f"fleet/R{r}_step", float(np.median(lat) * 1e6),
            f"items_per_s={S * BATCH / np.median(lat):.0f}")
        row(f"fleet/R{r}_p99", float(np.percentile(lat, 99) * 1e6),
            f"esc={m['fleet']['windows_escalated']}"
            f";fog_shed={sum(m['fog_shed'])}"
            f";core={sum(m['core_processed'])}"
            f";traces={ex.trace_count}")
        row(f"fleet/R{r}_exchange_bytes", float(xb),
            f"intra_region={ib};flat_equiv={fb}"
            f";cross_capacity={cfg.cross_capacity}"
            f";fog_budget={FOG}")
        # two-hop lineage: hop1 (edge->fog) populates in every region,
        # hop2 (fog->core) only on region 0's core ranks — the
        # per-region view makes the confinement visible
        lin = ex.lineage_percentiles()
        for stage in ("hop1", "hop2", "e2e"):
            s = lin[stage]
            row(f"fleet/R{r}_lat_{stage}", s["p50_us"],
                f"p95_us={s['p95_us']:.1f};p99_us={s['p99_us']:.1f}"
                f";count={s['count']}")
        per = ex.lineage_percentiles(by="region")
        row(f"fleet/R{r}_lat_regions", float(r), ";".join(
            f"r{i}_e2e_count={p['e2e']['count']}"
            f";r{i}_hop2_count={p['hop2']['count']}"
            for i, p in enumerate(per)))


if __name__ == "__main__":
    bench(faults="--faults" in sys.argv, churn="--churn" in sys.argv,
          regions="--regions" in sys.argv)
