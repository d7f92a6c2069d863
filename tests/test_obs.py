"""Observability layer: golden schemas, event-log causality, latency
histogram correctness, tracer export, and the full fault+churn arc.

Golden-key tests pin every schema the perf trajectory depends on — a
refactor that renames or drops a ``StreamMetrics`` counter, an event
kind, or a BENCH artifact key must fail here, not silently orphan the
committed baselines.  The subprocess test (same 8-forced-device pattern
as ``test_fleet_faults.py``) drives one fault -> churn -> remesh arc
with the *full* instrumentation on and asserts the three acceptance
properties together: the JSONL event log parses and validates causally
ordered, the in-step latency histogram yields percentiles, and the
trace-count bounds hold unchanged — instrumentation costs zero
recompiles.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (EVENT_KINDS, DEFAULT_EDGES, EventLog, NULL_TRACER,
                       Tracer, bench_payload, histogram_init,
                       histogram_percentiles, histogram_update,
                       metrics_snapshot, parse_derived, write_bench)
from repro.obs import export as OX
from repro.obs.events import ENVELOPE_FIELDS
from repro.stream.executor import StreamMetrics


# --- golden schemas -------------------------------------------------------

def test_stream_metrics_golden_keys():
    """The counter set the BENCH baselines and dashboards key on."""
    assert StreamMetrics._fields == (
        "steps", "items_offered", "items_accepted", "items_rejected",
        "items_dequeued", "items_late", "items_replayed",
        "items_deduped", "items_backfilled",
        "windows_emitted", "rules_fired", "windows_escalated",
        "windows_stored", "windows_dropped", "core_overflow",
        "drift_counts", "keyed")
    m = StreamMetrics(*(jnp.zeros((), jnp.int32)
                        for _ in StreamMetrics._fields[:-2]),
                      drift_counts=jnp.zeros((3,), jnp.int32))
    d = m.as_dict()
    # a keyed plan's counters appear only with one (stream.keyed)
    assert tuple(d) == StreamMetrics._fields[:-1]
    assert all(v == 0 for k, v in d.items() if k != "drift_counts")
    assert d["drift_counts"] == [0, 0, 0]      # per-field -> list


def test_fleet_metrics_golden_keys():
    from repro.stream.fleet.executor import FleetMetrics
    assert FleetMetrics._fields == (
        "shard", "fleet", "escalations_sent", "fog_shed",
        "core_received", "core_processed", "fleet_core_overflow",
        "late_excluded", "watermark", "region_watermark")
    zeros = StreamMetrics(*(jnp.zeros((2,), jnp.int32)
                            for _ in StreamMetrics._fields[:-2]),
                          drift_counts=jnp.zeros((2, 3), jnp.int32))
    m = FleetMetrics(shard=zeros, fleet=zeros,
                     escalations_sent=jnp.zeros((2,), jnp.int32),
                     fog_shed=jnp.zeros((2,), jnp.int32),
                     core_received=jnp.zeros((2,), jnp.int32),
                     core_processed=jnp.zeros((2,), jnp.int32),
                     fleet_core_overflow=jnp.zeros((2,), jnp.int32),
                     late_excluded=jnp.zeros((2,), jnp.int32),
                     watermark=jnp.zeros((2,), jnp.float32),
                     region_watermark=jnp.zeros((2,), jnp.float32))
    d = m.as_dict()
    assert tuple(d) == FleetMetrics._fields
    assert tuple(d["shard"]) == StreamMetrics._fields[:-1]
    assert tuple(d["fleet"]) == StreamMetrics._fields[:-1]
    assert d["shard"]["steps"] == [0, 0]       # per-shard -> list
    assert d["fleet"]["steps"] == 0            # replicated -> scalar
    assert d["shard"]["drift_counts"] == [[0, 0, 0], [0, 0, 0]]
    assert d["fleet"]["drift_counts"] == [0, 0, 0]  # replicated -> row


def test_event_schema_golden():
    assert EVENT_KINDS == frozenset({
        "budget_resize", "health_change", "leave", "join",
        "backup_assign", "remesh", "stall_buffer", "replay_queue",
        "replay_delivery", "backlog_drain", "slot_drain", "requeue",
        "fog_budget_resize", "slo_breach", "slo_recover",
        "ingest_reject", "drift_detected"})
    assert ENVELOPE_FIELDS == ("seq", "wall_time", "tick", "kind",
                               "shard", "cause")


def test_bench_artifact_schema(tmp_path):
    rows = [{"name": "suite/a", "us_per_call": 12.5,
             "derived": "items_per_s=100;traces=1;note=ok;flag"}]
    payload = bench_payload("demo", rows)
    assert tuple(payload) == OX.BENCH_KEYS
    assert payload["schema_version"] == OX.BENCH_SCHEMA_VERSION
    assert payload["platform"]["backend"] == jax.default_backend()
    assert payload["rows"][0]["derived"] == {
        "items_per_s": 100, "traces": 1, "note": "ok", "flag": True}
    path = write_bench(payload, str(tmp_path))
    assert os.path.basename(path) == "BENCH_demo.json"
    assert json.load(open(path)) == json.loads(json.dumps(payload))
    assert not list(tmp_path.glob("*.tmp"))    # atomic: no temp residue


def test_parse_derived():
    assert parse_derived("") == {}
    assert parse_derived("a=1;b=2.5;c=x;d") == {
        "a": 1, "b": 2.5, "c": "x", "d": True}
    assert parse_derived("r=2..64") == {"r": "2..64"}


# --- event log ------------------------------------------------------------

def test_event_log_roundtrip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("leave", tick=3, shard=4, cause="decommissioned", backup=6)
    log.emit("backup_assign", tick=3, shard=6, cause="replay target",
             for_shard=4)
    log.emit("join", tick=9, shard=4, cause="rejoined")
    log.close()
    recs = EventLog.load(path)
    assert recs == log.records
    EventLog.validate(recs)
    assert [r["kind"] for r in log.of_kind("leave", "join")] == [
        "leave", "join"]
    assert recs[0]["backup"] == 6 and recs[0]["seq"] == 0
    # dump() is path-independent re-export
    recs2 = EventLog.load(log.dump(str(tmp_path / "copy.jsonl")))
    assert recs2 == recs


def test_event_log_rejects_bad_records():
    log = EventLog()
    with pytest.raises(ValueError, match="unknown event kind"):
        log.emit("budget_resise", tick=0)
    with pytest.raises(ValueError, match="shadow the envelope"):
        log.emit("join", tick=0, **{"seq": 7})
    log.emit("join", tick=0)
    assert len(log) == 1                       # failed emits left no trace


def test_event_log_validate_causality():
    def rec(seq, wall, tick, kind="join"):
        return {"seq": seq, "wall_time": wall, "tick": tick,
                "kind": kind, "shard": None, "cause": None}

    EventLog.validate([rec(0, 1.0, 0), rec(1, 1.0, None), rec(2, 2.0, 3)])
    with pytest.raises(ValueError, match="seq"):
        EventLog.validate([rec(0, 1.0, 0), rec(0, 2.0, 1)])
    with pytest.raises(ValueError, match="wall_time"):
        EventLog.validate([rec(0, 2.0, 0), rec(1, 1.0, 1)])
    with pytest.raises(ValueError, match="causally"):
        EventLog.validate([rec(0, 1.0, 5), rec(1, 2.0, 3)])
    with pytest.raises(ValueError, match="envelope"):
        EventLog.validate([{"seq": 0, "kind": "join"}])
    with pytest.raises(ValueError, match="unknown kind"):
        EventLog.validate([rec(0, 1.0, 0, kind="nope")])


# --- latency histogram ----------------------------------------------------

def test_histogram_percentiles_vs_numpy(rng):
    samples = rng.lognormal(mean=-7.0, sigma=1.0, size=400)  # ~1ms scale
    counts = histogram_init()
    for s in samples:
        counts = histogram_update(counts, float(s))
    got = histogram_percentiles(counts, qs=(50, 95, 99))
    assert got["count"] == 400
    ratio = DEFAULT_EDGES[1] / DEFAULT_EDGES[0]
    for q in (50, 95, 99):
        exact = np.percentile(samples, q) * 1e6
        # upper-edge convention: conservative within one bucket ratio
        assert exact <= got[f"p{q}_us"] <= exact * ratio * 1.01, (q, exact)


def test_histogram_update_single_trace():
    traces = []

    @jax.jit
    def upd(counts, v):
        traces.append(1)
        return histogram_update(counts, v)

    counts = histogram_init()
    for v in (1e-4, 3e-3, 0.5, 1e3, 0.0, -1.0):   # incl. overflow + skips
        counts = upd(counts, jnp.float32(v))
    assert len(traces) == 1                       # fixed shape: one trace
    got = histogram_percentiles(counts)
    assert got["count"] == 4                      # non-positive skipped
    assert got["p99_us"] == pytest.approx(DEFAULT_EDGES[-1] * 1e6)


def test_histogram_empty():
    got = histogram_percentiles(histogram_init())
    assert got == {"count": 0, "p50_us": 0.0, "p95_us": 0.0, "p99_us": 0.0}


# --- tracer ---------------------------------------------------------------

def test_tracer_spans_and_export(tmp_path):
    tr = Tracer()
    with tr.span("outer", tick=1):
        with tr.span("inner"):
            pass
    with tr.span("inner"):
        pass
    sp = tr.stage_percentiles()
    assert set(sp) == {"outer", "inner"}
    assert sp["inner"]["count"] == 2
    assert sp["outer"]["p50_us"] >= sp["inner"]["p50_us"] > 0
    doc = tr.to_chrome_trace()
    assert {e["name"] for e in doc["traceEvents"]} == {"outer", "inner"}
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in doc["traceEvents"])
    outer = next(e for e in doc["traceEvents"] if e["name"] == "outer")
    assert outer["args"] == {"tick": 1}
    path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
    assert json.load(open(path)) == json.loads(json.dumps(doc))
    tr.clear()
    assert tr.stage_percentiles() == {}


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("x"):
        pass
    with NULL_TRACER.step_annotation("x", 1):
        pass
    assert NULL_TRACER.spans == []
    assert not NULL_TRACER.enabled


def test_span_parents_and_offcpu_time():
    """A span records the innermost open span of its thread as parent,
    and a span that sleeps shows the sleep as time off the CPU."""
    import threading
    import time

    tr = Tracer()
    with tr.span("outer", late=7):
        with tr.span("sleep"):
            time.sleep(0.02)

        def other():
            with tr.span("other"):
                pass

        side = threading.Thread(target=other)
        side.start()
        side.join()
    by = {sp.name: sp for sp in tr.spans}
    assert by["outer"].parent is None
    assert by["other"].parent is None          # another thread's root
    assert by["sleep"].parent == by["outer"].id
    assert by["outer"].args == {"late": 7}
    sleep = by["sleep"]
    assert (sleep.t1 - sleep.t0) - (sleep.cpu1 - sleep.cpu0) >= 0.015, sleep
    assert sleep.cpu1 - sleep.cpu0 < 0.005
    assert all(sp[:5] == (sp.name, sp.t0, sp.t1, sp.tid, sp.args)
               for sp in tr.spans)
    ev = {e["name"]: e for e in tr.to_chrome_trace()["traceEvents"]}
    assert ev["sleep"]["tdur"] == pytest.approx(
        (sleep.cpu1 - sleep.cpu0) * 1e6)
    assert ev["sleep"]["dur"] - ev["sleep"]["tdur"] >= 15e3
    assert ev["outer"]["args"] == {"late": 7}


def test_null_tracer_reads_no_clock(monkeypatch, rng):
    """With the disabled tracer a tick reads the clock as it always
    did (start, ``now``, end) and the tracer itself reads none."""
    import time as _time

    from repro.obs import trace as OT
    from repro.stream import executor as SE

    calls = {"trace": 0, "step": 0}

    class Clock:
        def __init__(self, key):
            self.key = key

        def __getattr__(self, name):
            return getattr(_time, name)

        def perf_counter(self):
            calls[self.key] += 1
            return _time.perf_counter()

        def thread_time(self):
            calls[self.key] += 1
            return _time.thread_time()

    ex, state = _stream_executor()
    items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
    state, _ = ex.step(state, items, jnp.arange(32, dtype=jnp.float32))
    monkeypatch.setattr(OT, "time", Clock("trace"))
    monkeypatch.setattr(SE, "time", Clock("step"))
    ex.step(state, items, 32 + jnp.arange(32, dtype=jnp.float32))
    assert calls == {"trace": 0, "step": 3}


def test_profile_export_on_the_capture_clock(tmp_path):
    """After a ``Tracer.profile()`` capture the Chrome export is on the
    capture's clock: each span starts within 2 ms of its
    ``TraceAnnotation`` in the ``.xplane.pb``."""
    import glob
    import time

    from jax.profiler import ProfileData

    tr = Tracer()
    jax.block_until_ready(jnp.ones(4) + 1)
    with tr.profile(str(tmp_path)):
        for i in range(4):
            with tr.span(f"probe{i}"):
                with tr.span(f"inner{i}"):
                    time.sleep(0.005)
            time.sleep(0.01)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    marks = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("probe", "inner")):
                    marks[ev.name] = ev.start_ns
    events = tr.to_chrome_trace()["traceEvents"]
    assert {e["name"] for e in events} == set(marks) and len(marks) == 8
    for e in events:
        assert abs(e["ts"] * 1e3 - marks[e["name"]]) < 2e6, (e, marks)


def test_stream_executor_span_tree(rng):
    """One traced tick is the tree stream.step > stream.dispatch >
    (stream.operands, stream.call), and tracing adds no trace."""
    ex, state = _stream_executor()
    tr = Tracer()
    ex.set_tracer(tr)
    quiet, qstate = _stream_executor()
    for i in range(3):
        items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
        ts = jnp.asarray(i * 32 + np.arange(32), jnp.float32)
        state, out = ex.step(state, items, ts)
        qstate, _ = quiet.step(qstate, items, ts)
        jax.block_until_ready(out)
    assert ex.trace_count == quiet.trace_count == 1
    spans = tr.spans
    by_id = {sp.id: sp for sp in spans}

    def parent(sp):
        return by_id[sp.parent].name if sp.parent is not None else None

    assert [sp.name for sp in spans].count("stream.step") == 3
    for sp in spans:
        assert parent(sp) == {"stream.step": None,
                              "stream.dispatch": "stream.step",
                              "stream.operands": "stream.dispatch",
                              "stream.call": "stream.dispatch"}[sp.name]
        assert by_id.get(sp.parent, sp).t0 <= sp.t0 <= sp.t1 \
            <= by_id.get(sp.parent, sp).t1
    steps = [sp for sp in spans if sp.name == "stream.step"]
    # the operands come first, then the call, inside the dispatch
    ops, call = (next(sp for sp in spans if sp.name == n
                      and by_id[by_id[sp.parent].parent] is steps[1])
                 for n in ("stream.operands", "stream.call"))
    assert ops.t1 <= call.t0


# --- single-device executor with instrumentation on -----------------------

def _stream_executor():
    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig, StreamExecutor

    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE)])
    edge_fn = lambda p, b: (b, b[:, :5])  # noqa: E731
    scfg = StreamConfig(micro_batch=32, window=16, stride=16, capacity=128)
    ex = StreamExecutor(scfg, engine,
                        pipe.two_tier_pipeline(edge_fn, edge_fn, engine))
    return ex, ex.init_state(3)


def test_stream_executor_obs(rng):
    """Tracing + in-step histogram on a live executor: still ONE trace,
    and the snapshot carries the full stable schema."""
    ex, state = _stream_executor()
    tr = Tracer()
    ex.set_tracer(tr)
    steps = 6
    for i in range(steps):
        items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
        ts = jnp.asarray(i * 32 + np.arange(32), jnp.float32)
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
    assert ex.trace_count == 1, ex.trace_count
    lat = ex.latency_percentiles()
    # first step feeds dt=0 (skipped: missing measurement, not fast);
    # the second withholds the traced (compile-polluted) step's wall
    # time — warmup_excluded accounts for it
    assert lat["count"] == steps - 2
    assert lat["warmup_excluded"] == 1
    assert lat["p99_us"] >= lat["p50_us"] > 0
    assert tr.stage_percentiles()["stream.dispatch"]["count"] == steps

    snap = metrics_snapshot(ex, state)
    assert tuple(snap) == OX.SNAPSHOT_KEYS
    assert snap["kind"] == "StreamExecutor"
    assert tuple(snap["metrics"]) == StreamMetrics._fields[:-1]
    assert snap["metrics"]["steps"] == steps
    assert snap["trace_count"] == 1
    assert "stream.dispatch" in snap["stages"]
    json.dumps(snap)                           # fully JSON-serializable


# --- the full arc, instrumented (subprocess: 8 forced devices) ------------

_ARC_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.obs import EventLog, Tracer, metrics_snapshot
    from repro.obs import export as OX
    from repro.runtime.elastic import ElasticBudget
    from repro.runtime.straggler import StragglerDetector
    from repro.stream import StreamConfig
    from repro.stream.fleet import (Churn, Fault, FaultInjector,
                                    FaultSchedule, FleetConfig,
                                    FleetController, FleetExecutor)

    LOG_PATH = sys.argv[1]
    D, BATCH, E = 3, 32, 8
    edge_fn = lambda p, b: (b * 1.5, b[:, :5])
    core_fn = lambda p, b: (b + 100.0, b[:, :5])
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE,
                             priority=2)])
    scfg = StreamConfig(micro_batch=BATCH, window=16, stride=16,
                        capacity=4 * BATCH, lateness=4.0)
    ex = FleetExecutor(
        FleetConfig(stream=scfg, num_shards=E, num_core=2,
                    core_budget=4, core_budget_max=16),
        engine, pipe.two_tier_pipeline(edge_fn, core_fn, engine))
    tracer = Tracer()
    log = EventLog(LOG_PATH)
    ex.set_tracer(tracer)
    ctl = FleetController(
        ex,
        budget_policy=ElasticBudget(min_budget=2, max_budget=64,
                                    patience=2),
        wall_detector=StragglerDetector(E, window=3, threshold=3.0,
                                        patience=2),
        event_log=log, tracer=tracer)
    state = ex.init_state(D)

    # one arc: a stall on shard 2, then shard 5 leaves -> backup replay
    # -> rejoins, then a true re-mesh down to 7 devices
    sched = FaultSchedule([Fault(shard=2, start=4, end=7)],
                          churn=[Churn(shard=5, leave=10, join=15)])
    inj = FaultInjector(sched, event_log=log)
    rng = np.random.default_rng(0)
    backups, t = {}, 0
    while t < 20 or inj.pending:
        if t == 10:
            backups = {5: ctl.leave(5)}
        if t == 15:
            ctl.join(5)
        drain = t >= 20
        items = (np.zeros((E, BATCH, D), np.float32) if drain else
                 rng.standard_normal((E, BATCH, D)).astype(np.float32))
        if not drain:
            items[:, :, 0] += (t % 3 == 0) * 1.5
        ts = np.tile(t * BATCH + np.arange(BATCH, dtype=np.float32),
                     (E, 1))
        with tracer.span("inject", tick=t):
            items, ts, offered, replay = inj.inject(
                t, items, ts, fresh=not drain, backups=backups)
        state, out = ex.step(state, jnp.asarray(items), jnp.asarray(ts),
                             offered=jnp.asarray(offered),
                             replay=jnp.asarray(replay))
        ctl.tick(state, step_times=sched.stall_time(t, E))
        t += 1

    # instrumentation must not have cost a single extra trace
    assert ex.trace_count <= ctl.max_trace_count <= 1 + ctl.resizes, \\
        (ex.trace_count, ctl.max_trace_count, ctl.resizes)
    pre_remesh_traces = ex.trace_count

    devs = [d for j, d in enumerate(jax.devices()) if j != 5]
    keep = [j for j in range(E) if j != 5]
    state, payload = ctl.remesh(state, devs, keep=keep)
    items = rng.standard_normal((E - 1, BATCH, D)).astype(np.float32)
    ts = np.tile(t * BATCH + np.arange(BATCH, dtype=np.float32),
                 (E - 1, 1))
    state, out = ex.step(state, jnp.asarray(items), jnp.asarray(ts))
    ctl.tick(state, step_times=np.full(E - 1, 0.1))
    assert ex.trace_count == pre_remesh_traces + 1   # remesh: exactly one

    # acceptance surface 1: latency percentiles from the traced step
    lat = ex.latency_percentiles()
    assert lat["count"] > 0 and lat["p99_us"] >= lat["p50_us"] > 0
    snap = metrics_snapshot(ex, state)
    assert tuple(snap) == OX.SNAPSHOT_KEYS
    assert "fleet.dispatch" in snap["stages"]
    assert "control.tick" in snap["stages"]
    json.dumps(snap)

    # acceptance surface 2: the arc's event log
    log.close()
    recs = EventLog.load(LOG_PATH)
    EventLog.validate(recs)
    kinds = {r["kind"] for r in recs}
    for k in ("stall_buffer", "backlog_drain", "leave", "backup_assign",
              "replay_queue", "replay_delivery", "join", "remesh",
              "budget_resize", "health_change"):
        assert k in kinds, (k, sorted(kinds))
    leave, = (r for r in recs if r["kind"] == "leave")
    assign, = (r for r in recs if r["kind"] == "backup_assign")
    remesh, = (r for r in recs if r["kind"] == "remesh")
    assert leave["shard"] == 5 and leave["tick"] == 10
    assert assign["shard"] == 5 and assign["backup"] is not None
    assert remesh["old_shards"] == 8 and remesh["new_shards"] == 7
    # causal story: the leave precedes its replays, which precede remesh
    order = [r["kind"] for r in recs]
    assert order.index("leave") < order.index("replay_delivery") \\
        < order.index("remesh")
    print("ARC_OK", len(recs), ex.trace_count)
""")


def test_instrumented_arc(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = tmp_path / "obs_arc.py"
    script.write_text(_ARC_SCRIPT)
    log_path = tmp_path / "arc_events.jsonl"
    out = subprocess.run([sys.executable, str(script), str(log_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ARC_OK" in out.stdout
    # the parent re-parses the artifact the child wrote: JSONL on disk,
    # every line a JSON object, causally ordered
    recs = EventLog.load(str(log_path))
    assert len(recs) > 10
    EventLog.validate(recs)


# --- the fleet tick's span tree (subprocess: 4 forced devices) ------------

_FLEET_SPANS_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax, jax.numpy as jnp

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.obs import SLO, Tracer
    from repro.stream import StreamConfig
    from repro.stream.fleet import (FleetConfig, FleetController,
                                    FleetExecutor)

    D, BATCH, E, R = 3, 32, 4, 2
    edge_fn = lambda p, b: (b * 1.5, b[:, :5])
    core_fn = lambda p, b: (b + 100.0, b[:, :5])
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE)])
    scfg = StreamConfig(micro_batch=BATCH, window=16, stride=16,
                        capacity=4 * BATCH)

    def build(tracer):
        ex = FleetExecutor(
            FleetConfig(stream=scfg, num_shards=E, num_core=1,
                        num_regions=R, core_budget=8),
            engine, pipe.two_tier_pipeline(edge_fn, core_fn, engine))
        kw = {} if tracer is None else {"tracer": tracer}
        ctl = FleetController(ex, slos=(SLO("e2e", target_seconds=1.0),),
                              **kw)
        if tracer is not None:
            ex.set_tracer(tracer)
        return ex, ctl, ex.init_state(D)

    tr = Tracer()
    runs = {"on": build(tr), "off": build(None)}
    rng = np.random.default_rng(0)
    TICKS = 3
    for t in range(TICKS):
        items = jnp.asarray(rng.standard_normal((E, BATCH, D)), jnp.float32)
        ts = jnp.asarray(np.tile(t * BATCH + np.arange(BATCH), (E, 1)),
                         jnp.float32)
        for key, (ex, ctl, state) in runs.items():
            state = ex.step(state, items, ts)[0]
            ctl.tick(state)
            runs[key] = (ex, ctl, state)
    for ex, ctl, _ in runs.values():
        assert ex.trace_count == ctl.max_trace_count == 1, \\
            (ex.trace_count, ctl.max_trace_count)

    spans = tr.spans
    by_id = {sp.id: sp for sp in spans}
    want = {"fleet.step": None, "fleet.dispatch": "fleet.step",
            "fleet.operands": "fleet.dispatch",
            "fleet.call": "fleet.dispatch",
            "fleet.device_execute": "fleet.step",
            "control.tick": None, "control.pull": "control.tick",
            "control.decide": "control.tick"}
    names = [sp.name for sp in spans]
    assert sorted(set(names)) == sorted(want), sorted(set(names))
    for name in want:
        assert names.count(name) == TICKS, (name, names.count(name))
    for sp in spans:
        got = by_id[sp.parent].name if sp.parent is not None else None
        assert got == want[sp.name], (sp.name, got)
        if sp.parent is not None:
            up = by_id[sp.parent]
            assert up.t0 <= sp.t0 <= sp.t1 <= up.t1, sp.name
    print("SPANS_OK")
""")


def test_fleet_span_tree(tmp_path):
    """With a tracer on, every fleet tick is the tree fleet.step >
    fleet.dispatch > (fleet.operands, fleet.call), fleet.device_execute,
    and every control tick, SLO lane included, control.tick >
    control.pull, control.decide; the trace counts match those of an
    untraced twin."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = tmp_path / "fleet_spans.py"
    script.write_text(_FLEET_SPANS_SCRIPT)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SPANS_OK" in out.stdout
