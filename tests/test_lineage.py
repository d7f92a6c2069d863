"""Event-time latency lineage, SLO burn-rate lane, cost model, and the
perf-regression gate.

The lineage property tests are seeded-numpy randomized properties (the
hypothesis variants live in ``test_property.py`` behind its
``importorskip``): percentile monotonicity, merge associativity/
commutativity, and pooled-equals-merged — the invariants that make the
per-shard / per-region / fleet-pooled lineage views consistent.  The
warmup-exclusion regression test pins the fix for the compile-polluted
step histogram (a p99 six orders of magnitude above p95 in the old
``BENCH_fleet.json``).  The subprocess test drives a ring-backpressure
arc on an 8-shard, 2-region fleet and asserts the SLO lane end to end:
``slo_breach`` then ``slo_recover`` land in a validated event log,
per-shard and per-region lineage views localize the latency to the
throttled shard, and the whole arc stays on ONE trace.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import (DEFAULT_EDGES, LINEAGE_STAGES, SLO, SloEvaluator,
                       analyze, roofline)
from repro.obs import latency as OL

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _rand_bank(rng, scale=200):
    """Random lineage bank [n_stages, buckets] with empty rows mixed in."""
    bank = rng.integers(0, scale, (len(LINEAGE_STAGES),
                                   len(DEFAULT_EDGES) + 1))
    bank[rng.random(len(LINEAGE_STAGES)) < 0.25] = 0   # some empty stages
    return bank.astype(np.int64)


# --- histogram batch update ----------------------------------------------

def test_histogram_update_batch_vs_numpy(rng):
    vals = rng.lognormal(mean=-7.0, sigma=2.0, size=512).astype(np.float32)
    vals[:32] = 0.0                        # same-tick samples: bucket 0
    mask = rng.random(512) < 0.7
    counts = OL.histogram_update_batch(
        jnp.zeros(len(DEFAULT_EDGES) + 1, jnp.int32), vals, mask)
    # reference: clamp-to-first-bucket + searchsorted, masked rows only
    ref = np.zeros(len(DEFAULT_EDGES) + 1, np.int64)
    for v in np.maximum(vals[mask], DEFAULT_EDGES[0] * 0.5):
        ref[np.searchsorted(DEFAULT_EDGES, v)] += 1
    np.testing.assert_array_equal(np.asarray(counts, np.int64), ref)
    assert int(counts.sum()) == int(mask.sum())   # zero-latency not lost


def test_histogram_update_batch_single_trace():
    traces = []

    @jax.jit
    def upd(counts, v, m):
        traces.append(1)
        return OL.histogram_update_batch(counts, v, m)

    counts = jnp.zeros(len(DEFAULT_EDGES) + 1, jnp.int32)
    for v in (0.0, 1e-3, 1e4):             # incl. zero + overflow
        counts = upd(counts, jnp.full((8,), v, jnp.float32),
                     jnp.ones((8,), bool))
    assert len(traces) == 1


# --- lineage properties (seeded-numpy; hypothesis mirrors skipped) --------

def test_percentiles_monotone_property(rng):
    """p50 <= p95 <= p99 on random histograms, incl. empty/degenerate."""
    for _ in range(50):
        bank = _rand_bank(rng)
        for stage in LINEAGE_STAGES:
            p = OL.lineage_percentiles(bank)[stage]
            assert p["p50_us"] <= p["p95_us"] <= p["p99_us"], (stage, p)
            if p["count"] == 0:
                assert p["p99_us"] == 0.0


def test_merge_associative_commutative_property(rng):
    for _ in range(25):
        a, b, c = (_rand_bank(rng) for _ in range(3))
        np.testing.assert_array_equal(OL.histogram_merge(a, b),
                                      OL.histogram_merge(b, a))
        np.testing.assert_array_equal(
            OL.histogram_merge(OL.histogram_merge(a, b), c),
            OL.histogram_merge(a, OL.histogram_merge(b, c)))


def test_pooled_equals_merged_property(rng):
    """Summing per-shard banks == bucketing every sample into one
    histogram == what lineage_percentiles does to leading axes."""
    for _ in range(10):
        shards = np.stack([_rand_bank(rng) for _ in range(6)])
        pooled = shards[0]
        for s in shards[1:]:
            pooled = OL.histogram_merge(pooled, s)
        np.testing.assert_array_equal(pooled, shards.sum(axis=0))
        assert (OL.lineage_percentiles(shards)
                == OL.lineage_percentiles(pooled))


def test_lineage_update_rejects_typo_stage():
    bank = OL.lineage_init()
    with pytest.raises(ValueError):
        OL.lineage_update(bank, {"windwo": (jnp.zeros(4), jnp.ones(4, bool))})


def _edge_cases(rng, n=65536):
    """A batch of latencies with every hard case: values exactly on the
    (float32) edges, zeros, negatives, values past the last edge, NaN
    and inf, the rest log-spread over the whole range; about a third
    of the rows masked out."""
    e32 = DEFAULT_EDGES.astype(np.float32)
    vals = np.exp(rng.uniform(np.log(1e-7), np.log(1e3), n)).astype(
        np.float32)
    k = len(e32)
    vals[:k] = e32                                   # exactly on edges
    vals[k:2 * k] = np.nextafter(e32, np.float32(np.inf))   # just above
    vals[2 * k:2 * k + 64] = 0.0
    vals[2 * k + 64:2 * k + 128] = -1.0
    vals[2 * k + 128:2 * k + 192] = 5e2                    # overflow
    vals[2 * k + 192:2 * k + 196] = [np.nan, np.inf, -np.inf, 1e30]
    mask = rng.random(n) < 0.67
    mask[:2 * k + 196] = True
    return vals, mask


def test_histogram_update_batch_65536_rows_bit_identical(rng):
    """The loop-free bucketing equals numpy's ``searchsorted`` (left
    side, float32 edges, clamped at ``edges[0] / 2``) and the
    ``searchsorted`` + scatter-add formulation it replaced, on one
    tick's worth of rows, added onto existing counts."""
    vals, mask = _edge_cases(rng)
    e32 = DEFAULT_EDGES.astype(np.float32)
    start = rng.integers(0, 1000, len(DEFAULT_EDGES) + 1).astype(np.int32)
    got = np.asarray(jax.jit(OL.histogram_update_batch)(
        jnp.asarray(start), vals, mask))
    clamped = np.maximum(vals, e32[0] * np.float32(0.5))
    ref = start.astype(np.int64) + np.bincount(
        np.searchsorted(e32, clamped[mask], side="left"),
        minlength=len(start))
    np.testing.assert_array_equal(got, ref)
    e = jnp.asarray(DEFAULT_EDGES, jnp.float32)
    old = jnp.asarray(start).at[jnp.searchsorted(
        e, jnp.maximum(jnp.asarray(vals), e[0] * 0.5))].add(
            jnp.asarray(mask).astype(jnp.int32))
    np.testing.assert_array_equal(got, np.asarray(old))


def test_histogram_update_scalar_on_edges():
    """The one-sample update buckets exactly like ``searchsorted`` and
    still skips non-positive and NaN samples."""
    e32 = DEFAULT_EDGES.astype(np.float32)
    upd = jax.jit(OL.histogram_update)
    counts = OL.histogram_init()
    ref = np.zeros(len(e32) + 1, np.int64)
    for v in list(e32[::7]) + [e32[3] * 1.01, 0.0, -2.0, np.nan, 1e4]:
        counts = upd(counts, jnp.float32(v))
        if v > 0:
            ref[np.searchsorted(e32, np.float32(v))] += 1
    np.testing.assert_array_equal(np.asarray(counts), ref)


def test_lineage_update_buckets_a_stage_group_once(rng):
    """A tuple of stages takes one measurement into each of its rows;
    absent stages keep their counts, and a typo'd name in a group is
    refused."""
    vals, mask = _edge_cases(rng, n=4096)
    bank = jnp.asarray(_rand_bank(rng).astype(np.int32))
    w = (vals[:1024], mask[:1024])
    got = np.asarray(OL.lineage_update(bank, {
        "queueing": (vals, mask), ("window", "e2e"): w}))
    want = np.asarray(bank).copy()
    for name, (v, m) in (("queueing", (vals, mask)), ("window", w),
                         ("e2e", w)):
        i = LINEAGE_STAGES.index(name)
        want[i] = np.asarray(OL.histogram_update_batch(
            jnp.asarray(want[i]), v, m))
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError):
        OL.lineage_update(bank, {("window", "e2f"): w})


@pytest.mark.parametrize("fused", [False, True], ids=["staged", "fused"])
def test_compiled_step_lineage_has_no_loop_or_scatter(fused):
    """The lineage histograms compile to no ``while`` and no scatter:
    the compiled one-chip step, at a small size on the CPU, has neither
    under ``obs:lineage``."""
    import re

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig, StreamExecutor

    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE)])
    edge_fn = lambda p, b: (b, b[:, :5])  # noqa: E731
    scfg = StreamConfig(micro_batch=64, window=16, stride=16, capacity=128,
                        fused=fused)
    ex = StreamExecutor(scfg, engine,
                        pipe.two_tier_pipeline(edge_fn, edge_fn, engine))
    text = ex.lower(ex.init_state(3), jnp.zeros((64, 3)),
                    jnp.zeros((64,))).compile().as_text()
    lineage = [ln for ln in text.splitlines()
               if re.search(r'op_name="[^"]*obs:lineage', ln)]
    assert lineage                      # the scope is in the module
    bad = [ln.strip()[:120] for ln in lineage
           if re.search(r"= .*?\b(while|scatter)\(", ln)]
    assert not bad, bad


# --- warmup exclusion (regression: compile-polluted p99) ------------------

def _stream_executor(micro_batch=32, window=16, stride=16, capacity=128):
    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig, StreamExecutor

    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE)])
    edge_fn = lambda p, b: (b, b[:, :5])  # noqa: E731
    scfg = StreamConfig(micro_batch=micro_batch, window=window,
                        stride=stride, capacity=capacity)
    ex = StreamExecutor(scfg, engine,
                        pipe.two_tier_pipeline(edge_fn, edge_fn, engine))
    return ex, ex.init_state(3)


def test_warmup_excluded_from_step_histogram(rng):
    """The traced (compile) step's wall time must never enter the
    histogram: before the fix, one ~second compile tick put p99 six
    orders of magnitude above p95 in the committed baselines."""
    ex, state = _stream_executor()
    steps = 8
    first_step_s = None
    for i in range(steps):
        items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
        ts = jnp.asarray(i * 32 + np.arange(32), jnp.float32)
        t = time.perf_counter()
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
        if i == 0:
            first_step_s = time.perf_counter() - t
    lat = ex.latency_percentiles()
    # first tick feeds the 0.0 initial sentinel; the second withholds
    # the compile-polluted wall time and counts it instead
    assert lat["count"] == steps - 2
    assert lat["warmup_excluded"] == 1
    # the compile tick (dominated by tracing, orders above steady
    # state) must be absent from the tail
    assert lat["p99_us"] * 1e-6 < first_step_s
    assert ex.trace_count == 1


# --- single-device lineage through a live executor ------------------------

def test_stream_executor_lineage_counts(rng):
    ex, state = _stream_executor()
    steps = 6
    for i in range(steps):
        items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
        ts = jnp.asarray(i * 32 + np.arange(32), jnp.float32)
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
    assert ex.trace_count == 1             # lineage is shape-invisible
    m = state.metrics.as_dict()
    lin = ex.lineage_percentiles()
    assert set(lin) == set(LINEAGE_STAGES)
    # every dequeued row is a queueing sample; every emitted window a
    # window + e2e sample; the exchange hops need a fleet
    assert lin["queueing"]["count"] == m["items_dequeued"] > 0
    assert lin["window"]["count"] == m["windows_emitted"] > 0
    assert lin["e2e"]["count"] == m["windows_emitted"]
    assert lin["hop1"]["count"] == lin["hop2"]["count"] == 0
    # steady single-device flow is all same-tick: bucket 0 throughout
    assert lin["queueing"]["p99_us"] == pytest.approx(
        DEFAULT_EDGES[0] * 1e6)
    # ... and the snapshot carries the same dict
    from repro.obs import metrics_snapshot
    snap = metrics_snapshot(ex, state)
    assert snap["lineage"] == lin


def test_stream_executor_lineage_sees_ring_backpressure(rng):
    """Over-offering builds ring residency, which must surface as
    cross-tick queueing latency (the signal the SLO lane watches)."""
    ex, state = _stream_executor(capacity=256)
    for i in range(8):
        # 64 offered, 32 dequeued: residency grows 32 rows per tick
        items = jnp.asarray(rng.standard_normal((64, 3)), jnp.float32)
        ts = jnp.asarray(i * 64 + np.arange(64), jnp.float32)
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
    lin = ex.lineage_percentiles()
    assert ex.trace_count == 1
    # most dequeued rows waited >= 1 real tick: p50 must leave bucket 0
    assert lin["queueing"]["p50_us"] > DEFAULT_EDGES[0] * 1e6
    assert lin["queueing"]["p99_us"] >= lin["queueing"]["p50_us"]


# --- SLO evaluator --------------------------------------------------------

def _bank_with(stage, good=0, bad=0, target=1e-3):
    """Cumulative bank: `good` samples under target, `bad` over."""
    bank = np.zeros((len(LINEAGE_STAGES), len(DEFAULT_EDGES) + 1), np.int64)
    i = LINEAGE_STAGES.index(stage)
    bank[i, 0] = good
    bank[i, np.searchsorted(DEFAULT_EDGES, target) + 2] = bad
    return bank


def test_slo_validation():
    with pytest.raises(ValueError, match="stage"):
        SLO("x", target_seconds=1.0, stage="nope")
    with pytest.raises(ValueError, match="objective"):
        SLO("x", target_seconds=1.0, objective=1.0)
    with pytest.raises(ValueError, match="target_seconds"):
        SLO("x", stage="e2e")                  # latency SLO needs a target
    with pytest.raises(ValueError, match="fast_window"):
        SLO("x", target_seconds=1.0, fast_window=9, slow_window=3)
    with pytest.raises(ValueError, match="burn_threshold"):
        SLO("x", target_seconds=1.0, burn_threshold=0.0)
    SLO("drops", stage="drops")                # drop SLO needs no target
    with pytest.raises(ValueError, match="duplicate"):
        SloEvaluator([SLO("x", target_seconds=1.0),
                      SLO("x", target_seconds=2.0)])


def test_slo_breach_and_recover_transitions():
    slo = SLO("lat", target_seconds=1e-3, stage="e2e", objective=0.9,
              fast_window=2, slow_window=3, burn_threshold=2.0)
    ev = SloEvaluator([slo])
    bank, edges = np.zeros_like(_bank_with("e2e")), []
    script = [(100, 0)] * 3 + [(50, 50)] * 4 + [(100, 0)] * 4
    for good, bad in script:
        bank = bank + _bank_with("e2e", good, bad)
        st, = ev.observe(bank=bank)
        edges.append((st.breached, st.recovered, st.breaching))
    breaches = [i for i, e in enumerate(edges) if e[0]]
    recovers = [i for i, e in enumerate(edges) if e[1]]
    assert len(breaches) == 1 and len(recovers) == 1   # each edge once
    assert breaches[0] < recovers[0]
    # level matches the evaluator's breaching property trajectory
    assert all(e[2] for e in edges[breaches[0]:recovers[0]])
    assert ev.breaching == ()


def test_slo_no_data_holds_level():
    """Zero new samples is neither an error nor a recovery."""
    slo = SLO("lat", target_seconds=1e-3, objective=0.9,
              fast_window=1, slow_window=2, burn_threshold=1.0)
    ev = SloEvaluator([slo])
    bank = _bank_with("e2e", good=0, bad=50)
    st, = ev.observe(bank=bank)
    assert st.breached and ev.breaching == ("lat",)
    st, = ev.observe(bank=bank)            # no new samples
    assert st.breaching and not st.recovered


def test_slo_drop_lane():
    slo = SLO("drops", stage="drops", objective=0.5, fast_window=1,
              slow_window=1, burn_threshold=1.5)
    ev = SloEvaluator([slo])
    st, = ev.observe(drops=(0, 100))       # all emitted, none dropped
    assert not st.breaching
    st, = ev.observe(drops=(90, 200))      # 90 of 100 new windows dropped
    assert st.breached
    st, = ev.observe(drops=(90, 300))      # clean again
    assert st.recovered


def test_slo_straddling_bucket_counts_bad():
    """A sample in the bucket straddling the target counts bad — bucket
    resolution must never under-report a breach."""
    target = float(DEFAULT_EDGES[40] * 1.01)     # just above an edge
    slo = SLO("lat", target_seconds=target, objective=0.5,
              fast_window=1, slow_window=1, burn_threshold=1.0)
    ev = SloEvaluator([slo])
    bank = np.zeros((len(LINEAGE_STAGES), len(DEFAULT_EDGES) + 1), np.int64)
    bank[LINEAGE_STAGES.index("e2e"), 41] = 10   # upper edge > target
    st, = ev.observe(bank=bank)
    assert st.breached


# --- cost model -----------------------------------------------------------

def test_costmodel_analyze_attributes_stages():
    @jax.jit
    def f(x, w):
        with jax.named_scope("obs:mix"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("obs:reduce"):
            return y.sum(axis=0)

    x = jnp.ones((32, 16), jnp.float32)
    w = jnp.ones((16, 16), jnp.float32)
    cost = analyze(f, x, w)
    assert cost["flops"] > 0 and cost["bytes_accessed"] > 0
    assert cost["transcendentals"] >= 0
    assert "obs:mix" in cost["stages"]
    assert cost["stages"]["obs:mix"]["ops"] > 0
    assert cost["stages"]["obs:mix"]["bytes"] > 0


def test_roofline_utilization(monkeypatch):
    monkeypatch.delenv("REPRO_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("REPRO_PEAK_BW", raising=False)
    rl = roofline(2e9, 1e9, 1.0)
    assert rl["gflops"] == pytest.approx(2.0)
    assert rl["gbs"] == pytest.approx(1.0)
    assert rl["ai"] == pytest.approx(2.0)
    assert rl["flops_util"] == rl["bw_util"] == 0.0   # peak undeclared
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "4e9")
    monkeypatch.setenv("REPRO_PEAK_BW", "8e9")
    rl = roofline(2e9, 1e9, 1.0)
    assert rl["flops_util"] == pytest.approx(0.5)
    assert rl["bw_util"] == pytest.approx(0.125)


def test_stream_executor_step_cost(rng):
    ex, state = _stream_executor()
    items = rng.standard_normal((32, 3)).astype(np.float32)
    ts = np.arange(32, dtype=np.float32)
    cost = ex.step_cost(state, items, ts)
    assert cost["flops"] > 0
    # the named-scope stages of the tick show up in the attribution
    assert any(k.startswith("obs:") for k in cost["stages"])
    # analysis must not have consumed the live state or added a trace
    state, out = ex.step(state, jnp.asarray(items), jnp.asarray(ts))
    jax.block_until_ready(out)
    assert ex.trace_count <= 1


# --- perf-regression gate -------------------------------------------------

def _gate():
    from benchmarks import compare
    return compare


def _rows():
    return [
        {"name": "s/step", "us_per_call": 100.0,
         "derived": {"items_per_s": 1000.0, "traces": 1}},
        {"name": "s/hist", "us_per_call": 90.0,
         "derived": {"hist_p99_us": 400.0, "hist_count": 50,
                     "warmup_excluded": 1}},
    ]


def test_compare_self_is_clean():
    CMP = _gate()
    base = {"rows": _rows()}
    res = CMP.compare_payloads(_rows(), base)
    assert res["ok"] and not res["regressions"]
    report = CMP.format_report(res, "demo")
    assert "PASS" in report


def test_compare_timing_tolerance_and_direction():
    CMP = _gate()
    base = {"rows": _rows()}
    fresh = _rows()
    fresh[0]["us_per_call"] = 180.0        # +80%: inside the 2x band
    fresh[0]["derived"]["items_per_s"] = 5000.0   # faster: never flags
    assert CMP.compare_payloads(fresh, base)["ok"]
    fresh[0]["us_per_call"] = 250.0        # 2.5x: regression
    res = CMP.compare_payloads(fresh, base)
    assert not res["ok"]
    assert ("s/step", "us_per_call", 100.0, 250.0) in res["regressions"]
    # throughput is bigger-is-better: a 2.5x *drop* flags
    fresh = _rows()
    fresh[0]["derived"]["items_per_s"] = 300.0
    assert not CMP.compare_payloads(fresh, base)["ok"]


def test_compare_counters_exact_and_missing_rows():
    CMP = _gate()
    base = {"rows": _rows()}
    fresh = _rows()
    fresh[0]["derived"]["traces"] = 2      # semantic: exact match
    res = CMP.compare_payloads(fresh, base)
    assert ("s/step", "traces", 1, 2) in res["regressions"]
    # a silently dropped row is a regression; a new row is only info
    res = CMP.compare_payloads(_rows()[:1], base)
    assert not res["ok"] and res["missing"]
    fresh = _rows() + [{"name": "s/new", "us_per_call": 1.0, "derived": {}}]
    res = CMP.compare_payloads(fresh, base)
    assert res["ok"] and ("s/new", "us_per_call") in res["new"]


def test_compare_missing_baseline_fails_loudly(tmp_path, capsys):
    CMP = _gate()
    ok = CMP.compare_suite("ghost", _rows(),
                           baseline_path=str(tmp_path / "nope.json"))
    assert not ok
    assert "no committed baseline" in capsys.readouterr().out


def test_timing_key_classification():
    CMP = _gate()
    for k in ("us_per_call", "hist_p99_us", "items_per_s", "gflops",
              "flops_util", "ai"):
        assert CMP.is_timing_key(k), k
    for k in ("traces", "hist_count", "warmup_excluded", "flops",
              "esc", "intra_region"):
        assert not CMP.is_timing_key(k), k


def test_roofline_report_missing_dir_exits_2(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + _REPO
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.roofline_report",
         str(tmp_path / "no_such_dir")],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "usage" in out.stderr


# --- the SLO arc on a fleet (subprocess: 8 forced devices) ----------------

_SLO_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.obs import EventLog, SLO
    from repro.obs.latency import DEFAULT_EDGES
    from repro.runtime.elastic import ElasticBudget
    from repro.stream import StreamConfig
    from repro.stream.fleet import (FleetConfig, FleetController,
                                    FleetExecutor)

    LOG_PATH = sys.argv[1]
    D, DEQ, N, E, R = 3, 32, 64, 8, 2
    STALLED = 2                       # the throttled shard (region 0)
    edge_fn = lambda p, b: (b, b[:, :5])
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE)])
    scfg = StreamConfig(micro_batch=DEQ, window=16, stride=16,
                        capacity=256, lateness=1e9)
    ex = FleetExecutor(
        FleetConfig(stream=scfg, num_shards=E, num_core=2,
                    core_budget=16, num_regions=R, fog_budget=8),
        engine, pipe.two_tier_pipeline(edge_fn, edge_fn, engine))
    log = EventLog(LOG_PATH)
    slo = SLO("queueing-100us", target_seconds=1e-4, stage="queueing",
              objective=0.95, fast_window=2, slow_window=4,
              burn_threshold=2.0)
    ctl = FleetController(
        ex, budget_policy=ElasticBudget(min_budget=16, max_budget=16),
        event_log=log, slos=(slo,))
    state = ex.init_state(D)

    # producer arc on the throttled shard: steady -> stall (nothing
    # offered) -> catch-up (the full 64-slot burst: ring residency
    # grows 32 rows per tick) -> drain -> steady.  Every other shard
    # offers a steady 32 fresh rows per tick throughout.
    def offered_rows(tick):
        if 4 <= tick < 6:
            return 0                  # stalled uplink
        if 6 <= tick < 10:
            return N                  # catch-up burst
        if 10 <= tick < 14:
            return 0                  # drain the backlog
        return DEQ

    rng = np.random.default_rng(0)
    decisions = []
    for t in range(20):
        items = rng.standard_normal((E, N, D)).astype(np.float32)
        ts = np.tile(t * N + np.arange(N, dtype=np.float32), (E, 1))
        offered = np.zeros((E, N), bool)
        offered[:, :DEQ] = True
        offered[STALLED] = np.arange(N) < offered_rows(t)
        state, out = ex.step(state, jnp.asarray(items), jnp.asarray(ts),
                             offered=jnp.asarray(offered))
        jax.block_until_ready(out)
        decisions.append(ctl.tick(state))

    assert ex.trace_count == 1, ex.trace_count   # SLO lane: zero retraces
    m = state.metrics.as_dict()
    assert sum(m["shard"]["items_rejected"]) == 0   # ring never overflowed

    # the breach level rode the control decisions as a policy signal
    breach_ticks = [t for t, d in enumerate(decisions) if d.slo_breached]
    assert breach_ticks, "SLO never breached under backpressure"
    assert all(d.slo_breached == ("queueing-100us",)
               for t, d in enumerate(decisions) if t in breach_ticks)
    assert not decisions[-1].slo_breached        # recovered by the end

    # ... and the transitions landed in a validated event log, once each
    log.close()
    recs = EventLog.load(LOG_PATH)
    EventLog.validate(recs)
    breaches = [r for r in recs if r["kind"] == "slo_breach"]
    recovers = [r for r in recs if r["kind"] == "slo_recover"]
    assert len(breaches) == 1 and len(recovers) == 1
    assert breaches[0]["slo"] == "queueing-100us"
    assert breaches[0]["stage"] == "queueing"
    assert breaches[0]["fast_burn"] >= 2.0
    assert breaches[0]["tick"] < recovers[0]["tick"]

    # lineage localizes the latency: per-shard, only the throttled
    # shard's queueing tail left bucket 0; per-region, only its region
    bucket0_us = DEFAULT_EDGES[0] * 1e6
    per_shard = ex.lineage_percentiles(by="shard")
    for s in range(E):
        q = per_shard[s]["queueing"]
        assert q["count"] > 0
        if s == STALLED:
            assert q["p99_us"] > 100.0, q
        else:
            assert q["p99_us"] <= bucket0_us * 1.01, (s, q)
    per_region = ex.lineage_percentiles(by="region")
    assert per_region[0]["queueing"]["p99_us"] > 100.0
    assert per_region[1]["queueing"]["p99_us"] <= bucket0_us * 1.01
    # the three views pool consistently
    fleet_q = ex.lineage_percentiles()["queueing"]["count"]
    assert fleet_q == sum(p["queueing"]["count"] for p in per_shard)
    assert fleet_q == sum(p["queueing"]["count"] for p in per_region)
    print("SLO_ARC_OK", breaches[0]["tick"], recovers[0]["tick"])
""")


def test_fleet_slo_breach_arc(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_REPO, "src")
    script = tmp_path / "slo_arc.py"
    script.write_text(_SLO_SCRIPT)
    log_path = tmp_path / "slo_events.jsonl"
    out = subprocess.run([sys.executable, str(script), str(log_path)],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SLO_ARC_OK" in out.stdout
