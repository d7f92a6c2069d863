"""Stream subsystem regression tests: window ops vs pure-numpy
references (incl. the Pallas window_reduce kernel), watermark policy,
and the micro-batch executor invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import pipeline as pipe
from repro.core import rules
from repro.kernels.window_reduce import window_reduce, window_reduce_ref
from repro.stream import (StreamConfig, StreamExecutor, apply_watermark,
                          session_window, sliding_window, tumbling_window,
                          window_features)

REDUCERS = ("sum", "mean", "max", "min", "count")


def _block(rng, t, d, p_valid=0.8):
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = jnp.asarray(rng.random(t) < p_valid)
    return x, v


# ---- window operators vs the numpy oracle --------------------------------

@pytest.mark.parametrize("t,d,w,s", [
    (32, 4, 8, 8),      # tumbling, aligned
    (37, 3, 8, 8),      # tumbling, partial tail window
    (37, 3, 8, 3),      # sliding, partial tails
    (10, 1, 4, 1),      # dense sliding
    (5, 2, 16, 4),      # window larger than the block
    (64, 5, 1, 1),      # degenerate width-1 windows
])
@pytest.mark.parametrize("reducer", REDUCERS)
def test_sliding_window_matches_numpy_ref(rng, t, d, w, s, reducer):
    x, v = _block(rng, t, d)
    ref_o, ref_c = window_reduce_ref(np.asarray(x), np.asarray(v), w, s,
                                     reducer)
    out, count = sliding_window(x, v, w, s, reducer=reducer)
    assert out.shape[0] == -(-t // s)
    np.testing.assert_allclose(np.asarray(out), ref_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(count), ref_c)


def test_tumbling_partial_tail_masked(rng):
    x, _ = _block(rng, 10, 2, p_valid=1.0)
    v = jnp.ones(10, bool)
    out, count = tumbling_window(x, v, 4, reducer="sum")
    assert out.shape == (3, 2)
    np.testing.assert_array_equal(np.asarray(count), [4, 4, 2])
    # tail window sums only its 2 real samples
    np.testing.assert_allclose(np.asarray(out[2]),
                               np.asarray(x[8:]).sum(0), rtol=1e-6)


def test_fully_masked_window_reduces_to_zero():
    x = jnp.ones((8, 3)) * 5.0
    v = jnp.asarray([True] * 4 + [False] * 4)
    for reducer in REDUCERS:
        out, count = tumbling_window(x, v, 4, reducer=reducer)
        assert int(count[1]) == 0
        np.testing.assert_array_equal(np.asarray(out[1]), 0)


def test_custom_callable_reducer(rng):
    x, v = _block(rng, 16, 2)

    def masked_range(vals, mask):   # max - min over valid samples
        m = mask[:, :, None]
        big = jnp.finfo(vals.dtype).max
        mx = jnp.max(jnp.where(m, vals, -big), axis=1)
        mn = jnp.min(jnp.where(m, vals, big), axis=1)
        return jnp.where(jnp.any(mask, 1)[:, None], mx - mn, 0)

    out, _ = sliding_window(x, v, 8, 4, reducer=masked_range)
    mx, _ = sliding_window(x, v, 8, 4, reducer="max")
    mn, _ = sliding_window(x, v, 8, 4, reducer="min")
    np.testing.assert_allclose(np.asarray(out), np.asarray(mx - mn),
                               rtol=1e-5, atol=1e-5)


def test_complete_only_framing(rng):
    x, v = _block(rng, 24, 2)
    out, count = sliding_window(x, v, 8, 4, partial=False)
    assert out.shape[0] == (24 - 8) // 4 + 1
    ref_o, ref_c = window_reduce_ref(np.asarray(x), np.asarray(v), 8, 4,
                                     "mean")
    np.testing.assert_allclose(np.asarray(out), ref_o[:out.shape[0]],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(count), ref_c[:out.shape[0]])


def test_window_features_columns(rng):
    x, v = _block(rng, 20, 3)
    feats, count = window_features(x, v, 8, 4)
    for col, red in [(0, "mean"), (1, "max"), (2, "min"), (3, "sum")]:
        ref, _ = window_reduce_ref(np.asarray(x[:, :1]), np.asarray(v), 8, 4,
                                   red)
        np.testing.assert_allclose(np.asarray(feats[:, col]), ref[:, 0],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(feats[:, 4]),
                                  np.asarray(count, np.float32))


# ---- Pallas kernel vs its ref --------------------------------------------

@pytest.mark.parametrize("t,d,w,s", [
    (32, 4, 8, 8), (37, 3, 8, 3), (10, 1, 4, 1), (5, 2, 16, 4),
    (128, 130, 16, 8),              # > one lane tile wide
    (300, 7, 32, 16),
])
@pytest.mark.parametrize("reducer", REDUCERS)
def test_window_reduce_kernel_matches_ref(rng, t, d, w, s, reducer):
    x, v = _block(rng, t, d)
    ref_o, ref_c = window_reduce_ref(np.asarray(x), np.asarray(v), w, s,
                                     reducer)
    out, count = window_reduce(x, v, w, s, reducer=reducer, interpret=True)
    np.testing.assert_allclose(np.asarray(out), ref_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(count), ref_c)


def test_pallas_backend_equals_jnp_backend(rng):
    x, v = _block(rng, 96, 6)
    for reducer in REDUCERS:
        j, jc = sliding_window(x, v, 16, 8, reducer=reducer)
        p, pc = sliding_window(x, v, 16, 8, reducer=reducer,
                               backend="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(j), np.asarray(p),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(jc), np.asarray(pc))


# ---- session windows -------------------------------------------------------

def _session_ref(x, valid, ts, gap, reducer="mean"):
    """Pure-numpy session window oracle."""
    t = x.shape[0]
    order = np.argsort(np.where(valid, ts, np.inf), kind="stable")
    xs, vs, tss = x[order], valid[order], ts[order]
    sessions, cur = [], []
    last = None
    for i in range(t):
        if not vs[i]:
            continue
        if last is not None and tss[i] - last > gap:
            sessions.append(cur)
            cur = []
        cur.append(i)
        last = tss[i]
    if cur:
        sessions.append(cur)
    out = np.zeros_like(x)
    count = np.zeros(t, np.int32)
    closed = np.zeros(t, bool)
    for k, idxs in enumerate(sessions):
        vals = xs[idxs]
        count[k] = len(idxs)
        closed[k] = k < len(sessions) - 1
        out[k] = {"mean": vals.mean(0), "sum": vals.sum(0),
                  "max": vals.max(0), "min": vals.min(0),
                  "count": np.full(x.shape[1], len(idxs))}[reducer]
    return out, count, closed


@pytest.mark.parametrize("reducer", REDUCERS)
def test_session_window_matches_numpy_ref(rng, reducer):
    t, d, gap = 40, 3, 5.0
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = jnp.asarray(rng.random(t) < 0.8)
    # bursty arrivals: clusters separated by > gap silences
    ts = np.cumsum(rng.choice([0.5, 1.0, 12.0], t, p=[0.45, 0.45, 0.1]))
    ts = jnp.asarray(ts, jnp.float32)
    out, count, closed = session_window(x, v, ts, gap, reducer=reducer)
    ref_o, ref_c, ref_cl = _session_ref(np.asarray(x), np.asarray(v),
                                        np.asarray(ts), gap, reducer)
    np.testing.assert_allclose(np.asarray(out), ref_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(count), ref_c)
    np.testing.assert_array_equal(np.asarray(closed), ref_cl)


def test_session_window_gap_boundaries():
    # 3 samples, gaps of exactly `gap` (same session) and > gap (new)
    x = jnp.asarray([[1.0], [2.0], [10.0]])
    ts = jnp.asarray([0.0, 5.0, 11.0])
    out, count, closed = session_window(x, jnp.ones(3, bool), ts, 5.0,
                                        reducer="sum")
    np.testing.assert_array_equal(np.asarray(count), [2, 1, 0])
    np.testing.assert_allclose(np.asarray(out[:2]), [[3.0], [10.0]])
    # first session closed by the 11.0 arrival; the last stays open
    np.testing.assert_array_equal(np.asarray(closed), [True, False, False])


def test_session_window_unsorted_and_masked_input(rng):
    # out-of-order delivery and invalid rows must not split sessions
    x = jnp.asarray(rng.standard_normal((6, 2)), jnp.float32)
    ts = jnp.asarray([3.0, 1.0, 2.0, 100.0, 101.0, 50.0])
    v = jnp.asarray([True, True, True, True, True, False])
    out, count, closed = session_window(x, v, ts, 2.0, reducer="count")
    np.testing.assert_array_equal(np.asarray(count), [3, 2, 0, 0, 0, 0])
    assert bool(closed[0]) and not bool(closed[1])


def test_session_window_all_invalid():
    x = jnp.ones((4, 2))
    out, count, closed = session_window(x, jnp.zeros(4, bool),
                                        jnp.arange(4.0), 1.0)
    np.testing.assert_array_equal(np.asarray(count), 0)
    np.testing.assert_array_equal(np.asarray(out), 0)
    assert not bool(np.asarray(closed).any())


# ---- watermark ------------------------------------------------------------

def test_watermark_in_order_stream_drops_nothing():
    mx = jnp.asarray(jnp.finfo(jnp.float32).min)
    for blk in range(3):
        ts = jnp.asarray(np.arange(8) + blk * 8, jnp.float32)
        valid, n_late, mx = apply_watermark(ts, jnp.ones(8, bool), mx, 0.0)
        assert int(n_late) == 0 and bool(valid.all())
    assert float(mx) == 23.0


def test_watermark_drops_reordered_data_beyond_lateness():
    mx = jnp.asarray(jnp.finfo(jnp.float32).min)
    _, _, mx = apply_watermark(jnp.asarray([0., 50.]), jnp.ones(2, bool),
                               mx, 5.0)
    ts = jnp.asarray([49., 46., 44., 60.])    # 44 is > 5 behind max 50
    valid, n_late, mx = apply_watermark(ts, jnp.ones(4, bool), mx, 5.0)
    np.testing.assert_array_equal(np.asarray(valid),
                                  [True, True, False, True])
    assert int(n_late) == 1 and float(mx) == 60.0


def test_watermark_integer_timestamps():
    mx = jnp.asarray(0, jnp.int32)
    ts = jnp.arange(4, dtype=jnp.int32)
    valid, n_late, mx = apply_watermark(ts, jnp.ones(4, bool), mx, 1)
    assert int(n_late) == 0 and int(mx) == 3


def test_watermark_ignores_invalid_rows():
    mx = jnp.asarray(0.0, jnp.float32)
    ts = jnp.asarray([-100.0, 99.0])
    valid, n_late, mx = apply_watermark(ts, jnp.asarray([False, True]),
                                        mx, 1.0)
    assert int(n_late) == 0          # invalid row can't be "late"
    assert float(mx) == 99.0


# ---- executor --------------------------------------------------------------

def _make_executor(d=3, micro_batch=32, window=16, stride=8, capacity=128,
                   core_capacity=2, threshold=1.0, lateness=8.0):
    cfg = StreamConfig(micro_batch=micro_batch, window=window, stride=stride,
                       capacity=capacity, lateness=lateness)
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", threshold, rules.C_SEND_CORE,
                             priority=1)])

    def edge_fn(p, b):
        return b, b[:, :5]

    def core_fn(p, b):
        return b + 100.0, b[:, :5]

    p = pipe.two_tier_pipeline(edge_fn, core_fn, engine,
                               core_capacity=core_capacity)
    ex = StreamExecutor(cfg, engine, p)
    return ex, ex.init_state(d)


def _feed(ex, state, rng, steps, bias=0.0, batch=32, d=3, t0=0.0):
    for _ in range(steps):
        items = jnp.asarray(
            rng.standard_normal((batch, d)).astype(np.float32) + bias)
        ts = jnp.asarray(t0 + np.arange(batch), jnp.float32)
        t0 += batch
        state, out = ex.step(state, items, ts)
    return state, out, t0


def test_executor_single_trace_and_conservation(rng):
    ex, state = _make_executor()
    state, out, _ = _feed(ex, state, rng, 10)
    m = state.metrics
    assert ex.trace_count == 1
    # one compiled executable too: no tick compiled without a new trace
    assert ex._jstep._cache_size() == 1
    assert int(m.steps) == 10
    assert int(m.items_offered) == 320
    assert int(m.items_accepted) + int(m.items_rejected) \
        == int(m.items_offered)
    assert int(m.items_rejected) == 0        # consumption == production
    # every step emits exactly micro_batch // stride complete windows
    assert out.aggregates.shape[0] == 32 // 8
    assert int(m.windows_emitted) == 10 * 4


def test_executor_escalates_hot_windows_only(rng):
    ex, state = _make_executor(threshold=1.0)
    state, out_cold, t0 = _feed(ex, state, rng, 5, bias=0.0)
    cold_esc = int(state.metrics.windows_escalated)
    state, out_hot, _ = _feed(ex, state, rng, 5, bias=3.0, t0=t0)
    hot_esc = int(state.metrics.windows_escalated) - cold_esc
    assert cold_esc <= 2                     # noise can graze 1.0
    assert hot_esc >= 15                     # hot regime fires hard
    # escalated windows that fit core capacity got the core transform
    # (+100 on the record); overflow keeps the edge result, not zeros
    esc = np.asarray(out_hot.escalated)
    assert esc.any()
    record = np.concatenate([np.asarray(out_hot.features),
                             np.asarray(out_hot.aggregates)], axis=1)
    outputs = np.asarray(out_hot.outputs)
    cored = (outputs[:, 5:] > 50).all(axis=1)
    assert cored[esc].sum() == min(int(esc.sum()), 2)   # core_capacity=2
    overflow = esc & ~cored
    np.testing.assert_allclose(outputs[overflow], record[overflow],
                               rtol=1e-5)


def test_executor_core_capacity_overflow_accounting(rng):
    ex, state = _make_executor(core_capacity=1, threshold=-100.0)
    state, _, _ = _feed(ex, state, rng, 4)
    m = state.metrics
    # all 4 windows/step flagged, core fits 1 -> 3 overflow per step
    assert int(m.core_overflow) == 4 * 3


def test_executor_dynamic_core_budget(rng):
    """set_core_budget is a traced operand: shrinking it below the
    static core_capacity binds (fewer windows get core compute, the
    rest keep edge results and count as overflow) with zero re-traces;
    a budget at the capacity reproduces the static behavior."""
    ex, state = _make_executor(core_capacity=3, threshold=-100.0)
    state, out, t0 = _feed(ex, state, rng, 2)
    ex.set_core_budget(1)                    # binds: 4 windows, 1 slot
    state, out, t0 = _feed(ex, state, rng, 3, t0=t0)
    m = state.metrics
    # 2 steps at budget==capacity (1 overflow each) + 3 steps at
    # budget 1 (3 overflow each): the operand changed, the trace didn't
    assert int(m.core_overflow) == 2 * 1 + 3 * 3
    assert ex.trace_count == 1
    cored = (np.asarray(out.outputs)[:, 5:] > 50).all(axis=1)
    assert cored.sum() == 1                  # exactly the budget
    ex.set_core_budget(3)                    # back to the static cap
    state, out, _ = _feed(ex, state, rng, 1, t0=t0)
    assert (np.asarray(out.outputs)[:, 5:] > 50).all(axis=1).sum() == 3
    assert ex.trace_count == 1


def test_pipeline_overflow_keeps_consequence_and_skips_rules():
    """Core-capacity overflow items must keep their SEND_CORE
    consequence — the gather's zeroed features must not re-trigger
    rules (e.g. a count<thresh store rule firing on zeros)."""
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE,
                             priority=1),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=2),
    ])
    p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                               lambda _, b: (b + 100.0, b[:, :5]),
                               engine, core_capacity=1)
    # 3 hot windows (mean=2, count=16): all escalate, core fits 1
    batch = jnp.tile(jnp.asarray([[2., 2., 2., 2., 16.]]), (3, 1))
    r = p.run(batch)
    assert bool(r.escalated.all())
    assert not bool(r.stored.any())          # zeros never hit "sparse"
    np.testing.assert_array_equal(np.asarray(r.consequence),
                                  [rules.C_SEND_CORE] * 3)
    # exactly one got the core transform; the others keep edge results
    cored = np.asarray((r.outputs[:, 0] > 50))
    assert cored.sum() == 1
    np.testing.assert_allclose(np.asarray(r.outputs)[~cored],
                               np.asarray(batch)[~cored])


def test_executor_non_emitted_windows_consume_no_core_capacity(rng):
    """Underrun (empty) windows must not escalate on their zeroed
    features nor occupy core-capacity slots."""
    cfg = StreamConfig(micro_batch=32, window=16, stride=8, capacity=128,
                       min_count=4)
    engine = rules.RuleEngine([
        rules.threshold_rule("low", 0, "<=", 0.5, rules.C_SEND_CORE)])
    p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                               lambda _, b: (b + 100.0, b[:, :5]),
                               engine, core_capacity=2)
    ex = StreamExecutor(cfg, engine, p)
    state = ex.init_state(2)
    # step with an empty ring: all windows empty, rule matches mean=0
    state, out = ex.step(state, jnp.zeros((0, 2)), jnp.zeros((0,)))
    m = state.metrics
    assert int(m.windows_emitted) == 0
    assert int(m.windows_escalated) == 0
    assert int(m.core_overflow) == 0
    assert not bool(np.asarray(out.escalated).any())
    # and the core transform never touched the dead windows
    np.testing.assert_array_equal(np.asarray(out.outputs),
                                  np.zeros_like(np.asarray(out.outputs)))


def test_executor_backpressure_when_producer_outruns_consumer(rng):
    # offer 64/step, consume 32/step, ring holds 64: rejects must appear
    cfg = StreamConfig(micro_batch=32, window=16, stride=8, capacity=64)
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 99.0, rules.C_SEND_CORE)])
    p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                               lambda _, b: (b, b[:, :5]), engine)
    ex = StreamExecutor(cfg, engine, p)
    state = ex.init_state(2)
    t0 = 0.0
    for _ in range(6):
        items = jnp.asarray(rng.standard_normal((64, 2)), jnp.float32)
        ts = jnp.asarray(t0 + np.arange(64), jnp.float32)
        t0 += 64
        state, _ = ex.step(state, items, ts)
    m = state.metrics
    assert int(m.items_rejected) > 0
    assert int(m.items_accepted) + int(m.items_rejected) == 6 * 64
    assert ex.trace_count == 1


def test_executor_window_continuity_across_steps(rng):
    """Windows tile the stream exactly: feeding the same samples in one
    big block (complete-only framing) gives the same aggregates as
    feeding them in micro-batches."""
    d, batch, w, s, steps = 2, 16, 8, 4, 4
    ex, state = _make_executor(d=d, micro_batch=batch, window=w, stride=s,
                               threshold=1e9, lateness=1e9)
    samples = rng.standard_normal((batch * steps, d)).astype(np.float32)
    outs = []
    t0 = 0.0
    for i in range(steps):
        items = jnp.asarray(samples[i * batch:(i + 1) * batch])
        ts = jnp.asarray(t0 + np.arange(batch), jnp.float32)
        t0 += batch
        state, out = ex.step(state, items, ts)
        outs.append(np.asarray(out.aggregates))
    got = np.concatenate(outs)
    # oracle: same framing over the whole stream, first window starting
    # at -carry (invalid) — i.e. aggregates shifted by carry length
    carry = w - s
    padded = np.concatenate([np.zeros((carry, d), np.float32), samples])
    pvalid = np.concatenate([np.zeros(carry, bool),
                             np.ones(batch * steps, bool)])
    ref, _ = window_reduce_ref(padded, pvalid, w, s, "mean")
    nw = got.shape[0]
    np.testing.assert_allclose(got, ref[:nw], rtol=1e-5, atol=1e-5)


def test_executor_late_items_masked(rng):
    ex, state = _make_executor(lateness=4.0)
    state, _, t0 = _feed(ex, state, rng, 2)
    items = jnp.asarray(rng.standard_normal((32, 3)), jnp.float32)
    ts = np.asarray(t0 + np.arange(32), np.float32)
    ts[:3] -= 1000.0                          # 3 stragglers
    state, _ = ex.step(state, items, jnp.asarray(ts))
    assert int(state.metrics.items_late) == 3


def test_executor_pallas_backend_matches_jnp_bitwise(rng):
    """End-to-end executor parity: a pallas-backed run (interpret mode)
    must reproduce the jnp run bit-for-bit, step by step."""
    runs = {}
    for backend in ("jnp", "pallas"):
        cfg = StreamConfig(micro_batch=32, window=16, stride=8,
                           capacity=128, lateness=8.0, backend=backend,
                           interpret=backend == "pallas")
        engine = rules.RuleEngine([
            rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE,
                                 priority=1)])
        p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                                   lambda _, b: (b + 100.0, b[:, :5]),
                                   engine, core_capacity=2)
        ex = StreamExecutor(cfg, engine, p)
        state = ex.init_state(3)
        feed = np.random.default_rng(3)
        outs, t0 = [], 0.0
        for _ in range(6):
            items = jnp.asarray(feed.standard_normal((32, 3)), jnp.float32)
            ts = jnp.asarray(t0 + np.arange(32), jnp.float32)
            t0 += 32
            state, out = ex.step(state, items, ts)
            outs.append(jax.device_get(out))
        assert ex.trace_count == 1
        runs[backend] = (outs, jax.device_get(state.metrics))
    for sj, sp in zip(*(runs[b][0] for b in ("jnp", "pallas"))):
        for field, a, b in zip(sj._fields, sj, sp):
            np.testing.assert_array_equal(a, b, err_msg=field)
    for field, a, b in zip(runs["jnp"][1]._fields, *(runs[b][1] for b in
                                                     ("jnp", "pallas"))):
        np.testing.assert_array_equal(a, b, err_msg=field)


def test_executor_fused_matches_staged_bitwise(rng):
    """The fused tick (StreamConfig(fused=True)) must reproduce the
    staged window -> features -> rules path bit-for-bit on both fused
    backends, across steps with live carry, stragglers hitting the
    watermark, and a multi-rule conflict set — outputs AND metrics."""
    runs = {}
    for key, fused, backend in (("staged", False, "jnp"),
                                ("fused-jnp", True, "jnp"),
                                ("fused-pallas", True, "pallas")):
        cfg = StreamConfig(micro_batch=32, window=16, stride=8,
                           capacity=128, lateness=8.0, fused=fused,
                           backend=backend, interpret=backend == "pallas")
        engine = rules.RuleEngine([
            rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE,
                                 priority=1),
            rules.threshold_rule("sparse", 4, "<", 8.0,
                                 rules.C_STORE_EDGE)])
        p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                                   lambda _, b: (b + 100.0, b[:, :5]),
                                   engine, core_capacity=2)
        ex = StreamExecutor(cfg, engine, p)
        state = ex.init_state(3)
        feed = np.random.default_rng(11)
        outs, t0 = [], 0.0
        for i in range(6):
            items = jnp.asarray(feed.standard_normal((32, 3)), jnp.float32)
            ts = np.asarray(t0 + np.arange(32), np.float32)
            if i == 3:
                ts[:2] -= 1000.0          # stragglers hit the watermark
            t0 += 32
            state, out = ex.step(state, items, jnp.asarray(ts))
            outs.append(jax.device_get(out))
        assert ex.trace_count == 1
        runs[key] = (outs, jax.device_get(state.metrics))
    base_outs, base_metrics = runs["staged"]
    for key in ("fused-jnp", "fused-pallas"):
        for so, fo in zip(base_outs, runs[key][0]):
            for field, a, b in zip(so._fields, so, fo):
                np.testing.assert_array_equal(a, b,
                                              err_msg=f"{key}:{field}")
        for field, a, b in zip(base_metrics._fields, base_metrics,
                               runs[key][1]):
            np.testing.assert_array_equal(a, b, err_msg=f"{key}:{field}")


def test_executor_fused_requires_tabular_engine():
    """Callable rules can't run inside the fused kernel: the executor
    must refuse fused=True at construction, not corrupt at step time."""
    cfg = StreamConfig(micro_batch=32, window=16, stride=8, capacity=128,
                       fused=True)
    engine = rules.RuleEngine([
        rules.deadline_rule("slow", 4, 100.0)])      # callable-only rule
    assert engine.table() is None
    p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                               lambda _, b: (b, b[:, :5]), engine)
    with pytest.raises(ValueError, match="tabular"):
        StreamExecutor(cfg, engine, p)


def _overlap_batches(steps=5, batch=32, d=3, seed=5):
    feed = np.random.default_rng(seed)
    batches, t0 = [], 0.0
    for _ in range(steps):
        items = feed.standard_normal((batch, d)).astype(np.float32)
        ts = (t0 + np.arange(batch)).astype(np.float32)
        t0 += batch
        batches.append((jnp.asarray(items), jnp.asarray(ts)))
    return batches


def test_run_overlap_ingest_matches_direct_bitwise(rng):
    """Overlapped host ingest staging changes delivery *timing* only:
    with int8 off, run() outputs and metrics are bitwise those of the
    direct loop, every batch delivered (the flush drains the tail)."""
    batches = _overlap_batches()
    runs = {}
    for overlap in (False, True):
        cfg = StreamConfig(micro_batch=32, window=16, stride=8,
                           capacity=128, lateness=8.0,
                           overlap_ingest=overlap)
        engine = rules.RuleEngine([
            rules.threshold_rule("hot", 0, ">=", 0.5,
                                 rules.C_SEND_CORE)])
        p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                                   lambda _, b: (b + 100.0, b[:, :5]),
                                   engine, core_capacity=2)
        ex = StreamExecutor(cfg, engine, p)
        state, outs = ex.run(ex.init_state(3), iter(batches))
        assert ex.trace_count == 1
        assert len(outs) == len(batches)
        runs[overlap] = ([jax.device_get(o) for o in outs],
                         jax.device_get(state.metrics))
    for sa, sb in zip(runs[False][0], runs[True][0]):
        for field, a, b in zip(sa._fields, sa, sb):
            np.testing.assert_array_equal(a, b, err_msg=field)
    for field, a, b in zip(runs[False][1]._fields, runs[False][1],
                           runs[True][1]):
        np.testing.assert_array_equal(a, b, err_msg=field)


def test_run_overlap_int8_staging_is_lossy_but_complete(rng):
    """int8-quantized staging is opt-in and lossy: every batch still
    arrives (conservation holds), values only approximately (per-batch
    amax/127 scale), timestamps exactly (never quantized)."""
    batches = _overlap_batches()
    cfg = StreamConfig(micro_batch=32, window=16, stride=8, capacity=128,
                       lateness=8.0, overlap_ingest=True, ingest_int8=True)
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE)])
    p = pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]),
                               lambda _, b: (b + 100.0, b[:, :5]),
                               engine, core_capacity=2)
    ex = StreamExecutor(cfg, engine, p)
    state, outs = ex.run(ex.init_state(3), iter(batches))
    m = state.metrics
    assert int(m.steps) == len(batches)
    assert int(m.items_dequeued) == 32 * len(batches)
    assert int(m.items_late) == 0             # exact ts: watermark clean
    # windows aggregate the dequantized values: close, not (in general)
    # bit-equal to the exact-f32 run
    exact = StreamExecutor(
        StreamConfig(micro_batch=32, window=16, stride=8, capacity=128,
                     lateness=8.0), engine, p)
    estate, eouts = exact.run(exact.init_state(3), iter(batches))
    for eo, qo in zip(eouts, outs):
        np.testing.assert_allclose(np.asarray(qo.aggregates),
                                   np.asarray(eo.aggregates),
                                   rtol=0.05, atol=0.05)


def test_metrics_as_dict_snapshot(rng):
    ex, state = _make_executor()
    state, _, _ = _feed(ex, state, rng, 3)
    d = state.metrics.as_dict()
    assert set(d) == set(ex.init_state(3).metrics._fields)
    assert all(isinstance(v, int) for k, v in d.items()
               if k != "drift_counts")
    assert d["drift_counts"] == [0, 0, 0]    # [D] per-field -> list
    assert d["steps"] == 3 and d["items_offered"] == 96


def test_run_edge_commit_core_equals_run(rng):
    """The fleet's split execution path (run_edge -> core stage ->
    commit_core) must reproduce run() exactly — this is the local
    half of the fleet correctness oracle."""
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 0.5, rules.C_SEND_CORE,
                             priority=2),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=1),
    ])
    p = pipe.two_tier_pipeline(lambda _, b: (b * 2.0, b[:, :5]),
                               lambda _, b: (b + 100.0, b[:, :5]),
                               engine, core_capacity=None)
    batch = jnp.asarray(rng.standard_normal((8, 7)), jnp.float32)
    live = jnp.asarray(rng.random(8) < 0.8)
    whole = p.run(batch, live=live)
    partial, core_live = p.run_edge(batch, live=live)
    c_out, c_feats = p.run_core(partial.outputs)
    split = p.commit_core(partial, core_live, c_out, c_feats,
                          processed=jnp.ones(8, bool))
    np.testing.assert_array_equal(np.asarray(whole.escalated),
                                  np.asarray(core_live))
    for field in ("outputs", "consequence", "escalated", "stored",
                  "dropped"):
        np.testing.assert_allclose(np.asarray(getattr(whole, field)),
                                   np.asarray(getattr(split, field)),
                                   rtol=1e-6, err_msg=field)


def test_stream_config_validation():
    with pytest.raises(ValueError):
        StreamConfig(micro_batch=30, window=16, stride=8)   # 30 % 8 != 0
    with pytest.raises(ValueError):
        StreamConfig(micro_batch=32, window=8, stride=16)   # stride > window
    with pytest.raises(ValueError):
        StreamConfig(micro_batch=32, window=8, stride=8, capacity=16)
    with pytest.raises(ValueError):     # int8 rides the overlap stager
        StreamConfig(micro_batch=32, window=16, stride=8,
                     ingest_int8=True)
