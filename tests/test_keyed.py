"""The keyed windows' table (``repro.stream.keyed``) against a Python
dict on seeded keys: upserts under forced row collisions, counts across
ticks, what a fired window reports, eviction freeing slots, overflow
counted rather than dropped, and integer ids past 2**24 carried exactly
through the ring and admission."""
import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.stream import StreamConfig, StreamExecutor
from repro.stream import keyed as K
from repro.stream.ingest import AdmissionPlan

KIND, KEY, TIME = 0, 1, 2
BID = 2


def _plan(capacity=256, pane_ms=10, panes=2, results=4):
    return K.KeyedPlan(key_col=KEY, time_col=TIME, kind_col=KIND, kind=BID,
                       pane_ms=pane_ms, panes_per_window=panes,
                       capacity=capacity, results=results)


def _fmix(x):
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x85EBCA6B)
    x = x ^ (x >> np.uint32(13))
    x = x * np.uint32(0xC2B2AE35)
    return x ^ (x >> np.uint32(16))


def _checksum(totals: dict) -> int:
    k = np.array(list(totals), np.int64).astype(np.int32).view(np.uint32)
    c = np.array(list(totals.values()), np.int32).view(np.uint32)
    return int(_fmix(k * np.uint32(0x9E3779B1) + c).astype(np.uint64).sum()
               % 2 ** 32)


class Model:
    """The keyed stage's semantics on Python dicts."""

    def __init__(self, plan, lateness=0):
        self.p, self.lateness = plan, lateness
        self.counts: dict = {}
        self.fire = None
        self.max_time = None
        self.m = dict.fromkeys(K.KeyedMetrics._fields, 0)

    def tick(self, items, valid):
        p = self.p
        bids = valid & (items[:, KIND] == BID)
        pairs = Counter(zip(items[bids, KEY].tolist(),
                            (items[bids, TIME] // p.pane_ms).tolist()))
        for kq, n in pairs.items():
            self.counts[kq] = self.counts.get(kq, 0) + n
        self.m["pairs_upserted"] += len(pairs)
        self.m["bids_counted"] += int(bids.sum())
        self.m["events_filtered"] += int((valid & ~bids).sum())
        if valid.any():
            t = items[valid, TIME]
            self.max_time = t.max() if self.max_time is None \
                else max(self.max_time, t.max())
            if self.fire is None:
                self.fire = int((t // p.pane_ms).min())
        if self.fire is None or \
                self.max_time - self.lateness < (self.fire + 1) * p.pane_ms:
            return None
        w = self.fire
        oldest = w - p.panes_per_window + 1
        tot = Counter()
        for (k, q), n in self.counts.items():
            if oldest <= q <= w:
                tot[k] += n
        top = max(tot.values(), default=0)
        ties = sorted(k for k, n in tot.items() if n == top and top > 0)
        self.counts = {kq: n for kq, n in self.counts.items()
                       if kq[1] > oldest}
        self.fire += 1
        self.m["panes_evicted"] += 1
        self.m["windows_fired"] += 1
        self.m["results_truncated"] += max(0, len(ties) - p.results)
        return {"window_end": (w + 1) * p.pane_ms, "max_count": top,
                "ties": ties, "n_keys": len(tot), "checksum": _checksum(tot)}


def _table(st: K.KeyedState) -> dict:
    keys, panes, counts = (np.asarray(x).ravel()
                           for x in (st.keys, st.panes, st.counts))
    live = panes != K.EMPTY
    got = list(zip(keys[live].tolist(), panes[live].tolist()))
    assert len(set(got)) == len(got)        # a pair holds one slot
    return dict(zip(got, counts[live].tolist()))


def _run(plan, ticks, lateness=0):
    """The jitted keyed stage and the model over ``ticks`` of (items,
    valid); checks each tick's output, returns (state, metrics, model,
    outputs)."""
    step = jax.jit(functools.partial(K.keyed_step, plan,
                                     lateness=lateness))
    st, model = K.keyed_init(plan), Model(plan, lateness)
    total = K.keyed_metrics_init()
    outs = []
    for items, valid in ticks:
        st, out, dk = step(st, jnp.asarray(items), jnp.asarray(valid))
        total = K.KeyedMetrics(*(a + b for a, b in zip(total, dk)))
        want = model.tick(items, valid)
        out = jax.device_get(out)
        assert bool(out.fired) == (want is not None)
        if want is not None:
            assert int(out.window_end) == want["window_end"]
            assert int(out.max_count) == want["max_count"]
            assert int(out.n_ties) == len(want["ties"])
            assert int(out.n_keys) == want["n_keys"]
            assert int(out.checksum) == want["checksum"]
            n = min(len(want["ties"]), plan.results)
            got = out.keys[:n].tolist()
            assert len(set(got)) == n and set(got) <= set(want["ties"])
        outs.append((out, want))
    return st, jax.device_get(total), model, outs


def _tick(rng, t0, n, keys, span, p_bid=0.8, key_base=0):
    kind = np.where(rng.random(n) < p_bid, BID, 0)
    key = key_base + rng.choice(keys, n)
    time = t0 + np.sort(rng.integers(0, span, n))
    items = np.stack([kind, key, time], axis=1).astype(np.int32)
    return items, rng.random(n) < 0.95


@pytest.mark.parametrize("capacity", [128, 256])
def test_upsert_matches_dict_under_forced_collisions(rng, capacity):
    # one or two rows: every key shares a row with many others
    plan = _plan(capacity=capacity, pane_ms=1000)
    keys = rng.choice(1 << 30, 40, replace=False)
    ticks = [_tick(rng, 10 * t, 64, keys, 10) for t in range(6)]
    st, m, model, _ = _run(plan, ticks)
    assert int(m.key_overflow) == 0
    assert _table(st) == model.counts
    for f in ("pairs_upserted", "bids_counted", "events_filtered"):
        assert int(getattr(m, f)) == model.m[f], f


def test_upsert_counts_accumulate_across_ticks(rng):
    plan = _plan(capacity=1024, pane_ms=1 << 20)
    keys = np.arange(-5, 5) * 7919          # negative keys too
    ticks = [_tick(rng, 0, 128, keys, 1) for _ in range(8)]
    st, m, model, _ = _run(plan, ticks)
    table = _table(st)
    assert table == model.counts
    assert sum(table.values()) == int(m.bids_counted) > 8 * 64


def test_fire_reports_max_ties_keys_and_checksum(rng):
    plan = _plan(capacity=512, pane_ms=10, panes=2, results=3)
    keys = rng.choice(1 << 28, 12, replace=False)
    # about three ticks a pane, few bids a key: ties come and go
    ticks = [_tick(rng, 3 * t, 10, keys, 3) for t in range(40)]
    st, m, model, outs = _run(plan, ticks)
    fired = [w for _, w in outs if w is not None]
    assert len(fired) >= 10 and int(m.windows_fired) == len(fired)
    assert any(len(w["ties"]) > 1 for w in fired)
    assert _table(st) == model.counts
    for f in K.KeyedMetrics._fields:
        assert int(getattr(m, f)) == model.m[f], f


def test_results_past_capacity_are_truncated_and_counted(rng):
    plan = _plan(capacity=256, pane_ms=10, panes=1, results=2)
    # five keys with one bid each: five tied at the maximum
    items = np.array([[BID, k, 1] for k in (11, 12, 13, 14, 15)]
                     + [[0, 99, 12]], np.int32)
    _, m, _, outs = _run(plan, [(items, np.ones(6, bool))])
    out, want = outs[0]
    assert int(out.n_ties) == 5 and want["ties"] == [11, 12, 13, 14, 15]
    assert int(m.results_truncated) == 3


def test_eviction_frees_slots(rng):
    # one row of 128 slots: 100 keys in pane 0, then 100 others in pane
    # 2, which fit only once the window over panes 0-1 has evicted pane
    # 0 (a tick fires at most one window)
    plan = _plan(capacity=128, pane_ms=10, panes=2)
    a = np.array([[BID, k, 5] for k in range(100)], np.int32)
    p1 = np.array([[0, 9, 20]] * 100, np.int32)     # windows 0, then 1
    p2 = np.array([[0, 9, 21]] * 100, np.int32)     # (one a tick) fire
    b = np.array([[BID, 1000 + k, 25] for k in range(100)], np.int32)
    c = np.array([[BID, 5000, 31]] * 100, np.int32)
    ones = np.ones(100, bool)
    st, m, model, outs = _run(plan, [(x, ones) for x in (a, p1, p2, b, c)])
    assert int(m.key_overflow) == 0
    assert int(m.panes_evicted) == 3
    assert _table(st) == model.counts
    assert sorted(k for k, _ in _table(st)) == sorted(
        list(range(1000, 1100)) + [5000])


def test_overflow_is_counted_not_dropped(rng):
    plan = _plan(capacity=128, pane_ms=1000)
    items = np.array([[BID, 7 * k + 1, 0] for k in range(200)]
                     + [[BID, 1, 0]] * 56, np.int32)
    step = jax.jit(functools.partial(K.keyed_step, plan, lateness=0))
    st, _, dk = step(K.keyed_init(plan), jnp.asarray(items),
                     jnp.ones(256, bool))
    table = _table(st)
    assert int(dk.pairs_upserted) == 200
    assert int(dk.key_overflow) == 200 - 128 == 200 - len(table)
    # what was placed is exact: key 1 holds its 57 bids if it found a slot
    assert all(n == (57 if k == 1 else 1) for (k, _), n in table.items())
    assert int(dk.bids_counted) == 256


def test_ids_past_2_24_exact_through_ring_and_admission():
    """Keys 2**24 + 1 and 2**24 are one float32 value: a float32 ring
    and a float32 admission hash would make the second row a
    re-delivery of the first.  The int32 ring keeps both, and the fired
    window counts them apart."""
    plan = _plan(capacity=256, pane_ms=10, panes=1, results=4)
    cfg = StreamConfig(micro_batch=8, window=1, stride=1, capacity=8,
                       keyed=plan,
                       admission=AdmissionPlan(dedupe_window=16))
    ex = StreamExecutor(cfg)
    state = ex.init_state(3)
    big = [2 ** 24 + 1, 2 ** 24, 2 ** 30 + 3, 2 ** 24 + 1]
    items = np.array([[BID, k, 1] for k in big] + [[0, 5, 2]] * 4, np.int32)
    items[4:, KEY] = [1, 2, 3, 4]
    state, _ = ex.step(state, jnp.asarray(items),
                       jnp.asarray(items[:, TIME]))
    # a tick past the pane fires it
    late = np.array([[0, 9, 10 + i] for i in range(8)], np.int32)
    state, out = ex.step(state, jnp.asarray(late),
                         jnp.asarray(late[:, TIME]))
    m = state.metrics.as_dict()
    assert m["items_deduped"] == 1           # the one true repeat
    assert m["items_accepted"] == 15
    ring = np.asarray(state.rb.buf)
    assert ring.dtype == np.int32
    out = jax.device_get(out)
    assert bool(out.fired) and int(out.max_count) == 1
    assert int(out.n_keys) == 3 and int(out.n_ties) == 3
    assert sorted(out.keys[:3].tolist()) == sorted(set(big))
