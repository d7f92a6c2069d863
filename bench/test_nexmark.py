"""The NEXMark deployment (``bench/deployments/nexmark.py``): its cut
cell comes out correct through ``run_cell`` on the CPU, its generator
keeps Beam's mix and skew as a pure function of seed and tick, and each
planted fault, and the reference held in bfloat16, comes out not
correct."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from bench import deployment
from bench.run import cell_spec, run_cell
from repro.stream import keyed as K

CELL = "nexmark_q5.tps10m"
SEED = 2 ** 33 + 1601


def _small():
    bench, wl, cfg, traffic = cell_spec(CELL)
    dep = deployment.load(cfg)
    cfg, traffic = dep.small(cfg, traffic)
    return bench, wl, cfg, traffic, dep


def _run(monkeypatch, dep=None) -> dict:
    bench, wl, cfg, traffic, loaded = _small()
    dep = dep or loaded
    monkeypatch.setattr(deployment, "load", lambda cfg: dep)
    info: dict = {}
    res, _ = run_cell(bench, wl, cfg, traffic, SEED, 0.5, False,
                      log=lambda *a, **k: None, info=info)
    res["info"] = info
    return res


def test_small_cell_is_correct(monkeypatch):
    res = _run(monkeypatch)
    assert res["correct"], res["checks"]
    ref = res["info"]["reference"]
    assert ref == {k: res["info"]["program"][k] for k in ref}
    assert ref["windows_fired"] >= 3 and ref["key_overflow"] == 0
    assert ref["events_filtered"] > 0 and ref["bids_counted"] > 0
    assert set(res["metrics"]) == {"setup_s", "events_per_s"}


def test_reference_windows_pass_bfloat16s_exact_range():
    """The hot auction gathers more than 256 bids a window, so counts
    held in bfloat16 cannot be exact."""
    _, _, cfg, traffic, dep = _small()
    with dep.Reference(cfg, traffic, SEED) as ref:
        fired = [o for t in range(40) for o in ref.tick(t, False)
                 if o["fired"]]
    assert len(fired) >= 3
    assert max(o["max_count"] for o in fired) > 256
    assert all(o["n_keys"] > 100 for o in fired[1:])


def test_generator_is_a_pure_function_of_seed_and_tick():
    _, _, cfg, traffic, dep = _small()
    a, b = dep.Generator(cfg, traffic, SEED), dep.Generator(cfg, traffic,
                                                            SEED)
    for t in (0, 1, 7, 30, 26, 1):
        xa, ta = a.batch(t)
        xb, tb = b.batch(t)
        np.testing.assert_array_equal(xa, xb)
        np.testing.assert_array_equal(ta, tb)
        assert xa.dtype == ta.dtype == np.int32
    other = dep.Generator(cfg, traffic, SEED + 1).batch(3)[0]
    assert (other != a.batch(3)[0]).any()


def test_generator_shifts_equal_beams_arithmetic():
    """A tick made from its residue's rows equals the tick computed
    whole by Beam's arithmetic."""
    _, _, cfg, traffic, dep = _small()
    gen = dep.Generator(cfg, traffic, SEED)
    for t in (gen.steady, gen.steady + 1, gen.period + 3, 3 * gen.period,
              1000):
        rows, ts = gen.batch(t)
        np.testing.assert_array_equal(rows[0], gen.exact(t)[0])
        np.testing.assert_array_equal(ts[0], rows[0][:, dep.TIME])


def test_generator_mix_and_hot_share():
    _, _, cfg, traffic, dep = _small()
    cfg = dict(cfg, micro_batch=65_600)      # a whole number of epochs
    gen = dep.Generator(cfg, traffic, SEED)
    hot = []
    for t in (0, 5, 40):
        rows = gen.batch(t)[0][0]
        kinds = rows[:, dep.KIND].reshape(-1, 50)
        for k, n in ((dep.PERSON, 1), (dep.AUCTION, 3), (dep.BID, 46)):
            assert ((kinds == k).sum(axis=1) == n).all()
        e = t * cfg["micro_batch"] + np.arange(cfg["micro_batch"])
        bid = rows[:, dep.KIND] == dep.BID
        la = gen.last_auction(e[bid])
        hot.append(rows[bid, dep.ID] == la // 100 * 100 + 1000)
    assert abs(np.concatenate(hot).mean() - 0.5) < 0.02


def _drop_one_bid(orig):
    def select(plan, items, valid):
        m = orig(plan, items, valid)
        return m.at[jnp.argmax(m)].set(False)
    return select


def _person_as_bid(orig):
    def select(plan, items, valid):
        return orig(plan, items, valid) | (valid & (items[:, plan.kind_col]
                                                    == 0))
    return select


def _fire_a_tick_early(orig, tick_ms):
    def window_due(plan, max_time, fire_pane, lateness):
        return orig(plan, max_time + tick_ms, fire_pane, lateness)
    return window_due


def _evict_a_period_early(orig):
    def evict(plan, st, oldest):
        return orig(plan, st, oldest + 1)
    return evict


@pytest.mark.parametrize("fault", ["drop_one_bid", "person_as_bid",
                                   "fire_a_tick_early",
                                   "evict_a_period_early"])
def test_planted_fault_is_not_correct(monkeypatch, fault):
    _, _, cfg, traffic, _ = _small()
    tick_ms = cfg["micro_batch"] * 1000 // traffic["rate"]
    name, wrap = {
        "drop_one_bid": ("select", _drop_one_bid),
        "person_as_bid": ("select", _person_as_bid),
        "fire_a_tick_early": ("window_due", functools.partial(
            _fire_a_tick_early, tick_ms=tick_ms)),
        "evict_a_period_early": ("evict", _evict_a_period_early),
    }[fault]
    monkeypatch.setattr(K, name, wrap(getattr(K, name)))
    res = _run(monkeypatch)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0 or res["checks"]["counters_off"]["value"] > 0


def test_bfloat16_counts_are_not_correct(monkeypatch):
    """The control: the reference with its counts held in bfloat16, the
    nearest precision below the configuration's int32."""
    _, _, cfg, _, dep = _small()

    class Control(dep.Reference):
        def __init__(self, *a, **k):
            super().__init__(*a, **k, precision="control")

    monkeypatch.setattr(dep, "Reference", Control)
    res = _run(monkeypatch, dep)
    assert not res["correct"]
    assert res["checks"]["windows_off"]["value"] > 0
    assert res["checks"]["counters_off"]["value"] == 0


def test_config_states_its_cut_and_guarantees():
    bench, _, cfg, traffic = cell_spec(CELL)
    entry = {c["name"]: c for c in bench["configs"]}["nexmark_q5"]
    assert entry["reduced"] == cfg["reduced"]
    assert set(cfg["reduced"]) <= set(cfg["reduced_from"])
    assert cfg["table_capacity"] == 2 ** 24 and cfg["plugin"] == "nexmark"
    assert {"exactly_once", "lateness", "counts", "results",
            "capacity"} <= set(cfg["guarantees"])
    assert traffic["loop"] == "closed" and traffic["rate"] == 10_000_000
