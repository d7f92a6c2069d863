"""The seam between the harness and a deployment: a configuration
resolves to ``bench/deployments/<plugin>.py``, and a deployment that
arrives as a new file runs end to end through ``run_cell``, where the
comparison it brings decides ``correct``."""
import json
from pathlib import Path

import pytest

from bench import deployment
from bench.run import run_cell

ROOT = Path(__file__).resolve().parents[1]

#: what ``bench/run.py`` and ``bench/small.py`` take from a deployment
INTERFACE = ("Generator", "rows_per_tick", "build", "trim", "Reference",
             "newest_events", "Comparison", "work", "small")

#: a deployment of its own: sums of each tick's values by a small integer
#: key, a jitted segment sum against a numpy one; ``{fault}`` is added to
#: the program's sum of the last key
STUB = '''
import time

import numpy as np

KEYS = 8


def rows_per_tick(cfg):
    return cfg["rows"]


class Generator:
    """Rows of (key, value), both int32, from the seed and the tick."""

    def __init__(self, cfg, traffic, seed):
        self.rows, self.seed, self.pool = cfg["rows"], seed, None

    def batch(self, t):
        rng = np.random.default_rng([self.seed & (2 ** 64 - 1), t])
        items = np.stack([rng.integers(0, KEYS, self.rows),
                          rng.integers(-1000, 1000, self.rows)],
                         axis=1).astype(np.int32)
        ts = t * self.rows + np.arange(self.rows, dtype=np.int64)
        return items[None], ts[None]


class System:
    phase_names = ("step",)

    def __init__(self):
        import jax

        def sums(rows):
            s = jax.ops.segment_sum(rows[:, 1], rows[:, 0],
                                    num_segments=KEYS)
            return s.at[KEYS - 1].add({fault})

        self.jstep = jax.jit(sums)
        self.devices = [jax.devices()[0]]
        self.ticks = 0

    def step(self, items, ts, span):
        import jax

        with span("stub.step"):
            out = jax.device_get(self.jstep(items[0]))
        self.ticks += 1
        self.marks = (time.perf_counter(),)
        return [{{"sums": out}}]

    def compiled(self):
        return self.jstep._cache_size()

    def hlo_text(self, cfg):
        return ""

    def counters(self):
        return {{"ticks": self.ticks}}


def build(cfg, tracer, lane):
    return System()


def trim(out, full):
    return {{"sums": out["sums"].copy()}}


class Reference:
    def __init__(self, cfg, traffic, seed, gen):
        self.gen, self.ticks = Generator(cfg, traffic, seed), 0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass

    def tick(self, t, full):
        items, ts = self.gen.batch(t)
        keys, values = items[0, :, 0], items[0, :, 1]
        sums = np.zeros(KEYS, np.int64)
        np.add.at(sums, keys, values)
        newest = np.full(KEYS, -1, np.int64)
        np.maximum.at(newest, keys, ts[0])
        self.ticks += 1
        return [{{"sums": sums, "newest": newest}}]

    def counters(self):
        return {{"ticks": self.ticks}}


def newest_events(ref_tick, cfg):
    return ref_tick[0]["newest"]


class Comparison:
    def __init__(self):
        self.numbers = {{"sums_off": 0, "counters_off": 0}}
        self.bad_ticks = 0

    def tick(self, prog, ref, full):
        off = sum(int((p["sums"] != r["sums"]).sum())
                  for p, r in zip(prog, ref))
        self.numbers["sums_off"] += off
        self.bad_ticks += int(off > 0)

    def counters(self, prog, ref):
        off = [k for k in ref if prog.get(k) != ref[k]]
        self.numbers["counters_off"] += len(off)
        return off

    def checks(self):
        return {{k: {{"value": v, "limit": 0}}
                for k, v in self.numbers.items()}}

    @property
    def correct(self):
        return not any(self.numbers.values())


def work(cfg):
    return {{"ops": float(cfg["rows"]), "bytes": 8.0 * cfg["rows"]}}


def small(cfg, traffic):
    return cfg, traffic
'''

BENCH = {"end_to_end": [
    {"name": "setup_s", "unit": "s"},
    {"name": "events_per_s", "unit": "events/s",
     "workloads": ["stub.closed"]},
    {"name": "emit_p50_ms", "unit": "ms", "workloads": ["stub.open"]}],
    "per_layer": []}


def _stub_run(tmp_path, monkeypatch, loop: str, fault: int = 0) -> dict:
    (tmp_path / "stub.py").write_text(STUB.format(fault=fault))
    monkeypatch.setattr(deployment, "DIR", tmp_path)
    cfg = {"name": "stub", "plugin": "stub", "shards": 1, "rows": 256,
           "precision": {"matmul": "highest"}}
    traffic = {"loop": loop, "rate": 256 / 0.01}
    wl = {"name": f"stub.{loop}", "config": "stub", "traffic": loop,
          "chips": 1}
    info: dict = {}
    res, lines = run_cell(BENCH, wl, cfg, traffic, seed=2 ** 31 + 5,
                          seconds=0.3, trace=False,
                          log=lambda *a, **k: None, info=info)
    assert lines == [f"check {k}: {v['value']} (limit 0)"
                     for k, v in res["checks"].items()]
    res["info"] = info
    return res


def test_configuration_without_plugin_loads_sensor():
    mod = deployment.load({"name": "har_edge"})
    assert Path(mod.__file__) == deployment.DIR / "sensor.py"


@pytest.mark.parametrize("cell", [
    w["name"] for w in
    json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_has_a_whole_deployment(cell):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}[cell]
    cfg = json.loads((ROOT / "bench" / "configs" /
                      f"{wl['config']}.json").read_text())
    mod = deployment.load(cfg)
    assert [n for n in INTERFACE if not hasattr(mod, n)] == []


def test_missing_deployment_names_its_file(tmp_path, monkeypatch):
    monkeypatch.setattr(deployment, "DIR", tmp_path)
    with pytest.raises(FileNotFoundError, match=r"nexmark\.py"):
        deployment.load({"name": "auctions", "plugin": "nexmark"})


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_stub_deployment_runs_correct(tmp_path, monkeypatch, loop):
    res = _stub_run(tmp_path, monkeypatch, loop)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["info"]["program"] == {"ticks": res["attempted"] + 2}
    e2e = {"open": "emit_p50_ms", "closed": "events_per_s"}[loop]
    assert set(res["metrics"]) == {"setup_s", e2e}
    assert res["metrics"][e2e]["value"] > 0


def test_stub_deployment_off_by_one_is_not_correct(tmp_path, monkeypatch):
    res = _stub_run(tmp_path, monkeypatch, "closed", fault=1)
    assert not res["correct"]
    assert res["checks"]["sums_off"]["value"] == res["attempted"] + 2
    assert res["checks"]["counters_off"]["value"] == 0

