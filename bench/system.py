"""The system under test, built from a configuration file.

Everything here drives the program through its normal entry points:
``StreamExecutor.step`` for one chip, ``FleetExecutor.step`` followed by
``FleetController.tick`` for the fleet.  A tick copies the generator's
host rows to the device (the served path starts on the host), steps,
and fetches the tick's ``StepOutput`` to the host, as a sink would.
"""
from __future__ import annotations

import time

import numpy as np

from bench.reference import F32


def core_params(cfg: dict) -> np.ndarray:
    """The core stand-in's parameters: part of the deployment, fixed by
    the configuration's ``param_seed`` (the program bakes stage
    parameters into the compiled step as constants)."""
    c, w = cfg["core"], 5 + cfg["channels"]
    rng = np.random.default_rng(c["param_seed"])
    return (rng.standard_normal((w, w)) * c["param_scale"]).astype(F32)


def _program(cfg: dict, lane: dict | None):
    import jax.numpy as jnp

    from repro.core import pipeline as pipe
    from repro.core import rules as R
    from repro.stream import StreamConfig
    from repro.stream.ingest import AdmissionPlan, DataContract

    codes = {"send_core": R.C_SEND_CORE, "store_edge": R.C_STORE_EDGE,
             "drop": R.C_DROP, "notify": R.C_NOTIFY}
    engine = R.RuleEngine([
        R.threshold_rule(r["name"], r["feature"], r["op"], r["value"],
                         codes[r["then"]], priority=r["priority"])
        for r in cfg["rules"]])
    p = jnp.asarray(core_params(cfg))
    layers = cfg["core"]["layers"]

    def core_fn(params, batch):
        h = batch
        for _ in range(layers):
            h = jnp.tanh(h @ params)
        return h, batch[:, :5]

    def make_pipeline(capacity):
        return pipe.two_tier_pipeline(lambda _, b: (b, b[:, :5]), core_fn,
                                      engine, core_params=p,
                                      core_capacity=capacity)

    d, con = cfg["channels"], cfg["contract"]
    lane = lane or cfg["lane"]
    scfg = StreamConfig(
        micro_batch=cfg["micro_batch"], window=cfg["window"],
        stride=cfg["stride"], capacity=cfg["ring_capacity"],
        lateness=float(cfg["lateness"]), min_count=cfg["min_count"],
        backend=lane["backend"], fused=lane["fused"],
        interpret=lane.get("interpret", False),
        admission=AdmissionPlan(
            dedupe_window=cfg["dedupe_window"],
            contract=DataContract(lo=(con["lo"],) * d, hi=(con["hi"],) * d,
                                  require_finite=con["require_finite"])))
    return engine, make_pipeline, scfg


class EdgeSystem:
    """One ``StreamExecutor`` on the first device.  ``marks`` holds the
    clock at the end of each of the last tick's ``phase_names``."""

    phase_names = ("h2d", "dispatch", "d2h")

    def __init__(self, cfg: dict, tracer=None, lane: dict | None = None):
        import jax

        from repro.stream import StreamExecutor

        engine, make_pipeline, scfg = _program(cfg, lane)
        cap = int(cfg["core"]["capacity_share"] * scfg.windows_per_step)
        self.ex = StreamExecutor(scfg, engine, make_pipeline(cap))
        if tracer is not None:
            self.ex.set_tracer(tracer)
        self.devices = [jax.devices()[0]]
        self.state = self.ex.init_state(cfg["channels"])

    def step(self, items: np.ndarray, ts: np.ndarray, span) -> list[dict]:
        import jax

        with span("bench.h2d"):
            x = jax.device_put(items[0], self.devices[0])
            t = jax.device_put(ts[0], self.devices[0])
        a = time.perf_counter()
        self.state, out = self.ex.step(self.state, x, t)
        b = time.perf_counter()
        with span("bench.d2h"):
            host = jax.device_get(out)
        self.marks = (a, b, time.perf_counter())
        return [host._asdict()]

    def compiled(self) -> int:
        return self.ex._jstep._cache_size()

    def hlo_text(self, cfg: dict) -> str:
        """The compiled step's module text (its instructions' op names
        carry the named scopes); the compile comes from the cache."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(self.devices[0])
        b, d = cfg["micro_batch"], cfg["channels"]
        x = jax.ShapeDtypeStruct((b, d), jnp.float32, sharding=one)
        t = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one)
        return self.ex.lower(self.state, x, t).compile().as_text()

    def counters(self) -> dict:
        m = self.state.metrics.as_dict()
        return {k: [v] for k, v in m.items()}


class FleetSystem:
    """``FleetExecutor`` on an R x E mesh and its ``FleetController``;
    ``marks`` as on ``EdgeSystem``."""

    phase_names = ("h2d", "dispatch", "d2h", "control")

    def __init__(self, cfg: dict, tracer=None, lane: dict | None = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.runtime.elastic import ElasticBudget
        from repro.stream.fleet import FleetConfig, FleetExecutor
        from repro.stream.fleet.control import FleetController

        engine, make_pipeline, scfg = _program(cfg, lane)
        fl = cfg["fleet"]
        self.ex = FleetExecutor(
            FleetConfig(stream=scfg, num_shards=cfg["shards"],
                        num_core=fl["num_core"], num_regions=fl["regions"],
                        core_budget=fl["core_budget"],
                        core_budget_max=fl["core_budget_max"],
                        fog_budget=fl["fog_budget"],
                        fog_budget_max=fl["fog_budget_max"]),
            engine, make_pipeline(None))

        def policy(spec):
            return ElasticBudget(min_budget=spec["min"],
                                 max_budget=spec["max"],
                                 grow_at=spec["grow_at"],
                                 shrink_at=spec["shrink_at"],
                                 grow_factor=spec["factor"],
                                 patience=spec["patience"])

        kw = {} if tracer is None else {"tracer": tracer}
        self.ctl = FleetController(
            self.ex, budget_policy=policy(fl["core_policy"]),
            region_policies=[policy(fl["fog_policy"])
                             for _ in range(fl["regions"])], **kw)
        if tracer is not None:
            self.ex.set_tracer(tracer)
        self.devices = list(self.ex.mesh.devices.reshape(-1))
        self.rows = NamedSharding(self.ex.mesh, P(("region", "edge")))
        self.state = self.ex.init_state(cfg["channels"])

    def step(self, items: np.ndarray, ts: np.ndarray, span) -> list[dict]:
        import jax

        with span("bench.h2d"):
            x = jax.device_put(items, self.rows)
            t = jax.device_put(ts, self.rows)
        a = time.perf_counter()
        self.state, out = self.ex.step(self.state, x, t)
        b = time.perf_counter()
        with span("bench.d2h"):
            host = jax.device_get(out)
        c = time.perf_counter()
        self.ctl.tick(self.state)
        self.marks = (a, b, c, time.perf_counter())
        return [{k: v[s] for k, v in host._asdict().items()}
                for s in range(len(self.devices))]

    def compiled(self) -> int:
        return self.ex._jstep._cache_size()

    def hlo_text(self, cfg: dict) -> str:
        import jax
        import jax.numpy as jnp

        s, b, d = cfg["shards"], cfg["micro_batch"], cfg["channels"]
        x = jax.ShapeDtypeStruct((s, b, d), jnp.float32, sharding=self.rows)
        t = jax.ShapeDtypeStruct((s, b), jnp.float32, sharding=self.rows)
        return self.ex.lower(self.state, x, t).compile().as_text()

    def counters(self) -> dict:
        m = self.state.metrics.as_dict()
        out = dict(m["shard"])
        for k in ("fog_shed", "escalations_sent"):
            out[k] = m[k]
        out["core_received"] = sum(m["core_received"])
        out["core_processed"] = sum(m["core_processed"])
        out["fleet_core_overflow"] = m["fleet_core_overflow"]
        out["watermark"] = m["watermark"]
        out["region_watermark"] = m["region_watermark"][
            ::self.ex.cfg.edges_per_region]
        out["core_budget"] = self.ex.core_budget
        out["fog_budget"] = [int(x) for x in self.ex.region_budget]
        return out


def build(cfg: dict, tracer=None, lane: dict | None = None):
    return (FleetSystem if cfg.get("fleet") else EdgeSystem)(
        cfg, tracer, lane)
