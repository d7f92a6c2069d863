"""The sensor deployment: edge gateways that admit rows of float
channels (dedupe by event id, a contract, lateness), frame sliding
windows, apply IF-THEN rules and escalate flagged windows to a bounded
core stage; one ``StreamExecutor``, or a ``FleetExecutor`` with its
``FleetController`` where the configuration has a ``fleet``.

It binds ``bench/system.py`` (the program), ``bench/generator.py``,
``bench/reference.py``, ``bench/compare.py`` and ``bench/work.py`` to
the interface of ``bench/deployment.py``.
"""
from __future__ import annotations

import numpy as np

from bench import compare, reference, system
from bench import work as stage_work
from bench.generator import Generator  # noqa: F401

build = system.build
Comparison = compare.Comparison


def rows_per_tick(cfg: dict) -> int:
    return cfg["micro_batch"]


def trim(out: dict, full: bool) -> dict:
    keep = ("window_count", "consequence", "escalated", "features")
    if full:
        keep += ("aggregates", "outputs")
    return {k: out[k].copy() for k in keep}


class Reference(reference.Reference):
    """The plain reference with the core stand-in's parameters of the
    configuration; one chip shares the run's host pool."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, gen):
        super().__init__(cfg, traffic, seed, system.core_params(cfg),
                         pool=gen.pool if cfg["shards"] == 1 else None)

    @property
    def log(self) -> str:
        return (f"32-bit id collisions seen (re-delivery verdicts on rows "
                f"that differ): {self.collisions}")


def newest_events(ref_tick: list[dict], cfg: dict) -> np.ndarray:
    """The newest event of each window of the first shard; -1 where the
    window holds fewer than ``min_count`` rows and is not emitted."""
    r = ref_tick[0]
    return np.where(r["count"] >= cfg["min_count"], r["last_event"], -1)


def work(cfg: dict) -> dict[str, float]:
    return stage_work.window_rules(cfg)


def small(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """2,048-row ticks, windows of 16 every 8 rows, a dedupe window of
    64, the fleet's budgets scaled with them, re-deliveries 0.2 to 4 ms
    back (some inside the dedupe window, some past it).  Every mechanism
    of the full cell stays on: re-sent rows, contract rejects, NaN rows,
    late and out-of-order rows, binding fog and core budgets."""
    cfg = dict(cfg, micro_batch=2048, ring_capacity=2048, window=16,
               stride=8, dedupe_window=64)
    traffic = dict(traffic, resent_batches_per_tick=2, resent_batch_rows=8,
                   resent_delay_s=[2e-4, 4e-3],
                   out_of_contract_per_tick=8, nan_per_tick=2,
                   out_of_order_per_tick=8, late_per_tick=4, pool_batches=2,
                   rate=2048 / 0.05)
    if cfg.get("fleet"):
        fl = cfg["fleet"]
        cfg["fleet"] = dict(
            fl, core_budget=16, core_budget_max=32, fog_budget=16,
            fog_budget_max=32,
            core_policy=dict(fl["core_policy"], min=4, max=32),
            fog_policy=dict(fl["fog_policy"], min=4, max=32))
    return cfg, traffic
