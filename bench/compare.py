"""The comparison that decides ``correct``: the program's outputs of the
timed path against the plain reference, number by number, each against
a limit of its own (see PERF.md for the readings each was set from).

* ``windows_off``: windows, over every tick and shard, whose valid
  count, rule code or escalated mask differs.  Exact.
* ``counters_off``: end-of-run counters that differ: admission (offered,
  accepted, rejected, deduped, per-channel drift), watermark (dequeued,
  late), windows (emitted, fired, escalated, stored, dropped), core
  overflow, and on the fleet the fog shedding, the exchange counters,
  the watermarks and the budgets in force.  Exact.
* ``mean_ulp``: the widest gap, in float32 units in the last place, of
  a rule feature (every tick) or a channel mean (sampled ticks).  Sums,
  extrema and counts are exact; the TPU's float32 division is not
  correctly rounded, so a mean may sit one unit off.
* ``core_gap``: on sampled ticks, the widest gap of a pipeline output
  over max(1, |reference|): the core stage's results where it ran, the
  edge record elsewhere.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"windows_off": 0, "counters_off": 0, "mean_ulp": 8,
          "core_gap": 1e-4}


def ulp_gap(a: np.ndarray, b: np.ndarray) -> int:
    """Widest distance in float32 ulps (ordered integer view)."""
    def ordered(x):
        i = np.ascontiguousarray(x, np.float32).view(np.int32).astype(
            np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)
    if a.size == 0:
        return 0
    return int(np.abs(ordered(a) - ordered(b)).max())


class Comparison:
    def __init__(self):
        self.numbers = {k: 0 for k in LIMITS}
        self.numbers["core_gap"] = 0.0
        self.ticks = 0
        self.bad_ticks = 0

    def tick(self, prog: list[dict], ref: list[dict], full: bool) -> None:
        t = {"windows_off": 0, "mean_ulp": 0, "core_gap": 0.0}
        for p, r in zip(prog, ref):
            off = (p["window_count"] != r["count"]) \
                | (p["consequence"] != r["code"]) \
                | (p["escalated"].astype(bool) != r["escalated"])
            t["windows_off"] += int(off.sum())
            t["mean_ulp"] = max(t["mean_ulp"],
                                ulp_gap(p["features"], r["features"]))
            if full:
                t["mean_ulp"] = max(t["mean_ulp"],
                                    ulp_gap(p["aggregates"], r["aggregates"]))
                gap = np.abs(p["outputs"].astype(np.float64) - r["outputs"]) \
                    / np.maximum(1.0, np.abs(r["outputs"]))
                t["core_gap"] = max(t["core_gap"], float(gap.max()))
        n = self.numbers
        n["windows_off"] += t["windows_off"]
        n["mean_ulp"] = max(n["mean_ulp"], t["mean_ulp"])
        n["core_gap"] = max(n["core_gap"], t["core_gap"])
        self.ticks += 1
        self.bad_ticks += int(any(v > LIMITS[k] for k, v in t.items()))

    def counters(self, prog: dict, ref: dict) -> list[str]:
        """Count the counters that differ; returns their names."""
        off = [k for k in ref if _plain(prog.get(k)) != _plain(ref[k])]
        self.numbers["counters_off"] += len(off)
        return off

    def checks(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.numbers.items()}

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.numbers.items())


def _plain(v):
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v
