"""Seeded traffic generator, apart from the program under test.

A traffic mix (``bench/traffic/<mix>.json``) is data; this one generator
reads every mix.  Set-up builds a pool of micro-batches in host memory
from the seed.  Tick ``t`` of shard ``s`` is pool batch ``t % pool`` with
its event times rewritten so they keep increasing, the shard's hot
regime applied, rows outside the contract, NaN rows and out-of-order
rows injected, and some rows replaced by re-deliveries of rows sent
earlier.  A tick is a pure function of the seed, the shard and ``t``,
so the reference regenerates any tick without the program.

Event time is in sample periods of the configuration's sensors
(``sample_hz``): event ``i`` of a stream is created at ``i / rate``
seconds and carries ``ts = i * sample_hz / rate``.

Re-deliveries follow a producer's retry of a batch whose
acknowledgement was lost: each tick carries ``resent_batches_per_tick``
runs of ``resent_batch_rows`` rows, each run repeating the rows that the
stream sent ``delay`` seconds earlier, ``delay`` uniform in the mix's
``resent_delay_s``, as they were sent (a re-send of a re-send repeats
the original).  A row whose original would precede the stream's start
stays a fresh row.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.deployment import seed_sequence


@dataclasses.dataclass
class Plan:
    """The draws of one shard-tick: where each kind of row goes, each
    kind's positions in rising order beside what is done there."""
    resent: np.ndarray          # positions of re-sent rows
    back: np.ndarray            # ... and how many stream rows back each
    bad: np.ndarray             # positions outside the contract
    bad_col: np.ndarray         # ... and the channel set out of range
    nan: np.ndarray             # positions of NaN rows (bad rows too)
    nan_col: np.ndarray
    ooo: np.ndarray             # out-of-order positions
    ooo_by: np.ndarray          # ... and their delays, sample periods
    late: np.ndarray            # positions past the lateness
    late_by: np.ndarray


def _sorted(pos: np.ndarray, *attrs: np.ndarray) -> tuple[np.ndarray, ...]:
    o = np.argsort(pos)
    return (pos[o], *(a[o] for a in attrs))


def _at(keys: np.ndarray, pos: np.ndarray | None):
    """(rows of ``pos`` found in the rising ``keys``, index in ``keys``
    of each); ``pos`` None stands for every row of the tick."""
    if pos is None:
        return keys, np.arange(keys.size)
    if keys.size == 0:
        return np.zeros(0, np.intp), np.zeros(0, np.intp)
    j = np.minimum(np.searchsorted(keys, pos), keys.size - 1)
    hit = keys[j] == pos
    return np.flatnonzero(hit), j[hit]


class Generator:
    """Ticks of ``[S, B, D]`` rows and ``[S, B]`` event times, for the
    shards ``shards`` (default: every shard of the configuration).  Each
    shard's pool and each shard-tick's draws come from seeds of their
    own, so one shard's rows can be made without the others'."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 shards: list[int] | None = None,
                 pool: np.ndarray | None = None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.shard_ids = list(range(cfg["shards"])) if shards is None \
            else list(shards)
        self.rows = cfg["micro_batch"]
        self.d = cfg["channels"]
        self.ts_step = cfg["sample_hz"] / traffic["rate"]
        lo, hi = traffic["resent_delay_s"]
        self.back_rows = (max(1, round(lo * traffic["rate"])),
                          max(1, round(hi * traffic["rate"])))
        if pool is None:
            pool = np.stack([
                np.random.default_rng(seed_sequence(seed, 0, s))
                .standard_normal((traffic["pool_batches"], self.rows,
                                  self.d), dtype=np.float32)
                for s in self.shard_ids])
        self.pool = pool
        self._plans: dict[tuple[int, int], Plan] = {}
        self._every = np.arange(self.rows, dtype=np.float64)
        # the plans a re-send can reach back to, and a tick's own
        self._keep = self.back_rows[1] // self.rows + 2

    def hot(self, t: int, s: int) -> bool:
        """Shards run hot in turn: shard s is hot on ticks where
        ``(t + s) // period`` is odd."""
        return bool(((t + s) // self.traffic["hot_period_ticks"]) % 2)

    def plan(self, t: int, shard: int) -> Plan:
        key = (t, shard)
        if key in self._plans:
            return self._plans[key]
        tr, b = self.traffic, self.rows
        n_bad = tr["out_of_contract_per_tick"]
        n_ooo, n_late = tr["out_of_order_per_tick"], tr["late_per_tick"]
        rng = np.random.default_rng(seed_sequence(self.seed, 1, t, shard))
        runs, run = tr["resent_batches_per_tick"], tr["resent_batch_rows"]
        resent = (rng.choice(b // run, runs, replace=False)[:, None] * run
                  + np.arange(run)).ravel()
        back = np.repeat(rng.integers(*self.back_rows, runs, endpoint=True),
                         run)
        # the other kinds go to rows that are not re-sent
        pos = rng.choice(b, n_bad + n_ooo + n_late + resent.size,
                         replace=False)
        pos = pos[~np.isin(pos, resent)][:n_bad + n_ooo + n_late]
        i = np.cumsum([0, n_bad, n_ooo])
        bad_col = rng.integers(0, self.d, n_bad)
        nan_col = rng.integers(0, self.d, tr["nan_per_tick"])
        ooo_by = rng.uniform(*tr["out_of_order_delay"], n_ooo)
        late_by = rng.uniform(*tr["late_delay"], n_late)
        bad = pos[i[0]:i[1]]
        p = Plan(*_sorted(resent, back), *_sorted(bad, bad_col),
                 *_sorted(bad[:nan_col.size], nan_col),
                 *_sorted(pos[i[1]:i[2]], ooo_by.astype(np.float32)),
                 *_sorted(pos[i[2]:], late_by.astype(np.float32)))
        self._plans[key] = p
        for k in [k for k in self._plans if k[0] < t - self._keep]:
            del self._plans[k]
        return p

    def _fresh(self, t: int, s: int, pos: np.ndarray | None):
        """Rows ``pos`` (None: all) of shard-tick (t, s) before
        re-deliveries."""
        tr, shard = self.traffic, self.shard_ids[s]
        base = self.pool[s, t % tr["pool_batches"]]
        items = base.copy() if pos is None else base[pos]
        if self.hot(t, shard):
            items[:, tr["hot_channel"]] += np.float32(tr["hot_shift"])
        idx = self._every if pos is None else pos.astype(np.float64)
        ts = ((t * self.rows + idx) * self.ts_step).astype(np.float32)
        p = self.plan(t, shard)
        r, j = _at(p.bad, pos)
        items[r, p.bad_col[j]] = np.float32(tr["out_of_contract_value"])
        r, j = _at(p.nan, pos)
        items[r, p.nan_col[j]] = np.nan
        r, j = _at(p.ooo, pos)
        ts[r] -= p.ooo_by[j]
        r, j = _at(p.late, pos)
        ts[r] -= p.late_by[j]
        return items, ts

    def _sent(self, t: int, s: int, pos: np.ndarray | None):
        """Rows ``pos`` (None: all) of shard-tick (t, s) as the stream
        sent them."""
        items, ts = self._fresh(t, s, pos)
        p = self.plan(t, self.shard_ids[s])
        r, j = _at(p.resent, pos)
        src = t * self.rows + (r if pos is None else pos[r]) - p.back[j]
        ok = src >= 0
        r, src = r[ok], src[ok]
        for u in np.unique(src // self.rows):
            at = src // self.rows == u
            items[r[at]], ts[r[at]] = self._sent(int(u), s,
                                                 src[at] % self.rows)
        return items, ts

    def batch(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        items, ts = zip(*(self._sent(t, s, None)
                          for s in range(len(self.shard_ids))))
        if len(items) == 1:
            return items[0][None], ts[0][None]
        return np.stack(items), np.stack(ts)
