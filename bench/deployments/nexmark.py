"""The NEXMark deployment: query 5, "hot items", on one chip.

An auction site's event stream (Tucker, Tufte, Papadimos and Maier,
2002), as Apache Beam's Nexmark suite generates it
(``sdks/java/testing/nexmark``: ``Query5``, ``NexmarkConfiguration``
defaults): persons, auctions and bids at 1 : 3 : 46, bids skewed to a
"hot" auction that moves every 100 auctions.  Query 5 counts bids per
auction in sliding windows of 10 s every 5 s and reports, when a window
closes, the auctions with the most bids.

The program is one ``StreamExecutor`` with a keyed plan
(``repro.stream.keyed``): admission (dedupe by 32-bit event id), the
ring of int32 rows, the watermark, then the keyed windows.  This module
brings its generator, the plain reference (numpy and pandas, nothing of
``repro``), the comparison and the work counts, as
``bench/deployment.py`` defines them.

Rows are ``WORDS`` int32 words (100 bytes, Beam's ``avgBidByteSize``):
the event's numeric fields at the columns below, then seeded words that
stand in for its strings.  Event time is milliseconds since the stream's
start: event ``e`` is created at ``e / rate`` seconds.
"""
from __future__ import annotations

import numpy as np

from bench.deployment import seed_sequence

#: columns of a row: the kind, the event's id (a bid's auction), the
#: other party (an auction's seller, a bid's bidder), a price (an
#: auction's initial bid, a bid's price), an auction's reserve and
#: category, the event time (ms) and an auction's expiry (ms)
KIND, ID, PARTY, PRICE, RESERVE, CATEGORY, TIME, EXPIRES = range(8)
WORDS = 25
PERSON, AUCTION, BID = 0, 1, 2
#: Beam's ``GeneratorConfig`` constants
FIRST_PERSON_ID, FIRST_AUCTION_ID, FIRST_CATEGORY_ID = 1000, 1000, 10
HOT_BATCH = 100          # HOT_AUCTION_RATIO, HOT_BIDDER_RATIO, HOT_SELLER_RATIO
NUM_CATEGORIES = 5
_U32 = np.uint32
_MASK = np.uint64(0xFFFFFFFF)


def rows_per_tick(cfg: dict) -> int:
    return cfg["micro_batch"]


# -- the generator ------------------------------------------------------


def _draw(r: np.ndarray, n) -> np.ndarray:
    """Uniform integers in [0, n) from uint32 draws (multiply-shift)."""
    return ((r.astype(np.uint64) * np.asarray(n, np.uint64)) >> 32) \
        .astype(np.int64)


def _price(u: np.ndarray) -> np.ndarray:
    """Beam's ``nextPrice``: round(10 ** (6 u) * 100), u in [0, 1)."""
    return np.round(10.0 ** (u * 6.0) * 100.0).astype(np.int32)


class Generator:
    """Ticks of ``[1, B, WORDS]`` int32 rows and ``[1, B]`` int32 event
    times: Beam's id arithmetic, with its random draws made from the
    seed in set-up.  The kinds and the ids' offsets repeat every
    ``period`` ticks (``total / gcd(B, total)``), so set-up draws one
    period of rows and a tick is its residue's rows under its own
    event ids: the ids that follow the last auction or person shift
    with the tick, the hot auction, hot bidder and hot seller, the
    event time and the expiry are computed for the tick.  Ticks before
    the stream has ``num_in_flight_auctions`` auctions and
    ``num_active_people`` people are computed whole.  A tick is a pure
    function of the seed and ``t``."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        b = self.b = cfg["micro_batch"]
        mix = cfg["events"]
        self.pp, self.ap = mix["person_proportion"], mix["auction_proportion"]
        self.total = self.pp + self.ap + mix["bid_proportion"]
        self.rate = int(traffic["rate"])
        self.period = self.total // np.gcd(b, self.total)
        self.epochs = self.period * b // self.total   # epochs a period
        # the first tick whose every row has the auctions in flight and
        # the active people behind it
        e = np.arange(1 << 16, dtype=np.int64) * b
        ok = (self.last_auction(e) >= mix["num_in_flight_auctions"]) \
            & (self.last_person(e) + 1 >= mix["num_active_people"])
        self.steady = int(np.argmax(ok))
        rng = np.random.default_rng(seed_sequence(seed, 0))
        # per row of each residue: four uint32 draws, two prices and the
        # stand-in words
        self.draws = [(rng.integers(0, 2 ** 32, (4, b), dtype=np.uint32),
                       _price(rng.random((2, b))),
                       rng.integers(-2 ** 31, 2 ** 31, (b, WORDS - 8),
                                    dtype=np.int32))
                      for _ in range(self.period)]
        # each residue's rows at its first steady tick, and what a later
        # tick of the residue recomputes or shifts
        self.pool = {t % self.period: self.exact(t) for t in
                     range(self.steady, self.steady + self.period)}

    def last_auction(self, e: np.ndarray) -> np.ndarray:
        """Beam's ``lastBase0AuctionId`` of event ids ``e``."""
        epoch, off = np.divmod(e, self.total)
        person = off < self.pp
        bid = off >= self.pp + self.ap
        epoch = np.where(person, epoch - 1, epoch)
        off = np.where(person | bid, self.ap - 1, off - self.pp)
        return epoch * self.ap + off

    def last_person(self, e: np.ndarray) -> np.ndarray:
        """Beam's ``lastBase0PersonId``."""
        epoch, off = np.divmod(e, self.total)
        return epoch * self.pp + np.minimum(off, self.pp - 1)

    def _next_person(self, last: np.ndarray, r: np.ndarray) -> np.ndarray:
        """Beam's ``nextBase0PersonId``: one of the active people or a
        few past the last."""
        mix = self.cfg["events"]
        people = last + 1
        active = np.minimum(people, mix["num_active_people"])
        return people - active + _draw(r, active + mix["person_id_lead"])

    def _expiry(self, e: np.ndarray, time: np.ndarray,
                r: np.ndarray) -> np.ndarray:
        """Beam's auction length: uniform in [1, 2 h], h the time until
        ``num_in_flight_auctions`` more auctions exist (at least 1)."""
        ahead = self.cfg["events"]["num_in_flight_auctions"] * self.total \
            // self.ap
        horizon = (e + ahead) * 1000 // self.rate - time
        return time + 1 + _draw(r, np.maximum(2 * horizon, 1))

    def exact(self, t: int) -> tuple[np.ndarray, dict]:
        """Tick ``t`` by Beam's arithmetic for every row, from the draws
        of its residue; and what ``batch`` shifts of it."""
        mix, b = self.cfg["events"], self.b
        r, price, words = self.draws[t % self.period]
        e = t * b + np.arange(b, dtype=np.int64)
        off = e % self.total
        kind = np.where(off < self.pp, PERSON,
                        np.where(off < self.pp + self.ap, AUCTION, BID))
        la, lp = self.last_auction(e), self.last_person(e)
        time = e * 1000 // self.rate
        # bids: the hot auction with probability 1 - 1/hotAuctionRatio,
        # else one of the auctions in flight or a few past the last
        is_bid, is_auc = kind == BID, kind == AUCTION
        hot = is_bid & (_draw(r[0], mix["hot_auction_ratio"]) > 0)
        lo = np.maximum(la - mix["num_in_flight_auctions"], 0)
        auction = np.where(hot, la // HOT_BATCH * HOT_BATCH,
                           lo + _draw(r[1], la - lo + 1
                                      + mix["auction_id_lead"]))
        hot_b = is_bid & (_draw(r[2], mix["hot_bidders_ratio"]) > 0)
        bidder = np.where(hot_b, lp // HOT_BATCH * HOT_BATCH + 1,
                          self._next_person(lp, r[3]))
        # auctions: a hot seller with probability 1 - 1/hotSellersRatio
        hot_s = is_auc & (_draw(r[2], mix["hot_sellers_ratio"]) > 0)
        seller = np.where(hot_s, lp // HOT_BATCH * HOT_BATCH,
                          self._next_person(lp, r[3]))
        rows = np.empty((b, WORDS), np.int32)
        rows[:, 8:] = words
        rows[:, KIND] = kind
        rows[:, TIME] = time
        rows[:, ID] = np.where(
            is_bid, auction + FIRST_AUCTION_ID,
            np.where(is_auc, la + FIRST_AUCTION_ID, lp + FIRST_PERSON_ID))
        rows[:, PARTY] = np.where(
            is_bid, bidder + FIRST_PERSON_ID,
            np.where(is_auc, seller + FIRST_PERSON_ID, 0))
        rows[:, PRICE] = np.where(kind == PERSON, 0, price[0])
        rows[:, RESERVE] = np.where(is_auc, price[0] + price[1], 0)
        rows[:, CATEGORY] = np.where(
            is_auc, FIRST_CATEGORY_ID + _draw(r[0], NUM_CATEGORIES), 0)
        rows[:, EXPIRES] = np.where(is_auc, self._expiry(e, time, r[1]), 0)
        auc = np.flatnonzero(is_auc)
        return rows, {
            "t": t, "person": (kind == PERSON).astype(np.int64),
            "hot": np.flatnonzero(hot), "la_hot": la[hot],
            "hot_b": np.flatnonzero(hot_b), "lp_hot_b": lp[hot_b],
            "hot_s": np.flatnonzero(hot_s), "lp_hot_s": lp[hot_s],
            "auc": auc, "r_auc": r[1][auc]}

    def batch(self, t: int) -> tuple[np.ndarray, np.ndarray]:
        if t < self.steady:
            rows, _ = self.exact(t)
            return rows[None], rows[:, TIME].copy()[None]
        base, at = self.pool[t % self.period]
        m = (t - at["t"]) // self.period
        s_a, s_p = m * self.epochs * self.ap, m * self.epochs * self.pp
        rows = base.copy()
        # the ids that follow the last auction (bids, auctions) or the
        # last person (persons) shift with it; a bid's or an auction's
        # other party follows the last person
        rows[:, ID] += (s_a + at["person"] * (s_p - s_a)).astype(np.int32)
        rows[:, PARTY] += ((1 - at["person"]) * s_p).astype(np.int32)
        rows[at["hot"], ID] = (at["la_hot"] + s_a) // HOT_BATCH * HOT_BATCH \
            + FIRST_AUCTION_ID
        rows[at["hot_b"], PARTY] = (at["lp_hot_b"] + s_p) // HOT_BATCH \
            * HOT_BATCH + 1 + FIRST_PERSON_ID
        rows[at["hot_s"], PARTY] = (at["lp_hot_s"] + s_p) // HOT_BATCH \
            * HOT_BATCH + FIRST_PERSON_ID
        e = t * self.b + np.arange(self.b, dtype=np.int64)
        time = e * 1000 // self.rate
        rows[:, TIME] = time
        auc = at["auc"]
        rows[auc, EXPIRES] = self._expiry(e[auc], time[auc], at["r_auc"])
        return rows[None], time.astype(np.int32)[None]


# -- the program --------------------------------------------------------


def plan_of(cfg: dict) -> dict:
    """The keyed plan's fields from the configuration."""
    win = cfg["window"]
    return dict(key_col=ID, time_col=TIME, kind_col=KIND, kind=BID,
                pane_ms=win["period_ms"],
                panes_per_window=win["size_ms"] // win["period_ms"],
                capacity=cfg["table_capacity"], results=cfg["results"])


class System:
    """One ``StreamExecutor`` with the keyed plan on the first device.
    ``marks`` holds the clock at the end of each of the last tick's
    ``phase_names``."""

    phase_names = ("h2d", "dispatch", "d2h")

    def __init__(self, cfg: dict, tracer=None):
        import jax

        from repro.stream import StreamConfig, StreamExecutor
        from repro.stream.ingest import AdmissionPlan
        from repro.stream.keyed import KeyedPlan

        self.ex = StreamExecutor(StreamConfig(
            micro_batch=cfg["micro_batch"], window=1, stride=1,
            capacity=cfg["ring_capacity"], lateness=float(cfg["lateness_ms"]),
            keyed=KeyedPlan(**plan_of(cfg)),
            admission=AdmissionPlan(dedupe_window=cfg["dedupe_window"])))
        if tracer is not None:
            self.ex.set_tracer(tracer)
        self.devices = [jax.devices()[0]]
        self.state = self.ex.init_state(WORDS)

    def step(self, items: np.ndarray, ts: np.ndarray, span) -> list[dict]:
        import time

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
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(self.devices[0])
        b = cfg["micro_batch"]
        x = jax.ShapeDtypeStruct((b, WORDS), jnp.int32, sharding=one)
        t = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
        return self.ex.lower(self.state, x, t).compile().as_text()

    def counters(self) -> dict:
        return self.state.metrics.as_dict()


def build(cfg: dict, tracer=None, lane: dict | None = None) -> System:
    """``lane`` is the sensor's kernel path; the keyed plan has none."""
    return System(cfg, tracer)


def trim(out: dict, full: bool) -> dict:
    return {k: np.asarray(v).copy() for k, v in out.items()}


# -- the plain reference ------------------------------------------------

FNV_BASIS, FNV_PRIME = _U32(2166136261), _U32(16777619)
I32_MIN = -2 ** 31
COUNTERS = ("items_offered", "items_accepted", "items_rejected",
            "items_dequeued", "items_late", "items_deduped",
            "events_filtered", "bids_counted", "pairs_upserted",
            "key_overflow", "panes_evicted", "windows_fired",
            "results_truncated")


#: threads that make the reference's ticks ahead
AHEAD_WORKERS = 8


def _codes(rows: np.ndarray, pane_ms: int) -> np.ndarray:
    """int64 (pane, auction) codes of bid rows: pane high, id low."""
    return (rows[:, TIME].astype(np.int64) // pane_ms << 32) \
        | (rows[:, ID].astype(np.int64) & 0xFFFFFFFF)


def fnv1a(ts: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """[N] int32 event times and [N, C] int32 rows -> [N] uint32 FNV-1a
    ids over the event time, then the row's words (0 bumped to 1)."""
    w = rows.view(_U32)
    h = np.full(ts.size, FNV_BASIS, _U32)
    h ^= ts.view(_U32)
    h *= FNV_PRIME
    for c in range(w.shape[1]):
        h ^= w[:, c]
        h *= FNV_PRIME
    h[h == 0] = 1
    return h


def _fmix(x: np.ndarray) -> np.ndarray:
    """murmur3's 32-bit finaliser on uint32."""
    x = x ^ (x >> _U32(16))
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> _U32(13))
    x = x * _U32(0xC2B2AE35)
    return x ^ (x >> _U32(16))


def checksum(keys: np.ndarray, counts: np.ndarray) -> int:
    """Sum over a window's keys of mix(key, count), mod 2 ** 32."""
    k = keys.astype(np.int32).view(_U32)
    c = counts.astype(np.int64).astype(np.int32).view(_U32)
    terms = _fmix(k * _U32(0x9E3779B1) + c)
    return int(terms.astype(np.uint64).sum() & _MASK)


class Reference:
    """Query 5 from the configuration's semantics, tick by tick:

    1. admission: event id = 32-bit FNV-1a over the event time and the
       row's words; an id already among the last K accepted or earlier
       in the tick is a re-delivery.  The ring holds one tick, so every
       admitted row is dequeued in offer order.
    2. watermark: a row whose event time (float32) is below the running
       max less the lateness is late and dropped.
    3. counts: each valid bid adds one to (auction, time // period) in a
       per-pane group-by.
    4. firing: when the max event time less the lateness reaches the end
       of the next window (at most one window a tick), its panes' counts
       are summed per auction; it reports the maximum, every auction at
       it, the number of auctions and the checksum; its oldest pane is
       dropped.

    ``precision="control"`` holds the counts in bfloat16 (per tick, per
    pane, per window): the nearest precision below the configuration's
    exact int32 counts, which the comparison must catch.
    """

    def __init__(self, cfg: dict, traffic: dict, seed: int, gen=None,
                 precision: str = "int32"):
        from concurrent.futures import ThreadPoolExecutor

        self.cfg, self.precision = cfg, precision
        self.gen = gen if gen is not None else Generator(cfg, traffic, seed)
        self.plan = plan_of(cfg)
        self.lateness = int(cfg["lateness_ms"])
        self.seen = np.zeros(0, _U32)
        self.max_ts = np.float32(np.finfo(np.float32).min)
        self.max_time = I32_MIN
        self.fire_pane = I32_MIN
        self.panes: dict[int, list[tuple[np.ndarray, np.ndarray]]] = {}
        self.c = dict.fromkeys(COUNTERS, 0)
        self.collisions = 0
        # what a tick needs of no other tick is made ahead on threads
        # (numpy leaves the interpreter lock for its array loops)
        self._pool = ThreadPoolExecutor(max_workers=AHEAD_WORKERS)
        self._ahead: dict = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for f in self._ahead.values():
            f.cancel()
        self._pool.shutdown(wait=True)
        self._ahead.clear()
        self.panes.clear()

    def _round(self, x: np.ndarray) -> np.ndarray:
        if self.precision != "control":
            return x
        import ml_dtypes

        return x.astype(np.float32).astype(ml_dtypes.bfloat16) \
            .astype(np.float32)

    def _prepare(self, t: int) -> dict:
        """Tick ``t``'s rows, ids, duplicates within the tick, and its
        bids' (pane, auction) pairs with their counts, as if every row
        were admitted and on time."""
        import pandas as pd

        items, ts = self.gen.batch(t)
        items, ts = items[0], ts[0]
        ids = fnv1a(ts, items)
        bid = items[:, KIND] == BID
        pairs, n = np.unique(_codes(items[bid], self.plan["pane_ms"]),
                             return_counts=True)
        return {"items": items, "ids": ids, "sorted": np.sort(ids),
                "in_tick": pd.Series(ids).duplicated(keep="first")
                .to_numpy(), "pairs": pairs, "n": n}

    def _prepared(self, t: int) -> dict:
        for u in range(t, t + 2 * AHEAD_WORKERS):
            if u not in self._ahead:
                self._ahead[u] = self._pool.submit(self._prepare, u)
        for u in [u for u in self._ahead if u < t]:
            self._ahead.pop(u).cancel()
        return self._ahead.pop(t).result()

    def _fire(self) -> dict:
        w, p = self.fire_pane, self.plan
        oldest = w - p["panes_per_window"] + 1
        parts = [x for q in range(oldest, w + 1)
                 for x in self.panes.get(q, [])]
        if parts:
            keys = np.concatenate([k for k, _ in parts])
            counts = np.concatenate([n for _, n in parts])
            if self.precision == "control":
                # per pane first, then the window, each in bfloat16
                sums = []
                for q in range(oldest, w + 1):
                    got = self.panes.get(q, [])
                    if got:
                        kq = np.concatenate([k for k, _ in got])
                        nq = np.concatenate([n for _, n in got])
                        u, inv = np.unique(kq, return_inverse=True)
                        sums.append((u, self._round(
                            np.bincount(inv, nq, u.size))))
                keys = np.concatenate([k for k, _ in sums])
                counts = np.concatenate([n for _, n in sums])
            u, inv = np.unique(keys, return_inverse=True)
            total = self._round(np.bincount(inv, counts, u.size)) \
                .astype(np.int64)
        else:
            u, total = np.zeros(0, np.int32), np.zeros(0, np.int64)
        top = int(total.max()) if total.size else 0
        ties = np.sort(u[total == top]) if top > 0 else u[:0]
        for q in [q for q in self.panes if q <= oldest]:
            del self.panes[q]
        self.fire_pane += 1
        c = self.c
        c["panes_evicted"] += 1
        c["windows_fired"] += 1
        c["results_truncated"] += max(0, ties.size - p["results"])
        return {"fired": True, "window_end": (w + 1) * p["pane_ms"],
                "max_count": top, "n_ties": int(ties.size),
                "n_keys": int(u.size), "checksum": checksum(u, total),
                "ties": ties}

    def tick(self, t: int, full: bool) -> list[dict]:
        x, p, c = self._prepared(t), self.plan, self.c
        items, ids = x["items"], x["ids"]
        b = ids.size
        # re-deliveries: earlier in the tick, or among the last K
        # accepted ids (rarely in the tick at all: look them up there)
        dup = x["in_tick"]
        at = np.minimum(np.searchsorted(x["sorted"], self.seen), b - 1)
        known = self.seen[x["sorted"][at] == self.seen]
        if known.size:
            dup = dup | np.isin(ids, known)
        n_dup = int(dup.sum())
        # no re-deliveries in the traffic: every verdict is a collision
        self.collisions += n_dup
        c["items_offered"] += b
        c["items_accepted"] += b - n_dup
        c["items_deduped"] += n_dup
        c["items_dequeued"] += b - n_dup
        self.seen = np.concatenate(
            [self.seen, ids[~dup][-self.cfg["dedupe_window"]:]]
        )[-self.cfg["dedupe_window"]:]
        tf = items[:, TIME].astype(np.float32)
        late = ~dup & (tf < np.float32(self.max_ts
                                       - np.float32(self.lateness)))
        if n_dup < b:
            self.max_ts = np.float32(max(self.max_ts, tf[~dup].max()))
        c["items_late"] += int(late.sum())
        valid = ~dup & ~late
        bid = items[:, KIND] == BID
        c["bids_counted"] += int((valid & bid).sum())
        c["events_filtered"] += int((valid & ~bid).sum())
        pairs, n = x["pairs"], x["n"]
        gone = bid & ~valid
        if gone.any():          # take the dropped bids back out
            n = n.copy()
            np.subtract.at(n, np.searchsorted(
                pairs, _codes(items[gone], p["pane_ms"])), 1)
            pairs, n = pairs[n > 0], n[n > 0]
        c["pairs_upserted"] += int(pairs.size)
        for q in np.unique(pairs >> 32).tolist():
            at = (pairs >> 32) == q
            self.panes.setdefault(q, []).append(
                ((pairs[at] & 0xFFFFFFFF).astype(np.uint32).view(np.int32),
                 self._round(n[at].astype(np.float64))))
        if valid.any():
            t_ev = items[valid, TIME]
            self.max_time = max(self.max_time, int(t_ev.max()))
            if self.fire_pane == I32_MIN:
                self.fire_pane = int(t_ev.min()) // p["pane_ms"]
        out = {"fired": False, "newest": -1}
        if self.fire_pane != I32_MIN and self.max_time - self.lateness \
                >= (self.fire_pane + 1) * p["pane_ms"]:
            out = self._fire()
            out["newest"] = (t + 1) * b - 1
        return [out]

    def counters(self) -> dict:
        return dict(self.c)

    @property
    def log(self) -> str:
        return (f"32-bit id collisions (rows dropped as re-deliveries): "
                f"{self.collisions}")


def newest_events(ref_tick: list[dict], cfg: dict) -> np.ndarray:
    """The tick's newest event where it fired a window, else -1."""
    return np.array([ref_tick[0]["newest"]], np.int64)


# -- the comparison -----------------------------------------------------

LIMITS = {"windows_off": 0, "counters_off": 0}
FIELDS = ("window_end", "max_count", "n_ties", "n_keys", "checksum")


class Comparison:
    """Exact.  ``windows_off``: ticks whose firing differs from the
    reference's (fired or not, window end, maximum, tie count, key
    count, checksum), or whose result keys are not tied keys of the
    reference's window: all of them where they fit the output, and as
    many distinct ones as it holds where they do not.
    ``counters_off``: end-of-run counters that differ."""

    def __init__(self):
        self.numbers = dict.fromkeys(LIMITS, 0)
        self.bad_ticks = 0

    def tick(self, prog: list[dict], ref: list[dict], full: bool) -> None:
        p, r = prog[0], ref[0]
        off = bool(p["fired"]) != r["fired"]
        if not off and r["fired"]:
            off = any(int(p[k]) != int(r[k]) for k in FIELDS)
            n = min(r["n_ties"], p["keys"].size)
            got = set(p["keys"][:n].tolist())
            off |= len(got) != n or not got <= set(r["ties"].tolist())
        self.numbers["windows_off"] += int(off)
        self.bad_ticks += int(off)

    def counters(self, prog: dict, ref: dict) -> list[str]:
        off = [k for k in ref if prog.get(k) != ref[k]]
        self.numbers["counters_off"] += len(off)
        return off

    def checks(self) -> dict:
        return {k: {"value": v, "limit": LIMITS[k]}
                for k, v in self.numbers.items()}

    @property
    def correct(self) -> bool:
        return all(v <= LIMITS[k] for k, v in self.numbers.items())


# -- work and the cut cell ----------------------------------------------


def work(cfg: dict) -> dict[str, float]:
    """A lower bound on what any implementation of the keyed stage moves
    in one tick, from the configuration alone: the kind word of every
    row, the key and time words of the bids, and one 12-byte (key, pane,
    count) entry for each auction the tick creates that receives a bid
    while in flight (the uniform bids give it a Poisson count of mean
    bid / auction proportion / hotAuctionRatio).  One operation a row
    (the kind test) and one a bid (its count)."""
    mix, b = cfg["events"], cfg["micro_batch"]
    total = (mix["person_proportion"] + mix["auction_proportion"]
             + mix["bid_proportion"])
    bids = b * mix["bid_proportion"] / total
    bid_rate = mix["bid_proportion"] / mix["auction_proportion"] \
        / mix["hot_auction_ratio"]
    entries = b * mix["auction_proportion"] / total \
        * (1.0 - np.exp(-bid_rate))
    return {"ops": float(b + bids),
            "bytes": float(4 * b + 8 * bids + 12 * entries),
            "rows": float(b), "entries": float(entries)}


def small(cfg: dict, traffic: dict) -> tuple[dict, dict]:
    """2,048-event ticks of 2 ms of event time (1.024 M events/s),
    windows of 40 ms every 20 ms, a table of 16,384 slots and a dedupe
    window of 64: every mechanism of the full cell (the hot auction
    past 256 bids, windows firing and panes evicted) in a second of a
    CPU's time."""
    cfg = dict(cfg, micro_batch=2048, ring_capacity=2048,
               table_capacity=2 ** 14, dedupe_window=64,
               window={"size_ms": 40, "period_ms": 20})
    traffic = dict(traffic, rate=1_024_000)
    return cfg, traffic
