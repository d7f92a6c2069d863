"""Plain numpy reference of the edge stream pipeline and the edge fleet.

Written from the configuration's stated semantics, not from the
program: it imports nothing of ``repro`` and takes nothing the program
made.  Per tick and shard:

1. admission: event id = 32-bit FNV-1a over the wire row (event time,
   then the channels, as float32 bit patterns; id 0 is bumped to 1).  A
   row is a re-delivery when its id is in a hash set of the last K
   accepted ids or of an earlier row of the same tick.  Fresh rows pass
   the contract (finite, per-channel closed range) or are rejected and
   counted per violated channel.  The ring holds one micro-batch (N = B),
   so every admitted row is dequeued this tick, in offer order.
2. watermark: a dequeued row is late when its event time is below the
   watermark reference minus the lateness (float32), and then joins no
   window.  The reference is the stream's own running max, or on the
   fleet the tiered minimum of the shards' maxima (monotone).
3. windows: complete windows of ``window`` rows every ``stride`` over the
   carried ``window - stride`` rows and the dequeued rows.  Sums,
   extrema and counts in float32, summed left to right; the mean is the
   sum over max(count, 1).  Rule features are (mean, max, min, sum,
   count) of the signal channel.
4. rules: the rule table, lowest precedence first, condition overwrites;
   windows with count below ``min_count`` get code 0.
5. escalation: emitted windows whose code is send-to-core.  One chip:
   the first ``core_capacity`` of them get the core stage.  Fleet: per
   region the first fog budget of its candidates (edge-major) survive,
   then the first core budget of the survivors (region-major) get the
   core stage.  The rest keep their edge record.  Budgets move by the
   configuration's hysteresis policy after every tick.
6. core stage: ``h = tanh(h @ p)``, ``layers`` times, on the window record
   (rule features then the channel means).

``precision`` selects the arithmetic: ``"float32"`` as the configuration
states it (matmuls at full float32), or ``"control"``: the nearest
precision below, float32 matmuls at three bfloat16 passes and every
other float32 quantity rounded to bfloat16.  The control exists to show
that the comparison in ``bench/compare.py`` would catch such a program.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np
import pandas as pd

F32 = np.float32
F32_MIN = F32(np.finfo(np.float32).min)
F32_MAX = F32(np.finfo(np.float32).max)
BF16 = ml_dtypes.bfloat16
FNV_BASIS, FNV_PRIME = np.uint32(2166136261), np.uint32(16777619)
CODES = {"none": 0, "store_edge": 1, "send_core": 2,
         "trigger_topology": 3, "drop": 4, "notify": 5}
_CMP = {">=": np.greater_equal, ">": np.greater, "<=": np.less_equal,
        "<": np.less, "==": np.equal}


def fnv1a(wire_t: np.ndarray) -> np.ndarray:
    """[C, N] float32 wire columns (event time, then the channels) ->
    [N] uint32 FNV-1a ids of the N rows (0 bumped to 1)."""
    words = np.ascontiguousarray(wire_t, dtype=np.float32).view(np.uint32)
    h = np.full(words.shape[1], FNV_BASIS, np.uint32)
    for c in range(words.shape[0]):
        h ^= words[c]
        h *= FNV_PRIME
    h[h == 0] = 1
    return h


def rule_table(cfg: dict) -> list[tuple[int, str, float, int]]:
    """Rules in application order: lowest precedence first (higher
    priority wins, ties to the earlier rule)."""
    rules = cfg["rules"]
    order = sorted(range(len(rules)),
                   key=lambda i: (-rules[i]["priority"], i))
    return [(rules[i]["feature"], rules[i]["op"], float(rules[i]["value"]),
             CODES[rules[i]["then"]]) for i in reversed(order)]


class Hysteresis:
    """The configuration's elastic budget policy: after ``patience``
    ticks in a row at demand / budget >= grow_at the budget doubles (at
    least +1, at most max); after ``patience`` ticks at <= shrink_at it
    halves (at least min).  A move that the bound forbids keeps the
    count running."""

    def __init__(self, spec: dict):
        self.lo, self.hi = spec["min"], spec["max"]
        self.grow_at, self.shrink_at = spec["grow_at"], spec["shrink_at"]
        self.factor, self.patience = spec["factor"], spec["patience"]
        self.hot = self.cold = 0

    def propose(self, demand: int, budget: int) -> int:
        util = demand / max(budget, 1)
        if util >= self.grow_at:
            self.hot, self.cold = self.hot + 1, 0
        elif util <= self.shrink_at:
            self.hot, self.cold = 0, self.cold + 1
        else:
            self.hot = self.cold = 0
        if self.hot >= self.patience:
            new = min(self.hi, max(budget + 1, int(budget * self.factor)))
            if new != budget:
                self.hot = 0
                return new
        if self.cold >= self.patience:
            new = max(self.lo, int(budget / self.factor))
            if new != budget:
                self.cold = 0
                return new
        return budget


def _round(x: np.ndarray, precision: str) -> np.ndarray:
    """float32 as stated, or rounded through bfloat16 for the control."""
    if precision == "control":
        return x.astype(BF16).astype(F32)
    return x


def matmul(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """float32 matmul at full precision, or at three bfloat16 passes
    (hi*hi + hi*lo + lo*hi, float32 accumulation) for the control."""
    if precision != "control":
        return np.matmul(a.astype(F32), b.astype(F32), dtype=F32)
    def split(x):
        hi = x.astype(BF16).astype(F32)
        return hi, (x - hi).astype(BF16).astype(F32)
    ah, al = split(a.astype(F32))
    bh, bl = split(b.astype(F32))
    return (np.matmul(ah, bh, dtype=F32) + np.matmul(ah, bl, dtype=F32)
            + np.matmul(al, bh, dtype=F32))


def core_stage(cfg: dict, params: np.ndarray, records: np.ndarray,
               precision: str) -> np.ndarray:
    h = records.astype(F32)
    for _ in range(cfg["core"]["layers"]):
        h = _round(np.tanh(matmul(h, params, precision)), precision)
    return h


@dataclasses.dataclass
class ShardState:
    """What a shard carries from tick to tick."""
    seen: np.ndarray            # ids of the last K accepted rows, oldest first
    seen_rows: np.ndarray       # their wire rows (collision accounting)
    carry_x: np.ndarray         # [W - S, D] carried channel rows
    carry_valid: np.ndarray     # [W - S] bool
    carry_event: np.ndarray     # [W - S] int64 stream event index, -1 none
    max_ts: np.float32
    counters: dict


COUNTERS = ("items_offered", "items_accepted", "items_rejected",
            "items_dequeued", "items_late", "items_deduped",
            "windows_emitted", "rules_fired", "windows_escalated",
            "windows_stored", "windows_dropped", "core_overflow")


def shard_init(cfg: dict) -> ShardState:
    c, d = cfg["window"] - cfg["stride"], cfg["channels"]
    return ShardState(
        seen=np.zeros(0, np.uint32), seen_rows=np.zeros((0, 1 + d), F32),
        carry_x=np.zeros((c, d), F32), carry_valid=np.zeros(c, bool),
        carry_event=np.full(c, -1, np.int64), max_ts=F32_MIN,
        counters={**{k: 0 for k in COUNTERS},
                  "drift_counts": np.zeros(d, np.int64)})


@dataclasses.dataclass
class Admitted:
    """One shard-tick after admission and the watermark."""
    items: np.ndarray           # [B, D] the tick's offered rows
    idx: np.ndarray             # [n] offered positions dequeued, in order
    valid: np.ndarray           # [B] dequeued and not late
    event: np.ndarray           # [B] stream event index of each row, -1


def admit(cfg: dict, st: ShardState, items: np.ndarray,
          ts: np.ndarray) -> tuple[np.ndarray, int]:
    """Dedupe + contract.  Returns (admitted mask, 32-bit collisions
    seen) and updates the dedupe set and counters."""
    b, d = items.shape
    k = cfg["dedupe_window"]
    wire = np.empty((1 + d, b), F32)
    wire[0] = ts
    wire[1:] = items.T
    ids = fnv1a(wire)
    dup = pd.Series(np.concatenate([st.seen, ids])).duplicated(
        keep="first").to_numpy()[st.seen.size:]
    collisions = 0
    if dup.any():
        # a verdict on id alone: count the re-deliveries whose earlier
        # row with the same id differs (a 32-bit collision)
        hit = ids[dup]
        first = {}
        for i in np.flatnonzero(np.isin(st.seen, hit)).tolist():
            first.setdefault(int(st.seen[i]), st.seen_rows[i])
        hs = np.sort(hit)
        at = np.minimum(np.searchsorted(hs, ids), hs.size - 1)
        for i in np.flatnonzero(~dup & (hs[at] == ids)).tolist():
            first.setdefault(int(ids[i]), wire[:, i])
        for i in np.flatnonzero(dup).tolist():
            if first[int(ids[i])].tobytes() != wire[:, i].tobytes():
                collisions += 1
    fresh = ~dup
    con = cfg["contract"]
    # comparisons with NaN are false: a non-finite channel violates
    inside = (items >= F32(con["lo"])) & (items <= F32(con["hi"]))
    ok = inside.all(axis=1)
    admitted = fresh & ok
    c = st.counters
    n_adm = int(admitted.sum())
    c["items_offered"] += b
    c["items_accepted"] += n_adm
    c["items_deduped"] += int(dup.sum())
    c["items_rejected"] += b - n_adm - int(dup.sum())
    c["drift_counts"] += (~inside[fresh & ~ok]).sum(axis=0)
    acc = np.flatnonzero(admitted)[-k:]
    st.seen = np.concatenate([st.seen, ids[acc]])[-k:]
    st.seen_rows = np.concatenate([st.seen_rows, wire[:, acc].T])[-k:]
    return admitted, collisions


def dequeue(cfg: dict, st: ShardState, items, ts, admitted, tick: int,
            wm_ref: np.float32) -> Admitted:
    """Compact the admitted rows (the ring drains each tick), apply the
    watermark against ``wm_ref`` and advance the stream's max."""
    b = items.shape[0]
    idx = np.flatnonzero(admitted)
    n = idx.size
    t = np.zeros(b, F32)
    t[:n] = ts[idx]
    dq = np.arange(b) < n
    late = dq & (t < F32(F32(wm_ref) - F32(cfg["lateness"])))
    live_max = t[:n].max() if n else F32_MIN
    st.max_ts = F32(max(st.max_ts, wm_ref, live_max))
    event = np.full(b, -1, np.int64)
    event[:n] = tick * b + idx
    c = st.counters
    c["items_dequeued"] += n
    c["items_late"] += int(late.sum())
    return Admitted(items=items, idx=idx, valid=dq & ~late, event=event)


@dataclasses.dataclass
class Windows:
    count: np.ndarray           # [NW] int32
    features: np.ndarray        # [NW, 5]
    code: np.ndarray            # [NW] int32
    last_event: np.ndarray      # [NW] int64 newest event in the window
    agg: np.ndarray | None      # [NW, D] channel means (full ticks only)


def _rows(a: Admitted, cols) -> np.ndarray:
    """The dequeued block's rows of ``cols``, zero past the dequeued."""
    out = np.zeros((a.valid.size, len(cols)), F32)
    out[:a.idx.size] = a.items[a.idx][:, cols] if len(cols) > 1 \
        else a.items[a.idx, cols[0]][:, None]
    return out


def windows(cfg: dict, st: ShardState, a: Admitted, full: bool,
            precision: str) -> Windows:
    """Complete windows over carry ++ dequeued rows; updates the carry.
    With ``full`` every channel's mean too, else the signal channel
    alone (what the rules and the escalation need)."""
    w, s, mc = cfg["window"], cfg["stride"], cfg["min_count"]
    sig, d = cfg["signal_channel"], cfg["channels"]
    cols = list(range(d)) if full else [sig]
    x = np.concatenate([st.carry_x[:, cols], _rows(a, cols)])
    v = np.concatenate([st.carry_valid, a.valid])
    ev = np.concatenate([st.carry_event, a.event])
    nw = (x.shape[0] - w) // s + 1
    xs = np.where(v[:, None], _round(x, precision), F32(0))
    acc = np.zeros((nw, len(cols)), F32)
    mx = np.full(nw, F32_MIN)
    mn = np.full(nw, F32_MAX)
    cnt = np.zeros(nw, F32)
    scol = cols.index(sig)
    for k in range(w):
        rows = slice(k, k + (nw - 1) * s + 1, s)
        acc = _round(acc + xs[rows], precision)
        vk = v[rows]
        xk = _round(x[rows, scol], precision)
        mx = np.maximum(mx, np.where(vk, xk, F32_MIN))
        mn = np.minimum(mn, np.where(vk, xk, F32_MAX))
        cnt = cnt + vk.astype(F32)
    empty = cnt == 0
    mx = np.where(empty, F32(0), mx)
    mn = np.where(empty, F32(0), mn)
    cf = np.maximum(cnt, F32(1))
    means = _round(acc / cf[:, None], precision)
    feats = np.stack([means[:, scol], mx, mn, acc[:, scol], cnt],
                     axis=1).astype(F32)
    code = np.zeros(nw, F32)
    for fi, op, value, cq in rule_table(cfg):
        code = np.where(_CMP[op](feats[:, fi], F32(value)), F32(cq), code)
    code = np.where(cnt >= mc, code, F32(0)).astype(np.int32)
    last = np.where(v, np.arange(x.shape[0]), -1)
    last = np.maximum.accumulate(last)[np.arange(nw) * s + w - 1]
    start = np.arange(nw) * s
    last_event = np.where(last >= start, ev[np.maximum(last, 0)], -1)
    # the carry: the block's last W - S rows, every channel
    c = w - s
    b = a.valid.size
    tail = np.zeros((c, d), F32)
    pos = np.arange(b - c, b)
    have = pos < a.idx.size
    tail[have] = a.items[a.idx[pos[have]]]
    st.carry_x, st.carry_valid, st.carry_event = tail, v[-c:], ev[-c:]
    return Windows(count=cnt.astype(np.int32), features=feats, code=code,
                   last_event=last_event, agg=means if full else None)


class Shard:
    """One shard's reference, tick by tick, generating its own rows."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, shard: int,
                 precision: str, pool=None):
        from bench.generator import Generator

        self.cfg, self.precision = cfg, precision
        self.gen = Generator(cfg, traffic, seed, [shard], pool=pool)
        self.st = shard_init(cfg)

    def tick(self, t: int, full: bool, wm_ref) -> tuple[dict, int, float]:
        """(outputs, collisions, max event time after the tick)."""
        cfg, st = self.cfg, self.st
        items, ts = self.gen.batch(t)
        adm, coll = admit(cfg, st, items[0], ts[0])
        a = dequeue(cfg, st, items[0], ts[0], adm, t, wm_ref)
        win = windows(cfg, st, a, full, self.precision)
        emit = win.count >= cfg["min_count"]
        cand = emit & (win.code == CODES["send_core"])
        c = st.counters
        c["windows_emitted"] += int(emit.sum())
        c["rules_fired"] += int((win.code != 0).sum())
        c["windows_escalated"] += int(cand.sum())
        c["windows_stored"] += int(
            (emit & (win.code == CODES["store_edge"])).sum())
        c["windows_dropped"] += int(
            (emit & (win.code == CODES["drop"])).sum())
        out = {"count": win.count, "features": win.features,
               "code": win.code, "escalated": cand,
               "last_event": win.last_event}
        if full:
            out["aggregates"] = win.agg
        return out, coll, float(st.max_ts)


def _serve_shard(conn, *args) -> None:
    """A worker process: one shard's reference, driven over a pipe."""
    shard = Shard(*args)
    while True:
        msg = conn.recv()
        if msg is None:
            break
        if msg == "counters":
            conn.send(shard.st.counters)
        else:
            conn.send(shard.tick(*msg))
    conn.close()


class Reference:
    """The whole deployment, one or several shards, tick by tick.

    ``tick(t, full)`` regenerates tick t's rows from the seed and returns
    per shard a dict of what the program's ``StepOutput`` holds (all of
    it where ``full``, else counts, features, codes and the escalated
    mask), plus the newest event of each window.  The shards of a fleet
    are independent within a tick once the tick's watermark reference
    is fixed, so each runs in a process of its own; only the fleet's
    watermark, budgets and core stage are worked out here.
    """

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 core_params: np.ndarray, precision: str = "float32",
                 pool=None):
        self.cfg, self.p, self.precision = cfg, core_params, precision
        self.n = cfg["shards"]
        self.max_ts = [F32_MIN] * self.n
        self.overflow = [0] * self.n
        self.collisions = 0
        if self.n == 1:
            self.local = Shard(cfg, traffic, seed, 0, precision, pool=pool)
            self.conns, self.procs = [], []
        else:
            import multiprocessing as mp

            ctx = mp.get_context("spawn")
            self.local, self.conns, self.procs = None, [], []
            for s in range(self.n):
                mine, theirs = ctx.Pipe()
                proc = ctx.Process(target=_serve_shard, daemon=True,
                                   args=(theirs, cfg, traffic, seed, s,
                                         precision))
                proc.start()
                theirs.close()
                self.conns.append(mine)
                self.procs.append(proc)
        fl = cfg.get("fleet")
        if fl:
            self.regions = fl["regions"]
            self.core_budget = fl["core_budget"]
            self.fog_budget = [fl["fog_budget"]] * self.regions
            self.core_policy = Hysteresis(fl["core_policy"])
            self.fog_policies = [Hysteresis(fl["fog_policy"])
                                 for _ in range(self.regions)]
            self.watermark = F32_MIN
            self.region_watermark = [F32_MIN] * self.regions
            self.fleet_counters = {"fog_shed": [0] * self.n,
                                   "escalations_sent": [0] * self.n,
                                   "core_received": 0, "core_processed": 0,
                                   "fleet_core_overflow": 0}

    def close(self) -> None:
        """Stop the shard processes and wait until each has ended."""
        for c in self.conns:
            try:
                c.send(None)
            except OSError:             # that worker has already gone
                pass
        for proc in self.procs:
            proc.join(timeout=30)
            if proc.is_alive():
                proc.terminate()
                proc.join()
        self.conns, self.procs = [], []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def _wm_refs(self) -> list:
        if not self.cfg.get("fleet"):
            return list(self.max_ts)
        per = self.n // self.regions
        raw_r = [F32(min(self.max_ts[r * per:(r + 1) * per]))
                 for r in range(self.regions)]
        wm = F32(max(min(raw_r), self.watermark))
        self.region_watermark = [F32(max(a, b)) for a, b in
                                 zip(raw_r, self.region_watermark)]
        self.watermark = wm
        return [wm] * self.n

    def tick(self, t: int, full: bool) -> list[dict]:
        cfg, prec = self.cfg, self.precision
        refs = self._wm_refs()
        if self.local is not None:
            done = [self.local.tick(t, full, refs[0])]
        else:
            for c, ref in zip(self.conns, refs):
                c.send((t, full, ref))
            done = [c.recv() for c in self.conns]
        outs = [o for o, _, _ in done]
        self.collisions += sum(c for _, c, _ in done)
        self.max_ts = [F32(m) for _, _, m in done]
        if cfg.get("fleet"):
            processed = self._fleet_escalate(outs)
        else:
            cand = outs[0]["escalated"]
            cap = int(cfg["core"]["capacity_share"] * cand.size)
            processed = [cand & (np.cumsum(cand) <= cap)]
            self.overflow[0] += max(0, int(cand.sum()) - cap)
        for o, proc in zip(outs, processed):
            if full:
                rec = np.concatenate([o["features"], o["aggregates"]],
                                     axis=1)
                out = rec.copy()
                if proc.any():
                    out[proc] = core_stage(cfg, self.p, rec[proc], prec)
                o["outputs"] = out
            o["processed"] = proc
        return outs

    def _fleet_escalate(self, outs: list[dict]) -> list[np.ndarray]:
        """Fog budget per region, then the fleet core budget; then the
        control plane's policies for the next tick."""
        per = self.n // self.regions
        fc = self.fleet_counters
        survivors = []
        for r in range(self.regions):
            left = self.fog_budget[r]
            for s in range(r * per, (r + 1) * per):
                cand = outs[s]["escalated"]
                surv = cand & (np.cumsum(cand) <= left)
                left -= int(surv.sum())
                survivors.append(surv)
                fc["fog_shed"][s] += int(cand.sum() - surv.sum())
                fc["escalations_sent"][s] += int(surv.sum())
        left = self.core_budget
        total = sum(int(sv.sum()) for sv in survivors)
        processed = []
        for s, surv in enumerate(survivors):
            proc = surv & (np.cumsum(surv) <= left)
            left -= int(proc.sum())
            processed.append(proc)
            self.overflow[s] += int((outs[s]["escalated"] & ~proc).sum())
        fc["core_received"] += total
        fc["core_processed"] += min(total, self.core_budget)
        fc["fleet_core_overflow"] += max(0, total - self.core_budget)
        demand = [int(o["escalated"].sum()) for o in outs]
        self.core_budget = self.core_policy.propose(sum(demand),
                                                    self.core_budget)
        self.fog_budget = [
            self.fog_policies[r].propose(sum(demand[r * per:(r + 1) * per]),
                                         self.fog_budget[r])
            for r in range(self.regions)]
        return processed

    def counters(self) -> dict:
        """Cumulative counters per shard (and the fleet's), as lists."""
        if self.local is not None:
            per = [self.local.st.counters]
        else:
            for c in self.conns:
                c.send("counters")
            per = [c.recv() for c in self.conns]
        out = {k: [c[k] for c in per] for k in COUNTERS
               if k != "core_overflow"}
        out["core_overflow"] = list(self.overflow)
        out["drift_counts"] = [c["drift_counts"].tolist() for c in per]
        if self.cfg.get("fleet"):
            fc = self.fleet_counters
            out.update(fog_shed=list(fc["fog_shed"]),
                       escalations_sent=list(fc["escalations_sent"]),
                       core_received=fc["core_received"],
                       core_processed=fc["core_processed"],
                       fleet_core_overflow=fc["fleet_core_overflow"],
                       watermark=float(self.watermark),
                       region_watermark=[float(x)
                                         for x in self.region_watermark],
                       core_budget=self.core_budget,
                       fog_budget=list(self.fog_budget))
        return out
