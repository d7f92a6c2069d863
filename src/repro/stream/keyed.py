"""Keyed event-time windows: a device-resident (key, pane) -> count table.

A keyed plan (:class:`KeyedPlan`, carried on ``StreamConfig``) replaces
the positional windows, rules and pipeline of a stream step with a
group-by over event time.  Rows that pass the kind filter are counted by
``(key, pane)``, a pane being ``pane_ms`` of event time (integer
division of the row's event-time word, never a float division).  A
window is ``panes_per_window`` consecutive panes, one every pane (a
sliding window whose period is the pane).  When the watermark passes a
window's end the window fires: its per-key counts are the sums over its
panes, and it reports the keys whose count is the window's maximum (up
to ``results`` of them), the maximum, the number of tied keys, the
number of keys in the window and an order-free checksum over all of its
(key, count) pairs.  The pane no later window needs is then evicted.

The table is three ``int32[rows, ROW]`` arrays (key, pane, count): a
key hashes to one row, and each of its live panes holds one slot of that
row.  A slot whose pane is :data:`EMPTY` is free.  Because a lookup
reads the whole row, a freed slot never breaks a later lookup, so
eviction is a plain overwrite.  Keys are hashed (murmur3's finaliser),
so nothing assumes they are dense or monotone.

Per tick the cost is O(batch), whatever the table holds: the tick's
counted rows are sorted by (row, key, pane) once, reduced to distinct
pairs with their counts, each pair reads its table row, adds to the slot
that holds it or takes the ``r``-th free slot of the row where it is the
``r``-th new pair of the row this tick, and three scatters write the
result.  A new pair that finds no free slot is counted in
``key_overflow`` and not stored.  Only a tick on which a window is due
reads the whole table (``lax.cond``), once per pane of event time.

Device scopes: ``obs:keyed_update`` (selection, the sort, the upsert,
the watermark) and ``obs:keyed_fire`` (the due test and the firing).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

#: slots in a table row: one vector register's lanes on a TPU, so a row
#: is read whole in one gather
ROW = 128
#: the pane of a free slot, and the fire pane before the first row
EMPTY = int(jnp.iinfo(jnp.int32).min)
_I32_MAX = int(jnp.iinfo(jnp.int32).max)


@dataclasses.dataclass(frozen=True)
class KeyedPlan:
    """Static description of a keyed windowed count (trace constants).

    Columns index the row's item words (after the ring's metadata
    columns); with a keyed plan the ring holds int32 words.
    ``time_col`` holds integer event time in milliseconds.  Rows whose
    ``kind_col`` word equals ``kind`` are counted by ``key_col``; the
    others are only counted as filtered.  ``capacity`` is the table's
    slots, a power-of-two number of rows of :data:`ROW`; ``results`` is
    how many of a fired window's tied keys its output holds."""
    key_col: int
    time_col: int
    kind_col: int
    kind: int
    pane_ms: int
    panes_per_window: int
    capacity: int
    results: int

    def __post_init__(self):
        rows = self.capacity // ROW
        if self.capacity % ROW or rows < 1 or rows & (rows - 1):
            raise ValueError(f"capacity must be a power-of-two multiple of "
                             f"{ROW} slots, got {self.capacity}")
        if self.pane_ms < 2 or self.panes_per_window < 1 or self.results < 1:
            raise ValueError(f"need pane_ms >= 2, panes_per_window >= 1 and "
                             f"results >= 1, got {self}")

    @property
    def rows(self) -> int:
        return self.capacity // ROW


class KeyedMetrics(NamedTuple):
    """Monotone int32 counters of the keyed stage."""
    events_filtered: jnp.ndarray   # valid rows of another kind
    bids_counted: jnp.ndarray      # valid rows of the counted kind
    pairs_upserted: jnp.ndarray    # distinct (key, pane) pairs, per tick
    key_overflow: jnp.ndarray      # new pairs that found no free slot
    panes_evicted: jnp.ndarray
    windows_fired: jnp.ndarray
    results_truncated: jnp.ndarray  # tied keys left out of fired windows


class KeyedState(NamedTuple):
    """The table and the window clock, carried (and donated) in
    ``StreamState.keyed``."""
    keys: jnp.ndarray        # [rows, ROW] int32
    panes: jnp.ndarray       # [rows, ROW] int32, EMPTY where free
    counts: jnp.ndarray      # [rows, ROW] int32, 0 where free
    fire_pane: jnp.ndarray   # [] int32 last pane of the next window to fire
    max_time: jnp.ndarray    # [] int32 running max event time of valid rows


class KeyedOutput(NamedTuple):
    """One tick's output: the window it fired, if any (zeros if not)."""
    fired: jnp.ndarray       # [] bool
    window_end: jnp.ndarray  # [] int32 end of the window, exclusive, ms
    max_count: jnp.ndarray   # [] int32
    n_ties: jnp.ndarray      # [] int32 keys at the maximum
    n_keys: jnp.ndarray      # [] int32 keys in the window
    checksum: jnp.ndarray    # [] uint32 sum of mix(key, count) over them
    keys: jnp.ndarray        # [results] int32; the first min(n_ties,
    #                          results) are tied keys, in table order


def keyed_init(plan: KeyedPlan) -> KeyedState:
    shape = (plan.rows, ROW)
    return KeyedState(
        keys=jnp.zeros(shape, jnp.int32),
        panes=jnp.full(shape, EMPTY, jnp.int32),
        counts=jnp.zeros(shape, jnp.int32),
        fire_pane=jnp.asarray(EMPTY, jnp.int32),
        max_time=jnp.asarray(EMPTY, jnp.int32))


def keyed_metrics_init() -> KeyedMetrics:
    # distinct buffers per counter: the step donates its state
    return KeyedMetrics(*(jnp.zeros((), jnp.int32)
                          for _ in KeyedMetrics._fields))


def _fmix(x: jnp.ndarray) -> jnp.ndarray:
    """murmur3's 32-bit finaliser (uint32 in, uint32 out)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _u32(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)


def row_of(key: jnp.ndarray, rows: int) -> jnp.ndarray:
    """The table row of each key (``rows`` a power of two)."""
    return (_fmix(_u32(key)) & jnp.uint32(rows - 1)).astype(jnp.int32)


def pair_mix(key: jnp.ndarray, count: jnp.ndarray) -> jnp.ndarray:
    """The checksum's term of one (key, count) pair, uint32."""
    return _fmix(_u32(key) * jnp.uint32(0x9E3779B1) + _u32(count))


def select(plan: KeyedPlan, items: jnp.ndarray,
           valid: jnp.ndarray) -> jnp.ndarray:
    """[B] bool: the valid rows of the counted kind."""
    return valid & (items[:, plan.kind_col] == plan.kind)


def upsert(st: KeyedState, key: jnp.ndarray, pane: jnp.ndarray,
           live: jnp.ndarray) -> tuple[KeyedState, jnp.ndarray, jnp.ndarray]:
    """Add one to ``(key, pane)`` for every ``live`` row.  Returns the
    table, the distinct pairs this tick and the new pairs that found no
    free slot."""
    rows = st.keys.shape[0]
    n = key.shape[0]
    # one sort groups equal pairs and puts the pairs of a row together;
    # rows that are not live sort past every table row
    r = jnp.where(live, row_of(key, rows), rows)
    r, key, pane = jax.lax.sort((r, key, pane), num_keys=3, is_stable=False)
    live = r < rows
    same = (r[1:] == r[:-1]) & (key[1:] == key[:-1]) & (pane[1:] == pane[:-1])
    pos = jnp.arange(n, dtype=jnp.int32)
    first = jax.lax.cummax(jnp.where(
        jnp.concatenate([jnp.ones((1,), bool), ~same]), pos, 0))
    last = live & jnp.concatenate([~same, jnp.ones((1,), bool)])
    count = pos - first + 1                 # the pair's rows, at its last

    row = jnp.minimum(r, rows - 1)
    tk, tp = st.keys[row], st.panes[row]    # [n, ROW] each
    hit = (tk == key[:, None]) & (tp == pane[:, None])
    found = jnp.any(hit, axis=1)
    new = last & ~found
    # a new pair takes the free slot of its row whose rank among the
    # row's free slots is its rank among the row's new pairs this tick
    n_new = jnp.cumsum(new.astype(jnp.int32)) - new
    row_start = jnp.concatenate([jnp.ones((1,), bool), r[1:] != r[:-1]])
    rank = n_new - jax.lax.cummax(jnp.where(row_start, n_new, 0))
    free = tp == EMPTY
    nth = jnp.cumsum(free.astype(jnp.int32), axis=1)
    placed = new & (nth[:, -1] > rank)
    slot = jnp.where(found, jnp.argmax(hit, axis=1),
                     jnp.argmax(free & (nth == rank[:, None] + 1), axis=1))
    add = jnp.where(last & (found | placed), row, rows)   # rows: dropped
    put = jnp.where(placed, row, rows)
    st = st._replace(
        counts=st.counts.at[add, slot].add(count, mode="drop"),
        keys=st.keys.at[put, slot].set(key, mode="drop"),
        panes=st.panes.at[put, slot].set(pane, mode="drop"))
    return (st, jnp.sum(last.astype(jnp.int32)),
            jnp.sum((new & ~placed).astype(jnp.int32)))


def window_due(plan: KeyedPlan, max_time: jnp.ndarray,
               fire_pane: jnp.ndarray, lateness: int) -> jnp.ndarray:
    """Whether the watermark (max event time less the lateness) has
    reached the end of the window whose last pane is ``fire_pane``."""
    return (fire_pane != EMPTY) \
        & (max_time - lateness >= (fire_pane + 1) * plan.pane_ms)


def evict(plan: KeyedPlan, st: KeyedState, oldest: jnp.ndarray
          ) -> KeyedState:
    """Free every slot of pane ``oldest`` or older."""
    gone = st.panes <= oldest
    return st._replace(panes=jnp.where(gone, EMPTY, st.panes),
                       counts=jnp.where(gone, 0, st.counts))


def fire(plan: KeyedPlan, st: KeyedState
         ) -> tuple[KeyedState, KeyedOutput, jnp.ndarray]:
    """Fire the window whose last pane is ``st.fire_pane`` and evict its
    oldest pane.  Reads the whole table.  Returns the table, the output
    and the tied keys left out."""
    w = st.fire_pane
    oldest = w - (plan.panes_per_window - 1)
    inside = (st.panes >= oldest) & (st.panes <= w)
    # within a row, sort the window's entries first and by key, so a
    # key's panes sit side by side
    out_, k, c = jax.lax.sort(
        ((~inside).astype(jnp.int32), st.keys,
         jnp.where(inside, st.counts, 0)),
        dimension=1, num_keys=2, is_stable=False)
    valid = out_ == 0
    same = valid[:, 1:] & (k[:, 1:] == k[:, :-1])   # valid entries lead
    edge = jnp.ones((k.shape[0], 1), bool)
    start = jnp.concatenate([edge, ~same], axis=1)
    end = valid & jnp.concatenate([~same, edge], axis=1)
    # a key's total is the row's running sum at its last entry less the
    # running sum before its first; counts are not negative, so the
    # latter is the running max of that sum at the run starts
    cs = jnp.cumsum(c, axis=1)
    total = jnp.where(end, cs - jax.lax.cummax(jnp.where(start, cs - c, 0),
                                               axis=1), 0)
    top = jnp.max(total)
    tie = end & (total == top) & (top > 0)
    n_ties = jnp.sum(tie.astype(jnp.int32))
    flat = tie.reshape(-1)
    rank = jnp.cumsum(flat.astype(jnp.int32)) - 1
    dest = jnp.where(flat & (rank < plan.results), rank, plan.results)
    keys = jnp.zeros((plan.results,), jnp.int32).at[dest].set(
        k.reshape(-1), mode="drop")
    out = KeyedOutput(
        fired=jnp.ones((), bool), window_end=(w + 1) * plan.pane_ms,
        max_count=top, n_ties=n_ties,
        n_keys=jnp.sum(end.astype(jnp.int32)),
        checksum=jnp.sum(jnp.where(end, pair_mix(k, total), 0),
                         dtype=jnp.uint32),
        keys=keys)
    st = evict(plan, st, oldest)._replace(fire_pane=w + 1)
    return st, out, jnp.maximum(n_ties - plan.results, 0)


def _idle(plan: KeyedPlan, st: KeyedState
          ) -> tuple[KeyedState, KeyedOutput, jnp.ndarray]:
    zero = jnp.zeros((), jnp.int32)
    return st, KeyedOutput(
        fired=jnp.zeros((), bool), window_end=zero, max_count=zero,
        n_ties=zero, n_keys=zero, checksum=jnp.zeros((), jnp.uint32),
        keys=jnp.zeros((plan.results,), jnp.int32)), zero


def keyed_step(plan: KeyedPlan, st: KeyedState, items: jnp.ndarray,
               valid: jnp.ndarray, lateness: int
               ) -> tuple[KeyedState, KeyedOutput, KeyedMetrics]:
    """One tick of the keyed stage over the dequeued rows' item words
    ``items [B, D]`` (int32) and their ``valid`` mask (dequeued and not
    late).  Returns the state, the tick's output and the counters'
    increments."""
    with jax.named_scope("obs:keyed_update"):
        counted = select(plan, items, valid)
        t = items[:, plan.time_col]
        pane = jnp.floor_divide(t, plan.pane_ms)
        st, n_pairs, n_over = upsert(st, items[:, plan.key_col], pane,
                                     counted)
        max_time = jnp.maximum(st.max_time,
                               jnp.max(jnp.where(valid, t, EMPTY)))
        # the first window to fire is the first that holds a row
        start = jnp.min(jnp.where(valid, pane, _I32_MAX))
        st = st._replace(max_time=max_time, fire_pane=jnp.where(
            (st.fire_pane == EMPTY) & jnp.any(valid), start, st.fire_pane))
    with jax.named_scope("obs:keyed_fire"):
        st, out, truncated = jax.lax.cond(
            window_due(plan, st.max_time, st.fire_pane, lateness),
            functools.partial(fire, plan), functools.partial(_idle, plan),
            st)
    n_counted = jnp.sum(counted.astype(jnp.int32))
    fired = out.fired.astype(jnp.int32)
    return st, out, KeyedMetrics(
        events_filtered=jnp.sum(valid.astype(jnp.int32)) - n_counted,
        bids_counted=n_counted, pairs_upserted=n_pairs,
        key_overflow=n_over, panes_evicted=fired, windows_fired=fired,
        results_truncated=truncated)
