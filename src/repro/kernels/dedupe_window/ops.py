"""Vectorized dedupe-window stage (jnp backend).

The idempotent-ingestion dedupe window as fixed-shape masked ops,
designed to fuse into the executor's single traced step: event-id
hashing (FNV-1a over the raw f32 bit patterns of the wire row), a
bounded seen-window membership test (one sort of the ``K`` ring
entries and the ``N`` offered ids, O((N+K) log(N+K)) — the window is a
traced ``uint32[K]`` ring operand, so sizing it is a config change,
consulting it is not a recompile), and the accepted-hash recording
scatter.  Semantics are pinned bit-for-bit against the
pure-numpy oracle in ``ref.py`` (``tests/test_ingest.py``).

These are deliberately *not* jit-wrapped: they run inside the
executor's one XLA trace and must inline there, not form a call
boundary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dedupe_window.ref import (EMPTY_HASH, FNV_BASIS,
                                             FNV_PRIME)


def row_hash(rows: jnp.ndarray) -> jnp.ndarray:
    """[N, C] f32 (or int32) wire rows -> [N] uint32 FNV-1a event ids
    (exact — the 32-bit words are bitcast, not rounded, so a re-sent
    row hashes identically on every backend).  Hash 0 is reserved for
    "empty seen slot" and real rows landing on it are bumped to 1."""
    rows = jnp.asarray(rows)
    if rows.dtype != jnp.int32:
        rows = rows.astype(jnp.float32)
    words = jax.lax.bitcast_convert_type(rows, jnp.uint32)
    h = jnp.full(words.shape[:1], FNV_BASIS, jnp.uint32)
    for c in range(words.shape[1]):        # C is static (trace constant)
        h = (h ^ words[:, c]) * FNV_PRIME
    return jnp.where(h == EMPTY_HASH, jnp.uint32(1), h)


def dedupe_window(hashes: jnp.ndarray, offered: jnp.ndarray,
                  seen: jnp.ndarray
                  ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Membership test: ``(fresh, dup)`` [N] bool masks.

    ``dup`` marks offered rows already in the ``seen`` ring **or**
    equal to an earlier offered slot of this batch (first delivery
    wins, FIFO); ``fresh = offered & ~dup``.  A ``seen`` ring of size
    0 disables the window (everything offered is fresh) — the caller
    skips the stage statically in that case, this is just the
    consistent limit.

    One sort answers both questions in O((N+K) log(N+K)), with no
    ``[N, N]`` or ``[N, K]`` intermediate: the ring (positions
    ``0..K-1``) and the batch (``K..K+N-1``) are sorted together by
    ``(hash, position)``, with non-offered rows pushed past every
    valid position.  Equal hashes then sit in a run, valid entries
    first and in offer order, so a valid batch row is a duplicate
    exactly when the entry just before it holds the same hash (that
    entry is then valid too: a ring entry or an earlier offered row).
    A second sort, keyed by position, puts the flags back in offer
    order."""
    offered = jnp.asarray(offered, bool)
    k = seen.shape[0]
    if k == 0:
        return offered, jnp.zeros(offered.shape, bool)
    m = k + hashes.shape[0]
    key = jnp.concatenate([seen, hashes])
    idx = jnp.arange(m, dtype=jnp.int32)
    valid = jnp.concatenate([jnp.ones((k,), bool), offered])
    pos = jnp.where(valid, idx, idx + m)
    key, pos = jax.lax.sort((key, pos), num_keys=2, is_stable=False)
    dup = jnp.concatenate([
        jnp.zeros((1,), bool),
        (pos[1:] < m) & (key[1:] == key[:-1])])
    _, dup = jax.lax.sort((pos % m, dup), is_stable=False)
    dup = dup[k:]
    return offered & ~dup, dup


def seen_record(seen: jnp.ndarray, seen_pos: jnp.ndarray,
                hashes: jnp.ndarray, accepted: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Record hashes of ring-*accepted* rows into the seen window.

    ``accepted`` [N] bool marks the admitted rows that survived
    backpressure; they land in the ring in offer order starting at
    ``seen_pos`` (oldest entries overwritten).  When a single batch
    accepts more than K rows only the last K survive — the scatter
    keeps exactly that suffix so duplicate target slots never race
    (deterministic, matching the oracle's sequential overwrite)."""
    k = seen.shape[0]
    if k == 0:
        return seen, seen_pos
    accepted = jnp.asarray(accepted, bool)
    rank = jnp.cumsum(accepted.astype(jnp.int32)) - 1
    n_rec = jnp.sum(accepted.astype(jnp.int32))
    keep = accepted & (rank >= n_rec - k)      # last K accepted rows
    idx = jnp.where(keep, (seen_pos + rank) % k, k)   # k = dropped
    seen = seen.at[idx].set(hashes, mode="drop")
    return seen, (seen_pos + n_rec) % k
