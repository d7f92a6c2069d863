"""Timing helpers for the benchmark harness (CSV rows, stable medians).

Every ``row()`` both prints the CSV line and records it in a
module-level collector, so ``run.py --json`` can snapshot a suite's
rows into a ``BENCH_<suite>.json`` artifact (see ``repro.obs.export``)
without re-parsing stdout.
"""
from __future__ import annotations

import time
from typing import Callable

import jax

#: Rows collected since the last :func:`reset_rows` (dicts with
#: ``name``/``us_per_call``/``derived``) — the --json artifact source.
ROWS: list[dict] = []


def reset_rows() -> None:
    ROWS.clear()


def get_rows() -> list[dict]:
    return list(ROWS)


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds (block_until_ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def time_stateful(fn: Callable, state, *args, warmup: int = 2,
                  iters: int = 10) -> float:
    """Like time_fn for donated-state ops: fn(state, *args) -> (state, ...).
    The returned state feeds the next call (ring-buffer semantics)."""
    for _ in range(warmup):
        out = fn(state, *args)
        state = out[0]
        jax.block_until_ready(out)
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(state, *args)
        state = out[0]
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6


def row(name: str, us: float, derived: str = "") -> str:
    line = f"{name},{us:.1f},{derived}"
    ROWS.append({"name": name, "us_per_call": float(us),
                 "derived": derived})
    print(line, flush=True)
    return line

