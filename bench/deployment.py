"""A deployment: what one kind of configuration brings to the harness.

``bench/run.py`` knows nothing of what a system computes.  A
configuration file names its deployment under ``"plugin"`` (default
``"sensor"``), and ``load`` runs ``bench/deployments/<plugin>.py``, a
module that provides:

* ``Generator(cfg, traffic, seed)``: ``.batch(t) -> (items, ts)``, host
  arrays that the system's ``step`` takes for tick ``t``, a pure
  function of the seed and ``t``; ``.pool``, what set-up made in host
  memory (the reference may share it).
* ``rows_per_tick(cfg)``: events per shard and tick.  The open loop's
  due times and ``events_per_s`` count in these.
* ``build(cfg, tracer, lane)``: the system under test, with
  ``step(items, ts, span) -> [per-shard dict of host outputs]``,
  ``compiled()`` (executables of the step), ``hlo_text(cfg)``,
  ``counters()``, ``devices``, ``phase_names`` and ``marks`` (the clock
  at the end of each phase of the last tick).  ``lane`` is a test's
  override of the configuration's kernel path, or None.
* ``trim(out, full)``: what the comparison keeps of one shard's outputs
  of a tick; ``full`` on the ticks sampled for the whole comparison.
* ``Reference(cfg, traffic, seed, gen)``: the plain reference, a context
  manager with ``tick(t, full) -> [per-shard dict]`` and ``counters()``,
  and optionally a ``log`` string for the run's standard error.  It
  regenerates its inputs from the seed, and works out its own fixed
  parameters; ``gen`` is the run's generator.
* ``newest_events(ref_tick, cfg)``: for each result of the reference's
  tick, the stream index of the newest event in it, or -1 where the
  result is not emitted; the emission latency's start.
* ``Comparison()``: ``tick(prog, ref, full)``, ``counters(prog, ref)``
  (the names that differ), ``checks()`` (each number beside its limit),
  ``correct`` and ``bad_ticks``.
* ``work(cfg)``: the operation and byte counts that the roofline readers
  of ``bench/metrics/`` see as ``ctx.work``.
* ``small(cfg, traffic)``: the configuration and traffic cut to a size
  a CPU test can hold, every mechanism kept on.

A new deployment is that module, its configuration and traffic files,
the metric readers it needs, and entries in ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

#: where ``load`` looks for ``<plugin>.py``
DIR = Path(__file__).resolve().parent / "deployments"


def load(cfg: dict):
    """The deployment module that the configuration names."""
    name = cfg.get("plugin", "sensor")
    path = DIR / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r} names the deployment "
            f"{name!r}, but there is no {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_deployment_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_sequence(seed: int, *words: int) -> np.random.SeedSequence:
    """SeedSequence of a run seed (any integer) and extra words."""
    return np.random.SeedSequence([seed & (2 ** 64 - 1), seed < 0, *words])
