"""What the program's host tracer costs a tick when it is on.

    python3 bench/tracing_cost.py --workload <cell> [--seed 7] [--blocks 6] [--seconds 2]

One process on the cell's chips: the cell's system as ``bench/run.py``
builds it and warms it up, then the harness's closed loop
(``run.drive``, the generator on its thread) in alternating blocks of
``--seconds``, with an ``obs.Tracer`` installed and with the disabled
tracer, the JAX profiler off throughout.  A tick is what the harness
times: dispatch to the tick's outputs on the host, the fleet's control
tick included.  Prints one JSON line: per side the ticks, the median
tick and the median of the program's ``last_step_seconds`` in ms, and
the device.  Exits non-zero, printing no result, where JAX finds no TPU
or fewer chips than the cell needs.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def alternate(cfg: dict, traffic: dict, seed: int, blocks: int,
              seconds: float, lane: dict | None = None) -> dict:
    """Ticks of the cell's system with the tracer on and off in
    alternating blocks: ``{"on"|"off": ([tick s], [last_step_seconds])}``
    and the tracer's span count under ``"spans"``.  ``lane`` lets a CPU
    test run it on the fused tick's jnp lane."""
    from bench import run, system
    from bench.generator import Generator
    from repro.obs import NULL_TRACER, Tracer

    gen = Generator(cfg, traffic, seed)
    tracer = Tracer()
    sysm = system.build(cfg, tracer, lane)
    span = lambda name: contextlib.nullcontext()        # noqa: E731
    for t in range(run.WARMUP_TICKS):
        sysm.step(*gen.batch(t), span)
    producer = run.Producer(gen, run.WARMUP_TICKS)
    item = producer.get()
    out = {"on": ([], []), "off": ([], [])}
    for block in range(2 * blocks):
        side = ("on", "off")[block % 2]
        tr = tracer if side == "on" else NULL_TRACER
        sysm.ex.set_tracer(tr)
        if hasattr(sysm, "ctl"):
            sysm.ctl.tracer = tr
        ticks, steps = out[side]
        done, _, _, item = run.drive(
            sysm, item, producer, item[0], cfg["micro_batch"], False, 0.0,
            seconds, span,
            on_tick=lambda *a: steps.append(sysm.ex.last_step_seconds))
        ticks += [e - d for _, _, d, e in done]
    producer.close()
    return {**out, "spans": len(tracer.spans)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--blocks", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from bench import run

    _, wl, cfg, traffic = run.cell_spec(args.workload)
    import jax
    import numpy as np

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["chips"]:
        print(f"bench/tracing_cost.py: {wl['name']} needs {wl['chips']} "
              f"TPU chips, JAX found {len(devs)} {devs[0].platform} "
              f"device(s)", file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    with jax.default_matmul_precision(cfg["precision"]["matmul"]):
        got = alternate(cfg, traffic, args.seed, args.blocks, args.seconds)
    res = {"workload": args.workload, "spans_recorded": got["spans"],
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind, "count": wl["chips"]}}
    for side in ("on", "off"):
        ticks, steps = got[side]
        res[f"ticks_{side}"] = len(ticks)
        res[f"tick_ms_{side}"] = float(np.median(ticks)) * 1e3
        res[f"last_step_ms_{side}"] = float(np.median(steps)) * 1e3
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
