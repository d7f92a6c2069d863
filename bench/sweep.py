"""Find the highest open-loop rate a cell's system sustains.

    python3 bench/sweep.py --workload <cell> --rates 2.6e6 2.8e6 ... --seconds 5

One process: the cell's set-up as ``bench/run.py`` makes it, then the
open loop at each rate in turn (events per second, on the cell's own
rows and event times), each for ``--seconds``.  A rate is sustained when
the backlog does not grow: by the end of its run the generator's
lateness (dispatch time minus due time; the median of the last ten
ticks) is back under one fill time B / rate.  A stall of the system
makes a transient backlog at any rate; what it drains at a sustained
rate, it keeps at an unsustained one.  Prints one JSON line per rate
and the highest sustained rate; writes them to
``<out>/sweep_<cell>.json`` (``--out``, default ``.bench_out``).
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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default=".bench_out")
    args = ap.parse_args()
    import jax
    import numpy as np

    from bench import deployment, run

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench, wl, cfg, traffic = run.cell_spec(args.workload)
    jax.config.update("jax_default_matmul_precision",
                      cfg["precision"]["matmul"])
    dep = deployment.load(cfg)
    b = dep.rows_per_tick(cfg)
    gen = dep.Generator(cfg, traffic, args.seed)
    sysm = dep.build(cfg, None, None)
    span = lambda name: contextlib.nullcontext()        # noqa: E731
    for t in range(run.WARMUP_TICKS):
        sysm.step(*gen.batch(t), span)
    producer = run.Producer(gen, run.WARMUP_TICKS)
    item = producer.get()
    rows = []
    for rate in args.rates:
        ticks, _, _, item = run.drive(sysm, item, producer, item[0], b, True,
                                      rate, args.seconds, span)
        late = np.array([d - u for _, u, d, _ in ticks])
        serve = np.array([e - d for _, _, d, e in ticks])
        fill = b / rate
        end = float(np.median(late[-10:]))
        row = {"rate": rate, "ticks": len(ticks), "fill_ms": 1e3 * fill,
               "late_p50_ms": 1e3 * float(np.median(late)),
               "late_p95_ms": 1e3 * float(np.percentile(late, 95)),
               "late_end_ms": 1e3 * end,
               "tick_p50_ms": 1e3 * float(np.median(serve)),
               "sustained": end < fill}
        rows.append(row)
        print(json.dumps(row), flush=True)
    producer.close()
    ok = [r["rate"] for r in rows if r["sustained"]]
    out = {"rates": rows, "highest_sustained": max(ok) if ok else None}
    dest = ROOT / args.out
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"sweep_{args.workload}.json").write_text(json.dumps(out))
    print(json.dumps({"highest_sustained": out["highest_sustained"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
