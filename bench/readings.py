"""Readings that the limits of ``bench/compare.py`` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1 2 ... --seconds 3

For each seed, one run of the cell as ``bench/run.py`` makes it (the
program's readings: its numbers against the reference), and the control:
the reference computed in the nearest precision below the
configuration's (``reference.py``, ``precision="control"``), put in the
program's place over the same ticks.  The control has to fail the
comparison.  Writes every reading to ``<out>/readings_<cell>.json``
(``--out``, default ``.bench_out``) and prints the per-number maxima
of the program and minima of the control.  The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def control_numbers(cfg: dict, traffic: dict, seed: int, ticks: int,
                    full_every: int = 4) -> dict:
    """The control against the reference over ``ticks`` ticks of the
    seed's traffic: the comparison's numbers."""
    from bench import compare
    from bench.reference import Reference
    from bench.system import core_params

    p = core_params(cfg)
    cmp = compare.Comparison()
    with Reference(cfg, traffic, seed, p) as ref, \
            Reference(cfg, traffic, seed, p, precision="control") as ctl:
        for t in range(ticks):
            full = t % full_every == 0
            cmp.tick([as_program(x) for x in ctl.tick(t, full)],
                     ref.tick(t, full), full)
        cmp.counters(ctl.counters(), ref.counters())
    return {"numbers": cmp.numbers, "correct": cmp.correct}


def as_program(r: dict) -> dict:
    """A reference tick in the shape of the program's outputs."""
    out = {"window_count": r["count"], "consequence": r["code"],
           "escalated": r["escalated"], "features": r["features"]}
    if "aggregates" in r:
        out.update(aggregates=r["aggregates"], outputs=r["outputs"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control-ticks", type=int, default=48)
    ap.add_argument("--out", default=".bench_out")
    args = ap.parse_args()
    import jax

    from bench import run

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bench, wl, cfg, traffic = run.cell_spec(args.workload)
    out = {"program": [], "control": []}
    for seed in args.seeds:
        res, _ = run.run_cell(bench, wl, cfg, traffic, seed, args.seconds,
                              False, log=lambda *a, **k: None)
        out["program"].append({"seed": seed, "correct": res["correct"],
                               "ticks": res["attempted"],
                               **{k: v["value"]
                                  for k, v in res["checks"].items()}})
        print("program", json.dumps(out["program"][-1]), flush=True)
    for seed in args.seeds[:3]:
        c = control_numbers(cfg, traffic, seed, args.control_ticks)
        out["control"].append({"seed": seed, "correct": c["correct"],
                               **c["numbers"]})
        print("control", json.dumps(out["control"][-1]), flush=True)
    keys = out["program"][0].keys() - {"seed", "correct", "ticks"}
    summary = {k: {"program_max": max(r[k] for r in out["program"]),
                   "control_min": min(r[k] for r in out["control"])}
               for k in sorted(keys)}
    out["summary"] = summary
    dest = ROOT / args.out
    dest.mkdir(parents=True, exist_ok=True)
    (dest / f"readings_{args.workload}.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
