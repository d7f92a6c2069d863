"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (assignment format).

  PYTHONPATH=src python -m benchmarks.run                  # all suites
  PYTHONPATH=src python -m benchmarks.run messaging        # one suite
  PYTHONPATH=src python -m benchmarks.run fleet --json     # + BENCH file
  PYTHONPATH=src python -m benchmarks.run fleet --compare  # perf gate

``--json`` additionally writes one ``BENCH_<suite>.json`` artifact per
suite (stable schema, see ``repro.obs.export``) — the committed
baselines the perf trajectory is measured against.  ``--compare``
diffs the fresh rows against the committed baseline with per-metric
noise tolerances (see ``benchmarks.compare``): a readable delta table,
exit 1 on regression.  The flags compose — ``--json --compare`` gates
first, then rewrites the artifact.  Unknown suite names exit 2 with a
usage message.
"""
import sys

from benchmarks import (common, fleet, ingest, messaging, pipeline_e2e,
                        roofline_report, routing, scaling, store_query,
                        streaming, tiering)
from repro.compile_cache import use_compile_cache

SUITES = {
    "tiering": tiering.bench,          # paper Table I
    "messaging": messaging.bench,      # paper Fig. 4 / Fig. 8
    "store_query": store_query.bench,  # paper Figs. 5-7
    "routing": routing.bench,          # paper Figs. 9-10
    "scaling": scaling.bench,          # paper Figs. 11-12
    "pipeline_e2e": pipeline_e2e.bench,  # paper Fig. 14
    "streaming": streaming.bench,      # continuous stream analytics
    "ingest": ingest.bench,            # admission lane: dedupe/backfill
    "fleet": fleet.bench,              # sharded edge fleet, E in {1,4,8}
    "fleet_faults":                    # degraded fleet under control plane
        lambda: fleet.bench(faults=True),
    "fleet_churn":                     # leave -> backup replay -> join,
        lambda: fleet.bench(churn=True),   # then a true re-mesh
    "fleet_regions":                   # (R, E) hierarchy, R in {1,2,4}
        lambda: fleet.bench(regions=True),
    "roofline":                        # roofline columns of committed
        roofline_report.bench,         # BENCH artifacts (streaming path)
}


def usage() -> str:
    return ("usage: python -m benchmarks.run [suite ...] "
            "[--json] [--compare]\n"
            "known suites: " + " ".join(sorted(SUITES)))


def main(argv: list | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    flags = {a for a in argv if a.startswith("--")}
    unknown_flags = flags - {"--json", "--compare"}
    names = [a for a in argv if not a.startswith("--")]
    unknown = [n for n in names if n not in SUITES]
    if unknown or unknown_flags:
        bad = ", ".join(unknown + sorted(unknown_flags))
        print(f"unknown suite(s)/flag(s): {bad}\n{usage()}",
              file=sys.stderr)
        raise SystemExit(2)
    which = names or list(SUITES)
    use_compile_cache()
    failed = []
    print("name,us_per_call,derived")
    for name in which:
        common.reset_rows()
        SUITES[name]()
        rows = common.get_rows()
        from repro.obs import export as OX
        if "--compare" in flags:
            from benchmarks import compare as CMP
            fresh = OX.bench_payload(name, rows)["rows"]
            if not CMP.compare_suite(name, fresh):
                failed.append(name)
        if "--json" in flags:
            if rows:
                path = OX.write_bench(OX.bench_payload(name, rows))
                print(f"# wrote {path}", file=sys.stderr)
            else:
                print(f"# suite {name} emitted no rows; not writing a "
                      f"BENCH artifact", file=sys.stderr)
    if failed:
        print(f"perf regression in: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
