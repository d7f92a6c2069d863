"""A cell cut to a size a CPU test can hold, by its deployment's
``small`` (``bench/deployment.py``), with the sensor deployment's fused
tick on its jnp lane (the Pallas kernel runs only on the chip)."""
from __future__ import annotations

from bench import deployment
from bench.run import cell_spec

LANE = {"backend": "jnp", "fused": True}


def small_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench, wl, cfg, traffic = cell_spec(name)
    cfg, traffic = deployment.load(cfg).small(cfg, traffic)
    return bench, wl, cfg, traffic


def run_small(name: str, seed: int, seconds: float = 0.6,
              fault: str | None = None) -> dict:
    """One run of the cut cell on the CPU, as ``bench/run.py`` makes it
    past its look for a chip; with ``fault``, the program broken as
    ``bench/faults.py`` says.  Returns the result line with both sides'
    counters under ``info``."""
    import contextlib

    from bench.faults import planted
    from bench.run import run_cell

    bench, wl, cfg, traffic = small_cell(name)
    info: dict = {}
    with planted(fault) if fault else contextlib.nullcontext():
        res, _ = run_cell(bench, wl, cfg, traffic, seed, seconds, False,
                          lane=LANE, log=lambda *a, **k: None, info=info)
    res["info"] = info
    return res


if __name__ == "__main__":
    # python3 -m bench.small <cell> <seed> [fault ...]: one JSON line per
    # run (the fleet's tests run here, with virtual CPU devices)
    import json
    import sys

    cell, seed = sys.argv[1], int(sys.argv[2])
    for fault in sys.argv[3:] or [None]:
        print(json.dumps({"fault": fault,
                          **run_small(cell, seed, fault=fault)},
                         default=str), flush=True)
