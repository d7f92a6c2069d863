"""A cell cut to a size a CPU test can hold: 2,048-row ticks, windows
of 16 every 8 rows, a dedupe window of 64, the fleet's budgets scaled
with them, re-deliveries 0.2 to 4 ms back (some inside the dedupe
window, some past it), and the fused tick on its jnp lane (the Pallas
kernel runs only on the chip).  Every mechanism of the full cell stays on: re-sent
rows, contract rejects, NaN rows, late and out-of-order rows, binding
fog and core budgets."""
from __future__ import annotations

from bench.run import cell_spec

LANE = {"backend": "jnp", "fused": True}


def small_cell(name: str) -> tuple[dict, dict, dict, dict]:
    bench, wl, cfg, traffic = cell_spec(name)
    cfg = dict(cfg, micro_batch=2048, ring_capacity=2048, window=16,
               stride=8, dedupe_window=64)
    traffic = dict(traffic, resent_batches_per_tick=2, resent_batch_rows=8,
                   resent_delay_s=[2e-4, 4e-3],
                   out_of_contract_per_tick=8, nan_per_tick=2,
                   out_of_order_per_tick=8, late_per_tick=4, pool_batches=2,
                   rate=2048 / 0.05)
    if cfg.get("fleet"):
        fl = cfg["fleet"]
        cfg["fleet"] = dict(
            fl, core_budget=16, core_budget_max=32, fog_budget=16,
            fog_budget_max=32,
            core_policy=dict(fl["core_policy"], min=4, max=32),
            fog_policy=dict(fl["fog_policy"], min=4, max=32))
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
