"""The plain reference agrees with the program on a seeded feed at a
small size on the CPU: re-sent rows, contract rejects, NaN rows, late
rows, and (on the fleet) binding fog and core budgets."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from bench import reference
from bench.small import run_small

ROOT = Path(__file__).resolve().parents[1]


def _fleet_runs(seed: int, *faults: str) -> list[dict]:
    """Runs of the cut fleet cell in a child with four CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.small", "har_fleet4.sat", str(seed),
         *faults], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


def _exercised(ref: dict, shards: int) -> None:
    for k in ("items_deduped", "items_late", "windows_escalated",
              "core_overflow"):
        assert min(ref[k]) > 0, (k, ref[k])
    # contract rejects: out-of-contract and NaN rows, per channel
    assert min(ref["items_rejected"]) > 0
    assert np.asarray(ref["drift_counts"]).sum() > 0
    assert len(ref["items_offered"]) == shards


def test_edge_program_agrees_with_reference():
    res = run_small("har_edge.steady", seed=20260417)
    assert res["correct"], res["checks"]
    assert res["checks"]["windows_off"]["value"] == 0
    assert res["checks"]["counters_off"]["value"] == 0
    assert res["checks"]["mean_ulp"]["value"] <= 1
    _exercised(res["info"]["reference"], 1)


def test_fleet_program_agrees_with_reference():
    res, = _fleet_runs(2 ** 31 + 7)
    assert res["correct"], res["checks"]
    ref = res["info"]["reference"]
    _exercised(ref, 4)
    # both budgets bound: regions shed at the fog tier, the fleet past
    # its core budget
    assert sum(ref["fog_shed"]) > 0
    assert ref["fleet_core_overflow"] > 0


def test_hysteresis_policy_grows_and_shrinks():
    pol = reference.Hysteresis({"min": 4, "max": 32, "grow_at": 0.9,
                                "shrink_at": 0.25, "factor": 2.0,
                                "patience": 2})
    b = 8
    seq = []
    for demand in (8, 8, 40, 40, 40, 0, 0, 0, 0):
        b = pol.propose(demand, b)
        seq.append(b)
    assert seq == [8, 16, 16, 32, 32, 32, 16, 16, 8]


def test_fnv1a_matches_the_published_constants():
    # FNV-1a of one 32-bit word, folded whole: (basis ^ w) * prime mod 2^32
    w = np.array([[1.0]], np.float32)
    word = int(w.view(np.uint32)[0, 0])
    want = ((2166136261 ^ word) * 16777619) % 2 ** 32
    assert int(reference.fnv1a(w)[0]) == want
