"""The comparison fails what it must: the control (the reference in the
nearest precision below the configuration's) and each fault planted
under the timed path come out not correct."""
import pytest

from bench.readings import control_numbers
from bench.small import run_small, small_cell
from bench.test_reference import _fleet_runs


@pytest.mark.parametrize("cell", ["har_edge.steady", "har_fleet4.sat"])
def test_control_fails(cell):
    _, _, cfg, traffic = small_cell(cell)
    c = control_numbers(cfg, traffic, seed=31, ticks=8, full_every=2)
    assert not c["correct"], c


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_edge_fault_is_not_correct(fault):
    res = run_small("har_edge.steady", seed=41, fault=fault)
    assert not res["correct"], (fault, res["checks"])


@pytest.fixture(scope="module")
def fleet_faults():
    runs = _fleet_runs(43, "state_unchanged", "half_batch",
                       "exchange_left_out", "answer_altered")
    return {r["fault"]: r for r in runs}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "exchange_left_out", "answer_altered"])
def test_fleet_fault_is_not_correct(fleet_faults, fault):
    assert not fleet_faults[fault]["correct"], fleet_faults[fault]["checks"]
