"""The traffic generator: a tick is a pure function of the seed and the
tick, and re-deliveries repeat rows sent a retry delay earlier."""
import numpy as np
import pytest

from bench.generator import Generator
from bench.small import small_cell

CELLS = ["har_edge.steady", "har_fleet4.sat"]


def _stream(cell: str, seed: int, ticks: int):
    _, _, cfg, traffic = small_cell(cell)
    gen = Generator(cfg, traffic, seed)
    return gen, [gen.batch(t) for t in range(ticks)]


@pytest.mark.parametrize("cell", CELLS)
def test_tick_is_a_pure_function_of_seed_and_tick(cell):
    _, out = _stream(cell, 2 ** 33 + 9, 12)
    _, _, cfg, traffic = small_cell(cell)
    again = Generator(cfg, traffic, 2 ** 33 + 9).batch(11)
    np.testing.assert_array_equal(again[0], out[11][0])
    np.testing.assert_array_equal(again[1], out[11][1])


@pytest.mark.parametrize("cell", CELLS)
def test_resends_repeat_rows_sent_a_retry_delay_earlier(cell):
    gen, out = _stream(cell, 17, 24)
    lo, hi = gen.back_rows
    b = gen.rows
    for s in range(len(gen.shard_ids)):
        first: dict[bytes, int] = {}
        dist = []
        for t, (items, ts) in enumerate(out):
            wire = np.concatenate([ts[s][:, None], items[s]], 1)
            for i, row in enumerate(map(bytes, wire.view(np.uint32))):
                if row in first:
                    dist.append(t * b + i - first[row])
                first[row] = t * b + i
        runs = gen.traffic["resent_batches_per_tick"]
        run = gen.traffic["resent_batch_rows"]
        # every tick whose retries reach back past the start re-sends
        assert len(dist) >= (len(out) - hi // b - 1) * runs * run
        assert lo <= min(dist) and max(dist) <= hi


def test_other_kinds_avoid_resent_rows():
    gen, _ = _stream("har_edge.steady", 5, 1)
    for t in range(20):
        p = gen.plan(t, 0)
        kinds = [p.resent, p.bad, p.ooo, p.late]
        every = np.concatenate(kinds)
        assert np.unique(every).size == every.size
        assert p.resent.size == (gen.traffic["resent_batches_per_tick"]
                                 * gen.traffic["resent_batch_rows"])
        assert set(p.nan) <= set(p.bad)
