"""The windows-and-rules work count against the stage's shapes."""
import json
from pathlib import Path

import pytest

from bench import work

BENCH = Path(__file__).resolve().parent


@pytest.mark.parametrize("config", ["har_edge", "har_fleet4"])
def test_window_rules_counts_follow_the_shapes(config):
    cfg = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    w = work.window_rules(cfg)
    # 65,536 dequeued rows after 64 carried ones, windows of 128 every 64
    assert w["rows"] == 65536 + 64
    assert w["windows"] == 1024
    # read: 25 float32 words and a validity byte a row; written: 31
    # float32 words a window
    assert w["bytes"] == 65600 * (25 * 4 + 1) + 1024 * 31 * 4
    # 1024 windows x 128 rows x (23 sums + max + min + birth + count),
    # 24 divisions a window, 2 rules x (compare + select)
    assert w["ops"] == 1024 * 128 * 27 + 1024 * 24 + 1024 * 2 * 2


def test_least_time_on_v5e_is_bound_by_bytes():
    cfg = json.loads((BENCH / "configs" / "har_edge.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    least, bound = work.least_seconds(work.window_rules(cfg),
                                      peaks["TPU v5 lite"])
    assert bound == "bytes"
    assert least == pytest.approx(
        (65600 * 101 + 1024 * 124) / 819e9)
