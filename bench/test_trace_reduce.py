"""The trace reduction on small hand-made traces and, where present, on
a small trace recorded on a v5e (``bench/testdata``)."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as TR

DATA = Path(__file__).resolve().parent / "testdata"


def _op(name, a, b, scope="(none)"):
    return TR.Op(name, scope, float(a), float(b))


def test_scopes_come_from_the_op_names_of_the_compiled_module():
    hlo = "\n".join([
        "ENTRY %main {",
        '  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
        'metadata={op_name="jit(_step)/obs:ingest/obs:admission/eq" '
        'source_file="x.py"}',
        '  ROOT %custom-call.1 = f32[8]{0} custom-call(), '
        'metadata={op_name="jit(_step)/obs:fused_tick/pallas_call"}',
        '  %copy.2 = f32[8]{0} copy(f32[8]{0} %p)',
        "}"])
    assert TR.scopes_of([hlo]) == {"fusion.3": "obs:admission",
                                   "custom-call.1": "obs:fused_tick"}


def test_self_time_leaves_out_nested_ops():
    ops = [_op("while.1", 0, 100), _op("a", 10, 30), _op("b", 40, 50),
           _op("c", 41, 45), _op("d", 120, 130)]
    TR.nest(ops)
    assert [o.self_ns for o in ops] == [70, 20, 6, 4, 10]


def test_busy_scope_and_gaps():
    ops = [_op("x", 0, 10, "obs:admission"), _op("y", 10, 20, "obs:rules"),
           _op("z", 30, 40, "obs:admission"), _op("w", 90, 120)]
    TR.nest(ops)
    assert TR.busy_intervals(ops, 0, 100) == [(0, 20), (30, 40), (90, 100)]
    assert TR.busy_ns(ops, 0, 100) == 40
    # ops wholly inside the window count with their self time
    assert TR.scope_ns(ops, 0, 100) == {"obs:admission": 20.0,
                                        "obs:rules": 10.0}
    host = [("bench.tick", 0, 100), ("bench.d2h", 18, 35)]
    assert TR.idle_gaps(ops, host, 0, 100) == [("bench.d2h", 10),
                                               ("bench.tick", 50)]
    assert TR.label_at(host, 150) == "(no span)"


def test_recorded_v5e_trace():
    # two ticks of har_edge.steady on one v5e, recorded by bench/run.py
    # --trace 1, with the compiled step's module text
    with gzip.open(DATA / "edge_ticks.hlo.txt.gz", "rt") as f:
        hlo = f.read()
    tr = TR.load(DATA / "edge_ticks.xplane.pb.gz",
                 {"bench.tick", "bench.h2d", "bench.d2h"}.__contains__,
                 TR.scopes_of([hlo]))
    assert len(tr.devices) == 1
    ticks = sorted((a, b) for n, a, b in tr.host if n == "bench.tick")
    assert len(ticks) == 2
    lo, hi = ticks[0][0], ticks[-1][1]
    ops = tr.devices[0].ops
    busy = TR.busy_ns(ops, lo, hi)
    assert 0 < busy < hi - lo
    scopes = TR.scope_ns(ops, lo, hi)
    # every named stage of the one-chip tick is found, and the self
    # times add up to the busy time
    for s in ("obs:admission", "obs:fused_tick", "obs:pipeline"):
        assert scopes.get(s, 0) > 0, scopes
    assert sum(scopes.values()) == pytest.approx(busy, rel=0.02)
