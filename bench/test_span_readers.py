"""The readers of the program's host spans (``bench/metrics``) on
hand-made spans: exact values, and nothing where a span is absent or,
as from a program whose tracer records no CPU time or ids, incomplete."""
import pytest

from bench.run import Ctx, read_metric
from repro.obs.trace import Span

NEW = ("operands_ms.steady", "call_ms.steady", "offcpu_ms.steady",
       "step_self_ms.steady", "operands_ms.sat", "call_ms.sat",
       "offcpu_ms.sat", "step_self_ms.sat", "control_pull_ms.sat",
       "control_decide_ms.sat")


def _span(name, t0, t1, cpu0=0.0, cpu1=0.0, sid=0, parent=None):
    return Span(name, t0, t1, 1, {}, sid, parent, cpu0, cpu1)


def _tick(prefix, at, operands_s, dispatch_cpu_s, sid=0):
    """One tick's spans, ids from ``sid``: a 12 ms step, inside it a
    10 ms dispatch with ``dispatch_cpu_s`` of it on the CPU, the
    operands ``operands_s`` and then the call to 9 ms; on the fleet a
    1 ms wait for the device after the dispatch, and a control tick of
    a 2 ms pull and a 1.5 ms decision."""
    step, dispatch = sid, sid + 1
    spans = [_span(f"{prefix}.operands", at, at + operands_s,
                   sid=sid + 2, parent=dispatch),
             _span(f"{prefix}.call", at + operands_s, at + 0.009,
                   sid=sid + 3, parent=dispatch),
             _span(f"{prefix}.dispatch", at, at + 0.010, 5.0,
                   5.0 + dispatch_cpu_s, sid=dispatch, parent=step)]
    if prefix == "fleet":
        spans.append(_span("fleet.device_execute", at + 0.010, at + 0.011,
                           sid=sid + 4, parent=step))
        spans += [_span("control.pull", at + 0.012, at + 0.014,
                        sid=sid + 6, parent=sid + 5),
                  _span("control.decide", at + 0.014, at + 0.0155,
                        sid=sid + 7, parent=sid + 5),
                  _span("control.tick", at + 0.012, at + 0.016,
                        sid=sid + 5)]
    spans.append(_span(f"{prefix}.step", at - 0.001, at + 0.011, sid=step))
    return spans


def _ctx(spans):
    return Ctx(spans=spans)


@pytest.mark.parametrize("mix,prefix", [("steady", "stream"),
                                        ("sat", "fleet")])
def test_span_readers_exact(mix, prefix):
    # operands 3 and 5 ms, calls 6 and 4 ms, off the CPU 6 and 0 ms; the
    # step's own time 2 ms, or 1 ms beside the fleet's device wait
    ctx = _ctx(_tick(prefix, 1.0, 0.003, 0.004)
               + _tick(prefix, 2.0, 0.005, 0.010, sid=10))
    assert read_metric(f"operands_ms.{mix}", ctx) == pytest.approx(4.0)
    assert read_metric(f"call_ms.{mix}", ctx) == pytest.approx(5.0)
    assert read_metric(f"offcpu_ms.{mix}", ctx) == pytest.approx(3.0)
    assert read_metric(f"step_self_ms.{mix}", ctx) == pytest.approx(
        2.0 if mix == "steady" else 1.0)
    if mix == "sat":
        assert read_metric("control_pull_ms.sat", ctx) == pytest.approx(2.0)
        assert read_metric("control_decide_ms.sat", ctx) == pytest.approx(
            1.5)


def test_step_self_ms_skips_a_step_cut_by_the_window():
    # a child whose step began before the window counts for no step
    ctx = _ctx([_span("stream.dispatch", 1.0, 1.01, sid=1, parent=0)]
               + _tick("stream", 2.0, 0.003, 0.004, sid=10))
    assert read_metric("step_self_ms.steady", ctx) == pytest.approx(2.0)


@pytest.mark.parametrize("name", NEW)
def test_span_readers_none_without_spans(name):
    # spans of other names only
    assert read_metric(name, _ctx([_span("bench.tick", 0.0, 1.0)])) is None
    # a program whose tracer records (name, t0, t1, tid, args) alone,
    # and no split of the dispatch or the control tick
    old = [(sp.name, sp.t0, sp.t1, sp.tid, sp.args)
           for sp in (_tick("stream", 1.0, 0.003, 0.004)
                      + _tick("fleet", 2.0, 0.003, 0.004, sid=10))
           if sp.name.endswith((".dispatch", ".step", ".tick"))]
    assert read_metric(name, _ctx(old)) is None
