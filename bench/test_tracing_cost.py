"""``bench/tracing_cost.py`` on a cut cell on the CPU: both sides tick,
the tracer records spans only while it is installed, and the script
refuses to measure off the chip."""
from bench.small import LANE, small_cell
from bench.tracing_cost import alternate, main


def test_alternate_on_and_off():
    _, _, cfg, traffic = small_cell("har_edge.steady")
    got = alternate(cfg, traffic, seed=3130000017, blocks=2, seconds=0.3,
                    lane=LANE)
    for side in ("on", "off"):
        ticks, steps = got[side]
        assert ticks and len(ticks) == len(steps)
        assert all(s > 0 for s in steps)
    # stream.step, stream.dispatch, stream.operands and stream.call for
    # the two warm-up ticks and every tick with the tracer on
    assert got["spans"] == 4 * (2 + len(got["on"][0]))


def test_main_refuses_the_cpu(capsys):
    assert main(["--workload", "har_edge.steady"]) == 3
    assert "needs 1 TPU chips" in capsys.readouterr().err
