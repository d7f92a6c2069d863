"""Continuous stream analytics: sustained items/sec and step latency.

The paper's headline workload (and EdgeBench's): windowed aggregation
over a sustained sensor stream with rule-gated escalation.  Drives the
``StreamExecutor`` end to end — ring buffer -> sliding windows -> rule
engine -> capacity-bounded core escalation — and reports sustained
throughput, median and p99 per-step latency, and the jit trace count
(must be exactly 1 after warmup: the whole loop is one XLA executable).

The Pallas lanes compile the real TPU kernels.  Elsewhere they run only
in Pallas interpret mode, and only when the caller asks for it with
``REPRO_PALLAS_INTERPRET=1``; without it a host with no TPU skips them.
"""
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from repro.core import pipeline as pipe
from repro.core import rules
from repro.obs import costmodel as CM
from repro.stream import StreamConfig, StreamExecutor

D = 16            # sensor feature width
BATCH = 256       # items per micro-batch
STEPS = 200
WARMUP = 5
INTERPRET = os.environ.get("REPRO_PALLAS_INTERPRET") == "1"


def _edge_fn(p, batch):
    # batch [NW, 5 + D]: light smoothing + pass features through
    return batch, batch[:, :5]


def _core_fn(p, batch):
    # heavier core model stand-in: a few dense mixes over the record
    h = batch
    for _ in range(8):
        h = jnp.tanh(h @ p)
    return h, batch[:, :5]


def _executor(backend: str, fused: bool = False,
              overlap: bool = False) -> tuple[StreamExecutor, object]:
    cfg = StreamConfig(micro_batch=BATCH, window=64, stride=32,
                       capacity=4 * BATCH, lateness=64.0, backend=backend,
                       interpret=backend == "pallas" and INTERPRET,
                       fused=fused,
                       overlap_ingest=overlap)
    engine = rules.RuleEngine([
        rules.threshold_rule("hot_mean", 0, ">=", 0.25, rules.C_SEND_CORE,
                             priority=1),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=2),
    ])
    core_p = jnp.asarray(
        np.random.default_rng(0).standard_normal((5 + D, 5 + D)) * 0.1,
        jnp.float32)
    p = pipe.two_tier_pipeline(_edge_fn, _core_fn, engine, core_params=core_p,
                               core_capacity=BATCH // 32 // 4)
    ex = StreamExecutor(cfg, engine, p)
    return ex, ex.init_state(D)


def _drive(ex, state, steps):
    rng = np.random.default_rng(7)
    lat, t0 = [], 0.0
    for i in range(steps):
        base = rng.standard_normal((BATCH, D)).astype(np.float32)
        if (i // 20) % 2:
            base[:, 0] += 0.5              # alternating hot regime
        items = jnp.asarray(base)
        ts = jnp.asarray(t0 + np.arange(BATCH), jnp.float32)
        t0 += BATCH
        t = time.perf_counter()
        state, out = ex.step(state, items, ts)
        jax.block_until_ready(out)
        lat.append(time.perf_counter() - t)
    return state, np.asarray(lat)


def bench():
    backends = ["jnp"]
    if INTERPRET or jax.default_backend() == "tpu":
        backends.append("pallas")
    else:
        print(f"# streaming: skipping the pallas lanes: no TPU "
              f"({jax.default_backend()}) and REPRO_PALLAS_INTERPRET "
              f"is not 1", file=sys.stderr)
    for backend in backends:
      for fused in (False, True):
        ex, state = _executor(backend, fused=fused)
        state, _ = _drive(ex, state, WARMUP)
        state, lat = _drive(ex, state, STEPS)
        m = state.metrics.as_dict()        # one host pull for all counters
        items_s = BATCH / np.median(lat)
        p99 = float(np.percentile(lat, 99) * 1e6)
        assert ex.trace_count == 1, f"retraced: {ex.trace_count}"
        tag = f"{backend}_fused" if fused else backend
        row(f"streaming/{tag}_step", float(np.median(lat) * 1e6),
            f"items_per_s={items_s:.0f};fused={int(fused)}")
        row(f"streaming/{tag}_p99", p99,
            f"esc={m['windows_escalated']}/{m['windows_emitted']}"
            f";traces={ex.trace_count};fused={int(fused)}")
        if fused:
            # the fused lane re-reports only throughput/latency + the
            # one-tick cost (named-scope sub-attribution rides the
            # obs:fused_tick scope) — the staged lane below keeps the
            # full hist/lineage rows, and parity tests pin that the
            # two lanes' counters are bitwise identical anyway
            rng = np.random.default_rng(7)
            cost = ex.step_cost(state,
                                rng.standard_normal((BATCH, D)).astype(
                                    np.float32),
                                np.arange(BATCH, dtype=np.float32))
            rl = CM.roofline(cost["flops"], cost["bytes_accessed"],
                             float(np.median(lat)))
            row(f"streaming/{tag}_cost", float(np.median(lat) * 1e6),
                f"flops={cost['flops']:.0f}"
                f";bytes={cost['bytes_accessed']:.0f}"
                f";gflops={rl['gflops']:.4f};gbs={rl['gbs']:.4f}"
                f";ai={rl['ai']:.4f};flops_util={rl['flops_util']:.6f}"
                f";bw_util={rl['bw_util']:.6f};fused=1")
            continue
        # the in-step device histogram's view of the same run (warmup/
        # compile ticks are EXCLUDED — warmup_excluded counts them — so
        # its tail tracks steady-state, not the one compile)
        h = ex.latency_percentiles()
        row(f"streaming/{backend}_hist", h["p50_us"],
            f"hist_p95_us={h['p95_us']:.1f}"
            f";hist_p99_us={h['p99_us']:.1f};hist_count={h['count']}"
            f";warmup_excluded={h['warmup_excluded']}")
        # event-time lineage: per-stage percentiles of the same run
        # (tick-quantized; single device, so hops stay empty)
        lin = ex.lineage_percentiles()
        for stage in ("queueing", "window", "e2e"):
            s = lin[stage]
            row(f"streaming/{backend}_lat_{stage}", s["p50_us"],
                f"p95_us={s['p95_us']:.1f};p99_us={s['p99_us']:.1f}"
                f";count={s['count']}")
        # device cost + roofline coordinates of ONE tick at the bench
        # shapes (XLA's own post-fusion cost model; utilization columns
        # read $REPRO_PEAK_FLOPS/$REPRO_PEAK_BW, 0.0 = peak undeclared)
        rng = np.random.default_rng(7)
        cost = ex.step_cost(state,
                            rng.standard_normal((BATCH, D)).astype(
                                np.float32),
                            np.arange(BATCH, dtype=np.float32))
        rl = CM.roofline(cost["flops"], cost["bytes_accessed"],
                         float(np.median(lat)))
        row(f"streaming/{backend}_cost", float(np.median(lat) * 1e6),
            f"flops={cost['flops']:.0f}"
            f";bytes={cost['bytes_accessed']:.0f}"
            f";gflops={rl['gflops']:.4f};gbs={rl['gbs']:.4f}"
            f";ai={rl['ai']:.4f};flops_util={rl['flops_util']:.6f}"
            f";bw_util={rl['bw_util']:.6f};fused=0")
    _bench_overlap()


def _batches(steps: int) -> list:
    """The _drive feed as a materialized producer list for run()."""
    rng = np.random.default_rng(7)
    out, t0 = [], 0.0
    for i in range(steps):
        base = rng.standard_normal((BATCH, D)).astype(np.float32)
        if (i // 20) % 2:
            base[:, 0] += 0.5
        out.append((jnp.asarray(base),
                    jnp.asarray(t0 + np.arange(BATCH), jnp.float32)))
        t0 += BATCH
    return out


def _bench_overlap():
    """Host/device ingest overlap on the fused jnp lane: wall time of
    ``StreamExecutor.run`` draining the same producer with the
    ``IngestStager`` on vs the direct loop.  Overlap changes delivery
    timing only — outputs stay bitwise (pinned in tests), so the only
    interesting column is the clock."""
    steps = 100
    batches = _batches(WARMUP + steps)

    def timed_run(overlap: bool):
        ex, state = _executor("jnp", fused=True, overlap=overlap)
        state, outs = ex.run(state, batches[:WARMUP])   # compile tick
        jax.block_until_ready(outs[-1])
        t = time.perf_counter()
        state, outs = ex.run(state, batches[WARMUP:])
        jax.block_until_ready(outs[-1])
        wall = time.perf_counter() - t
        assert ex.trace_count == 1, f"retraced: {ex.trace_count}"
        return wall, len(outs), state

    direct_s, n_direct, _ = timed_run(False)
    overlap_s, n_overlap, _ = timed_run(True)
    # the stager holds one batch back during the run and flushes it at
    # the end, so both lanes deliver every batch
    assert n_direct == n_overlap == steps, (n_direct, n_overlap)
    row("streaming/overlap_run", overlap_s / steps * 1e6,
        f"items_per_s={steps * BATCH / overlap_s:.0f}"
        f";direct_us={direct_s / steps * 1e6:.1f}"
        f";fused=1;overlap=1")


if __name__ == "__main__":
    bench()
