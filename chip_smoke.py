"""Bring-up check on a TPU: the stream executor and the edge fleet, run
through their normal entry points at deployment size.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the fleet on four chips, only

One chip: a ``StreamExecutor`` at D = 16 sensor features, 65,536-row
micro-batches, sliding windows of 64 every 32 rows, a 4-batch ring,
the admission lane on (dedupe window 1,024 and a data contract) and
the two-rule tabular engine of ``benchmarks/streaming.py``.  A seeded
generator feeds 20 ticks with an alternating hot regime, re-delivered
rows and out-of-contract rows into two lanes, the staged jnp lane and
the fused Pallas kernel.  Checked: one trace per lane; the lanes'
outputs and counters bit-for-bit equal; ingest conservation; one
block through the fused kernel equal to its numpy reference; and the
compiled fused step holding the TPU kernel (``tpu_custom_call``).
Each lane and fleet also holds one compiled executable, so no tick
after the first compiled; the slowest later tick is printed.

``--chips 4``: a ``FleetExecutor`` of four shards, one per chip, at the
same per-shard shapes, against four single-device runs of the shard
streams (the oracle of ``tests/test_fleet.py``, core budget
non-binding); then the same on a 2 x 2 (region, edge) mesh.

Exits non-zero when JAX finds no TPU or any check fails.  The last
line of standard output is one JSON object: ``{"ok": true, "device":
{"platform", "kind", "count"}}``.
"""
import argparse
import json
import sys
import time
from pathlib import Path

SEED = 0               # seeds the generated ticks and the kernel block
D = 16                 # sensor features per row
ROWS = 65536           # micro-batch rows per shard
WINDOW, STRIDE = 64, 32
TICKS = 20
FLEET_TICKS = 12
REDELIVERED = 256      # rows of the previous tick sent again each tick
OUT_OF_CONTRACT = 64   # rows per tick outside the contract's bounds


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def build(fused: bool):
    """(engine, pipeline factory, StreamConfig) of the smoke workload."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig
    from repro.stream.ingest import AdmissionPlan, DataContract

    engine = rules.RuleEngine([
        rules.threshold_rule("hot_mean", 0, ">=", 0.25, rules.C_SEND_CORE,
                             priority=1),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=2)])
    core_p = jnp.asarray(
        np.random.default_rng(0).standard_normal((5 + D, 5 + D)) * 0.1,
        jnp.float32)

    def core_fn(p, batch):
        h = batch
        for _ in range(8):
            h = jnp.tanh(h @ p)
        return h, batch[:, :5]

    def make_pipeline(core_capacity=None):
        return pipe.two_tier_pipeline(lambda p, b: (b, b[:, :5]), core_fn,
                                      engine, core_params=core_p,
                                      core_capacity=core_capacity)

    lane = dict(backend="pallas", fused=True) if fused \
        else dict(backend="jnp", fused=False)
    cfg = StreamConfig(
        micro_batch=ROWS, window=WINDOW, stride=STRIDE, capacity=4 * ROWS,
        lateness=64.0, interpret=False,
        admission=AdmissionPlan(
            dedupe_window=1024,
            contract=DataContract(lo=(-8.0,) * D, hi=(8.0,) * D)),
        **lane)
    return engine, make_pipeline, cfg


def feed(seed: int, ticks: int, shards: int):
    """Seeded ticks of ``[shards, ROWS, D]`` items and ``[shards, ROWS]``
    event times.  Shards run hot in turn; each tick ends by re-sending
    the last new rows of the tick before (the dedupe window still holds
    them) and carries rows outside the contract."""
    import numpy as np

    rng = np.random.default_rng(seed)
    r = REDELIVERED
    prev = None
    for i in range(ticks):
        items = rng.standard_normal((shards, ROWS, D)).astype(np.float32)
        for s in range(shards):
            if ((i + s) // 4) % 2:
                items[s, :, 0] += 0.5          # alternating hot regime
        ts = np.broadcast_to(
            np.arange(i * ROWS, (i + 1) * ROWS, dtype=np.float32),
            (shards, ROWS)).copy()
        bad = rng.choice(ROWS - 2 * r, OUT_OF_CONTRACT, replace=False)
        items[:, bad, rng.integers(0, D)] = 20.0
        items[:, bad[:4], 1] = np.nan
        if prev is not None:
            items[:, -r:] = prev[0][:, -2 * r:-r]
            ts[:, -r:] = prev[1][:, -2 * r:-r]
        prev = (items, ts)
        yield items, ts


def outputs_equal(a, b, what: str, rtol: float = 0.0) -> None:
    """StepOutput leaves equal: exact, or ``outputs`` within ``rtol``."""
    import numpy as np

    for name in a._fields:
        x, y = np.asarray(a._asdict()[name]), np.asarray(b._asdict()[name])
        if name == "outputs" and rtol:
            np.testing.assert_allclose(x, y, rtol=rtol, atol=rtol,
                                       err_msg=f"{what}: {name}")
        else:
            np.testing.assert_array_equal(x, y, err_msg=f"{what}: {name}")


def one_chip() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.fused_tick import fused_tick, fused_tick_ref
    from repro.stream import StreamExecutor

    lanes = {}
    for name, fused in (("staged_jnp", False), ("fused_pallas", True)):
        engine, make_pipeline, cfg = build(fused)
        ex = StreamExecutor(cfg, engine,
                            make_pipeline(core_capacity=ROWS // STRIDE // 4))
        lanes[name] = [ex, ex.init_state(D), [], []]
    for i, (items, ts) in enumerate(feed(SEED, TICKS, 1)):
        items, ts = jnp.asarray(items[0]), jnp.asarray(ts[0])
        outs = []
        for name, lane in lanes.items():
            ex, state = lane[0], lane[1]
            t = time.perf_counter()
            state, out = ex.step(state, items, ts)
            jax.block_until_ready(out)
            (lane[2] if i == 0 else lane[3]).append(time.perf_counter() - t)
            lane[1] = state
            outs.append(out)
        outputs_equal(outs[0], outs[1], f"tick {i}: staged vs fused")
        check(bool(np.isfinite(np.asarray(outs[1].aggregates)).all()),
              f"tick {i}: non-finite aggregates")

    metrics = {}
    for name, (ex, state, first, rest) in lanes.items():
        check(ex.trace_count == 1, f"{name}: {ex.trace_count} traces")
        check(ex._jstep._cache_size() == 1,
              f"{name}: {ex._jstep._cache_size()} compiled executables")
        m = state.metrics.as_dict()
        metrics[name] = m
        check(m["items_offered"] == m["items_accepted"] + m["items_rejected"]
              + m["items_deduped"], f"{name}: conservation broken: {m}")
        check(m["items_deduped"] > 0 and m["items_rejected"] > 0,
              f"{name}: admission lane idle: {m}")
        print(f"{name}: first tick (compile) {first[0]:.2f} s, "
              f"{1e3 * float(np.median(rest)):.3f} ms/tick after "
              f"(slowest {1e3 * max(rest):.3f} ms), "
              f"traces {ex.trace_count}, escalated "
              f"{m['windows_escalated']}/{m['windows_emitted']}, deduped "
              f"{m['items_deduped']}, rejected {m['items_rejected']}")
    check(metrics["staged_jnp"] == metrics["fused_pallas"],
          "staged and fused counters differ")

    ex, state = lanes["fused_pallas"][:2]
    hlo = ex.lower(state, jnp.zeros((ROWS, D), jnp.float32),
                   jnp.zeros((ROWS,), jnp.float32)).compile().as_text()
    check("tpu_custom_call" in hlo, "fused step holds no TPU kernel")

    # one block through the kernel against the numpy reference
    rng = np.random.default_rng(SEED + 1)
    t = ROWS + WINDOW - STRIDE
    seq = np.concatenate(
        [np.arange(t, dtype=np.float32)[:, None],
         rng.random((t, 1), dtype=np.float32),
         rng.standard_normal((t, D)).astype(np.float32)], axis=1)
    seq[:, 2] += np.where((np.arange(t) // 512) % 2, 0.5, 0.0)
    valid = rng.random(t) > 0.02
    table = ex.engine.table()
    agg, wcount, feats, w_birth, cons = (np.asarray(a) for a in fused_tick(
        jnp.asarray(seq), jnp.asarray(valid), WINDOW, STRIDE, table=table,
        backend="pallas"))
    r_agg, r_wcount, r_feats, r_birth, r_cons = fused_tick_ref(
        seq, valid, WINDOW, STRIDE, table)
    # counts, sums, extrema, birth stamps and rule codes: bit for bit.
    # The two means (agg, feats[:, 0]) divide on the TPU, whose f32
    # division is not correctly rounded: within one ulp of numpy's
    for what, g, w in (("wcount", wcount, r_wcount),
                       ("feats[:, 1:]", feats[:, 1:], r_feats[:, 1:]),
                       ("w_birth", w_birth, r_birth), ("cons", cons, r_cons)):
        np.testing.assert_array_equal(g, w, err_msg=f"fused_tick {what}")
    np.testing.assert_array_max_ulp(agg, r_agg, maxulp=1)
    np.testing.assert_array_max_ulp(feats[:, 0], r_feats[:, 0], maxulp=1)
    print(f"fused_tick: [{t}, {2 + D}] block vs fused_tick_ref: counts, "
          f"sums, extrema, birth stamps and rule codes equal; means within "
          f"1 ulp ({int((agg == r_agg).sum())}/{agg.size} equal)")


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.stream import StreamExecutor
    from repro.stream.fleet import FleetConfig, FleetExecutor

    check(jax.device_count() >= 4,
          f"--chips 4 needs 4 devices, JAX found {jax.device_count()}")
    # the core stage's matmul at full f32, as the tests run it: the
    # fleet and the oracle batch core records differently
    jax.config.update("jax_default_matmul_precision", "highest")
    engine, make_pipeline, cfg = build(fused=True)
    ticks = list(feed(SEED, FLEET_TICKS, 4))

    oracle = StreamExecutor(cfg, engine, make_pipeline())
    ostates = [oracle.init_state(D) for _ in range(4)]
    want = []
    for items, ts in ticks:
        outs = []
        for e in range(4):
            ostates[e], out = oracle.step(ostates[e], jnp.asarray(items[e]),
                                          jnp.asarray(ts[e]))
            outs.append(jax.device_get(out))
        want.append(outs)
    check(oracle.trace_count == 1, f"oracle: {oracle.trace_count} traces")
    want_m = [s.metrics.as_dict() for s in ostates]

    for regions in (1, 2):
        fx = FleetExecutor(
            FleetConfig(stream=cfg, num_shards=4, num_core=1,
                        core_budget=4 * cfg.windows_per_step,
                        num_regions=regions),
            engine, make_pipeline())
        state = fx.init_state(D)
        lat = []
        for i, (items, ts) in enumerate(ticks):
            t = time.perf_counter()
            state, out = fx.step(state, jnp.asarray(items), jnp.asarray(ts))
            lat.append(time.perf_counter() - t)
            out = jax.device_get(out)
            for e in range(4):
                shard = type(out)(*(leaf[e] for leaf in out))
                outputs_equal(shard, want[i][e],
                              f"R={regions} tick {i} shard {e}", rtol=1e-6)
        check(fx.trace_count == 1, f"R={regions}: {fx.trace_count} traces")
        check(fx._jstep._cache_size() == 1,
              f"R={regions}: {fx._jstep._cache_size()} compiled executables")
        m = state.metrics.as_dict()
        for e in range(4):
            for k in ("items_offered", "items_accepted", "items_rejected",
                      "items_deduped", "windows_emitted", "rules_fired",
                      "windows_escalated"):
                check(m["shard"][k][e] == want_m[e][k],
                      f"R={regions} shard {e} {k}: {m['shard'][k][e]} != "
                      f"{want_m[e][k]}")
        check(m["fleet_core_overflow"] == 0, f"R={regions}: core overflow")
        print(f"fleet R={regions} x E={4 // regions}: first tick (compile) "
              f"{lat[0]:.2f} s, {1e3 * float(np.median(lat[1:])):.3f} "
              f"ms/tick after (slowest {1e3 * max(lat[1:]):.3f} ms), "
              f"traces {fx.trace_count}, escalated "
              f"{m['fleet']['windows_escalated']}, equals 4 single-device "
              f"runs")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no repro package under {src}; run it "
                 f"from a checkout of the repository")
    sys.path.insert(0, str(src))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py: needs a TPU, JAX found {dev.platform}")
    from repro.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")

    if args.chips == 4:
        four_chips()
    else:
        one_chip()
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
