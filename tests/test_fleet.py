"""Fleet runtime tests: run in a subprocess with 8 forced host devices
(XLA device count locks at first jax init, so these cannot run in the
main pytest process — same pattern as ``test_multidevice.py``).

The correctness oracle (ISSUE 3): with 8 forced host devices, a
``FleetExecutor`` over E shards produces, per shard, the same window
aggregates/consequences as E independent single-device
``StreamExecutor`` runs on the per-shard streams — escalation results
equal whenever total escalations fit the fleet core budget — with
``trace_count == 1`` after warmup.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np
    import jax, jax.numpy as jnp
    jax.config.update("jax_threefry_partitionable", True)
    jax.config.update("jax_default_matmul_precision", "highest")

    from repro.core import pipeline as pipe
    from repro.core import rules
    from repro.stream import StreamConfig, StreamExecutor
    from repro.stream.fleet import FleetConfig, FleetExecutor

    def assert_placed(fx, state, items, ts):
        # the carried operands (state, latency histogram, lineage banks)
        # already hold the shardings the compiled step hands back, so
        # tick 0 keys the same trace as every later tick
        (st_sh, _), hist_sh, lin_sh = fx.lower(
            state, items, ts).compile().output_shardings
        for leaf, sh in zip(jax.tree.leaves(state), jax.tree.leaves(st_sh)):
            assert leaf.sharding == sh, (leaf.sharding, sh)
        assert fx._lat_hist.sharding == hist_sh, fx._lat_hist.sharding
        assert fx._lineage.sharding == lin_sh, fx._lineage.sharding

    D, BATCH = 3, 32
    edge_fn = lambda p, b: (b * 1.5, b[:, :5])
    core_fn = lambda p, b: (b + 100.0, b[:, :5])

    def two_tier(engine, core_capacity=None):
        return pipe.two_tier_pipeline(edge_fn, core_fn, engine,
                                      core_capacity=core_capacity)

    scfg = StreamConfig(micro_batch=BATCH, window=16, stride=8,
                        capacity=128, lateness=8.0)

    # --- 1. fleet == E independent single-device runs (oracle) --------
    E = 8
    engine = rules.RuleEngine([
        rules.threshold_rule("hot", 0, ">=", 1.0, rules.C_SEND_CORE,
                             priority=2),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=1)])
    fx = FleetExecutor(FleetConfig(stream=scfg, num_shards=E, num_core=2,
                                   core_budget=256), engine,
                       two_tier(engine))
    fstate = fx.init_state(D)
    assert_placed(fx, fstate, jnp.zeros((E, BATCH, D)), jnp.zeros((E, BATCH)))
    oracle = [StreamExecutor(scfg, engine, two_tier(engine))
              for _ in range(E)]
    ostates = [ox.init_state(D) for ox in oracle]

    rng = np.random.default_rng(0)
    t0 = 0.0
    for step in range(8):
        items = rng.standard_normal((E, BATCH, D)).astype(np.float32)
        if step >= 4:
            items[:, :, 0] += 1.5        # hot regime: escalations flow
        ts = np.tile(t0 + np.arange(BATCH, dtype=np.float32), (E, 1))
        t0 += BATCH
        fstate, fout = fx.step(fstate, jnp.asarray(items), jnp.asarray(ts))
        for e in range(E):
            ostates[e], oo = oracle[e].step(
                ostates[e], jnp.asarray(items[e]), jnp.asarray(ts[e]))
            np.testing.assert_array_equal(
                np.asarray(fout.aggregates[e]), np.asarray(oo.aggregates))
            np.testing.assert_array_equal(
                np.asarray(fout.consequence[e]), np.asarray(oo.consequence))
            np.testing.assert_array_equal(
                np.asarray(fout.escalated[e]), np.asarray(oo.escalated))
            np.testing.assert_allclose(
                np.asarray(fout.outputs[e]), np.asarray(oo.outputs),
                rtol=1e-6, atol=1e-6)
    assert fx.trace_count == 1, fx.trace_count
    # ... and one compiled executable: no tick compiled without a trace
    assert fx._jstep._cache_size() == 1, fx._jstep._cache_size()
    md = fstate.metrics.as_dict()
    for e in range(E):
        om = ostates[e].metrics.as_dict()
        for k in ("steps", "items_offered", "items_accepted", "items_late",
                  "windows_emitted", "rules_fired", "windows_escalated",
                  "windows_stored", "windows_dropped"):
            assert md["shard"][k][e] == om[k], (k, e)
    assert md["fleet"]["windows_escalated"] == sum(
        md["shard"]["windows_escalated"])
    assert md["fleet_core_overflow"] == 0
    assert sum(md["core_processed"]) == md["fleet"]["windows_escalated"]
    # core work really lands on the core sub-mesh (ranks 0..num_core-1)
    assert all(c == 0 for c in md["core_received"][2:])
    print("ORACLE_OK", md["fleet"]["windows_escalated"])

    # --- 2. fleet budget: first-B global slots win, rest keep edge ----
    engine2 = rules.RuleEngine([
        rules.threshold_rule("always", 0, ">=", -1e9, rules.C_SEND_CORE)])
    E2, BUDGET = 4, 5
    fx2 = FleetExecutor(FleetConfig(stream=scfg, num_shards=E2, num_core=2,
                                    core_budget=BUDGET), engine2,
                        two_tier(engine2))
    st2 = fx2.init_state(D)
    t0 = 0.0
    for step in range(3):
        items = rng.standard_normal((E2, BATCH, D)).astype(np.float32)
        ts = np.tile(t0 + np.arange(BATCH, dtype=np.float32), (E2, 1))
        t0 += BATCH
        st2, out2 = fx2.step(st2, jnp.asarray(items), jnp.asarray(ts))
    md2 = st2.metrics.as_dict()
    nw = scfg.windows_per_step
    per_step = E2 * nw                    # every window escalates
    assert md2["fleet"]["windows_escalated"] == 3 * per_step
    assert md2["fleet_core_overflow"] == 3 * (per_step - BUDGET)
    assert sum(md2["core_processed"]) == 3 * BUDGET
    # deterministic shard-major budget: shard 0 never overflows
    assert md2["shard"]["core_overflow"][0] == 0
    outs = np.asarray(out2.outputs)       # [E, NW, 5 + D]
    cored = (outs[..., 5:] > 50).all(-1)
    assert cored.sum() == BUDGET
    assert cored[0].sum() == nw and cored[1].sum() == BUDGET - nw
    # overflow windows keep their edge-stage results (scaled record,
    # not zeros): edge_fn is *1.5 on the record
    rec = np.concatenate([np.asarray(out2.features),
                          np.asarray(out2.aggregates)], axis=-1)
    np.testing.assert_allclose(outs[~cored], 1.5 * rec[~cored],
                               rtol=1e-5, atol=1e-6)
    print("BUDGET_OK")

    # --- 3. watermark is the fleet min: laggards hold back closing ----
    engine3 = rules.RuleEngine([
        rules.threshold_rule("never", 0, ">=", 1e9, rules.C_SEND_CORE)])
    scfg3 = StreamConfig(micro_batch=BATCH, window=16, stride=8,
                         capacity=256, lateness=4.0)
    fx3 = FleetExecutor(FleetConfig(stream=scfg3, num_shards=2, num_core=1,
                                    core_budget=4), engine3,
                        two_tier(engine3))
    st3 = fx3.init_state(D)
    solo = StreamExecutor(scfg3, engine3, two_tier(engine3))
    sst = solo.init_state(D)
    items = np.zeros((2, BATCH, D), np.float32)
    ts_a = np.stack([1000.0 + np.arange(BATCH, dtype=np.float32),
                     np.arange(BATCH, dtype=np.float32)])
    st3, _ = fx3.step(st3, jnp.asarray(items), jnp.asarray(ts_a))
    sst, _ = solo.step(sst, jnp.asarray(items[0]), jnp.asarray(ts_a[0]))
    # shard 0 sees data re-ordered back to ~500: late by its own max
    # (1031), but *not* by the fleet watermark (shard 1 is only at 31)
    ts_b = np.stack([500.0 + np.arange(BATCH, dtype=np.float32),
                     32.0 + np.arange(BATCH, dtype=np.float32)])
    st3, _ = fx3.step(st3, jnp.asarray(items), jnp.asarray(ts_b))
    sst, _ = solo.step(sst, jnp.asarray(items[0]), jnp.asarray(ts_b[0]))
    md3 = st3.metrics.as_dict()
    assert md3["shard"]["items_late"] == [0, 0], md3["shard"]["items_late"]
    assert int(sst.metrics.as_dict()["items_late"]) == BATCH
    # the shard's own max never rolls back to the fleet min
    st3, _ = fx3.step(st3, jnp.asarray(items),
                      jnp.asarray(ts_b + BATCH))
    assert fx3.trace_count == 1
    print("WATERMARK_OK")

    # --- 4. E=1 degenerates to the single-device executor -------------
    fx1 = FleetExecutor(FleetConfig(stream=scfg, num_shards=1, num_core=1,
                                    core_budget=64), engine,
                        two_tier(engine))
    st1 = fx1.init_state(D)
    sx1 = StreamExecutor(scfg, engine, two_tier(engine))
    ss1 = sx1.init_state(D)
    t0 = 0.0
    for step in range(4):
        it = rng.standard_normal((1, BATCH, D)).astype(np.float32) + 1.0
        ts = t0 + np.arange(BATCH, dtype=np.float32)
        t0 += BATCH
        st1, fo = fx1.step(st1, jnp.asarray(it), jnp.asarray(ts[None]))
        ss1, so = sx1.step(ss1, jnp.asarray(it[0]), jnp.asarray(ts))
        np.testing.assert_array_equal(np.asarray(fo.escalated[0]),
                                      np.asarray(so.escalated))
        np.testing.assert_allclose(np.asarray(fo.outputs[0]),
                                   np.asarray(so.outputs),
                                   rtol=1e-6, atol=1e-6)
    assert fx1.trace_count == 1
    print("SINGLE_OK")

    # --- 5. a remesh places on the new mesh: one re-trace each way ----
    def feed(t, e):
        items = rng.standard_normal((e, BATCH, D)).astype(np.float32)
        ts = np.tile((8 + t) * BATCH + np.arange(BATCH, dtype=np.float32),
                     (e, 1))
        return jnp.asarray(items), jnp.asarray(ts)

    devs = jax.devices()
    fstate, _ = fx.remesh(fstate, devs[:7], keep=list(range(7)),
                          fold_counters={7: 6})
    assert_placed(fx, fstate, *feed(0, 7))
    for t in range(2):
        fstate, _ = fx.step(fstate, *feed(t, 7))
    assert fx.trace_count == 2 <= 1 + fx.remeshes, fx.trace_count
    # a remesh builds the step anew: one executable on the new mesh
    assert fx._jstep._cache_size() == 1, fx._jstep._cache_size()
    fstate, _ = fx.remesh(fstate, devs, keep=list(range(7)) + [None])
    assert_placed(fx, fstate, *feed(2, 8))
    for t in range(2, 4):
        fstate, _ = fx.step(fstate, *feed(t, 8))
    assert fx.trace_count == 3 <= 1 + fx.remeshes, fx.trace_count
    assert fx._jstep._cache_size() == 1, fx._jstep._cache_size()
    print("REMESH_OK")
""")


@pytest.mark.parametrize("n", [1])
def test_fleet_executor_oracle_and_budget(n, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = tmp_path / "fleet_test.py"
    script.write_text(_SCRIPT)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ORACLE_OK" in out.stdout
    assert "BUDGET_OK" in out.stdout
    assert "WATERMARK_OK" in out.stdout
    assert "SINGLE_OK" in out.stdout
    assert "REMESH_OK" in out.stdout
