"""Ahead-of-time compiles for a described TPU v5e (2x2), no chip needed.

The TPU compiler is installed with JAX and compiles for a topology that
is described, not attached.  It refuses what interpret mode accepts: a
kernel block past VMEM, a tile it cannot align, a step that does not
fit HBM.  These tests compile the main path's kernels and whole steps
at deployment size, so such a refusal shows up here and not on the
chip.

Everything built from the topology lives in this file's module-scoped
fixtures: describing it loads the TPU library, which one process may
hold at a time, so it must happen inside a test of this one file (never
at import, in ``skipif`` or ``parametrize``, or in ``conftest.py``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import pipeline as pipe
from repro.core import rules
from repro.kernels.fused_tick import fused_tick
from repro.kernels.window_reduce import window_reduce
from repro.stream import StreamConfig, StreamExecutor
from repro.stream.fleet import FleetConfig, FleetExecutor
from repro.stream.ingest import AdmissionPlan, DataContract

D = 16            # sensor features
ROWS = 65536      # micro-batch rows (131,072 run the kernels out of VMEM)
WINDOW, STRIDE = 64, 32


@pytest.fixture(scope="module")
def topo():
    # the TPU compiler ships in libtpu; a JAX installed for the CPU alone
    # has none to compile with.  Any other failure to describe the chip
    # is a failure of the test.
    pytest.importorskip("libtpu")
    # persistent-cache entries written for a described chip cannot be
    # read back without one: keep the cache off around these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("region", "edge"))


def _engine():
    return rules.RuleEngine([
        rules.threshold_rule("hot_mean", 0, ">=", 0.25, rules.C_SEND_CORE,
                             priority=1),
        rules.threshold_rule("sparse", 4, "<", 8.0, rules.C_STORE_EDGE,
                             priority=2)])


def _pipeline(engine):
    return pipe.two_tier_pipeline(lambda p, b: (b, b[:, :5]),
                                  lambda p, b: (jnp.tanh(b), b[:, :5]),
                                  engine)


def _stream_config(rows):
    contract = DataContract(lo=(-8.0,) * D, hi=(8.0,) * D)
    return StreamConfig(micro_batch=rows, window=WINDOW, stride=STRIDE,
                        capacity=4 * rows, lateness=64.0, backend="pallas",
                        fused=True,
                        admission=AdmissionPlan(dedupe_window=1024,
                                                contract=contract))


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def test_fused_tick_kernel_compiles(one_chip):
    seq = jax.ShapeDtypeStruct((ROWS + WINDOW - STRIDE, 2 + D), jnp.float32,
                               sharding=one_chip)
    valid = jax.ShapeDtypeStruct(seq.shape[:1], bool, sharding=one_chip)
    hlo = fused_tick.lower(seq, valid, WINDOW, STRIDE,
                           table=_engine().table(), backend="pallas"
                           ).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_window_reduce_kernel_compiles(one_chip):
    x = jax.ShapeDtypeStruct((ROWS, D), jnp.float32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((ROWS,), bool, sharding=one_chip)
    hlo = window_reduce.lower(x, valid, WINDOW, STRIDE, reducer="mean",
                              partial=False).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_stream_step_compiles(one_chip):
    engine = _engine()
    ex = StreamExecutor(_stream_config(ROWS), engine, _pipeline(engine))
    state = _shapes(jax.eval_shape(lambda: ex.init_state(D)), one_chip)
    items = jax.ShapeDtypeStruct((ROWS, D), jnp.float32, sharding=one_chip)
    ts = jax.ShapeDtypeStruct((ROWS,), jnp.float32, sharding=one_chip)
    compiled = ex.lower(state, items, ts).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the whole step's working set stays far inside one chip's 16 GB
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 2 << 30


def test_fleet_step_compiles(mesh):
    engine = _engine()
    scfg = _stream_config(ROWS)
    fx = FleetExecutor(
        FleetConfig(stream=scfg, num_shards=4, num_core=1,
                    core_budget=4 * scfg.windows_per_step),
        engine, _pipeline(engine), mesh=mesh)
    rows = NamedSharding(mesh, P(("region", "edge")))
    state = _shapes(jax.eval_shape(lambda: fx._fresh_state(D)), rows)
    items = jax.ShapeDtypeStruct((4, ROWS, D), jnp.float32, sharding=rows)
    ts = jax.ShapeDtypeStruct((4, ROWS), jnp.float32, sharding=rows)
    hlo = fx.lower(state, items, ts).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert "all-to-all" in hlo
