"""Ahead-of-time compile of each configuration's step for a described
TPU v5e, no chip needed: which micro-batch compiles, and the compiled
step's memory.

    JAX_PLATFORMS=cpu python3 bench/aot.py [--rows 131072 65536 ...]

For every configuration in ``bench/configs`` and every micro-batch
given (largest first), lowers the program's own step at the
configuration's shapes (one chip: ``StreamExecutor``; fleet: the
``FleetExecutor`` on a mesh of described v5e chips) and compiles it with
the TPU compiler.  Prints, per configuration, the largest micro-batch
that compiles and that step's ``memory_analysis()``.  Run it alone: it
loads the TPU compiler, which one process may hold at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _shapes(tree, sharding):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def compile_step(cfg: dict, topo):
    """Compile one configuration's step for the described chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from bench.system import _program
    from repro.stream import StreamExecutor
    from repro.stream.fleet import FleetConfig, FleetExecutor

    engine, make_pipeline, scfg = _program(cfg, None)
    b, d = cfg["micro_batch"], cfg["channels"]
    fl = cfg.get("fleet")
    if fl is None:
        one = SingleDeviceSharding(topo.devices[0])
        cap = int(cfg["core"]["capacity_share"] * scfg.windows_per_step)
        ex = StreamExecutor(scfg, engine, make_pipeline(cap))
        state = _shapes(jax.eval_shape(lambda: ex.init_state(d)), one)
        items = jax.ShapeDtypeStruct((b, d), jnp.float32, sharding=one)
        ts = jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one)
        return ex.lower(state, items, ts).compile()
    s = cfg["shards"]
    mesh = Mesh(np.asarray(topo.devices[:s]).reshape(
        fl["regions"], s // fl["regions"]), ("region", "edge"))
    fx = FleetExecutor(
        FleetConfig(stream=scfg, num_shards=s, num_core=fl["num_core"],
                    num_regions=fl["regions"], core_budget=fl["core_budget"],
                    core_budget_max=fl["core_budget_max"],
                    fog_budget=fl["fog_budget"],
                    fog_budget_max=fl["fog_budget_max"]),
        engine, make_pipeline(None), mesh=mesh)
    rows = NamedSharding(mesh, P(("region", "edge")))
    state = _shapes(jax.eval_shape(lambda: fx._fresh_state(d)), rows)
    items = jax.ShapeDtypeStruct((s, b, d), jnp.float32, sharding=rows)
    ts = jax.ShapeDtypeStruct((s, b), jnp.float32, sharding=rows)
    return fx.lower(state, items, ts).compile()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, nargs="+", default=[131072, 65536])
    ap.add_argument("--configs", nargs="+",
                    default=sorted(p.stem for p in
                                   (BENCH / "configs").glob("*.json")))
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    report = {}
    for name in args.configs:
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        jax.config.update("jax_default_matmul_precision",
                          cfg["precision"]["matmul"])
        for rows in sorted(args.rows, reverse=True):
            c = dict(cfg, micro_batch=rows, ring_capacity=rows)
            try:
                compiled = compile_step(c, topo)
            except Exception as e:     # the compiler's refusal, reported
                print(f"{name} at {rows} rows: refused: "
                      f"{str(e).splitlines()[0][:300]}", flush=True)
                continue
            m = compiled.memory_analysis()
            hlo = compiled.as_text()
            report[name] = {
                "micro_batch": rows,
                "argument_bytes": m.argument_size_in_bytes,
                "output_bytes": m.output_size_in_bytes,
                "temp_bytes": m.temp_size_in_bytes,
                "alias_bytes": m.alias_size_in_bytes,
                "generated_code_bytes": m.generated_code_size_in_bytes,
                "tpu_kernel": "tpu_custom_call" in hlo,
                "all_to_all": "all-to-all" in hlo}
            print(f"{name} at {rows} rows: compiles: {report[name]}",
                  flush=True)
            break
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
