"""Compile a deployment's serving and fit programs for a described TPU v5e
and print what each needs of the chip's memory. Runs without a chip:

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py ml10m [capacity ...]

For each capacity (default: the configuration's) it compiles the pair
and top-N programs at every batch shape of the engine, and the fit at the
deployment's size, and prints ``memory_analysis()`` of each.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import registry
    from repro.core import knn
    from repro.core.landmark_cf import fit
    from repro.core.types import RatingMatrix
    from repro.serving import EngineConfig

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = json.loads((BENCH / "configs" / f"{argv[0]}.json").read_text())
    caps = [int(a) for a in argv[1:]] or [cfg["serving"]["capacity"]]
    u, p = cfg["data"]["n_users"], cfg["data"]["n_items"]
    spec = registry.get(cfg["model"]).model
    k = spec.k_neighbors
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def show(name, compiled):
        m = compiled.memory_analysis()
        print(f"{name}: args {m.argument_size_in_bytes} out "
              f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
              f"alias {m.alias_size_in_bytes}", flush=True)

    eng = EngineConfig(**cfg["engine"])
    for cap in caps:
        args = dict(idx=sds((cap, k), jnp.int32), w=sds((cap, k), jnp.float32),
                    r=sds((cap, p), jnp.float32), nv=sds((), jnp.int32),
                    tomb=sds((cap,), jnp.bool_))
        for b in eng.batch_shapes():
            ids = sds((b,), jnp.int32)

            def topn(idx, w, r, nv, tomb, users):
                from repro.core.types import NeighborGraph
                return knn.recommend_topn_graph(NeighborGraph(idx, w), r,
                                                users, n=eng.topn,
                                                n_valid=nv, tomb=tomb)

            def pair(idx, w, r, nv, tomb, users, items):
                from repro.core.types import NeighborGraph
                return knn.predict_pairs_graph(NeighborGraph(idx, w), r,
                                               users, items, n_valid=nv,
                                               tomb=tomb)

            show(f"cap {cap} topn b{b}", jax.jit(topn).lower(
                *args.values(), ids).compile())
            show(f"cap {cap} pair b{b}", jax.jit(pair).lower(
                *args.values(), ids, ids).compile())
    r = sds((u, p), jnp.float32)
    key = jax.random.PRNGKey(0)
    show(f"fit {u}x{p}", jax.jit(
        lambda x: fit(key, RatingMatrix(x, u, p), spec)).lower(r).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
