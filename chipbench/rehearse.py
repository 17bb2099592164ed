"""Compile a deployment's serving and fit programs for a described TPU v5e
and print what each needs of the chip's memory. Runs without a chip:

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py ml10m [capacity ...]
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py path/to/config.json

The first argument names a configuration under ``chipbench/configs`` or is
the path of one. For each capacity (default: the configuration's) it
compiles the read programs on the state as served, at every batch shape of
the engine, and prints ``memory_analysis()`` of each:

- ``serving.backend`` ``MutableLocalBackend``: ``mutation.predict_pairs``
  and ``recommend_topn`` on the bucketed state, its rating matrix zero-padded
  to ``buckets.lane_width(P)`` columns with ``n_items`` = P, on one chip;
  then the fit at the deployment's size;
- ``MutableShardedBackend``: the router's pair and top-N programs, as
  ``MutableShardedBackend`` calls them, on a ``pod`` mesh over the described
  2x2 host's four chips, each holding ``capacity / 4`` rows of width P. The
  figures are per device.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]


def show(name: str, compiled) -> None:
    m = compiled.memory_analysis()
    print(f"{name}: args {m.argument_size_in_bytes} out "
          f"{m.output_size_in_bytes} temp {m.temp_size_in_bytes} "
          f"alias {m.alias_size_in_bytes}", flush=True)


def local_reads(topo, cap: int, p: int, n: int, k: int, eng) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro import mutation
    from repro.core.landmark_cf import LandmarkState
    from repro.core.types import NeighborGraph
    from repro.lifecycle import buckets

    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    width = buckets.lane_width(p)
    mst = mutation.MutableState(
        buckets.BucketedState(
            LandmarkState(sds((n,), jnp.int32), sds((cap, n), jnp.float32),
                          sds((cap, width), jnp.float32),
                          graph=NeighborGraph(sds((cap, k), jnp.int32),
                                              sds((cap, k), jnp.float32))),
            sds((), jnp.int32), p),
        sds((n, p), jnp.float32), sds((cap,), jnp.bool_),
        sds((cap,), jnp.bool_))
    for b in eng.batch_shapes():
        ids = sds((b,), jnp.int32)
        show(f"cap {cap} width {width} topn b{b}", jax.jit(
            mutation.recommend_topn, static_argnames="n").lower(
                mst, ids, n=eng.topn).compile())
        show(f"cap {cap} width {width} pair b{b}", jax.jit(
            mutation.predict_pairs).lower(mst, ids, ids).compile())


def sharded_reads(topo, cap: int, p: int, n: int, k: int, eng) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.landmark_cf import LandmarkState, ShardedLandmarkState
    from repro.core.types import NeighborGraph
    from repro.launch.mesh import make_mesh
    from repro.serving import router

    mesh = make_mesh((len(topo.devices),), ("pod",), devices=topo.devices)
    s = mesh.shape["pod"]
    rows = s * (cap // s)

    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    row2 = P("pod", None)
    sst = ShardedLandmarkState(
        LandmarkState(sds((n,), jnp.int32), sds((rows, n), jnp.float32, row2),
                      sds((rows, p), jnp.float32, row2),
                      graph=NeighborGraph(sds((rows, k), jnp.int32, row2),
                                          sds((rows, k), jnp.float32, row2))),
        sds((s,), jnp.int32), sds((rows,), jnp.int32, P("pod")),
        mesh, ("pod",))
    tomb = sds((rows,), jnp.bool_)
    per = f"{s} x {rows // s} rows, width {p}, per device"
    for b in eng.batch_shapes():
        ids = sds((b,), jnp.int32)
        show(f"{per}: routed topn b{b}", jax.jit(
            lambda st, u, t: router.recommend_topn_routed(
                st, u, n=eng.topn, tomb=t)).lower(sst, ids, tomb).compile())
        show(f"{per}: routed pair b{b}", jax.jit(
            lambda st, u, i, t: router.predict_pairs_routed(
                st, u, i, tomb=t)).lower(sst, ids, ids, tomb).compile())


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs import registry
    from repro.core.landmark_cf import fit
    from repro.core.types import RatingMatrix
    from repro.serving import EngineConfig

    jax.config.update("jax_enable_compilation_cache", False)
    path = Path(argv[0]) if argv[0].endswith(".json") else (
        BENCH / "configs" / f"{argv[0]}.json")
    cfg = json.loads(path.read_text())
    caps = [int(a) for a in argv[1:]] or [cfg["serving"]["capacity"]]
    u, p = cfg["data"]["n_users"], cfg["data"]["n_items"]
    spec = registry.get(cfg["model"]).model
    n, k = spec.n_landmarks, spec.k_neighbors
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    eng = EngineConfig(**cfg["engine"])
    sharded = cfg["serving"]["backend"] == "MutableShardedBackend"
    for cap in caps:
        (sharded_reads if sharded else local_reads)(topo, cap, p, n, k, eng)
    if not sharded:
        r = jax.ShapeDtypeStruct((u, p), jnp.float32,
                                 sharding=SingleDeviceSharding(topo.devices[0]))
        key = jax.random.PRNGKey(0)
        show(f"fit {u}x{p}", jax.jit(
            lambda x: fit(key, RatingMatrix(x, u, p), spec)).lower(r).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
