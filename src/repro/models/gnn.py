"""GatedGCN (Bresson & Laurent 2017; Dwivedi benchmark arXiv:2003.00982).

Message passing is expressed with ``jax.ops.segment_sum`` over an edge-index —
JAX has no sparse SpMM beyond BCOO, so the scatter/gather formulation IS the
system (kernel_taxonomy §GNN). Edge arrays are sharded over every mesh axis;
node states stay replicated, so the per-layer ``segment_sum`` lowers to a local
partial scatter-add + one all-reduce of the (N, H) node block.

Update rule (edge-gated, with residuals; BatchNorm → LayerNorm for SPMD
friendliness, noted in DESIGN.md):

    ê_ij = C e_ij + D h_i + E h_j ;  e_ij' = e_ij + ReLU(LN(ê_ij))
    η_ij = σ(ê_ij) / (Σ_{j'→i} σ(ê_ij') + ε)
    h_i' = h_i + ReLU(LN(U h_i + Σ_{j→i} η_ij ⊙ (V h_j)))
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int = 16
    d_hidden: int = 70
    d_feat: int = 1433
    n_classes: int = 7
    task: str = "node"  # node | graph (molecule regression)
    dtype: Any = jnp.float32
    scan_unroll: bool = False  # calibration only
    comm_dtype: Any = None  # e.g. jnp.bfloat16: cast messages/node states for
    #                         the per-layer all-gather/all-reduce (2x wire cut;
    #                         §Perf hillclimb on ogb_products)


def gnn_logical(cfg: GNNConfig):
    lin = ("layers", "null", "null")
    vec = ("layers", "null")
    return {
        "embed_w": ("null", "null"),
        "embed_b": ("null",),
        "layers": {k: lin for k in ("U", "V", "C", "D", "E")}
        | {k: vec for k in ("ln_h", "ln_e")},
        "head_w": ("null", "null"),
        "head_b": ("null",),
    }


def init_gnn(key: jax.Array, cfg: GNNConfig) -> Dict[str, Any]:
    h = cfg.d_hidden
    ks = iter(jax.random.split(key, 8))

    def w(k, shape):
        return (jax.random.normal(k, shape) / np.sqrt(shape[0])).astype(cfg.dtype)

    lw = lambda k: (
        jax.random.normal(k, (cfg.n_layers, h, h)) / np.sqrt(h)
    ).astype(cfg.dtype)
    return {
        "embed_w": w(next(ks), (cfg.d_feat, h)),
        "embed_b": jnp.zeros((h,), cfg.dtype),
        "layers": {
            "U": lw(next(ks)),
            "V": lw(next(ks)),
            "C": lw(next(ks)),
            "D": lw(next(ks)),
            "E": lw(next(ks)),
            "ln_h": jnp.ones((cfg.n_layers, h), cfg.dtype),
            "ln_e": jnp.ones((cfg.n_layers, h), cfg.dtype),
        },
        "head_w": w(next(ks), (h, cfg.n_classes)),
        "head_b": jnp.zeros((cfg.n_classes,), cfg.dtype),
    }


def _ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale


def gnn_forward(
    params,
    node_feats: jax.Array,  # (N, d_feat)
    edge_src: jax.Array,  # (E,) int32 — padded edges point at node 0 w/ mask 0
    edge_dst: jax.Array,  # (E,)
    edge_mask: jax.Array,  # (E,) float 0/1
    cfg: GNNConfig,
    rules,
    graph_ids: Optional[jax.Array] = None,  # (N,) for graph-level readout
    n_graphs: int = 0,
) -> jax.Array:
    n = node_feats.shape[0]
    h = jnp.einsum("nf,fh->nh", node_feats.astype(cfg.dtype), params["embed_w"]) + params["embed_b"]
    e = jnp.zeros((edge_src.shape[0], cfg.d_hidden), cfg.dtype)
    emask = edge_mask[:, None].astype(cfg.dtype)

    cd = cfg.comm_dtype

    def layer(carry, lp):
        h, e = carry
        hu = jnp.einsum("nh,hk->nk", h, lp["U"])
        hv = jnp.einsum("nh,hk->nk", h, lp["V"])
        hd = jnp.einsum("nh,hk->nk", h, lp["D"])
        he = jnp.einsum("nh,hk->nk", h, lp["E"])
        if cd is not None:  # node→edge gathers move comm_dtype on the wire
            hv, hd, he = hv.astype(cd), hd.astype(cd), he.astype(cd)
            # pin post-cast projections node-sharded: otherwise GSPMD gathers
            # the f32 carry h and casts after (no wire saving)
            hv = constrain(hv, ("batch", "null"), rules)
            hd = constrain(hd, ("batch", "null"), rules)
            he = constrain(he, ("batch", "null"), rules)
        src_v = jnp.take(hv, edge_src, axis=0).astype(cfg.dtype)
        e_hat = (
            jnp.einsum("eh,hk->ek", e, lp["C"])
            + jnp.take(hd, edge_dst, axis=0).astype(cfg.dtype)
            + jnp.take(he, edge_src, axis=0).astype(cfg.dtype)
        )
        e_new = e + jax.nn.relu(_ln(e_hat, lp["ln_e"]))
        gate = jax.nn.sigmoid(e_hat) * emask
        gsum = gate.astype(cd) if cd is not None else gate
        denom = jax.ops.segment_sum(gsum, edge_dst, num_segments=n).astype(cfg.dtype) + 1e-6
        eta = gate / jnp.take(denom, edge_dst, axis=0)
        msg = eta * src_v * emask
        if cd is not None:  # edge→node scatter partials all-reduce in comm_dtype
            msg = msg.astype(cd)
        agg = jax.ops.segment_sum(msg, edge_dst, num_segments=n).astype(cfg.dtype)
        h_new = h + jax.nn.relu(_ln(hu + agg, lp["ln_h"]))
        # node states live sharded over the data axes (43 MB/chip at 2.45M
        # nodes vs 686 MB replicated); edge gathers all-gather h per layer.
        h_new = constrain(h_new, ("batch", "null"), rules)
        return (h_new, e_new), None

    layer = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable)
    (h, e), _ = jax.lax.scan(layer, (h, e), params["layers"], unroll=cfg.scan_unroll)
    if cfg.task == "graph":
        pooled = jax.ops.segment_sum(h, graph_ids, num_segments=n_graphs)
        cnt = jax.ops.segment_sum(jnp.ones((n, 1), cfg.dtype), graph_ids, num_segments=n_graphs)
        h = pooled / jnp.maximum(cnt, 1.0)
    return jnp.einsum("nh,hc->nc", h, params["head_w"]) + params["head_b"]


def gnn_loss(params, batch: Dict[str, jax.Array], cfg: GNNConfig, rules) -> jax.Array:
    logits = gnn_forward(
        params,
        batch["node_feats"],
        batch["edge_src"],
        batch["edge_dst"],
        batch["edge_mask"],
        cfg,
        rules,
        graph_ids=batch.get("graph_ids"),
        n_graphs=batch.get("n_graphs", 0),
    )
    if cfg.task == "graph":  # regression (ZINC-style)
        pred = logits[..., 0]
        return jnp.mean((pred - batch["targets"]) ** 2)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# §Perf H2: shard_map message passing with explicit wire control.
#
# GSPMD re-orders dtype converts outside its collectives (measured — see
# EXPERIMENTS §Perf H2), so the bf16 wire format and the partial-reduce
# structure are forced here explicitly:
#   · node states sharded over the data axes; per layer ONE bf16 all-gather
#   · edges dst-partitioned: every edge lives with its dst's node shard
#     (data-pipeline contract: sort edges by dst), so scatter-add partials
#     reduce over 'model' only — a (N/data, H) bf16 psum instead of a full
#     (N, H) f32 all-reduce.
# Wire per layer: 343 MB gather + ~43 MB psum vs 686+686 MB ⇒ ~3.5× less.
# ---------------------------------------------------------------------------
def gnn_forward_shardmap(
    params, node_feats, edge_src, edge_dst, edge_mask, cfg: GNNConfig,
    mesh, n_nodes_global: int,
    graph_ids=None, n_graphs: int = 0,
):
    """edge_src/edge_dst: GLOBAL node ids; the pipeline dst-sorts edges so an
    edge lives on its dst's node shard (ownership contract — off-shard dsts
    are masked defensively). node_feats sharded over ('pod','data'); edge
    arrays sharded over all axes."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    naxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    eaxes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    wire = jnp.bfloat16

    def inner(feats_l, src, dst, mask):
        # this shard's global node-row offset
        base = 0
        for a in naxes:
            base = base * mesh.shape[a] + jax.lax.axis_index(a)
        base = base * feats_l.shape[0]
        dst_l = dst - base
        owned = (dst_l >= 0) & (dst_l < feats_l.shape[0])
        mask = mask * owned.astype(mask.dtype)
        dst_l = jnp.clip(dst_l, 0, feats_l.shape[0] - 1)
        n_local = feats_l.shape[0]
        h = jnp.einsum("nf,fh->nh", feats_l.astype(cfg.dtype), params["embed_w"]) + params["embed_b"]
        e = jnp.zeros((src.shape[0], cfg.d_hidden), cfg.dtype)
        emask = mask[:, None].astype(cfg.dtype)

        def layer(carry, lp):
            h, e = carry
            # ONE bf16 all-gather of the node block per layer (the wire).
            h_full = jax.lax.all_gather(h.astype(wire), naxes, tiled=True)
            h_full = h_full.astype(cfg.dtype)
            hv = jnp.einsum("nh,hk->nk", h_full, lp["V"])
            hd = jnp.einsum("nh,hk->nk", h_full, lp["D"])
            he = jnp.einsum("nh,hk->nk", h_full, lp["E"])
            hu = jnp.einsum("nh,hk->nk", h, lp["U"])
            src_v = jnp.take(hv, src, axis=0)
            e_hat = (jnp.einsum("eh,hk->ek", e, lp["C"])
                     + jnp.take(hd, dst, axis=0)  # global ids into gathered h
                     + jnp.take(he, src, axis=0))
            e_new = e + jax.nn.relu(_ln(e_hat, lp["ln_e"]))
            gate = jax.nn.sigmoid(e_hat) * emask
            # dst-partitioned: partials live on the owner shard; reduce over
            # 'model' only, in bf16.
            denom = jax.lax.psum(
                jax.ops.segment_sum(gate.astype(wire), dst_l, num_segments=n_local),
                "model",
            ).astype(cfg.dtype) + 1e-6
            eta = gate / jnp.take(denom, dst_l, axis=0)
            agg = jax.lax.psum(
                jax.ops.segment_sum((eta * src_v * emask).astype(wire), dst_l,
                                    num_segments=n_local),
                "model",
            ).astype(cfg.dtype)
            h_new = h + jax.nn.relu(_ln(hu + agg, lp["ln_h"]))
            return (h_new, e_new), None

        layer_fn = jax.checkpoint(layer, policy=jax.checkpoint_policies.nothing_saveable)
        (h, e), _ = jax.lax.scan(layer_fn, (h, e), params["layers"],
                                 unroll=cfg.scan_unroll)
        return jnp.einsum("nh,hc->nc", h, params["head_w"]) + params["head_b"]

    return shard_map(
        inner, mesh=mesh,
        in_specs=(P(naxes, None), P(eaxes), P(eaxes), P(eaxes)),
        out_specs=P(naxes, None),
        check_vma=False,
    )(node_feats, edge_src, edge_dst, edge_mask)


def gnn_loss_shardmap(params, batch, cfg: GNNConfig, mesh, n_nodes_global):
    logits = gnn_forward_shardmap(
        params, batch["node_feats"], batch["edge_src"], batch["edge_dst"],
        batch["edge_mask"], cfg, mesh, n_nodes_global,
    )
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    local = -(ll * mask).sum()
    cnt = mask.sum()
    return local / jnp.maximum(cnt, 1.0)
