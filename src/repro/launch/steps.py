"""Cell builders: one jittable step per (architecture × input shape).

``build_cell(arch, shape, mesh)`` returns the step function plus
ShapeDtypeStruct inputs with NamedShardings attached — exactly what the
dry-run lowers and what train.py/serve.py execute with real arrays.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.core import graph as core_graph
from repro.core import knn as core_knn
from repro.core import selection as core_selection
from repro.core import similarity as core_similarity
from repro.core.types import NeighborGraph
from repro.distributed.sharding import filter_rules, sharding_for, spec_for, tree_shardings
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec_mod
from repro.models import transformer as lm_mod
from repro.train.optimizer import OptConfig, opt_init, opt_state_logical, opt_update


@dataclasses.dataclass
class Cell:
    arch: ArchConfig
    shape: ShapeSpec
    mesh: Mesh
    fn: Callable
    args: Tuple[Any, ...]  # ShapeDtypeStructs with shardings
    out_shardings: Any = None
    donate: Tuple[int, ...] = ()
    static_argnums: Tuple[int, ...] = ()

    def jit(self):
        return jax.jit(
            self.fn,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate,
            static_argnums=self.static_argnums,
        )

    def lower(self):
        with jax.set_mesh(self.mesh):
            return self.jit().lower(*self.args)


def _sds(shape, dtype, mesh, pspec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, pspec))


def _tree_sds(shapes_dtypes, shardings):
    return jax.tree_util.tree_map(
        lambda sd, sh: jax.ShapeDtypeStruct(sd.shape, sd.dtype, sharding=sh),
        shapes_dtypes,
        shardings,
    )


def _batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ------------------------------------------------------------------------- LM
def _lm_state_specs(arch: ArchConfig, mesh: Mesh):
    cfg = arch.model
    params_shape = jax.eval_shape(lambda: lm_mod.init_lm(jax.random.PRNGKey(0), cfg))
    logical = lm_mod.lm_logical(cfg)
    p_shardings = tree_shardings(logical, mesh, arch.rules)
    params_sds = _tree_sds(params_shape, p_shardings)
    opt_shape = jax.eval_shape(lambda: opt_init(params_shape, arch.opt))
    opt_logical = opt_state_logical(logical, arch.opt)
    o_shardings = tree_shardings(opt_logical, mesh, arch.rules)
    opt_sds = _tree_sds(opt_shape, o_shardings)
    return params_sds, opt_sds, p_shardings, o_shardings


def _lm_train_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> Cell:
    cfg, rules = arch.model, arch.rules
    b, s = shape.dims["batch"], shape.dims["seq"]
    accum = arch.grad_accum.get(shape.name, 1)
    mb = b // accum
    baxes = _batch_axes(mesh)

    params_sds, opt_sds, p_sh, o_sh = _lm_state_specs(arch, mesh)
    tok_spec = P(None, baxes, None) if accum > 1 else P(baxes, None)
    tok_shape = (accum, mb, s) if accum > 1 else (b, s)
    batch_sds = {
        "tokens": _sds(tok_shape, jnp.int32, mesh, tok_spec),
        "labels": _sds(tok_shape, jnp.int32, mesh, tok_spec),
    }

    loss_fn = lambda p, mbatch: lm_mod.lm_loss(p, mbatch, cfg, rules)

    def step(params, opt_state, batch):
        if accum > 1:
            def micro(carry, mbatch):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, mbatch)
                g = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(a.dtype), g_acc, g
                )
                return (g, l_acc + l), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.bfloat16), params
            )
            (grads, loss), _ = jax.lax.scan(micro, (g0, 0.0), batch,
                                            unroll=arch.calib_unroll)
            grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
            loss = loss / accum
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = opt_update(params, grads, opt_state, arch.opt)
        return new_params, new_opt, {"loss": loss}

    return Cell(
        arch, shape, mesh, step,
        (params_sds, opt_sds, batch_sds),
        out_shardings=(p_sh, o_sh, None),
        donate=(0, 1),
    )


def _lm_prefill_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> Cell:
    cfg, rules = arch.model, arch.rules
    b, s = shape.dims["batch"], shape.dims["seq"]
    baxes = _batch_axes(mesh)
    params_sds, _, p_sh, _ = _lm_state_specs(arch, mesh)
    tokens = _sds((b, s), jnp.int32, mesh, P(baxes, None))
    cache_sh = tree_shardings(lm_mod.cache_logical(), mesh, rules)

    def step(params, tokens):
        return lm_mod.lm_prefill(params, tokens, cfg, rules)

    return Cell(arch, shape, mesh, step, (params_sds, tokens),
                out_shardings=(None, cache_sh))


def _lm_decode_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh, landmark: bool) -> Cell:
    cfg, rules = arch.model, arch.rules
    b, cache_len = shape.dims["batch"], shape.dims["cache_len"]
    long_ctx = cache_len > 100_000
    baxes = _batch_axes(mesh) if b > 1 else ()
    rules = dict(rules)
    if b == 1:
        rules["batch"] = None
    params_sds, _, p_sh, _ = _lm_state_specs(arch, mesh)
    token = _sds((b, 1), jnp.int32, mesh, P(baxes if baxes else None, None))

    if landmark:
        cache_shape = jax.eval_shape(lambda: lm_mod.make_landmark_cache(cfg, b))
        cache_sh = tree_shardings(lm_mod.landmark_cache_logical(), mesh, rules)
        cache_sds = _tree_sds(cache_shape, cache_sh)

        def step(params, cache, token):
            return lm_mod.lm_landmark_decode_step(params, cache, token, cfg, rules)

    else:
        cache_shape = jax.eval_shape(lambda: lm_mod.make_cache(cfg, b, cache_len))
        cache_sh = tree_shardings(
            lm_mod.cache_logical(long_ctx, cfg.kv_quant), mesh, rules)
        cache_sds = _tree_sds(cache_shape, cache_sh)

        def step(params, cache, token):
            return lm_mod.lm_decode_step(params, cache, token, cfg, rules)

    return Cell(
        arch, shape, mesh, step, (params_sds, cache_sds, token),
        out_shardings=(None, cache_sh), donate=(1,),
    )


# ------------------------------------------------------------------------ GNN
def _gnn_batch_sds(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh):
    d = shape.dims
    eaxes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    if shape.name == "molecule":
        n_nodes = d["batch"] * d["n_nodes"]
        n_edges = d["batch"] * d["n_edges"]
    elif shape.name == "minibatch_lg":
        n_nodes, n_edges = d["pad_nodes"], d["pad_edges"]
    else:
        chips = int(np.prod([mesh.shape[a] for a in eaxes]))
        n_shards = int(np.prod([mesh.shape[a] for a in ("pod", "data") if a in mesh.axis_names]))
        n_nodes = -(-d["n_nodes"] // n_shards) * n_shards  # pad to node-shardable
        n_edges = -(-d["n_edges"] // chips) * chips  # pad to shardable
    e_spec = P(eaxes)
    naxes = _batch_axes(mesh)
    nspec = P(naxes, None) if n_nodes % max(
        int(np.prod([mesh.shape[a] for a in naxes])), 1) == 0 else P(None, None)
    batch = {
        "node_feats": _sds((n_nodes, d["d_feat"]), jnp.float32, mesh, nspec),
        "edge_src": _sds((n_edges,), jnp.int32, mesh, e_spec),
        "edge_dst": _sds((n_edges,), jnp.int32, mesh, e_spec),
        "edge_mask": _sds((n_edges,), jnp.float32, mesh, e_spec),
    }
    if shape.name == "molecule":
        batch["graph_ids"] = _sds((n_nodes,), jnp.int32, mesh, P(None))
        batch["targets"] = _sds((d["batch"],), jnp.float32, mesh, P(None))
    else:
        batch["labels"] = _sds((n_nodes,), jnp.int32, mesh, P(None))
    return batch


def _gnn_train_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh, variant: str = "base") -> Cell:
    d = shape.dims
    cfg = dataclasses.replace(
        arch.model,
        d_feat=d["d_feat"],
        n_classes=d["n_classes"],
        task="graph" if shape.name == "molecule" else "node",
    )
    rules = arch.rules
    params_shape = jax.eval_shape(lambda: gnn_mod.init_gnn(jax.random.PRNGKey(0), cfg))
    logical = gnn_mod.gnn_logical(cfg)
    p_sh = tree_shardings(logical, mesh, rules)
    params_sds = _tree_sds(params_shape, p_sh)
    opt_shape = jax.eval_shape(lambda: opt_init(params_shape, arch.opt))
    o_sh = tree_shardings(opt_state_logical(logical, arch.opt), mesh, rules)
    opt_sds = _tree_sds(opt_shape, o_sh)
    batch_sds = _gnn_batch_sds(arch, shape, mesh)
    n_graphs = d.get("batch", 0)

    n_nodes = batch_sds["node_feats"].shape[0]

    def step(params, opt_state, batch):
        if "graph_ids" in batch:
            batch = dict(batch, n_graphs=n_graphs)
        if variant == "comm":  # §Perf H2: shard_map wire-controlled messaging
            loss_fn = lambda p: gnn_mod.gnn_loss_shardmap(p, batch, cfg, mesh, n_nodes)
        else:
            loss_fn = lambda p: gnn_mod.gnn_loss(p, batch, cfg, rules)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt_update(params, grads, opt_state, arch.opt)
        return new_params, new_opt, {"loss": loss}

    return Cell(arch, shape, mesh, step, (params_sds, opt_sds, batch_sds),
                out_shardings=(p_sh, o_sh, None), donate=(0, 1))


# --------------------------------------------------------------------- recsys
_REC_INIT = {
    "fm": rec_mod.init_fm,
    "bert4rec": rec_mod.init_bert4rec,
    "mind": rec_mod.init_mind,
    "dien": rec_mod.init_dien,
}
_REC_LOGICAL = {
    "fm": rec_mod.fm_logical,
    "bert4rec": rec_mod.bert4rec_logical,
    "mind": rec_mod.mind_logical,
    "dien": rec_mod.dien_logical,
}
_REC_LOSS = {
    "fm": rec_mod.fm_loss,
    "bert4rec": rec_mod.bert4rec_loss,
    "mind": rec_mod.mind_loss,
    "dien": rec_mod.dien_loss,
}


def _rec_batch_sds(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh, kind: str):
    cfg = arch.model
    b = shape.dims["batch"]
    # recsys batches are huge (64k-256k) and the models tiny: shard the batch
    # over every mesh axis (the embedding shard_map reshards ids internally).
    all_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n_all = int(np.prod([mesh.shape[a] for a in all_axes]))
    baxes = all_axes if (b > 1 and b % n_all == 0) else (_batch_axes(mesh) if b > 1 else ())
    bspec = P(baxes) if baxes else P(None)
    bspec2 = P(baxes, None) if baxes else P(None, None)
    name = arch.name.split("-")[0]
    out: Dict[str, Any] = {}
    if name == "fm":
        out["field_ids"] = _sds((b, cfg.n_fields), jnp.int32, mesh, bspec2)
        if kind == "train":
            out["labels"] = _sds((b,), jnp.int32, mesh, bspec)
    else:
        out["item_ids"] = _sds((b, cfg.seq_len), jnp.int32, mesh, bspec2)
        if kind == "train":
            if name == "bert4rec":
                n_mask = cfg.seq_len // 5
                out["mask_positions"] = _sds((b, n_mask), jnp.int32, mesh, bspec2)
                out["targets"] = _sds((b, n_mask), jnp.int32, mesh, bspec2)
                out["negatives"] = _sds((cfg.n_negatives,), jnp.int32, mesh, P(None))
            elif name == "mind":
                out["targets"] = _sds((b,), jnp.int32, mesh, bspec)
                out["negatives"] = _sds((cfg.n_negatives,), jnp.int32, mesh, P(None))
            else:  # dien
                out["targets"] = _sds((b,), jnp.int32, mesh, bspec)
                out["labels"] = _sds((b,), jnp.int32, mesh, bspec)
    if kind == "scores":
        c = shape.dims.get("n_candidates", 16)
        if name == "bert4rec" or name == "mind":
            out["candidates"] = _sds((b, c), jnp.int32, mesh, bspec2)
        elif name == "dien":
            out["targets"] = _sds((b,), jnp.int32, mesh, bspec)
    if kind == "retrieval":
        out["cand_ids"] = _sds(
            (shape.dims["n_candidates"],), jnp.int32, mesh, P(None)
        )
    return out


def _rec_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh) -> Cell:
    cfg, rules = arch.model, arch.rules
    name = arch.name.split("-")[0]
    kind = shape.kind
    params_shape = jax.eval_shape(lambda: _REC_INIT[name](jax.random.PRNGKey(0), cfg))
    logical = _REC_LOGICAL[name](cfg)
    p_sh = tree_shardings(logical, mesh, rules)
    params_sds = _tree_sds(params_shape, p_sh)
    batch_sds = _rec_batch_sds(arch, shape, mesh, kind)

    if kind == "train":
        opt_shape = jax.eval_shape(lambda: opt_init(params_shape, arch.opt))
        o_sh = tree_shardings(opt_state_logical(logical, arch.opt), mesh, rules)
        opt_sds = _tree_sds(opt_shape, o_sh)
        loss_fn = _REC_LOSS[name]

        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(lambda p: loss_fn(p, batch, cfg, mesh))(params)
            new_params, new_opt = opt_update(params, grads, opt_state, arch.opt)
            return new_params, new_opt, {"loss": loss}

        return Cell(arch, shape, mesh, step, (params_sds, opt_sds, batch_sds),
                    out_shardings=(p_sh, o_sh, None), donate=(0, 1))

    if kind == "scores":
        def step(params, batch):
            if name == "fm":
                return rec_mod.fm_scores(params, batch["field_ids"], cfg, mesh)
            if name == "bert4rec":
                return rec_mod.bert4rec_scores(params, batch, cfg, mesh)
            if name == "mind":
                return rec_mod.mind_scores(params, batch, cfg, mesh)
            return rec_mod.dien_logits(params, batch, cfg, mesh)

        return Cell(arch, shape, mesh, step, (params_sds, batch_sds))

    # retrieval: score 1M candidates, return top-k
    def step(params, batch):
        if name == "fm":
            return rec_mod.fm_retrieval(params, batch["field_ids"], batch["cand_ids"], cfg,
                                        k=100, mesh=mesh)
        if name == "bert4rec":
            return rec_mod.bert4rec_retrieval(params, batch, cfg, k=100, mesh=mesh)
        if name == "mind":
            return rec_mod.mind_retrieval(params, batch, cfg, k=100, mesh=mesh)
        return rec_mod.dien_retrieval(params, batch, cfg, k=100, mesh=mesh)

    return Cell(arch, shape, mesh, step, (params_sds, batch_sds))


# ------------------------------------------------------------- landmark CF
def _cf_cell(arch: ArchConfig, shape: ShapeSpec, mesh: Mesh, variant: str = "base") -> Cell:
    from repro.core.types import round_up

    spec = arch.model
    d = shape.dims
    baxes = _batch_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in baxes])) if baxes else 1
    u = round_up(d["n_users"], max(n_shards, 1) * 8)
    p_items = d["n_items"]
    n_lm = d.get("n_landmarks", spec.n_landmarks)
    dtype = jnp.bfloat16 if u > 100_000 else jnp.float32
    # pod-scale: 2D-shard the rating block (users × data, items × model) —
    # the d1 moments contract over the sharded item axis (partial + psum) and
    # the mask/square temporaries stay tile-sized.
    model_ok = u > 100_000 and "model" in mesh.axis_names and p_items % mesh.shape["model"] == 0
    ratings = _sds((u, p_items), dtype, mesh, P(baxes, "model" if model_ok else None))

    if shape.kind == "cf_fit":
        key = _sds((2,), jnp.uint32, mesh, P(None))
        podscale = u > 100_000  # shard_map graph build instead of GSPMD

        def step(key, r):
            # Every cf_fit cell emits the O(U·k) NeighborGraph — the (U, U)
            # similarity matrix never exists in any variant.
            idx = core_selection.select_landmarks(key, r, n_lm, spec.selection)
            landmarks = r[idx]  # replicated (n, P)
            # d1 moments contract over the (possibly model-sharded) item axis
            # (local partial + psum — tile-sized temporaries; on TPU the fused
            # Pallas kernel replaces this schedule).
            rep = core_similarity.masked_similarity(r, landmarks, spec.d1)
            if podscale and variant == "fused":
                # §Perf hillclimb: fused sims+top-k Pallas kernel — the
                # (U_loc, chunk) sims tiles never leave VMEM, and the rep
                # moves as bf16 (2x wire+HBM). Self-exclusion happens outside
                # the kernel (each shard lacks its global row offset): emit
                # k+1, mask own ids, re-top-k to k.
                from jax import shard_map
                from jax.sharding import PartitionSpec as PS
                from repro.kernels.knn_topk import topk_sim_kernel

                repn = rep / jnp.maximum(
                    jnp.linalg.norm(rep, axis=1, keepdims=True), 1e-8
                )
                repn = repn.astype(jnp.bfloat16)
                vals, nbrs = shard_map(
                    lambda rl, rfull: topk_sim_kernel(
                        rl, rfull, k=spec.k_neighbors + 1, block=(1024, 512)
                    ),
                    mesh=mesh,
                    in_specs=(PS(baxes, None), PS(None, None)),
                    out_specs=(PS(baxes, None), PS(baxes, None)),
                    check_vma=False,
                )(repn, repn)
                vals, nbrs = core_graph.filter_self_from_topk(
                    vals, nbrs, jnp.arange(u), spec.k_neighbors)
            elif podscale:
                vals, nbrs = core_similarity.streaming_knn_graph_sharded(
                    rep, mesh, spec.d2, k=spec.k_neighbors, chunk_local=512,
                    exclude_self=True,
                )
            else:
                # rules pins the scan carry row-sharded — unconstrained, GSPMD
                # would replicate the (U, chunk) sims tile on every device.
                vals, nbrs = core_similarity.streaming_knn_graph(
                    rep, spec.d2, k=spec.k_neighbors, chunk=min(4096, u),
                    rules=filter_rules(arch.rules, mesh), exclude_self=True,
                )
            graph = core_graph.finalize_topk(vals, nbrs)
            return idx, rep, graph.weights, graph.indices

        return Cell(arch, shape, mesh, step, (key, ratings))

    # cf_predict: kNN Eq.1 over the fitted (U, k) NeighborGraph
    nbr_w = _sds((u, spec.k_neighbors), jnp.float32, mesh, P(baxes, None))
    nbr_i = _sds((u, spec.k_neighbors), jnp.int32, mesh, P(baxes, None))
    pairs = d["n_pairs"]
    users = _sds((pairs,), jnp.int32, mesh, P(baxes))
    items = _sds((pairs,), jnp.int32, mesh, P(baxes))

    def step(nbr_w, nbr_i, r, users, items):
        graph = NeighborGraph(nbr_i, nbr_w)
        return core_knn.predict_pairs_graph(graph, r, users, items)

    return Cell(arch, shape, mesh, step, (nbr_w, nbr_i, ratings, users, items))


# ----------------------------------------------------------------- dispatcher
def build_cell(arch: ArchConfig, shape_name: str, mesh: Mesh, variant: str = "base") -> Cell:
    shape = arch.shape(shape_name)
    if arch.family == "lm":
        if shape.kind == "train":
            return _lm_train_cell(arch, shape, mesh)
        if shape.kind == "prefill":
            return _lm_prefill_cell(arch, shape, mesh)
        if shape.kind == "decode":
            if variant == "kv_int8":
                arch = dataclasses.replace(
                    arch, model=dataclasses.replace(arch.model, kv_quant=True))
                return _lm_decode_cell(arch, shape, mesh, False)
            return _lm_decode_cell(arch, shape, mesh, variant == "landmark")
        raise ValueError(shape.kind)
    if arch.family == "gnn":
        return _gnn_train_cell(arch, shape, mesh, variant)
    if arch.family == "recsys":
        return _rec_cell(arch, shape, mesh)
    if arch.family == "cf":
        return _cf_cell(arch, shape, mesh, variant)
    raise ValueError(arch.family)
