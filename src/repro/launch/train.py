"""Training launcher: ``python -m repro.launch.train --arch smollm-360m ...``

Runs real steps on the available devices (host mesh by default). The same
cell builders drive the 256/512-chip dry-run; on a real pod this script is
what each host executes (jax.distributed handles the process group).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.base import ShapeSpec
from repro.data import synthetic as S
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.steps import build_cell
from repro.train.optimizer import opt_init
from repro.train.trainer import TrainerConfig, train_loop
from repro.models import gnn as gnn_mod
from repro.models import recsys as rec_mod
from repro.models import transformer as lm_mod

_REC_INIT = {"fm": rec_mod.init_fm, "bert4rec": rec_mod.init_bert4rec,
             "mind": rec_mod.init_mind, "dien": rec_mod.init_dien}


def _batches(arch, shape, smoke: bool):
    cfg = arch.smoke_model if smoke else arch.model
    step = 0
    while True:
        if arch.family == "lm":
            b, s = (4, 128) if smoke else (shape.dims["batch"], shape.dims["seq"])
            yield {k: jnp.asarray(v) for k, v in
                   S.lm_batch(0, step, b, s, cfg.vocab).items()}
        elif arch.family == "gnn":
            g = S.random_graph(step, 200, 800, cfg.d_feat, cfg.n_classes,
                               pad_edges_to=1024)
            yield {k: jnp.asarray(v) for k, v in g.items()}
        else:
            if arch.name == "fm":
                b = S.fm_train_batch(0, step, 256, cfg.field_vocabs)
            elif arch.name == "bert4rec":
                b = S.seq_rec_batch(0, step, 32, cfg.seq_len, cfg.n_items,
                                    n_mask=max(1, cfg.seq_len // 5),
                                    n_negatives=cfg.n_negatives)
            elif arch.name == "mind":
                b = S.seq_rec_batch(0, step, 32, cfg.seq_len, cfg.n_items,
                                    n_negatives=cfg.n_negatives)
            else:
                b = S.seq_rec_batch(0, step, 32, cfg.seq_len, cfg.n_items)
            yield {k: jnp.asarray(v) for k, v in b.items()}
        step += 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    arch = registry.get(args.arch)
    shape_name = args.shape or arch.shapes[0].name
    mesh = (make_production_mesh(multi_pod=args.multi_pod)
            if args.production_mesh else make_host_mesh())

    if args.smoke:
        arch = dataclasses.replace(arch, model=arch.smoke_model, grad_accum={})
        cfg = arch.model
        if arch.family == "lm":
            shape = ShapeSpec(shape_name, "train", dict(batch=4, seq=128))
        elif arch.family == "gnn":
            shape = ShapeSpec(shape_name, "train_graph",
                              dict(n_nodes=200, n_edges=800, d_feat=cfg.d_feat,
                                   n_classes=cfg.n_classes))
        else:
            shape = ShapeSpec(shape_name, "train", dict(batch=256 if arch.name == "fm" else 32))
        arch = dataclasses.replace(arch, shapes=(shape,))

    cell = build_cell(arch, shape_name, mesh)
    step_jit = cell.jit()

    # init real state
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        if arch.family == "lm":
            params = lm_mod.init_lm(key, arch.model)
        elif arch.family == "gnn":
            cfg = dataclasses.replace(
                arch.model,
                d_feat=arch.shapes[0].dims.get("d_feat", arch.model.d_feat),
                n_classes=arch.shapes[0].dims.get("n_classes", arch.model.n_classes),
            )
            params = gnn_mod.init_gnn(key, cfg)
        else:
            params = _REC_INIT[arch.name](key, arch.model)
        opt_state = opt_init(params, arch.opt)

    def step_fn(params, opt_state, batch):
        with jax.set_mesh(mesh):
            return step_jit(params, opt_state, batch)

    out = train_loop(
        step_fn, params, opt_state,
        _batches(arch, arch.shapes[0], args.smoke),
        TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                      ckpt_every=max(args.steps // 2, 1), log_every=10),
    )
    print(f"final loss {out['losses'][-1]:.4f} after {out['last_step'] + 1} steps; "
          f"stragglers flagged: {len(out['stragglers'])}")


if __name__ == "__main__":
    main()
