"""End-to-end behaviour: the paper's pipeline + trainer fault tolerance."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines import fit_mf, predict_mf, rsvd_config
from repro.core import LandmarkSpec, fit, fit_baseline, predict
from repro.data.ratings import kfold_split, mae, synthesize
from repro.data import synthetic as S
from repro.distributed.sharding import DEFAULT_RULES
from repro.models import transformer as lm_mod
from repro.train.optimizer import opt_init, opt_update
from repro.train.trainer import TrainerConfig, train_loop
from repro.configs import registry


def test_paper_pipeline_flops_linear_in_landmarks():
    """Claim C1: landmark fit cost grows ~linearly with n (HLO flops proxy)."""
    data = synthesize("movielens100k", seed=0)
    m = data.to_matrix(slice(None))
    flops = []
    for n in (10, 40, 80):
        spec = LandmarkSpec(n_landmarks=n, selection="random")
        lowered = jax.jit(
            lambda key, r: fit(key, type(m)(r, m.n_users, m.n_items), spec,
                               dense_sims=True).sims
        ).lower(jax.random.PRNGKey(0), m.ratings)
        cost = lowered.compile().cost_analysis()
        flops.append(cost["flops"])
    ratio = flops[2] / flops[0]
    assert 3.0 < ratio < 16.0, (flops, ratio)


def test_full_comparative_pipeline_runs():
    """Landmark kNN + one memory baseline + one model baseline on one fold."""
    data = synthesize("movielens100k", seed=5)
    tr, te = kfold_split(data, 0)
    te = te[:4000]
    m = data.to_matrix(tr)
    pu, pi = jnp.asarray(data.users[te]), jnp.asarray(data.items[te])
    spec = LandmarkSpec(n_landmarks=20, selection="popularity")

    st = fit(jax.random.PRNGKey(0), m, spec)
    lm_err = mae(np.asarray(predict(st, pu, pi, spec)), data.ratings[te])

    stb = fit_baseline(m, "cosine")
    knn_err = mae(np.asarray(predict(stb, pu, pi, spec)), data.ratings[te])

    cfg = rsvd_config(data.n_users, data.n_items, epochs=5)
    params, aux = fit_mf(data.users[tr], data.items[tr], data.ratings[tr], cfg)
    mf_err = mae(
        np.clip(np.asarray(predict_mf(params, cfg, data.users[te], data.items[te], aux)), 1, 5),
        data.ratings[te],
    )
    assert lm_err < 1.1 and knn_err < 1.2 and mf_err < 1.2
    assert lm_err <= knn_err + 0.02  # paper claim C3


def test_trainer_checkpoints_and_resumes(tmp_path):
    arch = registry.get("smollm-360m")
    cfg = arch.smoke_model
    params = lm_mod.init_lm(jax.random.PRNGKey(0), cfg)
    opt = opt_init(params, arch.opt)

    def batches():
        step = 0
        while True:
            b = S.lm_batch(0, step, 2, 16, cfg.vocab)
            yield {k: jnp.asarray(v) for k, v in b.items()}
            step += 1

    @jax.jit
    def step_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(
            lambda p: lm_mod.lm_loss(p, batch, cfg, DEFAULT_RULES)
        )(params)
        params, opt = opt_update(params, grads, opt, arch.opt)
        return params, opt, {"loss": loss}

    tc = TrainerConfig(total_steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                       log_every=100)
    out1 = train_loop(step_fn, params, opt, batches(), tc, log=lambda *_: None)
    assert len(out1["losses"]) == 6
    assert all(np.isfinite(l) for l in out1["losses"])  # 6 warmup steps: just sane

    # resume: trainer must pick up from step 6 and run the remaining 4
    tc2 = TrainerConfig(total_steps=10, ckpt_dir=str(tmp_path), ckpt_every=100,
                        log_every=100)
    out2 = train_loop(step_fn, params, opt, batches(), tc2, log=lambda *_: None)
    assert out2["last_step"] == 9
    assert len(out2["losses"]) == 4  # only steps 6..9 ran


def test_landmark_state_checkpoint_roundtrip(tmp_path):
    """The serve artifact: save/load a fitted LandmarkState (graph included),
    full and compact, and keep predictions (bf16-tolerant for compact)."""
    from repro.train.checkpoint import load_landmark_state, save_landmark_state

    data = synthesize("movielens100k", seed=2)
    m = data.to_matrix(slice(0, 30_000))
    spec = LandmarkSpec(n_landmarks=8, selection="popularity", k_neighbors=5)
    st = fit(jax.random.PRNGKey(0), m, spec)
    users = jnp.asarray(data.users[:500]); items = jnp.asarray(data.items[:500])
    want = np.asarray(predict(st, users, items, spec))

    save_landmark_state(str(tmp_path / "full"), st)
    got = np.asarray(predict(load_landmark_state(str(tmp_path / "full")),
                             users, items, spec))
    np.testing.assert_array_equal(got, want)

    save_landmark_state(str(tmp_path / "compact"), st, compact=True)
    stc = load_landmark_state(str(tmp_path / "compact"), widen=False)
    assert stc.graph.is_compact
    got_c = np.asarray(predict(load_landmark_state(str(tmp_path / "compact")),
                               users, items, spec))
    np.testing.assert_allclose(got_c, want, rtol=2e-2, atol=2e-2)


def test_serve_cf_smoke_lifecycle(tmp_path, capsys):
    """Acceptance: the CF serve path end-to-end — fit+checkpoint, load,
    predict wave, fold-in, predict wave — prints per-wave latency."""
    from repro.launch import serve

    serve.main([
        "--workload", "cf", "--smoke", "--ckpt", str(tmp_path),
        "--users", "128", "--items", "64", "--requests", "2",
        "--batch", "32", "--foldin", "4", "--waves", "2", "--topn", "3",
    ])
    out = capsys.readouterr().out
    assert "cf serve: done" in out
    assert "fold-in +4 users" in out
    assert out.count("p50=") >= 2  # a latency line per wave
    assert "wave 1: U=132" in out  # second wave sees the folded-in users

    # the artifact persisted: a second serve run loads it instead of refitting
    serve.main(["--workload", "cf", "--smoke", "--ckpt", str(tmp_path),
                "--users", "128", "--items", "64", "--requests", "2",
                "--batch", "32", "--foldin", "4", "--waves", "2"])
    out2 = capsys.readouterr().out
    assert "fit " not in out2 and "loaded U=128" in out2


def test_landmark_decode_is_finite_and_cheap():
    """Landmark O(n)/token decode: state size independent of context length."""
    cfg = registry.get("gemma-7b").smoke_model
    params = lm_mod.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(S.lm_batch(1, 0, 2, 24, cfg.vocab)["tokens"])

    lm_cache = lm_mod.make_landmark_cache(cfg, 2)
    lm_cache["k_lm"] = jax.random.normal(jax.random.PRNGKey(1),
                                         lm_cache["k_lm"].shape, cfg.dtype)
    lm_cache["q_lm"] = jax.random.normal(jax.random.PRNGKey(2),
                                         lm_cache["q_lm"].shape, cfg.dtype)
    state_bytes = sum(
        np.prod(v.shape) * v.dtype.itemsize
        for k, v in lm_cache.items() if hasattr(v, "shape") and v.ndim > 0
    )
    step = jax.jit(lambda p, c, t: lm_mod.lm_landmark_decode_step(p, c, t, cfg,
                                                                  DEFAULT_RULES))
    for t in range(8):
        logits, lm_cache = step(params, lm_cache, toks[:, t : t + 1])
    assert bool(jnp.isfinite(logits).all())
    # the state would be identical at 500k context: O(n_landmarks), not O(S)
    full_cache = lm_mod.make_cache(cfg, 2, 524288)
    full_bytes = sum(np.prod(v.shape) * v.dtype.itemsize
                     for v in (full_cache["k"], full_cache["v"]))
    assert state_bytes * 100 < full_bytes
