"""End-to-end training behaviour on the host device: loss decreases, both
distribution modes run, grad accumulation is consistent, checkpoint resume
reproduces the trajectory exactly."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import DataConfig, synthetic_batch
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.optim.optimizer import OptConfig
from repro.training.train_step import TrainConfig, init_opt_state, make_train_step

TINY = configs.get_reduced("llama3_2_1b", n_layers=2, d_model=64, n_heads=4,
                           n_kv_heads=2, d_ff=128, vocab_size=256, head_dim=16)


def _run(cfg, tcfg, steps=30, batch=8, seq=32, seed=0):
    mesh = make_host_mesh(1, 1)
    step_fn, ax, _ = make_train_step(cfg, tcfg, mesh, multi_pod=False)
    dcfg = DataConfig(seed=seed)
    with jax.set_mesh(mesh):
        params = lm.init_params(cfg, jax.random.key(seed))
        opt = init_opt_state(cfg, tcfg, params)
        losses = []
        for s in range(steps):
            b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, cfg, batch, seq, s))
            params, opt, m = step_fn(params, opt, b)
            losses.append(float(m["ce"]))
    return losses, params, opt


def test_loss_decreases_xla_mode():
    tcfg = TrainConfig(mode="xla", optimizer=OptConfig(lr=1e-3, warmup_steps=5,
                                                       total_steps=30))
    losses, _, _ = _run(TINY, tcfg)
    assert losses[-1] < losses[0] - 0.2, f"no learning: {losses[0]} -> {losses[-1]}"
    assert all(np.isfinite(losses))


def test_grad_accum_matches_single_batch():
    opt = OptConfig(lr=1e-3, warmup_steps=0, total_steps=10, clip_norm=0.0)
    t1 = TrainConfig(mode="xla", microbatches=1, optimizer=opt, donate=False)
    t2 = TrainConfig(mode="xla", microbatches=4, optimizer=opt, donate=False)
    mesh = make_host_mesh(1, 1)
    s1, _, _ = make_train_step(TINY, t1, mesh, False)
    s2, _, _ = make_train_step(TINY, t2, mesh, False)
    dcfg = DataConfig()
    with jax.set_mesh(mesh):
        params = lm.init_params(TINY, jax.random.key(0))
        o1 = init_opt_state(TINY, t1, params)
        o2 = init_opt_state(TINY, t2, params)
        b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, TINY, 8, 32, 0))
        p1, _, m1 = s1(params, o1, b)
        p2, _, m2 = s2(params, o2, b)
    assert abs(m1["loss"] - m2["loss"]) < 2e-2  # same data, averaged microbatches
    d = max(float(jnp.abs(a - b).max()) for a, b in
            zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 2e-4  # parameter updates agree


def test_checkpoint_resume_exact(tmp_path):
    from repro.checkpoint import CheckpointManager

    tcfg = TrainConfig(mode="xla", optimizer=OptConfig(lr=1e-3, warmup_steps=0,
                                                       total_steps=20), donate=False)
    mesh = make_host_mesh(1, 1)
    step_fn, _, _ = make_train_step(TINY, tcfg, mesh, False)
    dcfg = DataConfig()

    def advance(params, opt, start, n):
        hist = []
        for s in range(start, start + n):
            b = jax.tree.map(jnp.asarray, synthetic_batch(dcfg, TINY, 4, 32, s))
            params, opt, m = step_fn(params, opt, b)
            hist.append(float(m["loss"]))
        return params, opt, hist

    with jax.set_mesh(mesh):
        params = lm.init_params(TINY, jax.random.key(0))
        opt = init_opt_state(TINY, tcfg, params)
        # continuous 10-step run
        p_ref, o_ref, h_ref = advance(params, opt, 0, 10)
        # run 5, checkpoint, restore into fresh state, run 5 more
        p5, o5, h_first = advance(params, opt, 0, 5)
        mgr = CheckpointManager(str(tmp_path))
        mgr.save_async({"params": p5, "opt": o5}, 5)
        mgr.wait()
        shapes = jax.eval_shape(lambda: {"params": params, "opt": opt})
        state, step = mgr.restore_latest(shapes)
        assert step == 5
        p_res, o_res, h_resumed = advance(state["params"], state["opt"], 5, 5)

    np.testing.assert_allclose(h_first + h_resumed, h_ref, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_res)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   atol=1e-6)


def test_trainer_wrapper_runs():
    from repro.training.trainer import Trainer

    mesh = make_host_mesh(1, 1)
    tr = Trainer(cfg=TINY, tcfg=TrainConfig(mode="xla"), mesh=mesh, batch=4, seq=32)
    params, opt = tr.init_state()
    params, opt, hist = tr.run(params, opt, steps=3)
    assert len(hist) == 3
    assert np.isfinite(hist[-1]["loss"])
