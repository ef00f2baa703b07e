"""Optimizers: AdamW (+SGD-momentum), cosine schedule, global-norm clipping.

Pure pytree functions (no framework dependency).  ``state_dtype`` controls
the moment dtype: f32 default; bf16 for the 400B-class models where f32
moments would not fit 16 GiB/chip (recorded in DESIGN.md).  Optimizer
states inherit the parameters' sharding specs (so FSDP-sharded params get
ZeRO-3-sharded moments for free in pjit mode); the fmi mode additionally
implements explicit ZeRO-1 over the data axis (training/zero1.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    state_dtype: str = "float32"


def lr_at(cfg: OptConfig, step):
    """Linear warmup + cosine decay to min_lr_frac."""
    step = jnp.asarray(step, jnp.float32)
    warm = cfg.lr * jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    t = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1), 0, 1
    )
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(jnp.pi * t))
    return jnp.where(step < cfg.warmup_steps, warm, cfg.lr * cos)


def adamw_init(params, cfg: OptConfig):
    dt = jnp.dtype(cfg.state_dtype)
    zeros = lambda p: jnp.zeros(p.shape, dt)  # noqa: E731
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def clip_by_global_norm(grads, max_norm: float):
    if not max_norm:
        return grads, jnp.zeros((), jnp.float32)
    sq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)
    )
    norm = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads), norm


def adamw_update(grads, state, params, cfg: OptConfig):
    """Returns (new_params, new_state, metrics)."""
    with jax.named_scope("optimizer"):
        grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
        step = state["step"] + 1
        lr = lr_at(cfg, state["step"])
        b1, b2 = cfg.beta1, cfg.beta2
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p, g, m, v):
            gf = g.astype(jnp.float32)
            mf = b1 * m.astype(jnp.float32) + (1 - b1) * gf
            vf = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
            mh = mf / c1
            vh = vf / c2
            step_ = mh / (jnp.sqrt(vh) + cfg.eps)
            pf = p.astype(jnp.float32)
            if p.ndim >= 2:  # decoupled weight decay on matrices only
                step_ = step_ + cfg.weight_decay * pf
            return (
                (pf - lr * step_).astype(p.dtype),
                mf.astype(m.dtype),
                vf.astype(v.dtype),
            )

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_params = jax.tree.map(lambda t: t[0], out, is_leaf=lambda t: isinstance(t, tuple))
        new_m = jax.tree.map(lambda t: t[1], out, is_leaf=lambda t: isinstance(t, tuple))
        new_v = jax.tree.map(lambda t: t[2], out, is_leaf=lambda t: isinstance(t, tuple))
        return (
            new_params,
            {"m": new_m, "v": new_v, "step": step},
            {"lr": lr, "grad_norm": gnorm},
        )
