"""Explicit ZeRO-1 over the data axis, built from FMI collectives.

Instead of an allreduce(grads) followed by a replicated optimizer update,
each data rank owns 1/P of every parameter:

    grad chunk   = FMI reduce_scatter(grads)          (same bytes as ring AR phase 1)
    local update = AdamW on the owned chunk           (P x less optimizer FLOPs/memory)
    new params   = FMI allgather(updated chunk)       (ring AR phase 2 bytes)

Total communication equals one ring allreduce, but moment memory drops by
the data-parallel degree — the standard ZeRO-1 trade realized with the
paper's collective library.

Sharding is per leaf, by rows: each leaf is viewed as the matrix
``[prod(shape[:-1]), shape[-1]]``, its rows are
zero-padded to a multiple of ``ROWS · P``, and rank r owns row block r.
The collectives run with ``rows=True`` and never ravel: on the TPU a ravel
is a relayout copy, and over a billion-parameter flat vector the compiler
took minutes to place it.  The zero padding stays zero through the update
(zero gradient, zero moments, zero weight).

Both collective phases go through the nonblocking request layer
(:mod:`repro.core.requests`) in issue-all-then-waitall form: same
arithmetic as the old per-group blocking loop, but the program no longer
*orders* group k+1's collective after group k's wait — on the mesh
transport the traced issue order is the hint XLA's async scheduler
overlaps from (the eager software channels complete each collective at
issue; see ``requests._issue``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from ..core import collectives as C
from ..core import requests as R
from ..core.communicator import Communicator

ROWS = 8  # sublanes: every rank's row block is whole TPU tiles


@dataclass(frozen=True)
class RowLayout:
    """Static description of ZeRO-1's per-leaf row blocks."""

    treedef: Any
    shapes: tuple  # leaf shapes
    rows: tuple  # rows of each leaf's [rows, cols] view
    padded_rows: tuple  # rows padded to a multiple of ROWS * P
    cols: tuple


def make_layout(tree, P: int) -> RowLayout:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    rows = tuple(math.prod(s[:-1]) for s in shapes)
    return RowLayout(
        treedef=treedef,
        shapes=shapes,
        rows=rows,
        padded_rows=tuple(-(-r // (ROWS * P)) * ROWS * P for r in rows),
        cols=tuple(s[-1] if s else 1 for s in shapes),
    )


def _row_view(leaf, layout: RowLayout, i: int):
    x = leaf.reshape(layout.rows[i], layout.cols[i])
    pad = layout.padded_rows[i] - layout.rows[i]
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


@dataclass(frozen=True)
class FlatLayout:
    """Static description of the per-dtype flattening of a pytree."""

    treedef: Any
    dtypes: tuple  # group dtypes, in order
    group_leaf_idx: tuple  # tuple of tuples: leaf indices per group
    leaf_shapes: tuple
    leaf_sizes: tuple


def make_flat_layout(tree) -> FlatLayout:
    leaves, treedef = jax.tree.flatten(tree)
    groups: dict = {}
    for i, leaf in enumerate(leaves):
        groups.setdefault(jnp.dtype(leaf.dtype), []).append(i)
    return FlatLayout(
        treedef=treedef,
        dtypes=tuple(groups),
        group_leaf_idx=tuple(tuple(idxs) for idxs in groups.values()),
        leaf_shapes=tuple(tuple(l.shape) for l in leaves),
        leaf_sizes=tuple(math.prod(l.shape) for l in leaves),
    )


def flatten_groups(tree, layout: FlatLayout) -> list:
    leaves = jax.tree.leaves(tree)
    return [jnp.concatenate([leaves[i].reshape(-1).astype(dt) for i in idxs])
            for dt, idxs in zip(layout.dtypes, layout.group_leaf_idx)]


def unflatten_groups(flats: list, layout: FlatLayout):
    leaves: list = [None] * len(layout.leaf_shapes)
    for flat, idxs in zip(flats, layout.group_leaf_idx):
        off = 0
        for i in idxs:
            n = layout.leaf_sizes[i]
            leaves[i] = jax.lax.dynamic_slice_in_dim(flat, off, n).reshape(
                layout.leaf_shapes[i]
            )
            off += n
    return jax.tree.unflatten(layout.treedef, leaves)


def zero1_init(params, layout: RowLayout, comm: Communicator, state_dtype):
    """Local moment row blocks (each rank holds its 1/P of every leaf)."""
    dt = jnp.dtype(state_dtype)
    blocks = [(r // comm.size, c) for r, c in zip(layout.padded_rows, layout.cols)]
    return {
        "m": [jnp.zeros(b, dt) for b in blocks],
        "v": [jnp.zeros(b, dt) for b in blocks],
        "step": jnp.zeros((), jnp.int32),
    }


def zero1_update(grads, state, params, layout: RowLayout, comm: Communicator,
                 opt_cfg, algorithm: str = "recursive_halving",
                 ag_algorithm: str = "recursive_doubling", mean: bool = True):
    """Reduce-scatter -> sharded AdamW -> allgather.  Call inside shard_map
    (manual over comm.axes)."""
    from ..optim.optimizer import lr_at

    g_rows = [_row_view(g, layout, i) for i, g in enumerate(jax.tree.leaves(grads))]
    p_rows = [_row_view(p, layout, i) for i, p in enumerate(jax.tree.leaves(params))]
    P = comm.size

    step = state["step"] + 1
    lr = lr_at(opt_cfg, state["step"])
    b1, b2 = opt_cfg.beta1, opt_cfg.beta2
    c1 = 1 - b1 ** step.astype(jnp.float32)
    c2 = 1 - b2 ** step.astype(jnp.float32)

    # phase 1: reduce-scatter every leaf through the request layer,
    # issue-all-then-waitall — no program-order barrier between leaves
    # (see the module docstring for what overlap this does and does not buy)
    rs_reqs = [
        R.ireduce_scatter(g, comm, op="add", algorithm=algorithm, rows=True)
        for g in g_rows
    ]
    chunks = [c / P if mean else c for c in R.waitall(rs_reqs)]

    # global-norm clip on the *reduced* gradient: each rank owns 1/P of
    # every leaf, so the global sq-norm is an allreduce of chunk sq-norms
    gnorm = jnp.zeros((), jnp.float32)
    if opt_cfg.clip_norm:
        local_sq = sum(jnp.sum(jnp.square(c.astype(jnp.float32))) for c in chunks)
        total_sq = C.allreduce(local_sq[None], comm, algorithm="recursive_doubling")[0]
        gnorm = jnp.sqrt(total_sq)
        scale = jnp.minimum(1.0, opt_cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
        chunks = [(c.astype(jnp.float32) * scale).astype(c.dtype) for c in chunks]

    # phase 2: sharded AdamW per leaf, then the allgather of every updated
    # row block through the request layer, all issued before any is waited on
    new_m, new_v, ag_reqs = [], [], []
    try:
        r = comm.transport().rank()
        for i, (chunk, pr) in enumerate(zip(chunks, p_rows)):
            own = jax.lax.dynamic_slice_in_dim(pr, r * chunk.shape[0], chunk.shape[0])
            gfl = chunk.astype(jnp.float32)
            m = b1 * state["m"][i].astype(jnp.float32) + (1 - b1) * gfl
            v = b2 * state["v"][i].astype(jnp.float32) + (1 - b2) * gfl * gfl
            upd = (m / c1) / (jnp.sqrt(v / c2) + opt_cfg.eps)
            upd = upd + opt_cfg.weight_decay * own.astype(jnp.float32)
            own_new = (own.astype(jnp.float32) - lr * upd).astype(pr.dtype)
            ag_reqs.append(R.iallgather(own_new, comm, algorithm=ag_algorithm, rows=True))
            new_m.append(m.astype(state["m"][i].dtype))
            new_v.append(v.astype(state["v"][i].dtype))
        gathered = R.waitall(ag_reqs)
    except BaseException:
        # a failure mid-issue (e.g. RankFailure) must not strand the already
        # issued allgathers — cancel them so the elastic quiesce sees a clean
        # queue instead of stale-generation in-flight requests
        for req in ag_reqs:
            req.cancel()
        raise
    new_p = [full[: layout.rows[i]].reshape(layout.shapes[i])
             for i, full in enumerate(gathered)]
    params_new = jax.tree.unflatten(layout.treedef, new_p)
    return params_new, {"m": new_m, "v": new_v, "step": step}, {"lr": lr, "grad_norm": gnorm}
