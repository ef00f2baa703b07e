"""Channel MLPs: dense (Swi)GLU / GELU, and routed mixture-of-experts.

MoE dispatch has two implementations (config ``dispatch``):

* ``einsum`` — GShard-style capacity-based dispatch: a [B, S, E, C] one-hot
  routes tokens into a [B, E, C, D] buffer with one einsum per top-k slot.
  Partitions perfectly under GSPMD (E over the model axis → all-to-all),
  but the dispatch/combine einsums are real MXU FLOPs (≈ doubles MoE cost).
* ``scatter`` — sort-free scatter-add into the [B, E·C, D] buffer + gather
  combine; no dispatch FLOPs, but leans on GSPMD's scatter partitioning.

The §Perf hillclimb compares both on the compiled HLO (see EXPERIMENTS.md).

Expert parallelism: the expert dim E is sharded over the 'model' mesh axis
(EP); tokens cross that axis via the all-to-all GSPMD derives from the
sharding constraints.  Capacity is per sequence: C = ceil(S·k/E · factor);
overflow tokens are dropped (their residual passes through — standard
capacity-based MoE semantics).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import Axes, dense_init, swiglu


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None):
    D = cfg.d_model
    F = d_ff if d_ff is not None else cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.family == "audio":  # hubert: classic 2-matrix GELU MLP
        return {
            "up": dense_init(ks[0], (D, F), cfg.pdtype),
            "down": dense_init(ks[1], (F, D), cfg.pdtype),
        }
    return {
        "gate": dense_init(ks[0], (D, F), cfg.pdtype),
        "up": dense_init(ks[1], (D, F), cfg.pdtype),
        "down": dense_init(ks[2], (F, D), cfg.pdtype),
    }


def mlp_apply(p, x, cfg: ModelConfig, ax: Axes):
    dt = cfg.adtype
    if "gate" in p:
        h = swiglu(x @ p["gate"].astype(dt), x @ p["up"].astype(dt))
    else:
        h = jax.nn.gelu(x @ p["up"].astype(dt))
    h = ax.act_btf(h)
    return ax.act_btd(h @ p["down"].astype(dt))


# ---------------------------------------------------------------------------
# routed MoE
# ---------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig):
    m = cfg.moe
    D, E, Fe = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "gate": dense_init(ks[1], (E, D, Fe), cfg.pdtype, fan_in=D),
        "up": dense_init(ks[2], (E, D, Fe), cfg.pdtype, fan_in=D),
        "down": dense_init(ks[3], (E, Fe, D), cfg.pdtype, fan_in=Fe),
    }
    if m.n_shared:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=m.n_shared * Fe)
    return p


def _route(p, x, cfg: ModelConfig):
    """Returns (weights [B,S,K], expert ids [B,S,K], aux load-balance loss)."""
    m = cfg.moe
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if m.router_softmax:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)
    else:  # llama4-style sigmoid scores
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, m.top_k)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    E = m.n_experts
    me = jnp.mean(probs, axis=(0, 1))  # mean router prob per expert
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], E), axis=(0, 1))  # routed fraction
    aux = E * jnp.sum(me * ce)
    return w.astype(x.dtype), idx, aux


def moe_apply(p, x, cfg: ModelConfig, ax: Axes, dispatch: str | None = None):
    """x: [B, S, D] -> [B, S, D].  Returns (out, aux_loss).

    ``fmi`` dispatch (default for EP archs): explicit shard_map over the
    model axis.  x is TP-replicated when it reaches the MoE, so each shard
    scatters *locally* into its own experts' [E_loc, C, D] buffer (zero
    dispatch communication and zero dispatch FLOPs) and the partial outputs
    meet in ONE allreduce of [B, S, D] per layer — the same wire cost as a
    Megatron MLP.  GShard 'einsum' (dispatch-FLOPs-heavy) and global
    'scatter' (GSPMD-partitioning-hostile) are kept for the §Perf ablation.
    """
    m = cfg.moe
    B, S, D = x.shape
    E, K, Fe = m.n_experts, m.top_k, m.d_ff_expert
    C = max(1, math.ceil(S * K / E * m.capacity_factor))
    dt = cfg.adtype
    if dispatch is None:
        dispatch = m.dispatch

    w, idx, aux = _route(p, x, cfg)
    e_axis = ax.model if ax.divides(E, ax.model) else None
    if dispatch == "fmi" and (e_axis is None or ax.axsize(ax.model) <= 1):
        dispatch = "scatter"  # no EP axis available (single device / tests)

    # slot positions: for each (s, k) routed pair, its position within the
    # expert's capacity buffer (counted over the flattened (s, k) stream)
    oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)  # [B, S, K, E]
    flat = oh.reshape(B, S * K, E)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat  # [B, S*K, E]
    pos = jnp.sum(pos_in_e * flat, axis=-1).reshape(B, S, K)  # [B, S, K]
    keep = pos < C

    gate_w, up_w, down_w = (p[n].astype(dt) for n in ("gate", "up", "down"))

    if dispatch == "fmi":
        out = _moe_fmi(
            p, x, idx, w, pos, keep, cfg, ax, C, gate_w, up_w, down_w
        )
    elif dispatch == "einsum":
        buf = jnp.zeros((B, E, C, D), dt)
        for k in range(K):  # K small (<= 6); per-slot einsum keeps temps ~[B,S,E,C]
            d_k = (
                jax.nn.one_hot(idx[:, :, k], E, dtype=dt)
                * keep[:, :, k : k + 1].astype(dt)
            )  # [B, S, E]
            slot_k = jax.nn.one_hot(pos[:, :, k], C, dtype=dt)  # [B, S, C]
            disp = jnp.einsum("bse,bsc->bsec", d_k, slot_k)
            buf = buf + jnp.einsum("bsec,bsd->becd", disp, x)
        buf = ax.constrain(buf, P(ax.data, e_axis, None, None))
        h = swiglu(
            jnp.einsum("becd,edf->becf", buf, gate_w),
            jnp.einsum("becd,edf->becf", buf, up_w),
        )
        h = ax.constrain(h, P(ax.data, e_axis, None, None))
        eout = jnp.einsum("becf,efd->becd", h, down_w)  # [B, E, C, D]
        eout = ax.constrain(eout, P(ax.data, e_axis, None, None))
        out = jnp.zeros((B, S, D), dt)
        for k in range(K):
            d_k = (
                jax.nn.one_hot(idx[:, :, k], E, dtype=dt)
                * keep[:, :, k : k + 1].astype(dt)
                * w[:, :, k : k + 1]
            )
            slot_k = jax.nn.one_hot(pos[:, :, k], C, dtype=dt)
            comb = jnp.einsum("bse,bsc->bsec", d_k, slot_k)
            out = out + jnp.einsum("bsec,becd->bsd", comb, eout)
    elif dispatch == "scatter":
        # flat target slot e*C + c (dropped tokens land in a trash row E*C)
        tgt = jnp.where(keep, idx * C + pos, E * C).reshape(B, S * K)  # [B, S*K]
        x_rep = jnp.repeat(x, K, axis=1)  # [B, S*K, D]
        buf = jnp.zeros((B, E * C + 1, D), dt)
        buf = buf.at[jnp.arange(B)[:, None], tgt].add(x_rep)
        buf = buf[:, : E * C].reshape(B, E, C, D)
        buf = ax.constrain(buf, P(ax.data, e_axis, None, None))
        h = swiglu(
            jnp.einsum("becd,edf->becf", buf, gate_w),
            jnp.einsum("becd,edf->becf", buf, up_w),
        )
        eout = jnp.einsum("becf,efd->becd", h, down_w).reshape(B, E * C, D)
        eout = jnp.concatenate([eout, jnp.zeros((B, 1, D), dt)], axis=1)
        picked = eout[jnp.arange(B)[:, None], tgt].reshape(B, S, K, D)
        out = jnp.einsum("bskd,bsk->bsd", picked, w * keep.astype(dt))
    else:
        raise ValueError(f"unknown dispatch {dispatch!r}")

    if m.n_shared:
        out = out + mlp_apply(p["shared"], x, cfg, ax)
    return ax.act_btd(out), aux


def _moe_fmi(p, x, idx, w, pos, keep, cfg: ModelConfig, ax: Axes, C: int,
             gate_w, up_w, down_w):
    """Fully-manual EP block: shard_map over (data axes + model).

    Each chip: (1) FMI-allgathers its experts' FSDP weight shards over the
    data axis (ring ppermutes — differentiable, so the backward is the
    matching reduce-scatter for free), (2) scatters its *local batch shard*
    tokens into its own experts' [E_loc, C, D] buffer — no dispatch
    communication, since x is replicated over the model axis — and
    (3) psums the partial outputs over the model axis: one activation
    allreduce per layer, the same wire bytes as a Megatron MLP.
    """
    from ..core import collectives as COLL
    from ..core.communicator import Communicator

    m = cfg.moe
    B, S, D = x.shape
    E, K = m.n_experts, m.top_k
    dt = cfg.adtype
    tp = ax.axsize(ax.model)
    E_loc = E // tp
    w_keep = (w * keep.astype(dt)).astype(dt)

    # tokens whole (not sequence-sharded) entering the EP region
    tok_spec = P(ax.data, None, None) if ax.data else P(None, None, None)
    x = ax.constrain(x, tok_spec)
    idx = ax.constrain(idx, tok_spec)
    w_keep = ax.constrain(w_keep, tok_spec)
    pos = ax.constrain(pos, tok_spec)

    fsdp_axes = tuple(a for a in ax.fsdp if a != ax.model)
    fsdp_deg = ax.axsize(fsdp_axes) if fsdp_axes else 1
    gather_weights = fsdp_deg > 1
    comm_fsdp = (
        Communicator(axes=fsdp_axes, sizes=tuple(ax.sizes[a] for a in fsdp_axes))
        if gather_weights
        else None
    )
    w_spec = P(ax.model, fsdp_axes if gather_weights else None, None)
    manual = set(ax.data) | {ax.model} | set(fsdp_axes)

    def gather_dim1(wl, full_dim1: int):
        """FMI-allgather the FSDP-sharded dim-1 of an expert weight."""
        if not gather_weights:
            return wl
        e, d_loc, f = wl.shape
        flat = COLL.allgather(wl.reshape(-1), comm_fsdp, algorithm="ring")
        fullw = flat.reshape(fsdp_deg, e, d_loc, f)
        return jnp.moveaxis(fullw, 0, 1).reshape(e, fsdp_deg * d_loc, f)

    def body(xl, idxl, wl, posl, gw, uw, dw):
        b_loc = xl.shape[0]
        gw = gather_dim1(gw, D)
        uw = gather_dim1(uw, D)
        dw = gather_dim1(dw, m.d_ff_expert)
        rank = jax.lax.axis_index(ax.model)
        base = rank * E_loc
        local = (idxl >= base) & (idxl < base + E_loc)
        tgt = jnp.where(local, (idxl - base) * C + posl, E_loc * C)  # [b,S,K]
        rows = jnp.arange(b_loc)[:, None]
        buf = jnp.zeros((b_loc, E_loc * C + 1, D), dt)
        for k in range(K):  # per-slot scatter: transients stay [b, S, D]
            buf = buf.at[rows, tgt[:, :, k]].add(xl)
        buf = buf[:, : E_loc * C].reshape(b_loc, E_loc, C, D)
        h = swiglu(
            jnp.einsum("becd,edf->becf", buf, gw),
            jnp.einsum("becd,edf->becf", buf, uw),
        )
        eout = jnp.einsum("becf,efd->becd", h, dw).reshape(b_loc, E_loc * C, D)
        eout = jnp.concatenate([eout, jnp.zeros((b_loc, 1, D), dt)], axis=1)
        part = jnp.zeros((b_loc, S, D), dt)
        for k in range(K):
            picked = eout[rows, tgt[:, :, k]]  # [b, S, D]
            part = part + picked * (wl[:, :, k] * local[:, :, k].astype(dt))[..., None]
        # NB: psum stays in the activation dtype — an f32 upcast here poisons
        # the whole backward into f32 (f32 expert-grad stacks, ~3x memory).
        # XLA:CPU's all-reduce-promotion pass crashes on some bf16
        # all-reduces; the dry-run disables that pass (see launch/dryrun.py).
        return jax.lax.psum(part, ax.model)

    return jax.shard_map(
        body,
        in_specs=(tok_spec, tok_spec, tok_spec, tok_spec, w_spec, w_spec, w_spec),
        out_specs=tok_spec,
        axis_names=manual,
        check_vma=False,
    )(x, idx, w_keep, pos, gate_w, up_w, down_w)
