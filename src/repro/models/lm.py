"""Unified language model: one block machinery, ten architectures.

Every arch is a stack of *scan groups* (``cfg.group_size`` layers per group,
``cfg.n_groups`` groups).  Group parameters are stacked on a leading G axis
and the stack lowers as a single ``jax.lax.scan`` (small HLO, fast SPMD
partitioning at 100-layer scale) with optional remat.

Families and their group bodies:

    dense / audio : [attn -> mlp]
    moe           : [attn|mla -> moe]
    vlm           : [4 x (attn -> mlp), cross-attn -> mlp]
    ssm (xlstm)   : [(k-1) x mLSTM, sLSTM]
    hybrid        : [parallel(attn, ssd) -> mlp]

Entry points: :func:`init_params`, :func:`param_specs`, :func:`forward`,
:func:`loss_fn`, :func:`init_cache`, :func:`prefill`, :func:`decode_step`,
:func:`input_specs`, :func:`count_params`.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import attention as ATT
from . import mla as MLA
from . import moe as MOE
from . import ssm as SSM
from .config import ModelConfig
from .layers import Axes, dense_init, embed_init, rmsnorm


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab_size // 128) * 128


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_group(cfg: ModelConfig, key):
    """Parameters of ONE scan group (un-stacked)."""
    fam = cfg.family
    D = cfg.d_model
    ks = iter(jax.random.split(key, 64))
    nx = lambda: next(ks)  # noqa: E731
    ones = lambda: jnp.ones((D,), cfg.pdtype)  # noqa: E731

    if fam in ("dense", "audio"):
        return {
            "ln1": ones(), "attn": ATT.attn_init(nx(), cfg),
            "ln2": ones(), "mlp": MOE.mlp_init(nx(), cfg),
        }
    if fam == "moe":
        mixer = (
            {"mla": MLA.mla_init(nx(), cfg)}
            if cfg.mla
            else {"attn": ATT.attn_init(nx(), cfg)}
        )
        g = {"ln1": ones(), **mixer, "ln2": ones(), "moe": MOE.moe_init(nx(), cfg)}
        n_dense = cfg.moe.every_k - 1  # llama4: dense layers between MoE layers
        if n_dense:
            denses = [
                {
                    "ln1": ones(), "attn": ATT.attn_init(nx(), cfg),
                    "ln2": ones(), "mlp": MOE.mlp_init(nx(), cfg),
                }
                for _ in range(n_dense)
            ]
            g["dense"] = jax.tree.map(lambda *xs: jnp.stack(xs), *denses)
        return g
    if fam == "vlm":
        n_self = cfg.vlm.cross_every - 1
        selfs = [
            {
                "ln1": ones(), "attn": ATT.attn_init(nx(), cfg),
                "ln2": ones(), "mlp": MOE.mlp_init(nx(), cfg),
            }
            for _ in range(n_self)
        ]
        cross = {
            "ln1": ones(), "attn": ATT.attn_init(nx(), cfg, cross=True),
            "ln2": ones(), "mlp": MOE.mlp_init(nx(), cfg),
        }
        return {"self": jax.tree.map(lambda *xs: jnp.stack(xs), *selfs), "cross": cross}
    if fam == "ssm":
        n_m = cfg.ssm.slstm_every - 1
        ms = [SSM.mlstm_init(nx(), cfg) for _ in range(n_m)]
        return {
            "mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *ms),
            "slstm": SSM.slstm_init(nx(), cfg),
        }
    if fam == "hybrid":
        hd = cfg.hd
        return {
            "ln1": ones(),
            "attn": ATT.attn_init(nx(), cfg),
            "ssd": SSM.ssd_init(nx(), cfg),
            "wo_ssd": dense_init(nx(), (D, D), cfg.pdtype),
            "ln2": ones(),
            "mlp": MOE.mlp_init(nx(), cfg),
        }
    raise ValueError(fam)


def init_params(cfg: ModelConfig, key):
    kg, ke, kh, km = jax.random.split(key, 4)
    Vp = padded_vocab(cfg)
    group_keys = jax.random.split(kg, cfg.n_groups)
    groups = jax.vmap(lambda k: _init_group(cfg, k))(group_keys)
    params: dict[str, Any] = {
        "groups": groups,
        "final_norm": jnp.ones((cfg.d_model,), cfg.pdtype),
    }
    if cfg.family == "audio":
        params["mask_emb"] = embed_init(ke, (cfg.d_model,), cfg.pdtype)
        params["head"] = dense_init(kh, (cfg.d_model, Vp), cfg.pdtype)
    else:
        params["embed"] = embed_init(ke, (Vp, cfg.d_model), cfg.pdtype)
        if not cfg.tie_embeddings:
            params["head"] = dense_init(kh, (cfg.d_model, Vp), cfg.pdtype)
    return params


# ---------------------------------------------------------------------------
# sharding specs (FSDP over ax.fsdp, TP over ax.model; auto-drops axes that
# do not divide)
# ---------------------------------------------------------------------------

# matmul weights whose LAST dim is the TP (output) dim
_TP_OUT = {
    "wq", "wk", "wv", "up", "gate", "wx", "ffn_up", "in_proj", "wq_b", "wk_b",
    "wv_b", "head",
}
# matmul weights whose FIRST (non-stack) dim is the TP dim
_TP_IN = {"wo", "down", "ffn_down", "wo_ssd"}


def param_specs(cfg: ModelConfig, ax: Axes, mesh_shape: dict[str, int] | None = None):
    """PartitionSpec tree matching init_params' structure.

    TP-dim over ``ax.model`` (when set and divisible), FSDP-dim over
    ``ax.fsdp`` (a tuple — pure-DP policies shard weights over both mesh
    axes).  Axes that do not divide the dim are dropped (replicated)."""

    fsdp = ax.fsdp if len(ax.fsdp) != 1 else ax.fsdp[0]

    def ok_m(dim: int) -> bool:
        return ax.model is not None and ax.divides(dim, ax.model) and ax.axsize(ax.model) > 1

    def ok_f(dim: int) -> bool:
        return len(ax.fsdp) > 0 and ax.divides(dim, ax.fsdp) and ax.axsize(ax.fsdp) > 1

    def spec_for(path: tuple, leaf) -> P:
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        name = names[-1] if names else ""
        shape = leaf.shape
        stacked = "groups" in names  # leading G axis (and E axis for experts)
        base = [None] * len(shape)

        if name == "embed":
            if ok_m(shape[0]):
                base[0] = ax.model
            elif ok_f(shape[1]):
                base[1] = fsdp
            return P(*base)
        # expert tensors [G, E, D, F] / [G, E, F, D]
        if len(shape) == 4 and stacked and name in ("gate", "up", "down") and "moe" in names:
            if ok_m(shape[1]):
                base[1] = ax.model
            if ok_f(shape[2]):
                base[2] = fsdp
            return P(*base)
        if name in _TP_OUT and len(shape) >= 2:
            i, o = len(shape) - 2, len(shape) - 1
            if ok_m(shape[o]):
                base[o] = ax.model
            if ok_f(shape[i]):
                base[i] = fsdp
            return P(*base)
        if name in _TP_IN and len(shape) >= 2:
            i, o = len(shape) - 2, len(shape) - 1
            if ok_m(shape[i]):
                base[i] = ax.model
            if ok_f(shape[o]):
                base[o] = fsdp
            return P(*base)
        # norms, gates, convs, routers, biases: replicate (tiny)
        return P(*base)

    return jax.tree_util.tree_map_with_path(spec_for, jax.eval_shape(lambda: init_params(cfg, jax.random.key(0))))


# ---------------------------------------------------------------------------
# group body (train / prefill / decode share one code path)
# ---------------------------------------------------------------------------


def _dense_block(p, x, cfg, ax, cache, decode_pos, positions, kv_src=None):
    with jax.named_scope("attention"):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        a, cache = ATT.attn_apply(
            p["attn"], h, cfg, ax, kv_src=kv_src, positions=positions,
            cache=cache, decode_pos=decode_pos,
        )
        x = x + a
    with jax.named_scope("mlp"):
        h = rmsnorm(x, p["ln2"], cfg.norm_eps)
        x = x + MOE.mlp_apply(p["mlp"], h, cfg, ax)
    return ax.act_btd(x), cache


def _apply_group(gp, x, cfg: ModelConfig, ax: Axes, cache_g, decode_pos, positions, vis):
    """One scan group.  Returns (x, aux, new_cache_g)."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache_g

    if fam in ("dense", "audio"):
        c = None if cache_g is None else cache_g["attn"]
        x, c = _dense_block(gp, x, cfg, ax, c, decode_pos, positions)
        new_cache = None if cache_g is None else {"attn": c}

    elif fam == "moe":
        n_dense = cfg.moe.every_k - 1
        ds = [] if cache_g is not None else None
        for i in range(n_dense):  # dense interleave layers (llama4)
            dp = jax.tree.map(lambda a, i=i: a[i], gp["dense"])
            c = None if cache_g is None else jax.tree.map(lambda a, i=i: a[i], cache_g["dense"])
            x, c = _dense_block(dp, x, cfg, ax, c, decode_pos, positions)
            if ds is not None:
                ds.append(c)
        h = rmsnorm(x, gp["ln1"], cfg.norm_eps)
        c = None if cache_g is None else cache_g["attn"]
        if cfg.mla:
            a, c = MLA.mla_apply(
                gp["mla"], h, cfg, ax, positions=positions, cache=c,
                decode_pos=decode_pos,
            )
        else:
            a, c = ATT.attn_apply(
                gp["attn"], h, cfg, ax, positions=positions, cache=c,
                decode_pos=decode_pos,
            )
        x = x + a
        h = rmsnorm(x, gp["ln2"], cfg.norm_eps)
        mo, aux = MOE.moe_apply(gp["moe"], h, cfg, ax)
        x = ax.act_btd(x + mo)
        if cache_g is not None:
            new_cache = {"attn": c}
            if ds:
                new_cache["dense"] = jax.tree.map(lambda *xs: jnp.stack(xs), *ds)

    elif fam == "vlm":
        n_self = cfg.vlm.cross_every - 1
        cs = [] if cache_g is not None else None
        for i in range(n_self):
            sp = jax.tree.map(lambda a, i=i: a[i], gp["self"])
            c = None if cache_g is None else jax.tree.map(lambda a, i=i: a[i], cache_g["self"])
            x, c = _dense_block(sp, x, cfg, ax, c, decode_pos, positions)
            if cs is not None:
                cs.append(c)
        cp = gp["cross"]
        h = rmsnorm(x, cp["ln1"], cfg.norm_eps)
        a, _ = ATT.attn_apply(cp["attn"], h, cfg, ax, kv_src=vis)
        x = x + a
        h = rmsnorm(x, cp["ln2"], cfg.norm_eps)
        x = ax.act_btd(x + MOE.mlp_apply(cp["mlp"], h, cfg, ax))
        if cs is not None:
            new_cache = {"self": jax.tree.map(lambda *xs: jnp.stack(xs), *cs)}

    elif fam == "ssm":
        n_m = cfg.ssm.slstm_every - 1
        ms = [] if cache_g is not None else None
        for i in range(n_m):
            mp = jax.tree.map(lambda a, i=i: a[i], gp["mlstm"])
            st = None if cache_g is None else jax.tree.map(lambda a, i=i: a[i], cache_g["mlstm"])
            x, st = SSM.mlstm_apply(mp, x, cfg, ax, state=st)
            if ms is not None:
                ms.append(st)
        st = None if cache_g is None else cache_g["slstm"]
        x, st_new = SSM.slstm_apply(gp["slstm"], x, cfg, ax, state=st)
        if cache_g is not None:
            new_cache = {
                "mlstm": jax.tree.map(lambda *xs: jnp.stack(xs), *ms),
                "slstm": st_new,
            }

    elif fam == "hybrid":
        h = rmsnorm(x, gp["ln1"], cfg.norm_eps)
        ca = None if cache_g is None else cache_g["attn"]
        a, ca = ATT.attn_apply(
            gp["attn"], h, cfg, ax, positions=positions, cache=ca,
            decode_pos=decode_pos,
        )
        cs = None if cache_g is None else cache_g["ssd"]
        y, cs = SSM.ssd_apply(gp["ssd"], h, cfg, ax, state=cs)
        mixed = 0.5 * a + 0.5 * (y @ gp["wo_ssd"].astype(cfg.adtype))
        x = x + mixed
        h = rmsnorm(x, gp["ln2"], cfg.norm_eps)
        x = ax.act_btd(x + MOE.mlp_apply(gp["mlp"], h, cfg, ax))
        if cache_g is not None:
            new_cache = {"attn": ca, "ssd": cs}

    else:
        raise ValueError(fam)
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _embed_in(params, cfg: ModelConfig, ax: Axes, batch):
    dt = cfg.adtype
    with jax.named_scope("embed"):
        if cfg.family == "audio":
            x = batch["features"].astype(dt)
            mask = batch["mask"][..., None]
            x = jnp.where(mask, params["mask_emb"].astype(dt), x)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0).astype(dt)
        return ax.act_btd(x)


def _head_out(params, cfg: ModelConfig, ax: Axes, x):
    dt = cfg.adtype
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family != "audio" and cfg.tie_embeddings:
        logits = jnp.einsum("btd,vd->btv", x, params["embed"].astype(dt))
    else:
        logits = x @ params["head"].astype(dt)
    return ax.act_btv(logits)


def forward(
    params,
    cfg: ModelConfig,
    ax: Axes,
    batch: dict,
    cache=None,
    decode_pos=None,
):
    """Returns (logits [B,T,Vp], aux_loss, new_cache)."""
    x = _embed_in(params, cfg, ax, batch)
    T = x.shape[1]
    positions = (
        jnp.arange(T)
        if decode_pos is None
        else decode_pos + jnp.arange(T)
    )
    vis = batch.get("vision")
    if vis is not None:
        vis = vis.astype(cfg.adtype)

    def body(carry, xs):
        xc, auxc = carry
        gp, cg = xs if cache is not None else (xs, None)
        xc, aux_g, ncg = _apply_group(gp, xc, cfg, ax, cg, decode_pos, positions, vis)
        return (xc, auxc + aux_g), ncg

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable
        )

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        xs = (params["groups"], cache) if cache is not None else params["groups"]
        (x, aux), new_cache = jax.lax.scan(body, (x, aux0), xs)
    else:
        new_groups = []
        aux = aux0
        for g in range(cfg.n_groups):
            gp = jax.tree.map(lambda a, g=g: a[g], params["groups"])
            cg = None if cache is None else jax.tree.map(lambda a, g=g: a[g], cache)
            xs = (gp, cg) if cache is not None else gp
            (x, aux), ncg = body((x, aux), xs)
            new_groups.append(ncg)
        new_cache = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *new_groups)
            if cache is not None
            else None
        )

    with jax.named_scope("head_loss"):
        logits = _head_out(params, cfg, ax, x)
    return logits, aux, new_cache


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------


def loss_fn(logits, labels, cfg: ModelConfig, aux=0.0, z_loss: float = 1e-4,
            aux_weight: float = 1e-2, chunk: int = 512):
    """Cross-entropy with fused label pick (sharded-vocab safe), z-loss,
    MoE aux loss.  ``labels < 0`` positions are masked out.

    Computed in **sequence chunks** under remat: the f32 view of the logits
    only ever exists for [B, chunk, V] at a time — at a 202k vocab the
    whole-sequence f32 temporaries alone are ~6.6 GiB/chip (llama4 train
    cell went 20.0 -> fits after this change)."""
    B, S, Vp = logits.shape

    @jax.checkpoint
    def chunk_stats(lg, lb):
        lf = lg.astype(jnp.float32)
        if Vp != cfg.vocab_size:  # mask vocab padding out of the softmax
            iota_v = jax.lax.broadcasted_iota(jnp.int32, lf.shape, 2)
            lf = jnp.where(iota_v < cfg.vocab_size, lf, -1e30)
        lse = jax.nn.logsumexp(lf, axis=-1)
        iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, 2)
        pick = jnp.sum(jnp.where(iota == lb[..., None], lf, 0.0), axis=-1)
        mask = (lb >= 0).astype(jnp.float32)
        return (
            jnp.sum((lse - pick) * mask),
            jnp.sum(jnp.square(lse) * mask),
            jnp.sum(mask),
        )

    c = min(chunk, S)
    if S % c:
        c = S  # odd lengths: single chunk
    nc = S // c
    if nc > 1:
        lg = jnp.moveaxis(logits.reshape(B, nc, c, Vp), 1, 0)
        lb = jnp.moveaxis(labels.reshape(B, nc, c), 1, 0)
        ce_s, zl_s, n_s = jax.lax.map(lambda t: chunk_stats(*t), (lg, lb))
        ce_sum, zl_sum, n = ce_s.sum(), zl_s.sum(), n_s.sum()
    else:
        ce_sum, zl_sum, n = chunk_stats(logits, labels)
    n = jnp.maximum(n, 1.0)
    ce = ce_sum / n
    zl = zl_sum / n
    return ce + z_loss * zl + aux_weight * aux, ce


# ---------------------------------------------------------------------------
# caches / serving
# ---------------------------------------------------------------------------


def _init_group_cache(cfg: ModelConfig, batch: int, max_len: int):
    fam = cfg.family
    if fam in ("dense", "audio"):
        return {"attn": ATT.init_cache(cfg, batch, max_len)}
    if fam == "moe":
        c = {
            "attn": MLA.mla_init_cache(cfg, batch, max_len)
            if cfg.mla
            else ATT.init_cache(cfg, batch, max_len)
        }
        n_dense = cfg.moe.every_k - 1
        if n_dense:
            one = ATT.init_cache(cfg, batch, max_len)
            c["dense"] = jax.tree.map(lambda a: jnp.stack([a] * n_dense), one)
        return c
    if fam == "vlm":
        n_self = cfg.vlm.cross_every - 1
        one = ATT.init_cache(cfg, batch, max_len)
        return {"self": jax.tree.map(lambda a: jnp.stack([a] * n_self), one)}
    if fam == "ssm":
        n_m = cfg.ssm.slstm_every - 1
        m = SSM.mlstm_init_state(cfg, batch)
        return {
            "mlstm": jax.tree.map(lambda a: jnp.stack([a] * n_m), m),
            "slstm": SSM.slstm_init_state(cfg, batch),
        }
    if fam == "hybrid":
        kind = "ring" if cfg.sliding_window else "full"
        return {
            "attn": ATT.init_cache(cfg, batch, max_len, kind=kind),
            "ssd": SSM.ssd_init_state(cfg, batch),
        }
    raise ValueError(fam)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    one = _init_group_cache(cfg, batch, max_len)
    return jax.tree.map(lambda a: jnp.stack([a] * cfg.n_groups), one)


def cache_specs(cfg: ModelConfig, ax: Axes, batch: int = 1024, max_len: int = 32768):
    """PartitionSpec tree for the cache: batch over data axes, kv-heads (or,
    failing divisibility, the sequence dim) over the model axis.  ``batch``/
    ``max_len`` must be the real serving dims (divisibility decisions)."""

    def spec_for(path, leaf):
        shape = leaf.shape  # [G, B, ...] or [G, n, B, ...]
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        base = [None] * len(shape)
        # find the batch dim: first dim after leading stack dims that is not
        # a small stack axis — by construction dim 1 unless under 'self'/'mlstm'
        bdim = 2 if any(n in ("self", "mlstm", "dense") for n in names) else 1
        if bdim < len(base) and ax.data and ax.divides(shape[bdim], ax.data):
            base[bdim] = ax.data
        # shard kv-head dim over model if divisible; otherwise shard the
        # sequence dim (sequence-parallel decode attention — the partial
        # softmax reductions partition under GSPMD)
        tp_ok = ax.model is not None and ax.axsize(ax.model) > 1
        if names[-1] in ("k", "v") and len(shape) >= bdim + 3 and tp_ok:
            hdim = len(shape) - 2
            sdim = bdim + 1
            if ax.divides(shape[hdim], ax.model):
                base[hdim] = ax.model
            elif ax.divides(shape[sdim], ax.model):
                base[sdim] = ax.model
        elif names[-1] in ("ckv", "kpe", "pos") and len(shape) >= bdim + 2 and tp_ok:
            sdim = bdim + 1
            if ax.divides(shape[sdim], ax.model):
                base[sdim] = ax.model
        return P(*base)

    shapes = jax.eval_shape(lambda: init_cache(cfg, batch, max_len))
    return jax.tree_util.tree_map_with_path(spec_for, shapes)


def prefill(params, cfg: ModelConfig, ax: Axes, batch: dict, cache):
    """Fill the cache from a prompt; returns (last_logits, cache)."""
    logits, _aux, cache = forward(params, cfg, ax, batch, cache=cache, decode_pos=0)
    return logits[:, -1], cache


def decode_step(params, cfg: ModelConfig, ax: Axes, tokens, pos, cache, extra=None):
    """One decode step: tokens [B, 1], pos scalar -> (next_token, cache)."""
    batch = {"tokens": tokens}
    if extra:
        batch.update(extra)
    logits, _aux, cache = forward(
        params, cfg, ax, batch, cache=cache, decode_pos=pos
    )
    nxt = jnp.argmax(logits[:, -1, : cfg.vocab_size], axis=-1)[:, None]
    return nxt.astype(jnp.int32), cache


# ---------------------------------------------------------------------------
# input specs (dry-run stand-ins; weak-type-correct, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """ShapeDtypeStruct stand-ins for one *training* batch."""
    sd = jax.ShapeDtypeStruct
    specs = {}
    if cfg.family == "audio":
        specs["features"] = sd((batch, seq, cfg.d_model), jnp.bfloat16)
        specs["mask"] = sd((batch, seq), jnp.bool_)
        specs["labels"] = sd((batch, seq), jnp.int32)
    else:
        specs["tokens"] = sd((batch, seq), jnp.int32)
        specs["labels"] = sd((batch, seq), jnp.int32)
    if cfg.family == "vlm":
        specs["vision"] = sd((batch, cfg.vlm.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    return specs


def input_spec_shardings(cfg: ModelConfig, ax: Axes) -> dict:
    out = {}
    names = (
        ["features", "mask", "labels"] if cfg.family == "audio" else ["tokens", "labels"]
    )
    for n in names:
        out[n] = P(ax.data, None, None) if n == "features" else P(ax.data, None)
    if cfg.family == "vlm":
        out["vision"] = P(ax.data, None, None)
    return out


# ---------------------------------------------------------------------------
# parameter counting (exact, via eval_shape)
# ---------------------------------------------------------------------------


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    total = 0

    def visit(path, leaf):
        nonlocal total
        n = math.prod(leaf.shape)
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        if (
            active_only
            and cfg.moe
            and "moe" in names
            and names[-1] in ("gate", "up", "down")
        ):
            n = int(n * cfg.moe.top_k / cfg.moe.n_experts)
        total += n

    jax.tree_util.tree_map_with_path(visit, shapes)
    return total
