"""GQA self-attention, cross-attention, and KV caches (full + ring).

Cache kinds:

* ``full`` — contiguous [B, S_max, Hkv, hd]; decode writes at position ``t``
  and attends over the whole buffer with a causal mask (garbage beyond ``t``
  is masked).  Used by every full-attention arch.
* ``ring`` — sliding-window ring buffer [B, W, Hkv, hd] plus an absolute
  position array [B, W]; decode writes at ``t % W``.  O(W) memory at any
  context length — this is what makes hymba's 500k-token decode cell
  feasible.  (xlstm needs no cache at all.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..kernels import ops
from .config import ModelConfig
from .layers import Axes, apply_rope, dense_init, rmsnorm


def attn_init(key, cfg: ModelConfig, cross: bool = False):
    D, Hq, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (D, Hq * hd), cfg.pdtype),
        "wk": dense_init(ks[1], (D, Hkv * hd), cfg.pdtype),
        "wv": dense_init(ks[2], (D, Hkv * hd), cfg.pdtype),
        "wo": dense_init(ks[3], (Hq * hd, D), cfg.pdtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), cfg.pdtype)
        p["k_norm"] = jnp.ones((hd,), cfg.pdtype)
    if cross:
        p["gate"] = jnp.zeros((), cfg.pdtype)  # tanh-gated residual (llama-vision)
    return p


def init_cache(cfg: ModelConfig, batch: int, max_len: int, kind: str = "full"):
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    if kind == "ring":
        W = cfg.sliding_window
        return {
            "k": jnp.zeros((batch, W, Hkv, hd), cfg.adtype),
            "v": jnp.zeros((batch, W, Hkv, hd), cfg.adtype),
            "pos": jnp.full((batch, W), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, Hkv, hd), cfg.adtype),
        "v": jnp.zeros((batch, max_len, Hkv, hd), cfg.adtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _merge_heads(x):
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def _qkv(p, cfg: ModelConfig, x, kv_src, positions, ax: Axes, rope: bool):
    Hq, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.adtype
    q = _split_heads(x @ p["wq"].astype(dt), Hq, hd)
    src = x if kv_src is None else kv_src
    k = _split_heads(src @ p["wk"].astype(dt), Hkv, hd)
    v = _split_heads(src @ p["wv"].astype(dt), Hkv, hd)
    q, k, v = ax.act_bthd(q), ax.act_bthd(k), ax.act_bthd(v)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        if kv_src is None:
            k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attn_apply(
    p,
    x,  # [B, T, D]
    cfg: ModelConfig,
    ax: Axes,
    *,
    kv_src=None,  # cross-attention source [B, N, D] (no rope on kv)
    positions=None,  # [T] absolute positions of x
    cache=None,
    decode_pos=None,  # scalar absolute position (decode mode)
    backend: str = "auto",
):
    """Returns (out [B,T,D], new_cache)."""
    B, T, D = x.shape
    cross = kv_src is not None
    causal = cfg.causal and not cross
    window = 0 if cross else cfg.sliding_window
    if positions is None:
        positions = (
            jnp.arange(T) if decode_pos is None else jnp.full((T,), decode_pos)
        )
    q, k, v = _qkv(p, cfg, x, kv_src, positions, ax, rope=not cross)

    # a "decode step" is a single-token continuation; prefill (T > 1) writes
    # the cache but attends within x itself
    is_step = decode_pos is not None and T == 1

    new_cache = cache
    if cache is not None and not cross:
        if "pos" in cache:  # ring buffer (sliding window)
            W = cache["k"].shape[1]
            if not is_step:  # prefill: write last W tokens
                take = min(T, W)
                idx = (positions[-take:]) % W
                new_cache = {
                    "k": cache["k"].at[:, idx].set(k[:, -take:]),
                    "v": cache["v"].at[:, idx].set(v[:, -take:]),
                    "pos": cache["pos"].at[:, idx].set(
                        jnp.broadcast_to(positions[-take:], (B, take))
                    ),
                }
            else:
                slot = decode_pos % W
                new_cache = {
                    "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1),
                    "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1),
                    "pos": jax.lax.dynamic_update_slice_in_dim(
                        cache["pos"],
                        jnp.full((B, 1), decode_pos, jnp.int32),
                        slot,
                        1,
                    ),
                }
        else:  # full cache
            at = 0 if decode_pos is None else decode_pos
            new_cache = {
                "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k, at, 1),
                "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v, at, 1),
            }

    # ---- attend ----
    if is_step and cache is not None and not cross:
        if "pos" in new_cache:
            out = _ring_attend(q, new_cache, cfg, decode_pos)
        else:
            # direct masked attention: one token against the whole cache.
            # (flash chunking buys nothing at T=1 and its reshapes reshard a
            # sequence-sharded cache — measured in the §Perf log)
            out = _full_cache_attend(q, new_cache, cfg, decode_pos, window)
    else:
        out = flash_attend(q, k, v, ax, causal=causal, window=window,
                           backend=backend)

    out = ax.act_bthd(out)
    out = _merge_heads(out) @ p["wo"].astype(cfg.adtype)
    if cross:
        out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return ax.act_btd(out), new_cache


def flash_attend(q, k, v, ax: Axes, *, causal: bool, window: int = 0,
                 backend: str = "auto"):
    """Train/prefill attention, q [B,T,Hq,hd], k/v [B,S,Hkv,hd] ->
    [B,T,Hq,hd], through :func:`repro.kernels.ops.flash_attention`.

    A Mosaic kernel runs once per shard of the ambient mesh: batch over the
    data axes, heads over the model axis where both head counts divide.
    Inside a ``shard_map`` that leaves an axis automatic (fmi-mode training
    of a tensor-parallel model) no map can hold the kernel, and the step
    runs the XLA twin (:func:`repro.kernels.ops.per_shard`)."""

    def attend(q, k, v, backend=backend):
        out = ops.flash_attention(
            jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
            causal=causal, window=window, backend=backend,
        )
        return jnp.swapaxes(out, 1, 2)

    if ops.flash_backend(backend) not in ("pallas", "interpret"):
        return attend(q, k, v)
    heads = ax.model if (ax.model and ax.divides(q.shape[2], ax.model)
                         and ax.divides(k.shape[2], ax.model)) else None
    mapped = ops.per_shard(attend, P(ax.data or None, None, heads, None))
    if mapped is None:
        return attend(q, k, v, backend="xla")
    return mapped(q, k, v)


def _full_cache_attend(q, cache, cfg: ModelConfig, t, window: int):
    """Decode attention: q [B, 1, Hq, hd] vs cache [B, S, Hkv, hd].

    Scores/softmax in f32 via preferred_element_type (no materialized f32
    K/V copies); positions beyond ``t`` masked.  The S dim may be sharded
    over the model axis — the max/sum reductions and the weighted sum
    partition into per-shard partials + tiny all-reduces under GSPMD
    (sequence-parallel decode attention)."""
    B, _, Hq, hd = q.shape
    kc, vc = cache["k"], cache["v"]  # [B, S, Hkv, hd]
    S = kc.shape[1]
    Hkv = kc.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, hd)  # T=1 folded; q heads grouped per kv head
    s = jax.lax.dot_general(
        qg, kc, (((3,), (3,)), ((0, 1), (0, 2))), preferred_element_type=jnp.float32
    )  # contract hd; batch (B, Hkv) -> [B, Hkv, group, S]
    s = s * (hd**-0.5)
    k_pos = jnp.arange(S)[None, None, None, :]
    mask = k_pos <= t
    if window:
        mask = mask & (k_pos > t - window)
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jax.lax.dot_general(
        p.astype(q.dtype), vc, (((3,), (1,)), ((0, 1), (0, 2))),
        preferred_element_type=jnp.float32,
    )  # [B, Hkv, group, hd]
    return out.reshape(B, 1, Hq, hd).astype(q.dtype)


def _ring_attend(q, cache, cfg: ModelConfig, t):
    """Decode attention over a ring buffer: q [B, 1, Hq, hd]."""
    B, _, Hq, hd = q.shape
    Hkv = cfg.n_kv_heads
    group = Hq // Hkv
    kc, vc, pos = cache["k"], cache["v"], cache["pos"]  # [B, W, Hkv, hd], [B, W]
    qf = q.astype(jnp.float32) * (hd**-0.5)
    kf = jnp.repeat(kc.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(vc.astype(jnp.float32), group, axis=2)
    s = jnp.einsum("bthd,bwhd->bhtw", qf, kf)  # [B, Hq, 1, W]
    valid = (pos >= 0) & (pos <= t) & (pos > t - cfg.sliding_window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhtw,bwhd->bthd", pr, vf)
    return out.astype(q.dtype)
