"""Multi-head Latent Attention (DeepSeek-V2).

Train/prefill run the *unabsorbed* form (materialize per-head K/V from the
shared latent, then flash attention).  Decode runs the *absorbed* form: the
up-projections are folded into the query/output sides so attention works
directly against the compressed latent cache —

    cache:  c_kv [B, S, kv_lora]  +  k_pe [B, S, qk_rope]         (shared!)
    score:  (q_nope Wuk) · c_kv   +   q_pe · k_pe
    value:  (probs · c_kv) Wuv

so the per-token cache is kv_lora + qk_rope = 576 values instead of
2·H·hd = 32768 — a 57× KV-cache compression, which is the reason this arch
exists.  The 32k-decode dry-run cell uses exactly this path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import flash_attend
from .config import ModelConfig
from .layers import Axes, apply_rope, dense_init, rmsnorm


def mla_init(key, cfg: ModelConfig):
    m = cfg.mla
    D, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    return {
        "wq_a": dense_init(ks[0], (D, m.q_lora), cfg.pdtype),
        "q_norm": jnp.ones((m.q_lora,), cfg.pdtype),
        "wq_b": dense_init(ks[1], (m.q_lora, H * (m.qk_nope + m.qk_rope)), cfg.pdtype),
        "wkv_a": dense_init(ks[2], (D, m.kv_lora + m.qk_rope), cfg.pdtype),
        "kv_norm": jnp.ones((m.kv_lora,), cfg.pdtype),
        "wk_b": dense_init(ks[3], (m.kv_lora, H * m.qk_nope), cfg.pdtype),
        "wv_b": dense_init(ks[4], (m.kv_lora, H * m.v_dim), cfg.pdtype),
        "wo": dense_init(ks[5], (H * m.v_dim, D), cfg.pdtype),
    }


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, max_len, m.kv_lora), cfg.adtype),
        "kpe": jnp.zeros((batch, max_len, m.qk_rope), cfg.adtype),
    }


def _latent(p, x, cfg: ModelConfig, positions):
    """x -> (c_kv [B,T,kv_lora] normalized, k_pe [B,T,rope] roped)."""
    m = cfg.mla
    dt = cfg.adtype
    kv_a = x @ p["wkv_a"].astype(dt)
    c_kv, k_pe = kv_a[..., : m.kv_lora], kv_a[..., m.kv_lora :]
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(k_pe[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    return c_kv, k_pe


def _queries(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    H = cfg.n_heads
    dt = cfg.adtype
    q = rmsnorm(x @ p["wq_a"].astype(dt), p["q_norm"], cfg.norm_eps)
    q = (q @ p["wq_b"].astype(dt)).reshape(*x.shape[:2], H, m.qk_nope + m.qk_rope)
    q_nope, q_pe = q[..., : m.qk_nope], q[..., m.qk_nope :]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def mla_apply(
    p,
    x,
    cfg: ModelConfig,
    ax: Axes,
    *,
    positions=None,
    cache=None,
    decode_pos=None,
    backend: str = "auto",
):
    m = cfg.mla
    B, T, D = x.shape
    H = cfg.n_heads
    dt = cfg.adtype
    if positions is None:
        positions = (
            jnp.arange(T) if decode_pos is None else jnp.full((T,), decode_pos)
        )

    q_nope, q_pe = _queries(p, x, cfg, positions)
    q_nope, q_pe = ax.act_bthd(q_nope), ax.act_bthd(q_pe)
    c_kv, k_pe = _latent(p, x, cfg, positions)

    new_cache = cache
    if cache is not None:
        at = 0 if decode_pos is None else decode_pos
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice_in_dim(cache["ckv"], c_kv, at, 1),
            "kpe": jax.lax.dynamic_update_slice_in_dim(cache["kpe"], k_pe, at, 1),
        }

    scale = (m.qk_nope + m.qk_rope) ** -0.5
    # absorbed path only for single-token decode; prefill attends within x
    if decode_pos is not None and T == 1 and cache is not None:
        # --- absorbed decode against the latent cache ---
        wk_b = p["wk_b"].astype(dt).reshape(m.kv_lora, H, m.qk_nope)
        wv_b = p["wv_b"].astype(dt).reshape(m.kv_lora, H, m.v_dim)
        q_lat = jnp.einsum("bthn,lhn->bthl", q_nope, wk_b)  # [B,T,H,kv_lora]
        ckv_c, kpe_c = new_cache["ckv"], new_cache["kpe"]  # [B,S,...]
        s = (
            jnp.einsum("bthl,bsl->bhts", q_lat.astype(jnp.float32), ckv_c.astype(jnp.float32))
            + jnp.einsum("bthr,bsr->bhts", q_pe.astype(jnp.float32), kpe_c.astype(jnp.float32))
        ) * scale
        S = ckv_c.shape[1]
        k_pos = jnp.arange(S)[None, None, None, :]
        s = jnp.where(k_pos <= decode_pos, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhts,bsl->bthl", pr, ckv_c.astype(jnp.float32))  # latent ctx
        out = jnp.einsum("bthl,lhv->bthv", ctx.astype(dt), wv_b)  # [B,T,H,v]
    else:
        # --- unabsorbed train/prefill: materialize K/V, flash attention ---
        k_nope = (c_kv @ p["wk_b"].astype(dt)).reshape(B, T, H, m.qk_nope)
        v = (c_kv @ p["wv_b"].astype(dt)).reshape(B, T, H, m.v_dim)
        k_nope, v = ax.act_bthd(k_nope), ax.act_bthd(v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, T, H, m.qk_rope))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = flash_attend(q, k, v, ax, causal=True, backend=backend)

    out = ax.act_bthd(out)
    out = out.reshape(B, T, H * m.v_dim) @ p["wo"].astype(dt)
    return ax.act_btd(out), new_cache
