"""Flash attention Pallas TPU kernel (tiled online-softmax, causal/SWA, GQA).

TPU-native design (not a CUDA port): the grid is (batch·q_heads, q_blocks,
kv_blocks) with the kv axis *sequential* ("arbitrary"), so the online-softmax
running state (m, l, acc) lives in VMEM scratch across kv iterations and the
MXU sees [bq, d] × [d, bk] and [bq, bk] × [bk, dv] matmuls with
hardware-aligned tiles (bq = bk = 128 by default, multiples of the 128-lane
MXU).  Fully-masked kv blocks are skipped with ``pl.when`` — on a causal
T×S sweep this halves the executed FLOPs, and for sliding-window attention
reduces them to O(T·W).

Numerics: scores and accumulators are f32 regardless of input dtype; the
mask value is -1e30 (not -inf) to keep exp() NaN-free.

Validated on CPU with ``interpret=True`` against :func:`repro.kernels.ref.attention`
over shape/dtype sweeps (see tests/test_kernels.py).  TPU is the target.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bk, d]
    v_ref,  # [1, bk, dv]
    o_ref,  # [1, bq, dv]
    m_ref,  # scratch [bq, 1] f32
    l_ref,  # scratch [bq, 1] f32
    acc_ref,  # scratch [bq, dv] f32
    *,
    causal: bool,
    window: int,
    q_offset: int,
    sm_scale: float,
    bq: int,
    bk: int,
    seq_k: int,
    n_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq + q_offset  # absolute position of this q block
    k_start = ki * bk

    # block-level skip: kv block entirely in the future (causal) or entirely
    # left of the window
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        needed = needed & (k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, dv]
        # zero padded kv rows: they are masked out of p below, but NaN/garbage
        # padding would still poison p @ v (0 * NaN = NaN)
        kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < seq_k
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < seq_k  # tail padding
        if causal:
            mask = mask & (k_pos <= q_pos)
        if window:
            mask = mask & (k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "bq", "bk", "interpret",
    ),
)
def flash_attention(
    q: jax.Array,  # [B, Hq, T, d]
    k: jax.Array,  # [B, Hkv, S, d]
    v: jax.Array,  # [B, Hkv, S, dv]
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """GQA flash attention forward.  Compiled for the TPU by default;
    ``interpret=True`` executes the kernel body on the CPU for validation.
    Differentiate through :func:`repro.kernels.ops.flash_attention`, which
    gives this kernel its backward pass."""
    B, Hq, T, d = q.shape
    _, Hkv, S, dv = v.shape
    if Hq % Hkv:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    group = Hq // Hkv

    bq = min(bq, T)
    bk = min(bk, S)
    nq = pl.cdiv(T, bq)
    nk = pl.cdiv(S, bk)

    qr = q.reshape(B * Hq, T, d)
    kr = k.reshape(B * Hkv, S, d)
    vr = v.reshape(B * Hkv, S, dv)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        window=window,
        q_offset=q_offset,
        sm_scale=d**-0.5,
        bq=bq,
        bk=bk,
        seq_k=S,
        n_kv_blocks=nk,
    )

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, dv), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, T, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, T, dv)
