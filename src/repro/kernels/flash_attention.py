"""Flash attention Pallas TPU kernels (tiled online-softmax, causal/SWA, GQA):
the forward and its backward.

TPU-native design (not a CUDA port): the forward's grid is (batch·q_heads,
q_blocks, kv_blocks) with the kv axis *sequential* ("arbitrary"), so the
online-softmax running state (m, l, acc) lives in VMEM scratch across kv
iterations and the MXU sees [bq, d] × [d, bk] and [bq, bk] × [bk, dv]
matmuls with hardware-aligned tiles (bq = bk = 128 by default, multiples of
the 128-lane MXU).  Fully-masked kv blocks are skipped with ``pl.when`` — on
a causal T×S sweep this halves the executed FLOPs, and for sliding-window
attention reduces them to O(T·W).

The backward is two kernels fed by the forward's output and each row's
log-sum-exp (``lse``, f32, lane-dense ``[B·Hq, 1, T]``): ``attn_bwd_dkv``
runs (batch·kv_heads, kv_blocks) in parallel and walks the group's q heads
and the q blocks in sequence, accumulating dK and dV of the whole GQA group
in VMEM; ``attn_bwd_dq`` runs (batch·q_heads, q_blocks) in parallel and walks
the kv blocks.  Each recomputes its score tiles from ``lse``; a fully-masked
block is skipped, and its index map repeats the last block fetched, so a
skipped block costs no DMA.  Their tiles follow from the shapes
(:func:`_bwd_blocks`).

Numerics: scores, softmax and accumulators are f32 regardless of input
dtype; the mask value is -1e30 (not -inf) to keep exp() NaN-free.  The
backward's MXU operands are the storage dtype (p and dS cast to it), with f32
accumulation.

Validated on CPU with ``interpret=True`` against :func:`repro.kernels.ref.attention`
and its gradient over shape/dtype sweeps (see tests/test_kernels.py).  TPU is
the target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
NT = (((1,), (1,)), ((), ()))  # contract the last dims: a @ b.T
NN = (((1,), (0,)), ((), ()))  # a @ b


def _needed(q_start, k_start, bq, bk, causal, window):
    """Whether the [bq, bk] block at absolute positions (q_start, k_start)
    has an unmasked entry: not wholly in the future (causal) nor wholly left
    of the window."""
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window:
        needed = needed & (k_start + bk - 1 > q_start - window)
    return needed


def _mask(q_pos, k_pos, seq_k, causal, window):
    mask = k_pos < seq_k  # tail padding
    if causal:
        mask = mask & (k_pos <= q_pos)
    if window:
        mask = mask & (k_pos > q_pos - window)
    return mask


def _flash_kernel(
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bk, d]
    v_ref,  # [1, bk, dv]
    o_ref,  # [1, bq, dv]
    *refs,  # lse [1, bq] f32 if saved; scratch m, l [bq, 1] and acc [bq, dv] f32
    causal: bool,
    window: int,
    q_offset: int,
    sm_scale: float,
    bq: int,
    bk: int,
    seq_k: int,
    n_kv_blocks: int,
):
    lse_ref = refs[0] if len(refs) == 4 else None
    m_ref, l_ref, acc_ref = refs[-3:]
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * bq + q_offset  # absolute position of this q block
    k_start = ki * bk

    @pl.when(_needed(q_start, k_start, bq, bk, causal, window))
    def _body():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, dv]
        # zero padded kv rows: they are masked out of p below, but NaN/garbage
        # padding would still poison p @ v (0 * NaN = NaN)
        kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < seq_k
        v = jnp.where(kv_valid, v, 0.0)
        k = jnp.where(kv_valid, k, 0.0)
        s = jax.lax.dot_general(q, k, NT, preferred_element_type=jnp.float32)  # [bq, bk]

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _mask(q_pos, k_pos, seq_k, causal, window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)  # [bq, 1]
        p = jnp.exp(s - m_new)  # [bq, bk]
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, NN, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)
        if lse_ref is not None:  # [bq, 1] -> lane-dense [1, bq]
            lse_ref[...] = jnp.transpose(m_ref[...] + jnp.log(l))


def _forward(q, k, v, causal, window, q_offset, bq, bk, interpret, save_lse):
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
    out_specs = [pl.BlockSpec((1, bq, dv), lambda bh, qi, ki: (bh, qi, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * Hq, T, dv), q.dtype)]
    if save_lse:
        out_specs.append(pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)))
        out_shape.append(jax.ShapeDtypeStruct((B * Hq, 1, T), jnp.float32))

    outs = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, dv), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qr, kr, vr)
    out = outs[0].reshape(B, Hq, T, dv)
    return (out, outs[1]) if save_lse else out


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
    Differentiate through :func:`repro.kernels.ops.flash_attention`, whose
    backward is :func:`flash_attention_bwd`."""
    return _forward(q, k, v, causal, window, q_offset, bq, bk, interpret, False)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "q_offset", "bq", "bk", "interpret",
    ),
)
def flash_attention_fwd(q, k, v, causal=True, window=0, q_offset=0, bq=128, bk=128,
                        interpret=False):
    """:func:`flash_attention` and each row's log-sum-exp, the backward's
    residual: ``(out [B, Hq, T, dv], lse [B·Hq, 1, T] f32)``."""
    return _forward(q, k, v, causal, window, q_offset, bq, bk, interpret, True)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_blocks(T: int, S: int) -> tuple[int, int]:
    """The backward's (bq, bk): 512 where the sequence is longer (a multiple
    of the 128 lanes, as the lane-dense ``lse`` block needs), else the whole
    sequence.  At d = 128 a [512, 512] f32 score tile and its three
    companions take 4 MiB of VMEM."""
    return min(T, 512), min(S, 512)


def _dkv_kernel(
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bk, d]
    v_ref,  # [1, bk, dv]
    do_ref,  # [1, bq, dv]
    lse_ref,  # [1, bq] f32
    di_ref,  # [1, bq] f32
    dk_ref,  # [1, bk, d]
    dv_ref,  # [1, bk, dv]
    dk_acc,  # scratch [bk, d] f32
    dv_acc,  # scratch [bk, dv] f32
    *,
    causal: bool,
    window: int,
    q_offset: int,
    sm_scale: float,
    bq: int,
    bk: int,
    seq_q: int,
    seq_k: int,
    group: int,
    n_q_blocks: int,
):
    ki = pl.program_id(1)
    g = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * bq + q_offset
    k_start = ki * bk

    @pl.when(_needed(q_start, k_start, bq, bk, causal, window))
    def _body():
        q = q_ref[0]  # [bq, d]
        do = do_ref[0]  # [bq, dv]
        if seq_q % bq:  # padded q rows hold garbage, which would reach dK, dV
            q_valid = (qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)) < seq_q
            q = jnp.where(q_valid, q, 0)
            do = jnp.where(q_valid, do, 0)
        # scores transposed, [bk, bq]: a q row's lse and D broadcast down a lane
        s = jax.lax.dot_general(k_ref[0], q, NT, preferred_element_type=jnp.float32)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        mask = _mask(q_pos, k_pos, seq_k, causal, window)
        if seq_q % bq:
            mask = mask & (q_pos < seq_q + q_offset)
        p = jnp.where(mask, jnp.exp(s * sm_scale - lse_ref[...]), 0.0)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, NN, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_ref[0], do, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[...])
        if seq_q % bq:
            ds = jnp.where(mask, ds, 0.0)
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    @pl.when((g == group - 1) & (qi == n_q_blocks - 1))
    def _finalize():
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(
    q_ref,  # [1, bq, d]
    k_ref,  # [1, bk, d]
    v_ref,  # [1, bk, dv]
    do_ref,  # [1, bq, dv]
    lse_ref,  # [1, bq] f32
    di_ref,  # [1, bq] f32
    dq_ref,  # [1, bq, d]
    dq_acc,  # scratch [bq, d] f32
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
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * bq + q_offset
    k_start = ki * bk

    @pl.when(_needed(q_start, k_start, bq, bk, causal, window))
    def _body():
        k = k_ref[0]  # [bk, d]
        v = v_ref[0]  # [bk, dv]
        if seq_k % bk:  # padded kv rows hold garbage, which would reach dQ
            kv_valid = (k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)) < seq_k
            k = jnp.where(kv_valid, k, 0)
            v = jnp.where(kv_valid, v, 0)
        lse = jnp.expand_dims(lse_ref[0], -1)  # [bq, 1]
        di = jnp.expand_dims(di_ref[0], -1)
        s = jax.lax.dot_general(q_ref[0], k, NT, preferred_element_type=jnp.float32)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = _mask(q_pos, k_pos, seq_k, causal, window)
        p = jnp.where(mask, jnp.exp(s * sm_scale - lse), 0.0)
        dp = jax.lax.dot_general(do_ref[0], v, NT, preferred_element_type=jnp.float32)
        ds = p * (dp - di)
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


def _clamp(i, lo, hi):
    return jnp.minimum(jnp.maximum(i, lo), hi)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "interpret"),
)
def flash_attention_bwd(q, k, v, o, lse, do, causal=True, window=0, q_offset=0,
                        interpret=False):
    """dq, dk, dv of :func:`flash_attention` from its residuals (``o`` and
    ``lse`` from :func:`flash_attention_fwd`) and the output's cotangent
    ``do``."""
    B, Hq, T, d = q.shape
    _, Hkv, S, dv = v.shape
    group = Hq // Hkv
    bq, bk = _bwd_blocks(T, S)
    nq = pl.cdiv(T, bq)
    nk = pl.cdiv(S, bk)
    sm_scale = d**-0.5

    qr = q.reshape(B * Hq, T, d)
    kr = k.reshape(B * Hkv, S, d)
    vr = v.reshape(B * Hkv, S, dv)
    dor = do.reshape(B * Hq, T, dv)
    # D = rowsum(dO ∘ O), the softmax's correction to dP
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    di = di.reshape(B * Hq, 1, T)
    args = (qr, kr, vr, dor, lse, di)

    def q_block(ki, qi):
        """qi clamped to the q blocks kv block ``ki`` needs: a skipped step
        repeats a block that is fetched anyway."""
        lo, hi = 0, nq - 1
        if causal:
            lo = jnp.minimum(jax.lax.div(jnp.maximum(ki * bk - q_offset, 0), bq), hi)
        if window:
            hi = jnp.minimum(
                jax.lax.div(jnp.maximum(ki * bk + bk + window - 2 - q_offset, 0), bq), hi)
        return _clamp(qi, lo, hi)

    def kv_block(qi, ki):
        """ki clamped to the kv blocks q block ``qi`` needs."""
        q_start = qi * bq + q_offset
        lo, hi = 0, nk - 1
        if causal:
            hi = jnp.minimum(jax.lax.div(q_start + bq - 1, bk), hi)
        if window:
            lo = jnp.minimum(jax.lax.div(jnp.maximum(q_start - window + 1, 0), bk), nk - 1)
        return _clamp(ki, lo, hi)

    static = dict(causal=causal, window=window, q_offset=q_offset, sm_scale=sm_scale,
                  bq=bq, bk=bk, seq_k=S)
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, seq_q=T, group=group, n_q_blocks=nq, **static),
        grid=(B * Hkv, nk, group, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, ki, g, qi: (b * group + g, q_block(ki, qi), 0)),
            pl.BlockSpec((1, bk, d), lambda b, ki, g, qi: (b, ki, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, ki, g, qi: (b, ki, 0)),
            pl.BlockSpec((1, bq, dv), lambda b, ki, g, qi: (b * group + g, q_block(ki, qi), 0)),
            pl.BlockSpec((None, 1, bq), lambda b, ki, g, qi: (b * group + g, 0, q_block(ki, qi))),
            pl.BlockSpec((None, 1, bq), lambda b, ki, g, qi: (b * group + g, 0, q_block(ki, qi))),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, ki, g, qi: (b, ki, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, ki, g, qi: (b, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(kr.shape, k.dtype),
                   jax.ShapeDtypeStruct(vr.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="attn_bwd_dkv",
    )(*args)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_kv_blocks=nk, **static),
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, qi, ki: (bh // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, bk, dv), lambda bh, qi, ki: (bh // group, kv_block(qi, ki), 0)),
            pl.BlockSpec((1, bq, dv), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="attn_bwd_dq",
    )(*args)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv_.reshape(v.shape)
