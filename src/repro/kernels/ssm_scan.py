"""Chunked gated-linear-attention scan Pallas kernel (mLSTM / SSD / GLA).

The recurrence (per batch·head)

    C_t = f_t · C_{t-1} + i_t · k_t v_tᵀ          (matrix memory, [dk, dv])
    n_t = f_t · n_{t-1} + i_t · k_t               (normalizer,   [dk])
    o_t = (q_tᵀ C_t) / max(|q_tᵀ n_t|, 1)

covers xLSTM's mLSTM cell and the SSD/mamba-2 scalar-decay formulation used
by our hymba heads.  It is sequential in t, but the *chunked* form is
TPU-native: split T into chunks of L=128; inside a chunk everything is two
MXU matmuls on [L, dk]×[dk, L] and [L, L]×[L, dv] with a causal decay mask;
across chunks only the [dk, dv+1] state is carried — O(T·L) work instead of
O(T²) attention, while staying matmul-dense (unlike a naive per-step scan,
which would be VPU-bound).

Grid: (B·H, T/L) with the chunk axis sequential ("arbitrary"); the running
state lives in a VMEM scratch accumulator, augmented with one extra value
column carrying the normalizer (v_aug = [v | 1], so n_t is the last column
of C_t).

Decode (per-token) does not need this kernel: the recurrence above is three
cheap VPU ops; see repro/models/ssm.py.

Validated against :func:`repro.kernels.ref.gla_scan` (per-step oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gla_kernel(
    q_ref,  # [1, L, dk]
    k_ref,  # [1, L, dk]
    v_ref,  # [1, L, dv]
    b_ref,  # [1, 1, L]  chunk-local cumulative log forget gates
    ig_ref,  # [1, 1, L]  input gates
    o_ref,  # [1, L, dv]
    state_ref,  # out [1, dk, dv+1]  (final state, written at last chunk)
    C_ref,  # scratch [dk, dv+1] f32
    *,
    L: int,
    dv: int,
    seq_len: int,
    n_chunks: int,
    normalize: bool,
    sm_scale: float,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)

    q = q_ref[0].astype(jnp.float32) * sm_scale  # [L, dk]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    b = b_ref[0]  # [1, L]  log decay from chunk start to (incl.) t
    ig = ig_ref[0]  # [1, L]  (padded tail: b flat, i = 0 -> identity step)

    # zero padded tail rows: garbage there would poison the matmuls
    pos = ci * L + jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    valid = pos < seq_len
    v = jnp.where(valid, v, 0.0)
    k = jnp.where(valid, k, 0.0)

    v_aug = jnp.concatenate([v, jnp.ones((L, 1), jnp.float32)], axis=-1)  # [L, dv+1]

    # gates arrive as lane rows; the column forms come from an [L, L]
    # transpose (Mosaic transposes aligned 2-D tiles, not [1, L] rows)
    bs = jnp.broadcast_to(b, (L, L))  # bs[t, s] = b_s
    bt = bs.T  # bt[t, s] = b_t
    # intra-chunk: D[t, s] = exp(b_t - b_s) * i_s  for s <= t else 0
    causal = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0) >= jax.lax.broadcasted_iota(
        jnp.int32, (L, L), 1
    )
    decay = jnp.where(causal, jnp.exp(bt - bs), 0.0) * ig
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, L]
    intra = (s * decay) @ v_aug  # [L, dv+1]

    # inter-chunk: exp(b_t) * q_t @ C_carry
    inter = jnp.exp(bt[:, :1]) * jax.lax.dot_general(
        q, C_ref[...], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [L, dv+1]

    num = intra + inter
    if normalize:
        den = jnp.maximum(jnp.abs(num[:, dv:]), 1.0)  # [L, 1] (normalizer col)
        out = num[:, :dv] / den
    else:
        out = num[:, :dv]
    o_ref[0, ...] = out.astype(o_ref.dtype)

    # state update: C_new = exp(b_L) * C + sum_s exp(b_L - b_s) i_s k_s v_aug_sT
    b_last = b[:, L - 1:]  # [1, 1]
    w = jnp.exp(b_last - b) * ig  # [1, L]
    kw = k * jnp.broadcast_to(w, (L, L)).T[:, :1]  # [L, dk]
    C_ref[...] = jnp.exp(b_last) * C_ref[...] + jax.lax.dot_general(
        kw, v_aug, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_ref[0, ...] = C_ref[...].astype(state_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("normalize", "chunk", "interpret")
)
def gla_scan(
    q: jax.Array,  # [B, H, T, dk]
    k: jax.Array,  # [B, H, T, dk]
    v: jax.Array,  # [B, H, T, dv]
    log_f: jax.Array,  # [B, H, T]
    i_gate: jax.Array,  # [B, H, T]
    normalize: bool = True,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (out [B, H, T, dv], final_state [B, H, dk, dv+1]).  Compiled
    for the TPU by default; ``interpret=True`` runs it on the CPU."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, T)
    nc = pl.cdiv(T, L)

    qr = q.reshape(B * H, T, dk)
    kr = k.reshape(B * H, T, dk)
    vr = v.reshape(B * H, T, dv)
    # the chunk-local cumsum of the log forget gates runs here in XLA (Mosaic
    # has no cumsum); the padded tail gets log f = 0, i = 0: identity steps
    pad = ((0, 0), (0, 0), (0, nc * L - T))
    lf = jnp.pad(log_f.reshape(B * H, 1, T).astype(jnp.float32), pad)
    br = jnp.cumsum(lf.reshape(B * H, 1, nc, L), axis=-1).reshape(B * H, 1, nc * L)
    igr = jnp.pad(i_gate.reshape(B * H, 1, T).astype(jnp.float32), pad)

    kernel = functools.partial(
        _gla_kernel,
        L=L,
        dv=dv,
        seq_len=T,
        n_chunks=nc,
        normalize=normalize,
        sm_scale=dk**-0.5,
    )
    out, state = pl.pallas_call(
        kernel,
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, L, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, L, dk), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, L, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, L), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, 1, L), lambda bh, ci: (bh, 0, ci)),
        ],
        out_specs=[
            pl.BlockSpec((1, L, dv), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, dk, dv + 1), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, dk, dv + 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv + 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr, br, igr)
    return out.reshape(B, H, T, dv), state.reshape(B, H, dk, dv + 1)
