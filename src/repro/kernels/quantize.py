"""Blockwise and per-page int8 quantize/dequantize Pallas kernels.

Used by the compressed-allreduce path (repro.core.compression): gradients
are quantized to int8 with per-``block`` max-abs f32 scales before crossing
the expensive link (DCN), and dequantized+accumulated on arrival.  4× wire
reduction for f32, 2× for bf16, at <0.8% relative error per hop.

``quantize_page``/``dequantize_page`` are the KV-cache variants: one
max-abs scale per **(page, head)** over ``[n_pages, page_size, H, d]``
pools — the granularity ``kernels/paged_attention.py`` dequantizes at (a
scalar multiply per page block) and ``serving/kv_cache.py`` stores
alongside the pool under ``kv_dtype='int8'``.

Tiling.  TPU blocks must be (8, 128)-aligned in their last two dimensions
or span the whole array there.  The blockwise kernels take ``[8, 128·block]``
tiles, so the scale tile is ``[8, 128]``; the page kernels take eight whole
pages ``[8, page_size, H, d]`` per step, so the scale tile is ``[8, H]``.
Every max-abs reduction stays local to its tile.

Validated against repro.kernels.ref.quantize_blockwise in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quant_kernel(x_ref, q_ref, s_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)  # [tr, tn]
    tr, tn = x.shape
    xb = x.reshape(tr, tn // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)  # [tr, tn/block]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    q_ref[...] = q.reshape(tr, tn).astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, o_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)
    tr, tn = q.shape
    qb = q.reshape(tr, tn // block, block)
    o_ref[...] = (qb * s_ref[...][..., None]).reshape(tr, tn).astype(o_ref.dtype)


_ROWS = 8  # sublane tile
_LANES = 128  # lane tile: scales per blockwise tile, one row of lanes


def _block_tiles(R: int, N: int, block: int):
    """(rows, columns, grid) of the blockwise kernels' tiles."""
    if N % block:
        raise ValueError(f"N={N} not a multiple of block={block}")
    tr = min(_ROWS, R)
    nb = min(_LANES, N // block)
    return tr, nb * block, (pl.cdiv(R, tr), pl.cdiv(N // block, nb))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_blockwise(
    x: jax.Array,  # [R, N], N % block == 0
    block: int = 256,
    interpret: bool = False,
):
    R, N = x.shape
    tr, tn, grid = _block_tiles(R, N, block)
    q, s = pl.pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=grid,
        in_specs=[pl.BlockSpec((tr, tn), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((tr, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tn // block), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), jnp.int8),
            jax.ShapeDtypeStruct((R, N // block), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s


@functools.partial(jax.jit, static_argnames=("block", "interpret", "out_dtype"))
def dequantize_blockwise(
    q: jax.Array,  # [R, N] int8
    s: jax.Array,  # [R, N/block] f32
    block: int = 256,
    interpret: bool = False,
    out_dtype=jnp.float32,
):
    R, N = q.shape
    tr, tn, grid = _block_tiles(R, N, block)
    out = pl.pallas_call(
        functools.partial(_dequant_kernel, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, tn), lambda i, j: (i, j)),
            pl.BlockSpec((tr, tn // block), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((tr, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((R, N), out_dtype),
        interpret=interpret,
    )(q, s)
    return out


# ---------------------------------------------------------------------------
# per-(page, head) KV page quantization (kv_dtype='int8' pools)
# ---------------------------------------------------------------------------


def _quant_page_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # [tp, ps, H, d]
    amax = jnp.max(jnp.max(jnp.abs(x), axis=1), axis=-1)  # [tp, H]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale[:, None, :, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_page_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)  # [tp, ps, H, d]
    o_ref[...] = (q * s_ref[...][:, None, :, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_page(x: jax.Array, interpret: bool = False):
    """KV pages ``[n_pages, page_size, H, d]`` -> (int8 pages, f32 scales
    ``[n_pages, H]``).  Grid over groups of eight pages: the per-(page,
    head) max-abs reduction is block-local."""
    n_pages, ps, H, d = x.shape
    tp = min(_ROWS, n_pages)
    page_block = pl.BlockSpec((tp, ps, H, d), lambda i: (i, 0, 0, 0))
    q, s = pl.pallas_call(
        _quant_page_kernel,
        grid=(pl.cdiv(n_pages, tp),),
        in_specs=[page_block],
        out_specs=[page_block, pl.BlockSpec((tp, H), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n_pages, ps, H, d), jnp.int8),
            jax.ShapeDtypeStruct((n_pages, H), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, s


@functools.partial(jax.jit, static_argnames=("interpret", "out_dtype"))
def dequantize_page(q: jax.Array, s: jax.Array, interpret: bool = False,
                    out_dtype=jnp.float32):
    """Inverse of :func:`quantize_page` (per-(page, head) scales)."""
    n_pages, ps, H, d = q.shape
    tp = min(_ROWS, n_pages)
    page_block = pl.BlockSpec((tp, ps, H, d), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        _dequant_page_kernel,
        grid=(pl.cdiv(n_pages, tp),),
        in_specs=[page_block, pl.BlockSpec((tp, H), lambda i: (i, 0))],
        out_specs=page_block,
        out_shape=jax.ShapeDtypeStruct((n_pages, ps, H, d), out_dtype),
        interpret=interpret,
    )(q, s)
