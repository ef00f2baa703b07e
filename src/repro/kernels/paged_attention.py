"""Paged-attention decode Pallas TPU kernel over the rank-sharded page pool.

The TP serving decoder (:mod:`repro.serving.tp_lm`) historically *gathered*
each sequence's KV pages into a contiguous buffer before attending — a copy
per (sequence, layer, step) that scales with context length.  This kernel
removes the gather: the grid walks ``(row, q_head, page)`` and every K/V
block load is **indexed through the sequence's page table** via a
scalar-prefetch ``BlockSpec`` index map, so attention reads the paged pool
in place (the vLLM paged-attention idea on the TPU grid).

Design, in the idiom of :mod:`repro.kernels.flash_attention`:

* the page axis is *sequential* ("arbitrary"), so the online-softmax state
  ``(m, l, acc)`` lives in VMEM scratch across page iterations;
* padded page tails score ``NEG_INF = -1e30`` and their post-``exp``
  probabilities are forced to an exact ``+0.0`` — page-granular padding
  therefore contributes nothing, which is what lets the serving path keep
  its bit-exactness contract (decode ≡ prefill ≡ replay at any pow2 world:
  every execution reduces over the same fixed page reservation);
* **quantized KV pages** dequantize in the epilogue as two scalar
  multiplies: with per-(page, kv-head) max-abs scales,
  ``softmax((k_q·q)·k_scale·sm_scale) @ v_q · v_scale`` — int8 (and the
  fp8 scaffold) pages never materialize in f32.

Head mapping.  ``kv_head[h]`` names the in-page KV head a q head attends
to and ``page_offset[h]`` shifts its page ids — defaults give plain GQA
(``h // (Hq//Hkv)``, offset 0).  The serving engine uses the pair to run
**all ranks' head shards in one call** over the stacked pool
``[P·n_pages, ...]``: rank ``r``'s heads carry ``page_offset = r·n_pages``,
so each head still only ever touches its own rank's pool region — the
kernel itself stays a per-rank-pool kernel, the stacking is free
(``reshape`` of the lockstep driver's pool is a view).

Bit-exactness tiers (pinned by ``tests/test_kernels.py``): the kernel is
**bitwise invariant** to head partitioning, row batching, padded page-table
columns, and page relocation — the invariances the TP contract needs — and
matches the blocked-recurrence oracle :func:`repro.kernels.ref.paged_attention`
to ≤ a few ULP (two separately compiled XLA programs of the same f32 math;
on TPU one binary serves both sides).  See ``docs/kernels.md``.

Worked example — 3 tokens spread over 2 non-contiguous pages of 2 slots::

    >>> import numpy as np, jax.numpy as jnp
    >>> q = jnp.ones((1, 2, 4), jnp.float32)            # [B=1, Hq=2, d=4]
    >>> kp = jnp.ones((2, 2, 1, 4), jnp.float32)        # [pages, slots, Hkv, d]
    >>> vp = jnp.asarray(np.arange(16., dtype=np.float32).reshape(2, 2, 1, 4))
    >>> table = jnp.asarray([[1, 0]], jnp.int32)        # page order: 1 then 0
    >>> out = paged_attention(q, kp, vp, table, jnp.asarray([3], jnp.int32),
    ...                       interpret=True)          # CPU: the interpreter
    >>> out.shape                                       # [B, Hq, dv]
    (1, 2, 4)
    >>> bool(np.allclose(out[0, 0],                     # uniform over 3 slots
    ...      np.mean([[8, 9, 10, 11], [12, 13, 14, 15], [0, 1, 2, 3]], 0)))
    True
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(
    table_ref,  # scalar prefetch [B, npm] i32
    lengths_ref,  # scalar prefetch [B] i32
    kvh_ref,  # scalar prefetch [Hq] i32
    off_ref,  # scalar prefetch [Hq] i32
    q_ref,  # [1, 1, 1, d]
    k_ref,  # [1, ps, Hkv, d]  the whole page: every kv head
    v_ref,  # [1, ps, Hkv, dv]
    ks_ref,  # [scale_rows, Hkv] f32 K-scale rows around this page
    vs_ref,  # [scale_rows, Hkv] f32 V-scale rows around this page
    o_ref,  # [1, 1, 1, dv]
    m_ref,  # scratch [1, 1] f32
    l_ref,  # scratch [1, 1] f32
    acc_ref,  # scratch [1, dv] f32
    *,
    page_size: int,
    sm_scale: float,
    scale_rows: int,
):
    b = pl.program_id(0)
    h = pl.program_id(1)
    p = pl.program_id(2)
    kvh = kvh_ref[h]
    page = table_ref[b, p] + off_ref[h]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Mosaic cannot index a packed (int8/bf16) block at a dynamic sublane,
    # so kv head ``kvh`` is picked by a masked sum over the head axis: the
    # other heads add exact +0.0, the selected rows keep their bits
    def head_rows(ref):  # -> [ps, d] f32
        x = ref[0].astype(jnp.float32)  # [ps, Hkv, d]
        sel = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) == kvh
        return jnp.sum(jnp.where(sel, x, 0.0), axis=1)

    def head_scale(ref):  # -> [1, 1]
        row = ref[pl.ds(page % scale_rows, 1), :]  # [1, Hkv]
        sel = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1) == kvh
        return jnp.sum(jnp.where(sel, row, 0.0), axis=1, keepdims=True)

    q = q_ref[0, 0].astype(jnp.float32)  # [1, d]
    k = head_rows(k_ref)  # [ps, d]
    v = head_rows(v_ref)  # [ps, dv]

    slot = page_size * p + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    visible = slot < lengths_ref[b]

    # quantized pages: scores/values carry the per-(page, head) scales as
    # scalar multiplies (for f32/bf16 pools the scales are exactly 1.0, and
    # x * 1.0 is the identity in IEEE arithmetic — one code path, same bits)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (head_scale(ks_ref) * sm_scale)  # [1, ps]
    s = jnp.where(visible, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pr = jnp.exp(s - m_new)
    pr = jnp.where(visible, pr, 0.0)  # padded tails: exact +0.0
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pr, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ) * head_scale(vs_ref)
    m_ref[...] = m_new

    @pl.when(p == pl.num_programs(2) - 1)
    def _finalize():
        # an all-masked row (length 0: a batch-padding row) yields exact 0.0
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_attention(
    q: jax.Array,  # [B, Hq, d]
    k_pages: jax.Array,  # [n_pages, page_size, Hkv, d]   f32/bf16/int8/fp8
    v_pages: jax.Array,  # [n_pages, page_size, Hkv, dv]  f32/bf16/int8/fp8
    table: jax.Array,  # [B, npm] i32 page ids (pad columns with any valid id)
    lengths: jax.Array,  # [B] i32 visible tokens (0 allowed: row outputs 0)
    k_scale: jax.Array | None = None,  # [n_pages, Hkv] f32 (None = ones)
    v_scale: jax.Array | None = None,  # [n_pages, Hkv] f32 (None = ones)
    kv_head: jax.Array | None = None,  # [Hq] i32 (None = GQA h // group)
    page_offset: jax.Array | None = None,  # [Hq] i32 (None = zeros)
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention straight off the paged pool -> ``[B, Hq, dv]`` f32
    math (returned in ``q.dtype``).  Compiled for the TPU by default;
    ``interpret=True`` executes the kernel body on the CPU for validation."""
    B, Hq, d = q.shape
    n_pages, ps, Hkv, dv = v_pages.shape
    npm = table.shape[1]
    if Hq % Hkv and kv_head is None:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={Hkv}")
    if sm_scale is None:
        sm_scale = d**-0.5
    if k_scale is None:
        k_scale = jnp.ones((n_pages, Hkv), jnp.float32)
    if v_scale is None:
        v_scale = jnp.ones((n_pages, Hkv), jnp.float32)
    if kv_head is None:
        kv_head = jnp.arange(Hq, dtype=jnp.int32) // (Hq // Hkv)
    if page_offset is None:
        page_offset = jnp.zeros((Hq,), jnp.int32)

    # TPU blocks must be (8, 128)-aligned in their last two dimensions or
    # span them: q/out travel as [B, Hq, 1, d] rows, K/V as whole pages
    # (every kv head), and the scales as the aligned group of up to eight
    # pages that holds this one
    scale_rows = min(8, n_pages)

    def kv_map(bb, h, p, tbl, ln, kvh, off):
        return (tbl[bb, p] + off[h], 0, 0, 0)

    def sc_map(bb, h, p, tbl, ln, kvh, off):
        return ((tbl[bb, p] + off[h]) // scale_rows, 0)

    def row_map(bb, h, p, *_):
        return (bb, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, Hq, npm),
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), row_map),
            pl.BlockSpec((1, ps, Hkv, d), kv_map),
            pl.BlockSpec((1, ps, Hkv, dv), kv_map),
            pl.BlockSpec((scale_rows, Hkv), sc_map),
            pl.BlockSpec((scale_rows, Hkv), sc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dv), row_map),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, page_size=ps, sm_scale=sm_scale,
                               scale_rows=scale_rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, 1, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32),
      kv_head.astype(jnp.int32), page_offset.astype(jnp.int32),
      q.reshape(B, Hq, 1, d), k_pages, v_pages, k_scale, v_scale)
    return out.reshape(B, Hq, dv)
