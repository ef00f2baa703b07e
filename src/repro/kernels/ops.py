"""Backend-dispatching jit'd wrappers for the Pallas kernels.

Three backends per op:

* ``pallas``     — the Pallas TPU kernel (``interpret=False``); TPU only.
* ``interpret``  — the same kernel body executed on CPU (validation).
* ``xla``        — a memory-safe pure-jnp implementation (chunked
  flash-attention via ``lax.scan`` online softmax; chunked GLA via
  ``lax.scan`` over chunk blocks).  This is the default on CPU — it is what
  the dry-run compiles, so HLO cost/memory analysis reflects a flash-style
  schedule, not an O(T²)-memory naive attention.

``backend='auto'`` picks pallas on TPU and xla elsewhere; it never picks
the interpreter or the reference.  ``flash_attention`` has one more rule:
a traced ``q_offset`` runs the xla backend whatever was asked (see
:func:`flash_backend`).

A Mosaic kernel cannot be partitioned by GSPMD, so under a mesh the
callers map it over shards with :func:`per_shard`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import flash_attention as _fa
from . import quantize as _qz
from . import ssm_scan as _ss
from . import ref as _ref

NEG_INF = -1e30


def _default_backend() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def per_shard(fn, spec: P):
    """``fn`` mapped over the shards of the ambient mesh, every operand and
    the result laid out as ``spec``; ``None`` where no such map can hold a
    Mosaic kernel.

    GSPMD refuses to partition a Mosaic kernel, and refuses one inside a
    ``shard_map`` that leaves any mesh axis automatic, even of size 1.  So:
    outside a mesh, or where every axis is already manual, ``fn`` itself;
    under a mesh with no manual axis, ``fn`` in a ``shard_map`` over all of
    them; inside a ``shard_map`` that leaves some axis automatic, ``None``
    (nested maps do not merge their manual axes)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or set(mesh.manual_axes) == set(mesh.axis_names):
        return fn
    if mesh.manual_axes:
        return None
    return jax.shard_map(fn, in_specs=spec, out_specs=spec,
                         axis_names=set(mesh.axis_names), check_vma=False)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def _xla_flash_attention(q, k, v, causal=True, window=0, q_offset=0):
    """Chunked online-softmax attention in pure jnp (lax.scan over kv blocks
    of 512).

    O(T·bk) live memory instead of O(T·S); numerics identical to flash.
    Inputs stay in their storage dtype (bf16): scores/accumulators get f32
    via ``preferred_element_type`` on the matmuls — explicit ``astype(f32)``
    converts get hoisted out of the loop by XLA and materialize full f32
    copies of K/V (measured: +4 GiB/chip on the 32k cells).
    """
    B, Hq, T, d = q.shape
    _, Hkv, S, dv = v.shape
    group = Hq // Hkv
    bk = min(512, S)
    nk = -(-S // bk)
    pad = nk * bk - S
    kf = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kf = kf.reshape(B, Hkv, nk, bk, d)
    vf = vf.reshape(B, Hkv, nk, bk, dv)

    scale = d**-0.5
    q_pos = jnp.arange(T) + q_offset  # [T]
    bdims = (((3,), (3,)), ((0, 1), (0, 1)))  # contract d, batch (B, H)
    pv_dims = (((3,), (2,)), ((0, 1), (0, 1)))  # contract bk

    # checkpoint each kv block: backward recomputes the [T, bk] score tile
    # instead of saving it — this IS flash-attention backward, and it is
    # what keeps the 32k-prefill cells inside 16 GiB/chip
    @jax.checkpoint
    def step(carry, blk):
        m, l, acc = carry
        kb, vb, ki = blk  # [B, Hkv, bk, d], [B, Hkv, bk, dv], scalar
        kb = jnp.repeat(kb, group, axis=1)
        vb = jnp.repeat(vb, group, axis=1)
        s = jax.lax.dot_general(q, kb, bdims, preferred_element_type=jnp.float32)
        s = s * scale  # [B, Hq, T, bk] f32
        k_pos = ki * bk + jnp.arange(bk)  # [bk]
        mask = (k_pos[None, :] < S) & jnp.ones((T, 1), bool)
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(q.dtype), vb, pv_dims, preferred_element_type=jnp.float32
        )
        acc = acc * alpha[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hq, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hq, T), jnp.float32)
    a0 = jnp.zeros((B, Hq, T, dv), jnp.float32)
    kb = jnp.moveaxis(kf, 2, 0)
    vb = jnp.moveaxis(vf, 2, 0)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _kernel_flash(q, k, v, causal, window, q_offset, bq, bk, interpret):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bk=bk, interpret=interpret,
    )


def _kernel_flash_fwd(q, k, v, causal, window, q_offset, bq, bk, interpret):
    out, lse = _fa.flash_attention_fwd(
        q, k, v, causal=causal, window=window, q_offset=q_offset,
        bq=bq, bk=bk, interpret=interpret,
    )
    return out, (q, k, v, out, lse)


def _kernel_flash_bwd(causal, window, q_offset, bq, bk, interpret, res, g):
    # The residuals are the inputs, the output and each row's log-sum-exp
    # (f32), from which the backward kernels recompute the score tiles.
    # They choose their own tiles from the shapes; the forward's bq, bk do
    # not reach them.
    with jax.named_scope("flash_bwd"):
        return _fa.flash_attention_bwd(*res, g, causal=causal, window=window,
                                       q_offset=q_offset, interpret=interpret)


# optimize_remat: where the residuals are dead, as in the forward pass of a
# rematerialized layer, the primal kernel runs in place of the forward rule,
# and only the recompute writes lse
_kernel_flash.defvjp(_kernel_flash_fwd, _kernel_flash_bwd, optimize_remat=True)


def flash_backend(backend: str = "auto", q_offset=0) -> str:
    """The backend :func:`flash_attention` runs for these arguments.

    ``auto`` is ``pallas`` on the TPU and ``xla`` elsewhere.  A traced
    ``q_offset`` (a decode position inside ``jit``) always runs ``xla``,
    whatever was asked: the kernel bakes its offset into the grid, so it
    needs a static one.  Training and prefill pass ``q_offset=0``."""
    if backend == "auto":
        backend = _default_backend()
    return backend if isinstance(q_offset, int) else "xla"


_xla_flash = jax.jit(_xla_flash_attention, static_argnums=(3, 4))


def flash_attention(
    q, k, v, causal=True, window=0, q_offset=0, backend="auto", bq=128, bk=128
):
    """GQA flash attention: q [B,Hq,T,d], k/v [B,Hkv,S,d(v)] -> [B,Hq,T,dv].

    The backend is :func:`flash_backend` ``(backend, q_offset)``: a traced
    ``q_offset`` runs the xla twin.  The dispatch runs in Python, before
    any ``jit``, so a Python-int offset stays static.  The kernel backends
    are differentiable; their backward is the Pallas flash backward
    (:func:`repro.kernels.flash_attention.flash_attention_bwd`).
    """
    backend = flash_backend(backend, q_offset)
    if backend == "xla":
        return _xla_flash(q, k, v, causal, window, q_offset)
    if backend == "ref":
        return _ref.attention(q, k, v, causal, window, q_offset)
    return _kernel_flash(q, k, v, causal, window, q_offset, bq, bk,
                         backend == "interpret")


# ---------------------------------------------------------------------------
# paged decode attention
# ---------------------------------------------------------------------------


def _xla_paged_attention(q, k_pages, v_pages, table, lengths, k_scale,
                         v_scale, kv_head, page_offset, sm_scale):
    """Vectorized paged attention in pure jnp: gather the [B, H, npm, ps]
    K/V blocks through the page table, mask, one softmax.  O(B·H·npm·ps·d)
    live memory — fine for decode (one q row per sequence), and the
    gather-style baseline the kernel's bench rows compare against."""
    B, Hq, d = q.shape
    n_pages, ps, Hkv, dv = v_pages.shape
    npm = table.shape[1]
    pages = table[:, None, :] + page_offset[None, :, None]  # [B, Hq, npm]
    hsel = kv_head[None, :, None, None]  # broadcast over (B, ·, npm, ps)
    kh = jnp.take_along_axis(k_pages[pages], hsel[..., None, None],
                             axis=4)[..., 0, :].astype(jnp.float32)
    vh = jnp.take_along_axis(v_pages[pages], hsel[..., None, None],
                             axis=4)[..., 0, :].astype(jnp.float32)
    ks = jnp.take_along_axis(k_scale[pages], hsel, axis=3)[..., 0]
    vs = jnp.take_along_axis(v_scale[pages], hsel, axis=3)[..., 0]
    s = jnp.einsum("bhd,bhpsd->bhps", q.astype(jnp.float32), kh)
    s = s * (ks * sm_scale)[..., None]  # [B, Hq, npm, ps]
    slot = (jnp.arange(npm) * ps)[:, None] + jnp.arange(ps)[None, :]
    visible = slot[None, None] < lengths[:, None, None, None]
    s = jnp.where(visible, s, NEG_INF)
    m = jnp.max(s, axis=(-2, -1), keepdims=True)
    p = jnp.where(visible, jnp.exp(s - m), 0.0)
    pv = jnp.einsum("bhps,bhpsd->bhpd", p, vh)
    pv = jnp.sum(pv * vs[..., None], axis=2)  # [B, Hq, dv]
    l = jnp.sum(p, axis=(-2, -1))[..., None]
    return (pv / jnp.maximum(l, 1e-30)).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "backend"))
def paged_attention(q, k_pages, v_pages, table, lengths, k_scale=None,
                    v_scale=None, kv_head=None, page_offset=None,
                    sm_scale=None, backend="auto"):
    """Decode attention off the paged KV pool (see
    :func:`repro.kernels.paged_attention.paged_attention` for the layout
    contract).  ``xla`` is a vectorized gather-style jnp baseline."""
    from . import paged_attention as _pa

    if backend == "auto":
        backend = _default_backend()
    B, Hq, d = q.shape
    n_pages, ps, Hkv, dv = v_pages.shape
    if sm_scale is None:
        sm_scale = d**-0.5
    if backend == "xla":
        if k_scale is None:
            k_scale = jnp.ones((n_pages, Hkv), jnp.float32)
        if v_scale is None:
            v_scale = jnp.ones((n_pages, Hkv), jnp.float32)
        if kv_head is None:
            kv_head = jnp.arange(Hq, dtype=jnp.int32) // (Hq // Hkv)
        if page_offset is None:
            page_offset = jnp.zeros((Hq,), jnp.int32)
        return _xla_paged_attention(q, k_pages, v_pages,
                                    table.astype(jnp.int32),
                                    lengths.astype(jnp.int32), k_scale,
                                    v_scale, kv_head.astype(jnp.int32),
                                    page_offset.astype(jnp.int32), sm_scale)
    return _pa.paged_attention(
        q, k_pages, v_pages, table, lengths, k_scale=k_scale,
        v_scale=v_scale, kv_head=kv_head, page_offset=page_offset,
        sm_scale=sm_scale, interpret=(backend == "interpret"),
    )


# ---------------------------------------------------------------------------
# gated linear attention scan
# ---------------------------------------------------------------------------


def _xla_gla_scan(q, k, v, log_f, i_gate, normalize=True, chunk=128):
    """Chunked GLA in pure jnp: lax.scan over chunks, matmul-dense inside."""
    B, H, T, dk = q.shape
    dv = v.shape[-1]
    L = min(chunk, T)
    nc = -(-T // L)
    pad = nc * L - T

    def padt(x):
        return jnp.pad(x, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))

    qf = padt(q).astype(jnp.float32) * (dk**-0.5)
    kf = padt(k).astype(jnp.float32)
    vf = padt(v).astype(jnp.float32)
    lf = padt(log_f).astype(jnp.float32)
    ig = padt(i_gate).astype(jnp.float32)
    if pad:
        valid = jnp.arange(nc * L) < T
        lf = jnp.where(valid, lf, 0.0)
        ig = jnp.where(valid, ig, 0.0)

    def split(x):  # [B,H,nc*L,...] -> [nc, B, H, L, ...]
        x = x.reshape(x.shape[:2] + (nc, L) + x.shape[3:])
        return jnp.moveaxis(x, 2, 0)

    qs, ks, vs, lfs, igs = map(split, (qf, kf, vf, lf, ig))
    ones = jnp.ones((B, H, L, 1), jnp.float32)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    )

    def step(C, blk):
        qc, kc, vc, lfc, igc = blk
        v_aug = jnp.concatenate([vc, ones], axis=-1)
        b = jnp.cumsum(lfc, axis=-1)  # [B,H,L]
        decay = jnp.where(causal, jnp.exp(b[..., :, None] - b[..., None, :]), 0.0)
        decay = decay * igc[..., None, :]
        s = jnp.einsum("bhtd,bhsd->bhts", qc, kc)
        intra = jnp.einsum("bhts,bhsv->bhtv", s * decay, v_aug)
        inter = jnp.exp(b)[..., None] * jnp.einsum("bhtk,bhkv->bhtv", qc, C)
        num = intra + inter
        b_last = b[..., -1]
        w = jnp.exp(b_last[..., None] - b) * igc
        C = jnp.exp(b_last)[..., None, None] * C + jnp.einsum(
            "bhsk,bhsv->bhkv", kc * w[..., None], v_aug
        )
        return C, num

    C0 = jnp.zeros((B, H, dk, dv + 1), jnp.float32)
    C, nums = jax.lax.scan(step, C0, (qs, ks, vs, lfs, igs))
    nums = jnp.moveaxis(nums, 0, 2).reshape(B, H, nc * L, dv + 1)[:, :, :T]
    if normalize:
        den = jnp.maximum(jnp.abs(nums[..., dv:]), 1.0)
        out = nums[..., :dv] / den
    else:
        out = nums[..., :dv]
    return out.astype(q.dtype), C


@functools.partial(jax.jit, static_argnames=("normalize", "chunk", "backend"))
def gla_scan(q, k, v, log_f, i_gate, normalize=True, chunk=128, backend="auto"):
    """Chunked GLA/mLSTM scan -> (out [B,H,T,dv], state [B,H,dk,dv+1])."""
    if backend == "auto":
        backend = _default_backend()
    if backend == "xla":
        return _xla_gla_scan(q, k, v, log_f, i_gate, normalize, chunk)
    return _ss.gla_scan(
        q, k, v, log_f, i_gate, normalize=normalize, chunk=chunk,
        interpret=(backend == "interpret"),
    )


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


def quantize_blockwise(x, block=256, backend="auto"):
    if backend == "auto":
        backend = _default_backend()
    if backend == "xla":
        return _ref.quantize_blockwise(x, block)
    flat = x.reshape(1, -1) if x.ndim == 1 else x
    q, s = _qz.quantize_blockwise(flat, block=block, interpret=(backend == "interpret"))
    if x.ndim == 1:
        return q.reshape(-1), s.reshape(-1)
    return q, s


def dequantize_blockwise(q, s, block=256, backend="auto", out_dtype=jnp.float32):
    if backend == "auto":
        backend = _default_backend()
    if backend == "xla":
        return _ref.dequantize_blockwise(q, s, block)
    flat_q = q.reshape(1, -1) if q.ndim == 1 else q
    flat_s = s.reshape(1, -1) if s.ndim == 1 else s
    out = _qz.dequantize_blockwise(
        flat_q, flat_s, block=block, interpret=(backend == "interpret"),
        out_dtype=out_dtype,
    )
    return out.reshape(q.shape)
