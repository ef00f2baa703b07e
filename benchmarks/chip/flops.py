"""Operations and bytes that a training step and a kernel call need.

Counted from shapes alone, so that they are the same whatever implements the
work.  One multiply-add is 2 FLOPs.  A training step is three forward passes
of work (the forward and the two products of the backward); operations that
remat recomputes are not counted.  Causal attention needs the score and value
products for ``T (T + 1) / 2`` query-key pairs of each head.

Which count applies to a configuration is its plain reference's to say
(``refs/<reference>.py``: ``train_flops_per_token`` and ``attention_dims``);
the dense block's counts here take a ``refs.dense_lm.Sizes``.
"""

from __future__ import annotations


def matmul_params(s) -> int:
    """Weights that a token multiplies in a dense GQA decoder: every layer's
    projections and MLP, and the output head (tied or not).  The embedding
    lookup is no product."""
    attn = s.d_model * (s.n_heads + 2 * s.n_kv_heads) * s.head_dim
    attn += s.n_heads * s.head_dim * s.d_model
    mlp = 3 * s.d_model * s.d_ff
    return s.n_layers * (attn + mlp) + s.d_model * s.vocab


def causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def train_flops_per_token(s, seq: int) -> float:
    """Model FLOPs of one trained token of a dense GQA decoder: 6 per matmul
    weight, plus causal attention (QK^T and PV, forward and backward)
    averaged over the sequence."""
    attn_fwd = 2 * 2 * s.n_heads * s.head_dim * causal_pairs(seq) / seq
    return 6 * matmul_params(s) + 3 * s.n_layers * attn_fwd


def flash_fwd(batch: int, dims: tuple, seq: int, itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one causal flash-attention forward call over a
    whole layer, for the reference's ``attention_dims``, ``(n_heads,
    n_kv_heads, d_qk, d_v)``: QK^T at width ``d_qk`` and PV at ``d_v`` for the
    causal pairs; q, k and v read once and o written once."""
    h, hkv, d_qk, d_v = dims
    flops = 2 * batch * h * (d_qk + d_v) * causal_pairs(seq)
    elems = batch * seq * (h * d_qk + hkv * d_qk + hkv * d_v + h * d_v)
    return float(flops), float(elems * itemsize)


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
