"""Per-kernel validation: Pallas (interpret=True) and the xla backends vs
the pure-jnp oracles in repro/kernels/ref.py, swept over shapes/dtypes."""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_pallas
from repro.kernels.paged_attention import paged_attention
from repro.kernels.quantize import dequantize_blockwise as dq_pallas
from repro.kernels.quantize import dequantize_page as dqp_pallas
from repro.kernels.quantize import quantize_blockwise as q_pallas
from repro.kernels.quantize import quantize_page as qp_pallas
from repro.kernels.ssm_scan import gla_scan as gla_pallas

# The raw kernels compile for the TPU by default; on the CPU each call below
# runs the kernel body in the Pallas interpreter (interpret=True).
pa_pallas = functools.partial(paged_attention, interpret=True)

rng = np.random.default_rng(0)


def _mk(shape, dtype):
    return jnp.asarray(rng.normal(size=shape), dtype)


ATT_CASES = [
    # B, Hq, Hkv, T, S, d, causal, window, q_offset
    (2, 4, 2, 256, 256, 64, True, 0, 0),
    (1, 8, 2, 128, 384, 64, True, 0, 256),   # decode-style offset
    (2, 4, 4, 200, 200, 32, True, 0, 0),     # non-block-multiple
    (1, 2, 1, 256, 256, 64, False, 0, 0),    # bidirectional (hubert)
    (2, 4, 2, 256, 256, 64, True, 64, 0),    # sliding window
    (1, 1, 1, 64, 64, 128, True, 0, 0),
    (1, 4, 2, 1, 513, 64, True, 0, 512),     # single-token decode
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ATT_CASES, ids=[str(c) for c in ATT_CASES])
def test_flash_attention_pallas_vs_ref(case, dtype):
    B, Hq, Hkv, T, S, d, causal, window, off = case
    q, k, v = _mk((B, Hq, T, d), dtype), _mk((B, Hkv, S, d), dtype), _mk((B, Hkv, S, d), dtype)
    got = fa_pallas(q, k, v, causal=causal, window=window, q_offset=off, interpret=True)
    want = ref.attention(q, k, v, causal=causal, window=window, q_offset=off)
    atol = 3e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


@pytest.mark.parametrize("case", ATT_CASES[:4], ids=[str(c) for c in ATT_CASES[:4]])
def test_flash_attention_xla_backend_vs_ref(case):
    B, Hq, Hkv, T, S, d, causal, window, off = case
    q, k, v = _mk((B, Hq, T, d), jnp.float32), _mk((B, Hkv, S, d), jnp.float32), _mk((B, Hkv, S, d), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, q_offset=off, backend="xla")
    want = ref.attention(q, k, v, causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_flash_attention_dynamic_offset():
    """decode path: q_offset is traced (jitted position)."""
    import jax

    q, k, v = _mk((1, 4, 1, 32), jnp.float32), _mk((1, 2, 64, 32), jnp.float32), _mk((1, 2, 64, 32), jnp.float32)

    @jax.jit
    def step(pos):
        return ops.flash_attention(q, k, v, causal=True, q_offset=pos, backend="xla")

    got = step(jnp.int32(17))
    want = ref.attention(q, k, v, causal=True, q_offset=17)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


GLA_CASES = [
    (2, 2, 256, 32, 32, True, 128),
    (2, 2, 256, 32, 32, False, 128),
    (1, 4, 200, 64, 48, True, 128),   # non-multiple of chunk
    (1, 1, 512, 16, 16, True, 64),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", GLA_CASES, ids=[str(c) for c in GLA_CASES])
def test_gla_scan_pallas_vs_ref(case, dtype):
    B, H, T, dk, dv, norm, chunk = case
    q, k, v = _mk((B, H, T, dk), dtype), _mk((B, H, T, dk), dtype), _mk((B, H, T, dv), dtype)
    lf = jnp.asarray(-np.abs(rng.normal(size=(B, H, T)) * 0.5), jnp.float32)
    ig = jnp.asarray(np.abs(rng.normal(size=(B, H, T))), jnp.float32)
    got, _ = gla_pallas(q, k, v, lf, ig, normalize=norm, chunk=chunk, interpret=True)
    want = ref.gla_scan(q, k, v, lf, ig, normalize=norm)
    atol = 6e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol
    )


@pytest.mark.parametrize("case", GLA_CASES[:2], ids=[str(c) for c in GLA_CASES[:2]])
def test_gla_scan_xla_backend_matches_pallas_state(case):
    B, H, T, dk, dv, norm, chunk = case
    q, k, v = _mk((B, H, T, dk), jnp.float32), _mk((B, H, T, dk), jnp.float32), _mk((B, H, T, dv), jnp.float32)
    lf = jnp.asarray(-np.abs(rng.normal(size=(B, H, T)) * 0.5), jnp.float32)
    ig = jnp.asarray(np.abs(rng.normal(size=(B, H, T))), jnp.float32)
    o1, s1 = ops.gla_scan(q, k, v, lf, ig, normalize=norm, chunk=chunk, backend="xla")
    o2, s2 = gla_pallas(q, k, v, lf, ig, normalize=norm, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=2e-3)


@pytest.mark.parametrize("R,N,block", [(8, 1024, 256), (3, 512, 128), (16, 4096, 256), (1, 256, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_pallas_vs_ref(R, N, block, dtype):
    x = _mk((R, N), dtype)
    q1, s1 = q_pallas(x, block=block, interpret=True)
    q2, s2 = ref.quantize_blockwise(x, block)
    dq = np.abs(np.asarray(q1, np.int32) - np.asarray(q2, np.int32))
    if dtype == jnp.float32:
        assert (dq == 0).all()
    else:
        # bf16 inputs can land exactly on a round-to-nearest boundary where
        # a 1-ULP difference in the f32 scale (amax/127 evaluated by two
        # fusions) flips the integer: allow |dq| <= 1 at such ties
        assert dq.max() <= 1 and (dq != 0).mean() < 1e-2
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    d1 = dq_pallas(q1, s1, block=block, interpret=True)
    d2 = ref.dequantize_blockwise(q2, s2, block)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=float(np.asarray(s2).max()) * 1.01)
    # round-trip error bound: half an int8 step per block
    xf = np.asarray(x, np.float32).reshape(R, N // block, block)
    bound = np.abs(xf).max(-1, keepdims=True) / 127.0 * 0.5 + 1e-7
    err = np.abs(np.asarray(d1).reshape(xf.shape) - xf)
    assert (err <= bound + 1e-6).all()


def test_quantize_zero_block_is_exact():
    x = jnp.zeros((2, 512), jnp.float32)
    q, s = q_pallas(x, block=256, interpret=True)
    assert np.all(np.asarray(q) == 0)
    d = dq_pallas(q, s, block=256, interpret=True)
    assert np.all(np.asarray(d) == 0)


# ---------------------------------------------------------------------------
# paged decode attention: sweep vs oracles, bitwise invariances, quantized KV
# ---------------------------------------------------------------------------

# Tolerance tiers (docs/kernels.md):
#  * unquantized kernel vs the blocked oracle / the unpaged naive reference:
#    two separately compiled XLA programs of the same f32 math — a few ULP
#    (near-zero outputs make ULP metrics blow up, hence atol+rtol);
#  * int8 pages vs the int8 oracle: same tier (identical quantized inputs);
#  * int8 pages vs the unquantized f32 result: one max-abs rounding per
#    (page, head) — bounded well inside 2% of the value scale here;
#  * bitwise (exact) claims are reserved for the invariance tests below.
TIER_ORACLE = dict(rtol=2e-6, atol=2e-6)
TIER_INT8_VS_F32 = dict(atol=5e-2)

PA_CASES = [
    # B, Hq, Hkv, d, ps, n_pages, npm
    (2, 4, 4, 16, 8, 8, 3),     # MHA
    (2, 8, 2, 16, 8, 8, 2),     # GQA group 4
    (1, 4, 1, 32, 4, 6, 4),     # MQA, small pages
    (4, 2, 2, 8, 16, 8, 2),     # wide pages
    (3, 4, 2, 16, 8, 10, 3),    # odd batch
]


def _pa_case(case, pool_tier="f32"):
    """Random pools + a valid page table for one sweep case.  Returns
    (q, k_pages, v_pages, table, lengths, k_scale, v_scale)."""
    B, Hq, Hkv, d, ps, n_pages, npm = case
    q = _mk((B, Hq, d), jnp.float32)
    kp = _mk((n_pages, ps, Hkv, d), jnp.float32)
    vp = _mk((n_pages, ps, Hkv, d), jnp.float32)
    table = jnp.asarray(
        np.stack([rng.choice(n_pages, npm, replace=False) for _ in range(B)]),
        jnp.int32)
    lengths = jnp.asarray(
        rng.integers(1, npm * ps + 1, size=B).astype(np.int32))
    if pool_tier == "f32":
        return q, kp, vp, table, lengths, None, None
    if pool_tier == "bf16":
        return (q, kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), table,
                lengths, None, None)
    kq, ks = ref.quantize_page(kp)
    vq, vs = ref.quantize_page(vp)
    return q, kq, vq, table, lengths, ks, vs


@pytest.mark.parametrize("tier", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("case", PA_CASES, ids=[str(c) for c in PA_CASES])
def test_paged_attention_sweep_vs_blocked_oracle(case, tier):
    """shapes x dtypes x page_size x GQA vs the blocked-recurrence oracle."""
    q, kp, vp, tbl, ln, ks, vs = _pa_case(case, tier)
    got = pa_pallas(q, kp, vp, tbl, ln, k_scale=ks, v_scale=vs)
    want = ref.paged_attention(q, kp, vp, tbl, ln, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **TIER_ORACLE)


@pytest.mark.parametrize("case", PA_CASES, ids=[str(c) for c in PA_CASES])
def test_paged_attention_vs_unpaged_naive_reference(case):
    """Cross-oracle check: rebuild each row's contiguous K/V from its pages
    and compare against the naive unpaged ref.attention (single-token
    decode form) — validates the paging itself, not just the recurrence."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(case, "f32")
    B, Hq, d = q.shape
    _, ps, Hkv, _ = kp.shape
    got = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    for b in range(B):
        S = int(ln[b])
        kc = np.concatenate([np.asarray(kp[p]) for p in np.asarray(tbl[b])],
                            axis=0)[:S]  # [S, Hkv, d]
        vc = np.concatenate([np.asarray(vp[p]) for p in np.asarray(tbl[b])],
                            axis=0)[:S]
        want = ref.attention(
            q[b:b + 1, :, None],                      # [1, Hq, 1, d]
            jnp.asarray(kc.transpose(1, 0, 2))[None],  # [1, Hkv, S, d]
            jnp.asarray(vc.transpose(1, 0, 2))[None],
            causal=True, q_offset=S - 1)
        np.testing.assert_allclose(got[b], np.asarray(want)[0, :, 0],
                                   **TIER_ORACLE)


def test_paged_attention_int8_tier_vs_f32():
    case = PA_CASES[0]
    q, kp, vp, tbl, ln, _, _ = _pa_case(case, "f32")
    kq, ks = ref.quantize_page(kp)
    vq, vs = ref.quantize_page(vp)
    f32 = pa_pallas(q, kp, vp, tbl, ln)
    i8 = pa_pallas(q, kq, vq, tbl, ln, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(i8), np.asarray(f32),
                               **TIER_INT8_VS_F32)
    assert not np.array_equal(np.asarray(i8), np.asarray(f32))  # really quantized


def test_paged_attention_xla_backend_matches_kernel():
    for tier in ("f32", "int8"):
        q, kp, vp, tbl, ln, ks, vs = _pa_case(PA_CASES[1], tier)
        kern = pa_pallas(q, kp, vp, tbl, ln, k_scale=ks, v_scale=vs)
        xla = ops.paged_attention(q, kp, vp, tbl, ln, k_scale=ks, v_scale=vs,
                                  backend="xla")
        np.testing.assert_allclose(np.asarray(xla), np.asarray(kern),
                                   **TIER_ORACLE)


# -- the bitwise invariances the TP serving contract is built on ------------


def test_paged_attention_bitwise_head_partition_invariance():
    """Computing one head at a time (via kv_head remapping) is bitwise
    identical to the all-heads call — head sharding cannot change bits."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(PA_CASES[1], "f32")
    B, Hq, d = q.shape
    Hkv = kp.shape[2]
    full = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    group = Hq // Hkv
    for h in range(Hq):
        one = pa_pallas(q[:, h:h + 1], kp, vp, tbl, ln,
                        kv_head=jnp.asarray([h // group], jnp.int32))
        assert np.array_equal(np.asarray(one)[:, 0], full[:, h]), f"head {h}"


def test_paged_attention_bitwise_row_partition_invariance():
    """Splitting the batch across calls is bitwise identical to one call —
    continuous batching cannot change a sequence's bits."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(PA_CASES[0], "f32")
    full = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    for b in range(q.shape[0]):
        one = pa_pallas(q[b:b + 1], kp, vp, tbl[b:b + 1], ln[b:b + 1])
        assert np.array_equal(np.asarray(one)[0], full[b]), f"row {b}"


def test_paged_attention_bitwise_pad_column_invariance():
    """Extra table columns (pointing at arbitrary valid pages, fully masked
    by lengths) leave every output bit unchanged — the engine pads tables
    to a fixed pow2 width to bound recompiles."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(PA_CASES[0], "f32")
    base = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    for extra in (1, 3):
        padded = jnp.concatenate(
            [tbl, jnp.zeros((tbl.shape[0], extra), jnp.int32)], axis=1)
        got = np.asarray(pa_pallas(q, kp, vp, padded, ln))
        assert np.array_equal(got, base), f"pad {extra}"


def test_paged_attention_bitwise_page_relocation_invariance():
    """Moving pages to different pool slots (table updated to match) leaves
    every output bit unchanged — eviction/reuse cannot perturb survivors."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(PA_CASES[2], "f32")
    n_pages = kp.shape[0]
    base = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    perm = np.asarray(rng.permutation(n_pages))
    inv = np.empty_like(perm)
    inv[perm] = np.arange(n_pages)
    got = np.asarray(pa_pallas(q, jnp.asarray(np.asarray(kp)[perm]),
                               jnp.asarray(np.asarray(vp)[perm]),
                               jnp.asarray(inv[np.asarray(tbl)], dtype=jnp.int32),
                               ln))
    assert np.array_equal(got, base)


def test_paged_attention_bitwise_stacked_pool_vs_per_rank():
    """The serving engine's one-call-over-all-ranks trick: rank r's heads
    carry page_offset r*n_pages over the stacked [P*n_pages, ...] pool.
    Bitwise identical to P separate per-rank-pool calls."""
    P, Hl, Hkv, d, ps, n_pages, npm, B = 2, 2, 2, 8, 4, 6, 2, 3
    pools = [_pa_case((B, Hl, Hkv, d, ps, n_pages, npm), "f32")
             for _ in range(P)]
    q0, _, _, tbl, ln, _, _ = pools[0]
    qs = [q0] + [_mk((B, Hl, d), jnp.float32) for _ in range(P - 1)]
    per_rank = [np.asarray(pa_pallas(qs[r], pools[r][1], pools[r][2],
                                     tbl, ln)) for r in range(P)]
    stacked_k = jnp.concatenate([pools[r][1] for r in range(P)], axis=0)
    stacked_v = jnp.concatenate([pools[r][2] for r in range(P)], axis=0)
    qall = jnp.concatenate(qs, axis=1)  # [B, P*Hl, d]
    heads = np.arange(P * Hl, dtype=np.int32)
    got = np.asarray(pa_pallas(
        qall, stacked_k, stacked_v, tbl, ln,
        kv_head=jnp.asarray(heads % Hl),
        page_offset=jnp.asarray((heads // Hl) * n_pages)))
    for r in range(P):
        assert np.array_equal(got[:, r * Hl:(r + 1) * Hl], per_rank[r]), r


def test_paged_attention_zero_length_row_is_exact_zero():
    """Batch-padding rows (length 0) output exact +0.0 and do not perturb
    real rows' bits."""
    q, kp, vp, tbl, ln, _, _ = _pa_case(PA_CASES[0], "f32")
    base = np.asarray(pa_pallas(q, kp, vp, tbl, ln))
    ln0 = jnp.asarray(np.concatenate([np.asarray(ln), [0]]).astype(np.int32))
    q0 = jnp.concatenate([q, q[:1]], axis=0)
    tbl0 = jnp.concatenate([tbl, tbl[:1]], axis=0)
    got = np.asarray(pa_pallas(q0, kp, vp, tbl0, ln0))
    assert np.array_equal(got[:-1], base)
    assert (got[-1] == 0.0).all()


# -- per-(page, head) KV page quantization kernels --------------------------


@pytest.mark.parametrize("shape", [(6, 8, 2, 16), (3, 4, 4, 8)])
def test_quantize_page_pallas_vs_ref(shape):
    x = _mk(shape, jnp.float32)
    q1, s1 = qp_pallas(x, interpret=True)
    q2, s2 = ref.quantize_page(x)
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
    d1 = dqp_pallas(q1, s1, interpret=True)
    d2 = ref.dequantize_page(q2, s2)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-6)
    # round-trip error: half an int8 step per (page, head)
    xf = np.asarray(x, np.float32)
    bound = np.abs(xf).max(axis=(1, 3), keepdims=True) / 127.0 * 0.5 + 1e-7
    assert (np.abs(np.asarray(d1) - xf) <= bound + 1e-6).all()


def test_quantize_page_zero_page_is_exact():
    x = jnp.zeros((2, 4, 2, 8), jnp.float32)
    q, s = qp_pallas(x, interpret=True)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(s) == 1.0)  # zero pages keep unit scales
    assert np.all(np.asarray(dqp_pallas(q, s, interpret=True)) == 0)
