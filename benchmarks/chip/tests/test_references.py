"""Each configuration's plain reference says how its sizes become the
program's fields, the step's FLOPs and the attention kernel's work.

The dense cells read exactly what the harness computed before the
references said it (the literals below), and a latent-attention MoE
configuration, whose reference (``stub_mla_moe.py``) has nested program
fields, a FLOP count of its own, ``d_qk != d_v`` and a ``kv_norm`` leaf,
goes through the same harness files unedited.
"""

import hashlib
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import catalog, flops, trace, weights
from benchmarks.chip.metrics import flash_fwd_roofline, step_mfu
from benchmarks.chip.runners import lm_train
from benchmarks.chip.tests import stub_mla_moe, tiny
from benchmarks.chip.tests.test_catalog import check_published_widths

DATA = Path(__file__).resolve().parent / "data"
PEAKS = catalog.peaks("TPU v5 lite")


def _dense(name, n_layers, n_kv_heads, d_ff, vocab_size, rope_theta, tie_embeddings):
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=name, family="dense", n_layers=n_layers, d_model=4096, n_heads=32,
        n_kv_heads=n_kv_heads, d_ff=d_ff, vocab_size=vocab_size, head_dim=128, qk_norm=False,
        causal=True, sliding_window=0, rope_theta=rope_theta, tie_embeddings=tie_embeddings,
        norm_eps=1e-05, moe=None, mla=None, ssm=None, vlm=None, dtype="bfloat16",
        param_dtype="float32", remat=True, scan_layers=True)


YI = dict(config=("yi-6b", 3, 4, 11008, 64000, 5000000.0, False),
          flops_per_token=4989198336.0, flash_fwd=(137472507904.0, 75497472.0))
GOLDEN = {  # what the harness gave before the references said it
    "yi6b-train-s4k": YI,
    "granite8b-train-s1k": dict(config=("granite-3-8b", 4, 8, 12800, 49155, 10000000.0, True),
                                flops_per_token=6090301440.0,
                                flash_fwd=(68786585600.0, 167772160.0)),
    "yi6b-zero1-dp4-s4k": YI,
}


@pytest.mark.parametrize("workload", sorted(GOLDEN))
def test_dense_cells_count_as_before(workload):
    cell = catalog.load_cell(workload)
    ref, want = catalog.reference(cell.config), GOLDEN[workload]
    assert lm_train.model_config(cell.config) == _dense(*want["config"])
    seq = cell.traffic["seq_len"]
    assert ref.train_flops_per_token(cell.sizes, seq) == want["flops_per_token"]
    assert ref.train_flops_per_token is flops.train_flops_per_token
    dims = ref.attention_dims(cell.sizes)
    assert flops.flash_fwd(cell.traffic["rows_per_chip"], dims, seq) == want["flash_fwd"]


LEAF = {  # sha256 of each float32 leaf of the tiny copy, seed 2**31 + 7, first 16 digits
    "embed": "2041061a625f5f0b", "final_norm": "893a106828fbdb95",
    "groups/ln1": "fef951e6c76ad6a0", "groups/attn/wq": "f025bab2d2b80899",
    "groups/attn/wk": "7642dbb0abbf6686", "groups/attn/wv": "7519b17df21d40c1",
    "groups/attn/wo": "5ac487bc3d0433e1", "groups/ln2": "fef951e6c76ad6a0",
    "groups/mlp/gate": "596eb3618b49e5c1", "groups/mlp/up": "48bf3bdd295eda18",
    "groups/mlp/down": "e3f2352b94524f99", "head": "a23c677b4318288f",
}


@pytest.mark.parametrize("workload", ["yi6b-train-s4k", "granite8b-train-s1k"])
def test_dense_leaves_are_drawn_as_before(workload):
    cell = tiny.cell(workload)
    shapes = catalog.reference(cell.config).layout(cell.sizes)
    assert ("head" in shapes) != cell.sizes.tied
    key = weights.root_key(2**31 + 7)
    for name, shape in shapes.items():
        a = np.asarray(weights.leaf(key, name, shape, jnp.float32))
        assert hashlib.sha256(a.tobytes()).hexdigest()[:16] == LEAF[name], name


STUB = {  # DeepSeek-V2's published keys, cut in depth and in experts held
    "name": "stub-mla-moe", "program_arch": "deepseek-v2-236b", "reference": "stub_mla_moe",
    "hidden_size": 5120, "num_attention_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "moe_intermediate_size": 1536, "n_routed_experts": 8, "num_experts_per_tok": 6,
    "n_shared_experts": 2, "vocab_size": 102400, "num_hidden_layers": 2, "rope_theta": 10000,
    "reduced": {"num_hidden_layers": {"published": 60, "held": 2},
                "n_routed_experts": {"published": 160, "held": 8}},
    "precision": {"param_dtype": "float32", "compute_dtype": "bfloat16"},
}


@pytest.fixture
def stub(monkeypatch):
    """``catalog.reference`` finds the stub by its configuration's name."""
    real = catalog.reference
    monkeypatch.setattr(catalog, "reference", lambda c: (
        stub_mla_moe if c["reference"] == "stub_mla_moe" else real(c)))
    return catalog.Cell("stub", 1, STUB, {"rows_per_chip": 2, "seq_len": 256}, {}, (), ())


def test_stub_sets_nested_program_fields(stub):
    cfg = lm_train.model_config(stub.config)
    assert (cfg.n_layers, cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.n_shared) == (2, 8, 6, 2)
    assert (cfg.mla.kv_lora, cfg.mla.qk_nope, cfg.mla.qk_rope, cfg.mla.v_dim) == (
        512, 128, 64, 128)
    assert cfg.moe.capacity_factor == 1.25  # a field the reference does not set is kept
    check_published_widths(stub.config)


def test_stub_counts_its_own_flops(stub):
    s = stub.sizes
    ctx = trace.Context(trace=None, cell=stub, spans=None, window={}, tokens_per_s=1000.0,
                        peaks=PEAKS)
    want = 100.0 * stub_mla_moe.train_flops_per_token(s, 256) * 1000.0 / PEAKS["bf16_flops_per_s"]
    assert step_mfu.read(ctx) == want


def test_stub_attention_counts_both_head_widths(stub):
    s = stub.sizes
    f, b = flops.flash_fwd(2, stub_mla_moe.attention_dims(s), 256)
    assert f == 2 * 2 * 128 * (192 + 128) * flops.causal_pairs(256)
    assert b == 2 * 2 * 256 * (128 * 192 + 128 * 192 + 128 * 128 + 128 * 128)
    t = trace.load(str(DATA / "v5e_1chip_tiny_step.xplane.pb"), 1)
    ctx = trace.Context(trace=t, cell=stub, spans=None, window={}, tokens_per_s=1.0,
                        peaks=PEAKS)
    (plane,) = t.ops
    seconds, calls = t.op_time(plane, flash_fwd_roofline.is_flash)
    least = flops.roofline_s(f, b, PEAKS)
    assert flash_fwd_roofline.read(ctx) == 100.0 * calls * least / seconds


def test_stub_norm_gains_are_ones(stub):
    key = weights.root_key(3)
    shapes = stub_mla_moe.layout(stub.sizes)
    for name in ("groups/attn/kv_norm", "groups/attn/q_norm", "final_norm", "groups/ln1"):
        assert np.all(np.asarray(weights.leaf(key, name, shapes[name], jnp.float32)) == 1), name
    w = np.asarray(weights.leaf(key, "groups/attn/wkv_b", (2, 16, 24), jnp.float32))
    assert np.isfinite(w).all() and 0 < w.std() < 1
