"""FLOP and byte counts, against the program's analytic accounting and
against XLA's own count of an unrolled small model."""

import dataclasses

import jax
import pytest

from benchmarks.chip import catalog, flops
from benchmarks.chip.runners import lm_train
from benchmarks.chip.tests import tiny


@pytest.mark.parametrize("w", ["yi6b-train-s4k", "granite8b-train-s1k"])
def test_train_flops_match_the_program_accounting_without_recompute(w):
    """``launch/analysis.py`` counts a trained token as 4 forward passes of
    the layers (remat recomputes one) and 3 of the head; the model FLOPs are
    3 of each."""
    from repro.launch.analysis import layer_flops_per_tok
    from repro.models import lm

    cell = catalog.load_cell(w)
    cfg = lm_train.model_config(cell.config)
    S = cell.traffic["seq_len"]
    s = cell.sizes
    program = 3 * (cfg.n_layers * layer_flops_per_tok(cfg, (S + 1) / 2, S)
                   + 2 * s.d_model * lm.padded_vocab(cfg))
    ours = flops.train_flops_per_token(s, S)
    pad = 6 * s.d_model * (lm.padded_vocab(cfg) - s.vocab)  # the program counts padded rows
    assert ours + pad == pytest.approx(program, rel=1e-12)


def test_yi_flops_per_token():
    s = catalog.load_cell("yi6b-train-s4k").sizes
    layer = 4096 * 40 * 128 + 32 * 128 * 4096 + 3 * 4096 * 11008
    assert flops.matmul_params(s) == s.n_layers * layer + 4096 * 64000
    attention = 3 * s.n_layers * 2 * 32 * 128 * 4097
    assert flops.train_flops_per_token(s, 4096) == 6 * flops.matmul_params(s) + attention
    # at four layers: 6.13 GFLOP a token
    four = dataclasses.replace(s, n_layers=4)
    assert flops.train_flops_per_token(four, 4096) == pytest.approx(6.13e9, rel=2e-3)


def test_forward_flops_match_xla_on_an_unrolled_model():
    """Two thirds of a step is backward; one third, the forward pass, is
    what XLA counts for ``lm.forward``.  The XLA attention twin computes the
    whole score square and masks it, so the check uses a sequence short
    enough that attention is a small share, and allows for it."""
    from repro.models import lm
    from repro.models.layers import NO_SHARD

    cell = tiny.cell()
    cfg = dataclasses.replace(lm_train.model_config(cell.config), scan_layers=False,
                              remat=False, vocab_size=cell.sizes.vocab_rows)
    B, S = 2, 64
    pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    fwd = jax.jit(lambda p, b: lm.forward(p, cfg, NO_SHARD, b)[0])
    hlo = fwd.lower(pshapes, lm.input_specs(cfg, B, S)).compile().cost_analysis()["flops"]
    s = dataclasses.replace(cell.sizes, vocab=cell.sizes.vocab_rows)
    square = 2 * 2 * s.n_heads * s.head_dim * S * S * s.n_layers * B  # twin: unmasked
    ours = flops.train_flops_per_token(s, S) / 3 * B * S
    causal = 2 * 2 * s.n_heads * s.head_dim * flops.causal_pairs(S) * s.n_layers * B
    assert ours - causal + square == pytest.approx(hlo, rel=0.05)


def test_flash_fwd_cost():
    cell = catalog.load_cell("yi6b-train-s4k")
    f, b = flops.flash_fwd(1, catalog.reference(cell.config).attention_dims(cell.sizes), 4096)
    assert f == 4 * 32 * 128 * 4096 * 4097 / 2
    assert b == 2 * 4096 * 128 * (2 * 32 + 2 * 4)
    peaks = catalog.peaks("TPU v5 lite")
    assert flops.roofline_s(f, b, peaks) == f / 197e12  # compute-bound at d = 128
