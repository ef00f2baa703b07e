"""Roofline-analysis validation.

1. The analytic FLOPs model must agree with XLA's cost_analysis on an
   UNROLLED (scan_layers=False) reduced config — that is the ground truth
   HLO FLOP count (scanned modules under-report: XLA counts while bodies
   once; verified in test_scan_counted_once).
2. The HLO collective parser: computation splitting, while-loop trip
   recovery, execution multipliers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.analysis import cell_cost, layer_flops_per_tok
from repro.launch.dryrun import (
    _loop_multipliers,
    _split_computations,
    parse_collectives,
)
from repro.models import lm
from repro.models.layers import NO_SHARD




def test_scan_counted_once_by_xla():
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def mk(n, unroll):
        def f(w, x):
            if unroll:
                for _ in range(n):
                    x = jnp.tanh(x @ w)
                return x
            return jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x, None,
                                length=n)[0]
        return jax.jit(f).lower(w, x).compile().cost_analysis()["flops"]

    assert mk(8, True) > 7 * mk(8, False)  # scan body counted once


@pytest.mark.parametrize("arch", ["llama3_2_1b", "xlstm_125m", "hubert_xlarge"])
def test_analytic_flops_vs_unrolled_hlo(arch):
    """Forward-pass FLOPs: analytic formula vs XLA on the unrolled module."""
    cfg = configs.get_reduced(arch)
    cfg = dataclasses.replace(cfg, scan_layers=False, remat=False)
    B, S = 2, 64
    pshapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    bshapes = lm.input_specs(cfg, B, S)

    def fwd(params, batch):
        logits, _, _ = lm.forward(params, cfg, NO_SHARD, batch)
        return logits

    hlo_flops = jax.jit(fwd).lower(pshapes, bshapes).compile().cost_analysis()["flops"]
    tokens = B * S
    analytic = (
        layer_flops_per_tok(cfg, S / 2, S) * cfg.n_layers * tokens
        + 2 * cfg.d_model * lm.padded_vocab(cfg) * tokens
    )
    ratio = analytic / hlo_flops
    assert 0.7 < ratio < 1.45, f"{arch}: analytic/hlo = {ratio:.2f}"


def test_cell_cost_train_factor():
    cfg = configs.get("llama3_2_1b")
    c_train = cell_cost(cfg, "train", 256, 4096, 256)
    c_prefill = cell_cost(cfg, "prefill", 256, 4096, 256)
    # train ~= 4x forward for the layers (+3x head)
    assert 3.3 < c_train.flops_global / c_prefill.flops_global < 4.3


def test_decode_flops_tiny_vs_prefill():
    cfg = configs.get("yi_6b")
    dec = cell_cost(cfg, "decode", 128, 32768, 256)
    pre = cell_cost(cfg, "prefill", 32, 32768, 256)
    assert dec.flops_global < pre.flops_global / 1000


HLO_SAMPLE = """\
ENTRY %main (p0: f32[8]) -> f32[8] {
  %ar1 = f32[1024]{0} all-reduce(f32[1024]{0} %x), replica_groups=[16,16]<=[256], to_apply=%add
  %w = (s32[], f32[8]) while(%t), condition=%cond_a, body=%body_a
}
%body_a (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ag = f32[512]{0} all-gather(f32[32]{0} %y), replica_groups=[16,16]<=[256]
  %w2 = (s32[], f32[8]) while(%t2), condition=%cond_b, body=%body_b
}
%cond_a (arg: (s32[], f32[8])) -> pred[] {
  %c = s32[] constant(12)
  %lt = pred[] compare(%i, %c), direction=LT
}
%body_b (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %ar2 = bf16[256]{0} all-reduce(bf16[256]{0} %z), replica_groups={{0,256},{1,257}}, to_apply=%add
}
%cond_b (arg: (s32[], f32[8])) -> pred[] {
  %c2 = s32[] constant(4)
  %lt2 = pred[] compare(%j, %c2), direction=LT
}
"""


def test_hlo_computation_split_and_multipliers():
    comps = _split_computations(HLO_SAMPLE)
    assert set(comps) == {"main", "body_a", "cond_a", "body_b", "cond_b"}
    mult = _loop_multipliers(comps)
    assert mult["main"] == 1.0
    assert mult["body_a"] == 12.0
    assert mult["body_b"] == 48.0  # nested: 12 * 4


def test_parse_collectives_multiplied_and_classified():
    colls = parse_collectives(HLO_SAMPLE, pod_size=256)
    by_op = {c["op"]: c for c in colls}
    ar_entry = [c for c in colls if c["op"] == "all-reduce" and c["executions"] == 1.0]
    assert ar_entry and ar_entry[0]["local_bytes"] == 4096
    ag = by_op["all-gather"]
    assert ag["executions"] == 12.0
    assert ag["channel"] == "ici"
    ar_inner = [c for c in colls if c["executions"] == 48.0]
    assert ar_inner and ar_inner[0]["channel"] == "dcn"  # group {0, 256} crosses pods
    assert ar_inner[0]["local_bytes"] == 512  # bf16[256]
