"""The trace reduction, on traces recorded on a TPU v5e.

``data/v5e_1chip_tiny_step.xplane.pb``: a 0.05 s window of the harness
driving a tiny copy of ``yi6b-train-s4k`` (2 layers, d_model 256, 4/2 heads of
64, 2 x 256 tokens a step) on one chip, with the Pallas flash kernel.
"""

import json
from pathlib import Path

import pytest

from benchmarks.chip import catalog, trace
from benchmarks.chip.metrics import collective_ms_per_step, flash_fwd_roofline

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def one_chip():
    meta = json.loads((DATA / "v5e_1chip_tiny_step.json").read_text())
    return trace.load(str(DATA / "v5e_1chip_tiny_step.xplane.pb"), 1), meta


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_leaves_drop_the_ops_that_hold_others():
    ops = [trace.Op("while", 0, 10), trace.Op("a", 0, 4), trace.Op("b", 5, 9),
           trace.Op("c", 12, 13)]
    assert [o.name for o in trace.leaves(ops)] == ["a", "b", "c"]


def test_short_name():
    assert trace.short_name("%fusion.5 = (f32[2]{0}, bf16[4,4]{1,0}) fusion(f32[2] %p), "
                            "kind=kLoop") == "%fusion.5 fusion"
    assert trace.short_name("%all-gather-start.3 = bf16[8]{0} all-gather-start(bf16[2] %x)"
                            ) == "%all-gather-start.3 all-gather-start"


def test_busy_and_idle_fill_the_window(one_chip):
    t, meta = one_chip
    (plane,) = t.ops
    busy = t.busy_s()
    idle = sum(b - a for a, b in t.idle_gaps(plane)) / 1e9
    assert 0 < busy < t.window_s()
    assert busy + idle == pytest.approx(t.window_s(), rel=1e-9)
    # the host window brackets the device's steps
    assert t.window_s() >= meta["window"]["seconds"] * 0.9


def test_flash_calls_are_found(one_chip):
    t, meta = one_chip
    (plane,) = t.ops
    seconds, calls = t.op_time(plane, flash_fwd_roofline.is_flash)
    # each step runs the forward once per layer and remat runs it again
    assert calls == meta["window"]["steps"] * meta["num_hidden_layers"] * 2
    assert 0 < seconds < t.busy_s()


def test_no_collectives_on_one_chip(one_chip):
    t, _ = one_chip
    assert t.op_time(next(iter(t.ops)), collective_ms_per_step.is_collective)[1] == 0


def test_readers_on_the_recorded_trace(one_chip):
    t, meta = one_chip
    config = dict(catalog.load_cell("yi6b-train-s4k").config,
                  **{k: meta[k] for k in ("hidden_size", "num_attention_heads",
                                          "num_key_value_heads", "num_hidden_layers")})
    cell = catalog.Cell("tiny", 1, config, {"rows_per_chip": meta["rows_per_chip"],
                                            "seq_len": meta["seq_len"]}, {}, (), ())
    ctx = trace.Context(trace=t, cell=cell, spans=None, window=meta["window"],
                        tokens_per_s=1.0, peaks=catalog.peaks("TPU v5 lite"))
    share = flash_fwd_roofline.read(ctx)
    assert 0 < share <= 100
    assert collective_ms_per_step.read(ctx) is None
    b = t.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert {g[0] for g in b["idle_gaps"]} <= {"input", "dispatch", "wait", "no span"}
    assert b["idle_gaps"] == sorted(b["idle_gaps"], key=lambda g: -g[1])


def test_four_chip_collectives_are_found():
    """``data/v5e_4chip_tiny_zero1.xplane.pb``: the tiny copy of
    ``yi6b-zero1-dp4-s4k`` on four chips, FMI's ZeRO-1 collectives in it."""
    meta = json.loads((DATA / "v5e_4chip_tiny_zero1.json").read_text())
    t = trace.load(str(DATA / "v5e_4chip_tiny_zero1.xplane.pb"), 4)
    assert len(t.ops) == 4
    for plane in t.ops:
        seconds, calls = t.op_time(plane, collective_ms_per_step.is_collective)
        assert calls > 0 and 0 < seconds < t.window_s()
        assert t.op_time(plane, flash_fwd_roofline.is_flash)[1] == (
            meta["window"]["steps"] * meta["num_hidden_layers"] * 2)
    assert 0 < t.busy_s() < t.window_s()
    ctx = trace.Context(trace=t, cell=None, spans=None, window=meta["window"],
                        tokens_per_s=1.0, peaks=catalog.peaks("TPU v5 lite"))
    assert collective_ms_per_step.read(ctx) > 0
