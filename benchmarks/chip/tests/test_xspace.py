"""Each op's op-name path from a recorded trace, and device time by scope.

The recordings (``data/``, see ``test_trace.py``) predate the program's
named scopes: their paths name JAX's transformations and primitives only,
so every scope reader finds nothing there and returns ``None``.
"""

import json
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import catalog, scopes, trace, xspace
from benchmarks.chip.metrics import (flash_bwd_ms_per_step, fmi_ms_per_step,
                                     head_loss_ms_per_step, optimizer_ms_per_step)

DATA = Path(__file__).resolve().parent / "data"
READERS = (flash_bwd_ms_per_step, head_loss_ms_per_step, optimizer_ms_per_step,
           fmi_ms_per_step)


def _recording(name: str, chips: int):
    meta = json.loads((DATA / f"{name}.json").read_text())
    profile = str(DATA / f"{name}.xplane.pb")
    ctx = trace.Context(trace=trace.load(profile, chips), cell=None, spans=None,
                        window=meta["window"], tokens_per_s=1.0,
                        peaks=catalog.peaks("TPU v5 lite"), profile=profile)
    scopes.op_paths(ctx)
    return ctx


def test_tf_ops_of_the_device_plane():
    paths = xspace.tf_ops(str(DATA / "v5e_1chip_tiny_step.xplane.pb"),
                          keep=lambda name: name.startswith("/device:TPU"))
    (plane,) = paths
    ops = paths[plane]
    (convert,) = [n for n in ops if n.startswith("%convert_element_type.237 = ")]
    assert ops[convert] == "jit(local_step)/jvp()/convert_element_type"
    assert all(p.startswith("jit(local_step)/") and not p.endswith(":")
               for p in ops.values() if p)
    # XLA's own copies carry no op-name path
    copies = [n for n in ops if n.startswith("%copy-done")]
    assert copies and all(ops[n] == "" for n in copies)


def test_strip_type():
    assert xspace.strip_type("jit(f)/jvp()/exp:Exp") == "jit(f)/jvp()/exp"
    assert xspace.strip_type("jit(f)/dot_general:") == "jit(f)/dot_general"
    assert xspace.strip_type("jit(f)/dot_general") == "jit(f)/dot_general"


@pytest.mark.parametrize("name,chips,share", [("v5e_1chip_tiny_step", 1, 0.92),
                                              ("v5e_4chip_tiny_zero1", 4, 0.98)])
def test_op_paths_give_each_op_its_path(name, chips, share):
    """Most leaf-op time carries a path; the rest is ops that XLA put in,
    its copies and the waits of its async slices first."""
    t = _recording(name, chips).trace
    for plane in t.ops:
        ops, path = t.in_window(plane), t.op_paths[plane]
        total = sum(o.dur_ns for o in ops)
        with_path = sum(o.dur_ns for o in ops if path[o.name])
        assert with_path / total == pytest.approx(share, abs=0.01)
        assert not any(path[o.name] for o in ops
                       if o.name.startswith(("%copy-", "%slice-")))


def test_op_paths_find_the_profile_under_the_temp_dir(tmp_path):
    """The runner profiles into a fresh directory under the temporary
    directory and names it in the context; the profile there is read, and a
    newer one beside it is passed over."""
    name = "v5e_1chip_tiny_step"
    for run, src in (("tmpa", name), ("tmpb", "v5e_4chip_tiny_zero1")):
        d = tmp_path / run / "plugins" / "profile" / "2026_01_01_00_00_00"
        d.mkdir(parents=True)
        shutil.copy(DATA / f"{src}.xplane.pb", d / "host.xplane.pb")
    t = trace.load(str(tmp_path / "tmpa"), 1)
    ctx = trace.Context(trace=t, cell=None, spans=None, window={"steps": 1},
                        tokens_per_s=1.0, peaks={}, profile=str(tmp_path / "tmpa"))
    (plane,) = t.ops
    paths = scopes.op_paths(ctx)
    assert paths[plane][next(o.name for o in t.ops[plane]
                             if o.name.startswith("%convert_element_type.237 = "))] == (
        "jit(local_step)/jvp()/convert_element_type")
    assert scopes.op_paths(ctx) is paths  # read once


def test_op_paths_are_empty_without_the_profile():
    t = trace.load(str(DATA / "v5e_1chip_tiny_step.xplane.pb"), 1)
    ctx = trace.Context(trace=t, cell=None, spans=None, window={"steps": 1},
                        tokens_per_s=1.0, peaks={})
    assert scopes.op_paths(ctx) == {}
    assert [r.read(ctx) for r in READERS] == [None] * len(READERS)


# device ms a step under a scope of the recordings, as read from a profile
# found under the temporary directory before the context named it
SCOPE_MS = [
    ("v5e_1chip_tiny_step", 1, "checkpoint", "mean", 0.09285317857142858),
    ("v5e_1chip_tiny_step", 1, "local_step", "max", 0.1779235357142857),
    ("v5e_4chip_tiny_zero1", 4, "checkpoint", "mean", 0.0921161875),
    ("v5e_4chip_tiny_zero1", 4, "while", "max", 0.13723491666666668),
    ("v5e_4chip_tiny_zero1", 4, "local_step", "mean", 0.42090631250000005),
]


@pytest.mark.parametrize("name,chips,scope,over,ms", SCOPE_MS)
def test_scope_readings_are_unchanged(name, chips, scope, over, ms):
    ctx = _recording(name, chips)
    assert scopes.ms_per_step(ctx, lambda path: scopes.under(path, scope), over=over) == ms
    plane_paths = xspace.tf_ops(ctx.profile, keep=set(ctx.trace.ops).__contains__)
    assert scopes.op_paths(ctx) == plane_paths


def test_segments_take_off_the_transformations():
    assert scopes.segments("jit(step)/transpose(jvp(attention))/exp") == (
        "step", "attention", "exp")
    assert scopes.segments("jit(step)/jvp(head_loss)/dot_general") == (
        "step", "head_loss", "dot_general")
    assert scopes.segments(
        "jit(local_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/mlp/dot_general") == (
        "local_step", "while", "body", "closed_call", "checkpoint",
        "rematted_computation", "mlp", "dot_general")
    assert scopes.segments("") == ()


def test_under_matches_whole_segments():
    path = "jit(s)/shard_map/optimizer/fmi/reduce_scatter/recursive_halving/add"
    assert scopes.under(path, "optimizer")
    assert scopes.under(path, "fmi/reduce_scatter")
    assert scopes.under(path, "fmi/reduce_scatter/recursive_halving")
    assert not scopes.under(path, "fmi/allgather")
    assert not scopes.under(path, "reduce")
    assert scopes.under("jit(s)/transpose(jvp(head_loss))/mul", "head_loss")
    assert not scopes.under("", "head_loss")


def test_ms_per_step_means_or_takes_the_most():
    t = trace.Trace(ops={"/device:TPU:0": [trace.Op("%a", 0, 4e6), trace.Op("%b", 5e6, 6e6)],
                         "/device:TPU:1": [trace.Op("%a", 0, 2e6)]},
                    spans=[("wait", 0, 10e6)])
    scopes.attach(t, {"/device:TPU:0": {"%a": "a/fmi/x", "%b": "a/b"},
                      "/device:TPU:1": {"%a": "a/fmi/x"}})
    ctx = trace.Context(trace=t, cell=None, spans=None, window={"steps": 2},
                        tokens_per_s=1.0, peaks={})
    fmi = lambda path: scopes.under(path, "fmi")  # noqa: E731
    assert scopes.ms_per_step(ctx, fmi) == pytest.approx(1.5)
    assert scopes.ms_per_step(ctx, fmi, over="max") == pytest.approx(2.0)
    assert scopes.ms_per_step(ctx, lambda path: scopes.under(path, "nowhere")) is None


@pytest.mark.parametrize("name,chips", [("v5e_1chip_tiny_step", 1),
                                        ("v5e_4chip_tiny_zero1", 4)])
def test_readers_find_nothing_in_a_trace_without_scopes(name, chips):
    ctx = _recording(name, chips)
    assert [r.read(ctx) for r in READERS] == [None] * len(READERS)
