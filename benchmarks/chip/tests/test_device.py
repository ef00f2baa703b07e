"""The harness runs only on the chips a cell asks for, of a known kind."""

import json
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from benchmarks.chip import catalog, harness, run


def test_refuses_the_cpu():
    with pytest.raises(harness.NoChip):
        harness.check_devices(1)


def _fake(kind, n):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i) for i in range(n)]


def test_refuses_too_few_chips(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: _fake("TPU v5 lite", 1))
    with pytest.raises(harness.NoChip):
        harness.check_devices(4)
    assert len(harness.check_devices(1)) == 1


def test_refuses_an_unknown_device_kind(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: _fake("TPU v99", 4))
    with pytest.raises(KeyError):
        harness.check_devices(1)


def test_command_exits_nonzero_without_a_chip(capsys):
    assert run.main(["--workload", "yi6b-train-s4k", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_paths_alone_are_not_enough(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's files the
    command fails and prints no result."""
    shutil.copy(catalog.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(catalog.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cmd = json.loads((tmp_path / "BENCHMARK.json").read_text())["command"]
    p = subprocess.run([sys.executable] + cmd[1:] + ["--workload", "yi6b-train-s4k",
                       "--seed", "1", "--seconds", "1"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
