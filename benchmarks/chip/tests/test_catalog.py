"""Every name in BENCHMARK.json resolves to its file, and each configuration
file holds the program's published widths, cutting only what it lists."""

import functools
import importlib
import json

import pytest

from benchmarks.chip import catalog, compare
from benchmarks.chip.runners import lm_train

BENCH = catalog.benchmark()


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    cell = catalog.load_cell(w["name"])
    assert cell.chips == w["chips"]
    assert compare.compared(cell.limits) and set(cell.limits) <= set(compare.NAMES)
    assert callable(catalog.runner(cell).run_cell)
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_resolves(c):
    with open(catalog.ROOT / c["file"]) as f:
        config = json.load(f)
    assert config["name"] == c["name"] and config["source"] == c["source"]
    assert sorted(config["reduced"]) == sorted(c["reduced"])
    assert catalog.reference(config).layout(catalog.reference(config).Sizes.from_config(config))


def _flat(fields: dict, prefix: str = "") -> dict:
    """``{"moe.top_k": 6, ...}``: nested program fields by dotted name."""
    out = {}
    for k, v in fields.items():
        out.update(_flat(v, f"{prefix}{k}.") if isinstance(v, dict) else {prefix + k: v})
    return out


def _field(cfg, dotted: str):
    return functools.reduce(getattr, dotted.split("."), cfg)


def check_published_widths(config: dict):
    """Every program field that the reference maps, nested ones included, is
    the registry entry's, but those that the keys under ``reduced`` set."""
    from repro import configs

    ref = catalog.reference(config)

    def fields(c):
        return _flat(ref.program_fields(ref.Sizes.from_config(c)))

    held = fields(config)
    published = fields(dict(config, **{k: c["published"] for k, c in config["reduced"].items()}))
    base = configs.get(config["program_arch"])
    cfg = lm_train.model_config(config)
    for f, v in held.items():
        assert _field(base, f) == published[f], f  # the registry's entry is the published model
        assert _field(cfg, f) == v, f  # the program runs the file's sizes
    for key, cut in config["reduced"].items():
        assert config[key] == cut["held"] < cut["published"]
        assert fields(dict(config, **{key: cut["published"]})) != held, key  # the cut reaches it


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_keeps_published_widths(c):
    """The file's sizes are the program registry's, but for the cut keys."""
    with open(catalog.ROOT / c["file"]) as f:
        check_published_widths(json.load(f))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_resolves(m):
    assert callable(catalog.metric_reader(m["name"]))
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_peaks_table():
    p = catalog.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        catalog.peaks("TPU v99")


def test_every_cell_reports_its_metrics():
    names = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", names)) <= names
    importlib.import_module("benchmarks.chip.run")
