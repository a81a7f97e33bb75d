"""BENCHMARK.json keeps to its allowed characters, and every cell's
configuration, reference, adapter, kernel counts, traffic, limits and
metric readers resolve by name."""

import json

import pytest

from bench.lib import spec

B = spec.benchmark()


def _names():
    yield from (c["name"] for c in B["configs"])
    for w in B["workloads"]:
        yield w["name"]
        yield w["config"]
        yield w["traffic"]
    for m in B["end_to_end"] + B["per_layer"]:
        yield m["name"]
    for c in B["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert spec.NAME.match(name), name


@pytest.mark.parametrize("metric", B["end_to_end"] + B["per_layer"],
                         ids=lambda m: m["name"])
def test_units_and_keys(metric):
    assert spec.UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    allowed = {"name", "unit", "better", "source", "bound", "workloads",
               "layer", "moves"}
    assert set(metric) <= allowed


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve(w):
    c = spec.cell(w["name"])
    assert c["config"]["name"] == w["config"]
    spec.module("refs", c["config"]["reference"])
    spec.module("counts", c["config"]["reference"])
    arch = spec.module("adapters", c["config"]["reference"])
    for f in ("leaves", "to_program", "arch_config", "kv_values_per_token"):
        assert callable(getattr(arch, f)), f
    assert arch.KERNELS
    for k in arch.KERNELS:
        kmod = spec.module("counts", k)
        assert kmod.OP_NAMES and callable(kmod.step)
    for m in c["per_layer"]:
        assert callable(spec.module("metrics", m["name"]).read)
    assert set(c["limits"]) <= {"gap_max", "gap_mean"} and c["limits"]


def test_configs_state_their_cuts():
    for entry in B["configs"]:
        cfg = json.loads((spec.ROOT / entry["file"]).read_text())
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        for k in cfg["reduced"]:
            assert cfg["published"][k] != cfg["model"][k]


def test_per_layer_moves_an_end_to_end_metric():
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["per_layer"]:
        assert m["moves"] in e2e
