"""BENCHMARK.json against the rules that can be checked without a chip."""
import json
import os
import re

import pytest

from .conftest import PRESET, REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(params=[os.path.join(REPO, "BENCHMARK.json"), PRESET],
                ids=["real", "preset"])
def manifest(request):
    return load(request.param)


def test_names_units_and_keys(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for c in manifest["workloads"]:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["chips"] in (1, 4) and 1 <= len(c["why"]) <= 200
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_cells_configs_and_files(manifest):
    cells = {c["name"] for c in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    root = os.path.join(REPO, manifest["paths"][0])
    for c in manifest["workloads"]:
        assert c["config"] in configs
        assert c["name"] == c["config"] + "." + c["traffic"]
        traffic = load(os.path.join(root, "traffic", c["name"] + ".json"))
        assert traffic["chips"] == c["chips"]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "drivers", traffic["driver"] + ".py"))
    assert {c["config"] for c in manifest["workloads"]} == set(configs)
    for cfg in configs.values():
        assert cfg["file"].startswith(manifest["paths"][0] + "/")
        sizes = load(os.path.join(REPO, cfg["file"]))
        assert sizes["name"] == cfg["name"]
        assert len(cfg["source"]) <= 200 and len(cfg["reduced"]) <= 16
    four = sum(1 for c in manifest["workloads"] if c["chips"] == 4)
    assert four <= max(1, len(cells) // 4)


def test_every_layer_metric_moves_a_metric_its_cells_report(manifest):
    from benchmarks.lib.manifest import Manifest

    path = PRESET if manifest["paths"] != ["benchmarks"] else \
        os.path.join(REPO, "BENCHMARK.json")
    man = Manifest(path, REPO)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for cell in manifest["workloads"]:
        reported = {m["name"] for m in man.end_to_end(cell)}
        assert "setup_s" in reported and len(reported) >= 2
        layer = man.per_layer(cell)
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in reported, (cell["name"], m["name"])
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        family = m["name"].split(".")[0]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layer_metrics", family + ".py"))
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
