"""The hybrid state-space MoE configuration at toy widths, on the CPU: its
cell end to end, its manifest against the rules, its operation counts by
hand. The toy cell has a manifest of its own beside the preset's
(``preset/tiny_nemotron.manifest.json``, the same ``paths``), added as a PR
adds a cell: new files only."""
import os

import pytest

from . import test_manifest, test_run
from .conftest import PRESET, REPO

NEMOTRON_PRESET = os.path.join(os.path.dirname(PRESET),
                               "tiny_nemotron.manifest.json")


@pytest.fixture
def nemotron_run(preset_run, monkeypatch):
    """``preset_run`` on the toy cell's own manifest."""
    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", NEMOTRON_PRESET)
    return preset_run


def test_cell_runs_end_to_end(nemotron_run):
    test_run.test_cell_runs_end_to_end(
        nemotron_run, "tiny_nemotron.static", "tokens_per_s")


@pytest.mark.parametrize("check", [
    test_manifest.test_names_units_and_keys,
    test_manifest.test_cells_configs_and_files,
    test_manifest.test_every_layer_metric_moves_a_metric_its_cells_report,
], ids=["names", "files", "moves"])
def test_manifest_of_the_toy_cell(check, monkeypatch):
    monkeypatch.setattr(test_manifest, "PRESET", NEMOTRON_PRESET)
    check(test_manifest.load(NEMOTRON_PRESET))


def test_the_real_and_the_toy_manifest_list_the_same_new_metrics():
    real = test_manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    toy = test_manifest.load(NEMOTRON_PRESET)
    new = {m["name"] for m in real["per_layer"]
           if m["name"].split(".")[0] in ("ssm", "moe")}
    assert new and new <= {m["name"] for m in toy["per_layer"]}


def test_nemotron3_nano_ep16_by_hand():
    from benchmarks.configs.nemotron3_nano_ep16 import flops, reference

    c = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "nemotron3_nano_ep16", "config.json"))
    t, d = 8192, 2688
    # the cut holds 667 M parameters, as ISSUE 27 reckons
    leaves = reference.leaf_shapes(c)
    count = 0
    for shape in leaves.values():
        n = 1
        for s in shape:
            n *= s
        count += n
    assert count == pytest.approx(667e6, rel=1e-3)
    assert leaves["l0.in_proj"] == (d, 10304)
    assert leaves["l1.w1"] == (8, d, 1856) and leaves["l5.k"] == (d, 256)
    # expected load: 8192 x 6 x 8 / 128 slots a layer
    assert flops.expected_slots(c, t) == 3072
    scan = 2 * t * (128 * 8 * 128 + 128 * 64 * 64 + 2 * 64 * 64 * 128)
    assert flops.scan_ops_and_bytes(c, t)[0] == scan
    experts = 2 * 2 * 3072 * d * 1856
    assert flops.experts_ops_and_bytes(c, t)[0] == experts
    assert flops.experts_ops_and_bytes(c, t)[1] == (
        2 * 8 * d * 1856 + 3072 * 2 * (d + 1856)) * 2
    mamba = 2 * t * d * (10304 + 4096) + scan
    moe = 2 * t * d * (128 + 2 * 3712) + experts
    attention = 2 * t * d * (2 * 4096 + 2 * 256) + 2 * t * t * 4096
    by_hand = 3 * (4 * mamba + 4 * moe + attention + 2 * t * d * 16384)
    got = flops.flops_per_step(c, {"batch": 1, "seq_len": t})
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(17.6e12, rel=5e-3)    # "~17 TFLOP a step"
