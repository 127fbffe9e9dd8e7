"""The sparse-attention MoE configuration at toy widths, on the CPU: its cell
end to end, its manifest against the rules, its operation counts by hand. The
toy cell has a manifest of its own beside the preset's
(``preset/tiny_keye.manifest.json``, the same ``paths``), added as a PR adds
a cell: new files only."""
import math
import os

import pytest

from . import test_manifest, test_run
from .conftest import PRESET, REPO

KEYE_PRESET = os.path.join(os.path.dirname(PRESET),
                           "tiny_keye.manifest.json")


@pytest.fixture
def keye_run(preset_run, monkeypatch):
    """``preset_run`` on the toy cell's own manifest."""
    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", KEYE_PRESET)
    return preset_run


def test_cell_runs_end_to_end(keye_run):
    test_run.test_cell_runs_end_to_end(
        keye_run, "tiny_keye.static", "tokens_per_s")


@pytest.mark.parametrize("check", [
    test_manifest.test_names_units_and_keys,
    test_manifest.test_cells_configs_and_files,
    test_manifest.test_every_layer_metric_moves_a_metric_its_cells_report,
], ids=["names", "files", "moves"])
def test_manifest_of_the_toy_cell(check, monkeypatch):
    monkeypatch.setattr(test_manifest, "PRESET", KEYE_PRESET)
    check(test_manifest.load(KEYE_PRESET))


def test_the_real_and_the_toy_manifest_list_the_same_new_metrics():
    real = test_manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    toy = test_manifest.load(KEYE_PRESET)
    new = {m["name"] for m in real["per_layer"]
           if m["name"].split(".")[0] == "sparse_attn"}
    assert len(new) == 5 and new <= {m["name"] for m in toy["per_layer"]}
    cell = "keye_vl2_a3b_ep8.static_s16384"
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]
              if cell in m.get("workloads", ())}
    assert new < listed and {"tokens_per_s", "attention.kernels_ms.tokens",
                             "moe.experts_roofline_pct.tokens",
                             "phases.attributed_pct.tokens",
                             "exe_run.trace_s"} <= listed


def test_keye_vl2_a3b_ep8_by_hand():
    from benchmarks.configs.keye_vl2_a3b_ep8 import flops, reference

    c = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "keye_vl2_a3b_ep8", "config.json"))
    t, d = 16384, 2048
    # the cut holds 465.4 M parameters, as ISSUE 35 reckons
    leaves = reference.leaf_shapes(c)
    assert sum(math.prod(s) for s in leaves.values()) == 465_391_104
    # a published layer is the first two mixers, their norms included
    assert sum(math.prod(s) for name, s in leaves.items()
               if name.startswith(("l0.", "l1."))) == 96_899_456
    assert leaves["l0.idx.q"] == (d, 1024) and leaves["l0.k"] == (d, 512)
    assert leaves["l1.gate"] == (16, d, 768) == leaves["l1.up"]
    assert leaves["l1.down"] == (16, 768, d)
    assert leaves["l1.router"] == (d, 128) and leaves["emb"] == (18992, d)
    # the published values the cut leaves alone
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["sa_config"]["topk"], c["rope_scaling"]["mrope_section"]) == (
                128, 8, 2048, [16, 24, 24])
    # expected load: 16384 x 8 x 16 / 128 slots a layer, 1024 an expert
    assert flops.expected_slots(c, t) == 16384
    # 14,336 of 16,384 queries choose 2,048 keys; the first 2,048 see all
    selected = 2048 * 2049 // 2 + 14336 * 2048
    assert flops.selected_pairs(c, t) == selected == 31_458_304
    assert flops.causal_pairs(t) == 134_225_920
    index = 2 * 134_225_920 * 16 * 64
    attend = 2 * 2 * selected * 32 * 128
    experts = 3 * 2 * 16384 * d * 768
    assert flops.index_ops_and_bytes(c, t)[0] == index
    assert flops.index_ops_and_bytes(c, t)[1] == (
        t * (1024 + 64 + 16) * 4 + t * t)
    assert flops.attend_ops_and_bytes(c, t)[0] == attend
    assert flops.attend_ops_and_bytes(c, t)[1] == t * (64 + 8) * 128 * 2
    assert flops.experts_ops_and_bytes(c, t)[0] == experts
    assert flops.experts_ops_and_bytes(c, t)[1] == (
        3 * 16 * d * 768 + 16384 * (2 * d + 3 * 768)) * 2
    proj = 2 * t * d * (2 * 4096 + 2 * 512 + 64 * 17 + 16)
    layer_flops = proj + index + attend + 2 * t * d * 128 + experts
    assert layer_flops == pytest.approx(1.65e12, rel=5e-3)
    by_hand = 3 * (4 * layer_flops + 2 * t * d * 18992)
    got = flops.flops_per_step(c, {"batch": 1, "seq_len": t})
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(23.6e12, rel=5e-3)   # "about 23.6 TFLOP"
    # two sequences of half the length: half the pairs each, not a quarter
    two = flops.index_ops_and_bytes(c, t, seq_len=t // 2)[0]
    assert two == 2 * 2 * flops.causal_pairs(t // 2) * 16 * 64


def test_every_matrix_is_seeded_at_the_initializer_range():
    """The embedding table and the head are N(0, ``initializer_range``) like
    every matrix, the residual-branch outputs that over sqrt(mixers), the
    norms 1: the real configuration gives the table no scale of its own."""
    import math

    import jax
    import numpy as np

    from benchmarks.configs.keye_vl2_a3b_ep8 import reference

    c = test_manifest.load(os.path.join(
        os.path.dirname(PRESET), "configs", "tiny_keye", "config.json"))
    std = c["assumed"]["initializer_range"]
    params = reference.init_params(jax.random.PRNGKey(7), c)
    depth = len(c["hybrid_override_pattern"])
    for name, x in params.items():
        leaf = name.split(".", 1)[-1]
        x = np.asarray(x)
        if leaf in reference.ONES:
            assert np.all(x == 1.0), name
        elif leaf == "idx.ln_bias":
            assert np.all(x == 0.0), name
        elif x.size >= 512:
            want = std / math.sqrt(depth) if leaf in reference.BRANCH_OUT \
                else std
            assert x.std() == pytest.approx(want, rel=0.15), name
    real = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "keye_vl2_a3b_ep8", "config.json"))
    assert real["assumed"]["initializer_range"] == 0.02
    assert "embedding_std" not in real["assumed"]

