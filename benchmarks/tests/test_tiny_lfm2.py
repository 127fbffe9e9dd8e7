"""The short-convolution / rotary-attention configuration at toy widths, on
the CPU: its cell end to end, its manifest against the rules, its parameter
and operation counts by hand. The toy cell has a manifest of its own beside
the preset's (``preset/tiny_lfm2.manifest.json``, the same ``paths``), added
as a PR adds a cell: new files only."""
import json
import math
import os

import pytest

from . import test_manifest, test_run
from .conftest import PRESET, REPO

LFM2_PRESET = os.path.join(os.path.dirname(PRESET), "tiny_lfm2.manifest.json")
CELL = "lfm2_24b_a2b_ep8.static_s8192"
CONFIG = os.path.join(REPO, "benchmarks", "configs", "lfm2_24b_a2b_ep8",
                      "config.json")
NEW = {"shortconv.gate_ms.tokens", "shortconv.gate_roofline_pct.tokens",
       "shortconv.project_ms.tokens", "gqa.attend_roofline_pct.tokens"}


@pytest.fixture
def lfm2_run(preset_run, monkeypatch):
    """``preset_run`` on the toy cell's own manifest."""
    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", LFM2_PRESET)
    return preset_run


def test_cell_runs_end_to_end(lfm2_run):
    test_run.test_cell_runs_end_to_end(
        lfm2_run, "tiny_lfm2.static", "tokens_per_s")


def test_traced_run_reports_what_a_cpu_can(lfm2_run):
    """Without a chip no device metric; what is left is present and
    finite, and the readers this PR adds return nothing and raise
    nothing."""
    out, _ = lfm2_run("tiny_lfm2.static", 2 ** 31 + 46, 2, 1)
    assert out["correct"] is True
    assert out["metrics"] and not NEW & set(out["metrics"])
    for name, m in out["metrics"].items():
        assert math.isfinite(m["value"]), name


@pytest.mark.parametrize("check", [
    test_manifest.test_names_units_and_keys,
    test_manifest.test_cells_configs_and_files,
    test_manifest.test_every_layer_metric_moves_a_metric_its_cells_report,
], ids=["names", "files", "moves"])
def test_manifest_of_the_toy_cell(check, monkeypatch):
    monkeypatch.setattr(test_manifest, "PRESET", LFM2_PRESET)
    check(test_manifest.load(LFM2_PRESET))


def test_the_real_and_the_toy_manifest_list_the_same_new_metrics():
    real = test_manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    toy = test_manifest.load(LFM2_PRESET)
    new = {m["name"] for m in real["per_layer"]
           if m["name"].split(".")[0] in ("shortconv", "gqa")}
    assert new == NEW
    assert new <= {m["name"] for m in toy["per_layer"]}
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert new < listed and len(listed) == 18 + 4 and {
        "tokens_per_s", "attention.kernels_ms.tokens",
        "moe.experts_ms.tokens", "moe.route_ms.tokens",
        "moe.experts_roofline_pct.tokens", "phases.attributed_pct.tokens",
        "exe_run.idle_other_ms.tokens", "exe_run.trace_s",
        "startup.lower_s"} <= listed
    # the new metrics are this cell's alone
    for m in real["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL] and m["layer"] == "kernels"
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "lfm2_24b_a2b_ep8"


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config but the ``reduced`` keys."""
    c = test_manifest.load(CONFIG)
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 65536}
    reduced = set(c["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types", "num_dense_layers",
                       "num_experts_held", "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    was = c["published"]["layer_types"]
    assert len(was) == 40 and was.count("full_attention") == 10
    # published layers 1-5, 0-based
    assert c["layer_types"] == was[1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert c["num_experts_held"] == 8 and c["published"]["num_experts"] == 64
    # two letters a published layer: the mixer's, then D or E
    want = "".join({"conv": "C", "full_attention": "*"}[kind] + (
        "D" if i < c["num_dense_layers"] else "E")
        for i, kind in enumerate(c["layer_types"]))
    assert c["hybrid_override_pattern"] == want == "CD*ECECECE"
    for key in ("source", "deployment", "cut", "optimizer", "precision",
                "unused_keys"):
        assert c[key], key
    assert "8 chips" in c["deployment"] and "469,284,992" in c["cut"]
    assert c["assumed"]["tie_word_embeddings"] is True
    assert c["assumed"]["route_eps"] == 1e-6
    for key in ("short_conv", "attention", "router", "shared_expert",
                "initialisation", "documents", "tie_word_embeddings_from"):
        assert c["assumed"][key], key


def test_lfm2_24b_a2b_ep8_by_hand():
    from benchmarks.configs.lfm2_24b_a2b_ep8 import flops, reference

    c = test_manifest.load(CONFIG)
    t, d = 8192, 2048
    leaves = reference.leaf_shapes(c)
    # the cut holds 469.28 M parameters, as ISSUE 46 sums them
    total = sum(math.prod(s) for s in leaves.values())
    print("parameters %d (ISSUE 46: 469,284,992)" % total)
    assert total == 469_284_992

    def layer(i):
        return sum(math.prod(s) for name, s in leaves.items()
                   if name.startswith("l%d." % i))

    conv = d * 6144 + d * 3 + d * d
    assert conv == 16_783_360
    assert layer(0) == conv + d == layer(4) == layer(6) == layer(8)
    attn = 2 * d * d + 2 * d * 512 + 64 + 64
    assert attn == 10_485_888 and layer(2) == attn + d
    assert layer(1) == 3 * d * 11776 + d == 72_351_744 + d
    experts = d * 64 + 8 * 3 * d * 1536
    assert experts == 75_628_544
    assert layer(3) == experts + d == layer(5) == layer(7) == layer(9)
    assert leaves["emb"] == (8192, d) and "head" not in leaves
    assert list(leaves)[-1] == "norm_f"
    assert leaves["l3.gate"] == (8, d, 1536) == leaves["l3.up"]
    # 16 B a parameter on the training path, 12 of them held between steps
    assert total * 16 / 1e9 == pytest.approx(7.51, abs=5e-3)
    assert total * 12 / 1e9 == pytest.approx(5.63, abs=5e-3)
    # expected load: 8192 x 4 x 8 / 64 slots a layer, 512 an expert
    assert flops.expected_slots(c, t) == 4096
    pairs = t * (t + 1) // 2
    # the three counts by hand: the gate at 7 operations a channel and
    # position over four streams of [8192, 2048] bf16 and the float32 taps;
    # attention at 32 query heads of 64 + 64 over the causal pairs, q and
    # the context at 2048, k and v at 512 a token; the experts' three
    # products over 4,096 slots
    assert flops.gate_ops_and_bytes(c, t) == (
        t * d * 7, 4 * t * d * 2 + d * 3 * 4)
    assert flops.attend_ops_and_bytes(c, t) == (
        2 * pairs * 32 * 128, t * (2 * 2048 + 2 * 512) * 2)
    assert flops.experts_ops_and_bytes(c, t) == (
        3 * 2 * 4096 * d * 1536,
        (3 * 8 * d * 1536 + 4096 * (2 * d + 3 * 1536)) * 2)
    by_hand = 3 * (
        4 * (2 * t * (conv - d * 3) + t * d * 7)
        + 2 * t * (attn - 128) + 2 * pairs * 32 * 128
        + 2 * t * 3 * d * 11776
        + 4 * (2 * t * d * 64 + 3 * 2 * 4096 * d * 1536)
        + 2 * t * d * 8192)
    got = flops.flops_per_step(c, {"batch": 1, "seq_len": t})
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(9.97e12, rel=5e-3)    # "10 TFLOP a step"
    # attention's core is 0.82 TFLOP of it, the gates 1.4 GFLOP
    assert 3 * 2 * pairs * 32 * 128 == pytest.approx(0.825e12, rel=1e-2)


def test_every_leaf_is_seeded_as_the_configuration_says():
    import jax
    import numpy as np

    from benchmarks.configs.lfm2_24b_a2b_ep8 import reference

    c = test_manifest.load(os.path.join(
        os.path.dirname(PRESET), "configs", "tiny_lfm2", "config.json"))
    std = c["assumed"]["initializer_range"]
    params = reference.init_params(jax.random.PRNGKey(7), c)
    assert list(params) == list(reference.leaf_shapes(c))
    depth = len(c["hybrid_override_pattern"])
    for name, x in params.items():
        leaf = name.split(".", 1)[-1]
        x = np.asarray(x)
        if leaf in reference.ONES:
            assert np.all(x == 1.0), name
        elif x.size >= 512:
            want = std / math.sqrt(depth) if leaf in reference.BRANCH_OUT \
                else std
            assert x.std() == pytest.approx(want, rel=0.15), name
    assert test_manifest.load(CONFIG)["assumed"]["initializer_range"] == 0.02


def test_the_traffic_file_sets_limits_with_reasons():
    with open(os.path.join(REPO, "benchmarks", "traffic",
                           CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["batch"] * traffic["seq_len"] == 8192 \
        == traffic["items_per_step"]
    assert traffic["recompute"] is True and traffic["pool"] == 8
    for name in traffic["limits"]:
        assert traffic["limit_reasons"][name], name
    assert traffic["limit_reasons"]["readings"]
