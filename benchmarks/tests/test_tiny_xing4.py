"""The latent-attention configuration on hyper-connected streams at toy
widths, on the CPU: its cell end to end, its manifest against the rules, its
parameter and operation counts by hand. The toy cell has a manifest of its own
beside the preset's (``preset/tiny_xing4.manifest.json``, the same
``paths``), added as a PR adds a cell: new files only."""
import math
import os

import pytest

from . import test_manifest, test_run
from .conftest import PRESET, REPO

XING4_PRESET = os.path.join(os.path.dirname(PRESET),
                            "tiny_xing4.manifest.json")
CELL = "xing4_29b_a4b_ep8.static_s4096"


@pytest.fixture
def xing4_run(preset_run, monkeypatch):
    """``preset_run`` on the toy cell's own manifest."""
    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", XING4_PRESET)
    return preset_run


def test_cell_runs_end_to_end(xing4_run):
    test_run.test_cell_runs_end_to_end(
        xing4_run, "tiny_xing4.static", "tokens_per_s")


@pytest.mark.parametrize("check", [
    test_manifest.test_names_units_and_keys,
    test_manifest.test_cells_configs_and_files,
    test_manifest.test_every_layer_metric_moves_a_metric_its_cells_report,
], ids=["names", "files", "moves"])
def test_manifest_of_the_toy_cell(check, monkeypatch):
    monkeypatch.setattr(test_manifest, "PRESET", XING4_PRESET)
    check(test_manifest.load(XING4_PRESET))


def test_the_real_and_the_toy_manifest_list_the_same_new_metrics():
    real = test_manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    toy = test_manifest.load(XING4_PRESET)
    new = {m["name"] for m in real["per_layer"]
           if m["name"].split(".")[0] in ("mhc", "mla")}
    assert len(new) == 5 and new <= {m["name"] for m in toy["per_layer"]}
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert new < listed and {"tokens_per_s", "attention.kernels_ms.tokens",
                             "moe.experts_roofline_pct.tokens",
                             "phases.attributed_pct.tokens",
                             "exe_run.trace_s"} <= listed
    # the new metrics are this cell's alone
    for m in real["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config but the five ``reduced`` keys."""
    c = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "xing4_29b_a4b_ep8", "config.json"))
    published = {
        "first_k_dense_replace": 2, "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_nextn_predict_layers": 1, "hc_mult": 4,
        "hc_sinkhorn_iters": 20, "hc_eps": 1e-6, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "routed_scaling_factor": 2, "vocab_size": 131072}
    reduced = set(c["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts_held", "vocab_size",
                       "num_nextn_predict_layers"}
    for key, value in published.items():
        if key in reduced:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    assert c["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert c["hybrid_override_pattern"] == "LDLELELELE"
    assert "multi_token_prediction" in c["not_built"]
    for key in ("source", "deployment", "cut", "optimizer", "precision"):
        assert c[key], key
    for key in ("hyper_connections", "sinkhorn_order", "h_res_clamp",
                "streams_start_and_end", "hyper_initialisation", "rotary",
                "score_scale", "attention", "router", "initialisation"):
        assert c["assumed"][key], key


def test_xing4_29b_a4b_ep8_by_hand():
    from benchmarks.configs.xing4_29b_a4b_ep8 import flops, reference

    c = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "xing4_29b_a4b_ep8", "config.json"))
    t, d = 4096, 3584
    leaves = reference.leaf_shapes(c)
    # the cut holds 759.3 M parameters, as ISSUE 37 reckons
    assert sum(math.prod(s) for s in leaves.values()) == 759_346_190
    attention = (d * 768 + 768 + 768 * 32 * 192 + d * 576 + 512
                 + 512 * 32 * 256 + 32 * 128 * d)
    assert attention == 28_411_136
    hyper = 4 * d * 24 + 27
    assert sum(math.prod(s) for name, s in leaves.items()
               if name.startswith("l0.")) == attention + hyper + d
    assert sum(math.prod(s) for name, s in leaves.items()
               if name.startswith("l1.")) == 3 * d * 9216 + hyper + d
    assert sum(math.prod(s) for name, s in leaves.items()
               if name.startswith("l3.")) == (9 * 3 * d * 1024 + d * 64
                                              + hyper + d)
    assert leaves["l0.hc.phi"] == (4 * d, 24) and leaves["l0.hc.alpha"] == (3,)
    assert leaves["l0.q_b"] == (768, 32 * 192)
    assert leaves["l0.kv_a"] == (d, 576) and leaves["l0.kv_b"] == (512, 8192)
    assert leaves["l0.o"] == (4096, d) and leaves["l3.router"] == (d, 64)
    assert leaves["l3.gate"] == (8, d, 1024) == leaves["l3.up"]
    assert leaves["l3.s_w2"] == (1024, d) and leaves["emb"] == (16384, d)
    # expected load: 4096 x 4 x 8 / 64 slots a layer, 256 an expert
    assert flops.expected_slots(c, t) == 2048
    pairs = t * (t + 1) // 2
    assert flops.causal_pairs(t) == pairs == 8_390_656
    # scores at 192 and context at 128 for 32 heads; the rotary key once
    assert flops.attend_ops_and_bytes(c, t) == (
        2 * pairs * 32 * 320, t * (32 * (192 + 128 + 128 + 128) + 64) * 2)
    # the streams read, read and written (float32), h written, y read, Phi
    assert flops.mhc_ops_and_bytes(c, t) == (
        2 * t * (4 * d * 24 + 24 * d),
        t * 13 * d * 4 + t * d * 2 + 4 * d * 24 * 4)
    assert flops.experts_ops_and_bytes(c, t)[0] == 3 * 2 * 2048 * d * 1024
    latent = 2 * t * (attention - 768 - 512)
    by_hand = 3 * (
        5 * (latent + 2 * pairs * 32 * 320) + 2 * t * 3 * d * 9216
        + 4 * (2 * t * d * (64 + 3 * 1024) + 3 * 2 * 2048 * d * 1024)
        + 10 * flops.mhc_ops_and_bytes(c, t)[0] + 2 * t * d * 16384)
    got = flops.flops_per_step(c, {"batch": 1, "seq_len": t})
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(11.7e12, rel=5e-3)    # "11.7 TFLOP a step"
    # attention's core is 2.6 TFLOP of it
    assert 3 * 5 * 2 * pairs * 32 * 320 == pytest.approx(2.6e12, rel=1e-2)


def test_every_leaf_is_seeded_as_the_configuration_says():
    import jax
    import numpy as np

    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    c = test_manifest.load(os.path.join(
        os.path.dirname(PRESET), "configs", "tiny_xing4", "config.json"))
    std = c["assumed"]["initializer_range"]
    params = reference.init_params(jax.random.PRNGKey(7), c)
    depth = len(c["hybrid_override_pattern"])
    for name, x in params.items():
        leaf = name.split(".", 1)[-1]
        x = np.asarray(x)
        if leaf in reference.ONES:
            assert np.all(x == 1.0), name
        elif leaf == "hc.alpha":
            assert np.all(x == np.float32(0.01)), name
        elif leaf == "hc.b_pre":
            np.testing.assert_allclose(1 / (1 + np.exp(-x)), 0.25, rtol=1e-6)
        elif leaf == "hc.b_post":
            assert np.all(x == 0.0), name
        elif leaf == "hc.b_res":
            assert np.all(np.diag(x) == 0) and x.sum() == -8.0 * 12, name
        elif x.size >= 512:
            want = std / math.sqrt(depth) if leaf in reference.BRANCH_OUT \
                else std
            assert x.std() == pytest.approx(want, rel=0.15), name
    real = test_manifest.load(os.path.join(
        REPO, "benchmarks", "configs", "xing4_29b_a4b_ep8", "config.json"))
    assert real["assumed"]["initializer_range"] == 0.02
