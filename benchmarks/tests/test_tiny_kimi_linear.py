"""The delta-rule / latent-attention configuration at toy widths, on the CPU:
its cell end to end, its manifest against the rules, its parameter and
operation counts by hand. The toy cell has a manifest of its own beside the
preset's (``preset/tiny_kimi_linear.manifest.json``, the same ``paths``),
added as a PR adds a cell: new files only."""
import math
import os

import pytest

from . import test_manifest, test_run
from .conftest import PRESET, REPO

KIMI_PRESET = os.path.join(os.path.dirname(PRESET),
                           "tiny_kimi_linear.manifest.json")
CELL = "kimi_linear_48b_a3b_ep32.static_s8192"
CONFIG = os.path.join(REPO, "benchmarks", "configs",
                      "kimi_linear_48b_a3b_ep32", "config.json")


@pytest.fixture
def kimi_run(preset_run, monkeypatch):
    """``preset_run`` on the toy cell's own manifest."""
    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", KIMI_PRESET)
    return preset_run


def test_cell_runs_end_to_end(kimi_run):
    test_run.test_cell_runs_end_to_end(
        kimi_run, "tiny_kimi_linear.static", "tokens_per_s")


@pytest.mark.parametrize("check", [
    test_manifest.test_names_units_and_keys,
    test_manifest.test_cells_configs_and_files,
    test_manifest.test_every_layer_metric_moves_a_metric_its_cells_report,
], ids=["names", "files", "moves"])
def test_manifest_of_the_toy_cell(check, monkeypatch):
    monkeypatch.setattr(test_manifest, "PRESET", KIMI_PRESET)
    check(test_manifest.load(KIMI_PRESET))


def test_the_real_and_the_toy_manifest_list_the_same_new_metrics():
    real = test_manifest.load(os.path.join(REPO, "BENCHMARK.json"))
    toy = test_manifest.load(KIMI_PRESET)
    new = {m["name"] for m in real["per_layer"]
           if m["name"].split(".")[0] == "kda"}
    assert new == {"kda.scan_ms.tokens", "kda.scan_roofline_pct.tokens",
                   "kda.project_ms.tokens"}
    assert new <= {m["name"] for m in toy["per_layer"]}
    listed = {m["name"] for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert new < listed and {
        "tokens_per_s", "attention.kernels_ms.tokens",
        "mla.project_ms.tokens", "mla.attend_roofline_pct.tokens",
        "moe.experts_ms.tokens", "moe.route_ms.tokens",
        "moe.experts_roofline_pct.tokens", "phases.attributed_pct.tokens",
        "exe_run.idle_other_ms.tokens", "exe_run.trace_s",
        "startup.lower_s"} <= listed
    # the new metrics are this cell's alone, and it is the last cell
    for m in real["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
    assert real["workloads"][-1]["name"] == CELL
    assert real["configs"][-1]["name"] == "kimi_linear_48b_a3b_ep32"


def test_the_configuration_keeps_the_catalogs_numbers():
    """Every number of the source's config but the ``reduced`` keys."""
    c = test_manifest.load(CONFIG)
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_max_length": 1048576, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840, "mla_use_nope": True,
        "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "tie_word_embeddings": False, "use_grouped_topk": True,
        "hidden_act": "silu", "model_type": "kimi_linear"}
    reduced = set(c["reduced"])
    assert reduced == {"num_hidden_layers", "linear_attn_config",
                       "num_experts_held", "vocab_size"}
    for key, value in published.items():
        if key in reduced:
            assert c["published"][key] == value and c[key] != value, key
        else:
            assert c[key] == value, key
    # the group keeps its widths and lists the layers kept
    lin, was = c["linear_attn_config"], c["published"]["linear_attn_config"]
    for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
        assert lin[key] == was[key] == {"head_dim": 128, "num_heads": 32,
                                        "short_conv_kernel_size": 4}[key]
    assert lin["kda_layers"] == [1, 2, 3, 5] == was["kda_layers"][:4]
    assert lin["full_attn_layers"] == [4] == was["full_attn_layers"][:1]
    assert len(was["kda_layers"]) == 20 and len(was["full_attn_layers"]) == 7
    assert c["num_experts_held"] == 8 and c["published"]["num_experts"] == 256
    # two letters a published layer: the mixer's, then D or E
    mixers = {layer: "K" for layer in lin["kda_layers"]}
    mixers.update({layer: "L" for layer in lin["full_attn_layers"]})
    want = "".join(mixers[layer] + (
        "D" if layer <= c["first_k_dense_replace"] else "E")
        for layer in range(1, c["num_hidden_layers"] + 1))
    assert c["hybrid_override_pattern"] == want == "KDKEKELEKE"
    for key in ("source", "deployment", "cut", "optimizer", "precision",
                "unused_keys"):
        assert c[key], key
    assert "32 chips" in c["deployment"] and "602,449,792" in c["cut"]
    for key in ("kda", "kda_chunk", "kda_initialisation", "latent_attention",
                "router", "shared_expert", "initialisation", "documents"):
        assert c["assumed"][key], key


def test_kimi_linear_48b_a3b_ep32_by_hand():
    from benchmarks.configs.kimi_linear_48b_a3b_ep32 import flops, reference

    c = test_manifest.load(CONFIG)
    t, d = 8192, 2304
    leaves = reference.leaf_shapes(c)
    # the cut holds 602.45 M parameters, as ISSUE 43 reckons
    total = sum(math.prod(s) for s in leaves.values())
    print("parameters %d (ISSUE 43: 602.45 M)" % total)
    assert total == 602_449_792

    def layer(i):
        return sum(math.prod(s) for name, s in leaves.items()
                   if name.startswith("l%d." % i))

    kda = (3 * d * 4096 + 4096 * d + 2 * (d * 128 + 128 * 4096) + 4096
           + d * 32 + 3 * 4096 * 4 + 32 + 4096 + 128)
    assert kda == 39_518_368 and layer(0) == kda + d == layer(8)
    latent = d * 6144 + d * 576 + 512 + 512 * 8192 + 4096 * d
    assert latent == 29_114_880 and layer(6) == latent + d
    assert layer(1) == 3 * d * 9216 + d == 63_703_296
    assert layer(3) == 9 * 3 * d * 1024 + d * 256 + d == 64_293_120
    assert leaves["emb"] == (20480, d) and leaves["head"] == (d, 20480)
    assert leaves["l0.q_conv"] == (4096, 4) and leaves["l0.f_b"] == (128, 4096)
    assert leaves["l0.beta_w"] == (d, 32) and leaves["l0.a_log"] == (32,)
    assert leaves["l0.dt_bias"] == (4096,) == leaves["l0.g_bias"]
    assert leaves["l0.o_norm"] == (128,) and leaves["l0.o"] == (4096, d)
    assert leaves["l6.q"] == (d, 32 * 192) and leaves["l6.kv_a"] == (d, 576)
    assert leaves["l6.kv_b"] == (512, 8192) and leaves["l6.o"] == (4096, d)
    assert leaves["l3.router"] == (d, 256)
    assert leaves["l3.gate"] == (8, d, 1024) == leaves["l3.up"]
    # 16 B a parameter on the training path, 12 of them held between steps
    assert total * 16 / 2 ** 30 == pytest.approx(8.98, abs=5e-3)
    assert total * 12 / 2 ** 30 == pytest.approx(6.73, abs=5e-3)
    # expected load: 8192 x 8 x 8 / 256 slots a layer, 256 an expert
    assert flops.expected_slots(c, t) == 2048
    pairs = t * (t + 1) // 2
    assert flops.attend_ops_and_bytes(c, t) == (
        2 * pairs * 32 * 320, t * (32 * (192 + 128 + 128 + 128) + 64) * 2)
    # the delta rule at chunks of 64: for each of 128 x 32 (chunk, head)s
    # the two Gram matrices over 2,016 and 2,080 pairs at 128 channels, the
    # substitution, three products over 2,080 pairs at 128, three of 64
    # rows with the [128, 128] state; q, k, v, g in and o out at 4,096 a
    # token and beta at 32, two bytes each
    a_chunk = (2 * 128 * (2016 + 2080) + 2 * 64 ** 3 // 6
               + 3 * 2 * 2080 * 128 + 3 * 2 * 64 * 128 * 128)
    assert flops.kda_ops_and_bytes(c, t) == (
        128 * 32 * a_chunk, t * (5 * 4096 + 32) * 2)
    assert flops.experts_ops_and_bytes(c, t)[0] == 3 * 2 * 2048 * d * 1024
    kda_fc = 2 * t * (kda - 4096 - 32 - 4096 - 128)   # every leaf but 4
    by_hand = 3 * (
        4 * (kda_fc + 128 * 32 * a_chunk)
        + 2 * t * (latent - 512) + 2 * pairs * 32 * 320
        + 2 * t * 3 * d * 9216
        + 4 * (2 * t * d * (256 + 3 * 1024) + 3 * 2 * 2048 * d * 1024)
        + 2 * t * d * 20480)
    got = flops.flops_per_step(c, {"batch": 1, "seq_len": t})
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(19.0e12, rel=5e-3)    # "19 TFLOP a step"
    # latent attention's core is 2.06 TFLOP of it, the delta rule 0.44
    assert 3 * 2 * pairs * 32 * 320 == pytest.approx(2.06e12, rel=1e-2)
    assert 12 * 128 * 32 * a_chunk == pytest.approx(0.444e12, rel=1e-2)


def test_every_leaf_is_seeded_as_the_configuration_says():
    import jax
    import numpy as np

    from benchmarks.configs.kimi_linear_48b_a3b_ep32 import reference

    c = test_manifest.load(os.path.join(
        os.path.dirname(PRESET), "configs", "tiny_kimi_linear",
        "config.json"))
    std = c["assumed"]["initializer_range"]
    params = reference.init_params(jax.random.PRNGKey(7), c)
    depth = len(c["hybrid_override_pattern"])
    for name, x in params.items():
        leaf = name.split(".", 1)[-1]
        x = np.asarray(x)
        if leaf in reference.ONES:
            assert np.all(x == 1.0), name
        elif leaf == "g_bias":
            assert np.all(x == 0.0), name
        elif leaf == "a_log":
            assert np.all((0.0 <= x) & (x <= math.log(16.0))), name
        elif leaf == "dt_bias":
            dt = np.log1p(np.exp(x))
            assert np.all((0.999e-3 <= dt) & (dt <= 1.001e-1)), name
        elif x.size >= 512:
            want = std / math.sqrt(depth) if leaf in reference.BRANCH_OUT \
                else std
            assert x.std() == pytest.approx(want, rel=0.15), name
    assert test_manifest.load(CONFIG)["assumed"]["initializer_range"] == 0.02
