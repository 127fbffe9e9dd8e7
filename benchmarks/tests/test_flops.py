"""The FLOP functions against counts made by hand."""
import json
import os

import pytest

from .conftest import REPO


def cfg(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name,
                           "config.json")) as f:
        return json.load(f)


def test_bert_base_per_token():
    from benchmarks.configs.bert_base import flops

    c = cfg("bert_base")
    d, f, v, t = 768, 3072, 30522, 512
    layer = 2 * (4 * d * d + 2 * d * f) + 2 * 2 * t * d
    by_hand = 3 * (12 * layer + 2 * d * v * 80 / t)
    got = flops.flops_per_step(c, {"batch": 32, "seq_len": t,
                                   "masked_positions": 80}) / (32 * t)
    assert got == pytest.approx(by_hand)
    assert got == pytest.approx(588.2e6, rel=1e-3)   # "about 590 MFLOP"
    # the dygraph step projects every position onto the vocabulary
    all_pos = flops.flops_per_step(c, {"batch": 32, "seq_len": 128,
                                       "masked_positions": None}) / 4096
    assert all_pos == pytest.approx(664.4e6, rel=1e-3)
    # a data-parallel cell counts the global batch
    assert flops.flops_per_step(
        c, {"batch": 128, "replicas": 4, "seq_len": 128,
            "masked_positions": 20}) == pytest.approx(545.74e6 * 65536,
                                                      rel=1e-4)


def test_resnet50_per_image():
    from benchmarks.configs.resnet50 import flops

    c = cfg("resnet50")
    convs = flops.conv_shapes(c)
    assert len(convs) == 53
    assert convs[0] == (3, 64, 7, 2, 224)
    fwd = flops.forward_flops_per_image(c)
    assert fwd == pytest.approx(8.18e9, rel=2e-3)    # 4.09 GMAC forward
    stem = 2 * 3 * 64 * 49 * 112 * 112
    assert flops.flops_per_step(c, {"batch": 128}) == \
        pytest.approx(128 * (3 * fwd - stem))
