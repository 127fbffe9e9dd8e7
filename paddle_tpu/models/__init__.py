"""Built-in model families (fluid-style graph builders).

These are the benchmark/book models the reference exercises in
tests/book and its north-star configs; each is a plain function that
appends ops to the current program via the ``layers`` API.
"""
from .lenet import lenet  # noqa: F401
from .mlp import mlp  # noqa: F401
from .resnet import resnet, resnet50, resnet_cifar  # noqa: F401
from .wide_deep import wide_deep  # noqa: F401
from .hybrid_ssm_moe import (  # noqa: F401
    gqa_mixer,
    hybrid_ssm_moe,
    mamba2_mixer,
    moe_mixer,
)
from .transformer import (  # noqa: F401
    bert_base_pretrain,
    encoder_layer,
    multi_head_attention,
    transformer_encoder,
    transformer_wmt,
)
