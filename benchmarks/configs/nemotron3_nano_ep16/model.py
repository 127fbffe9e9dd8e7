"""nemotron3_nano_ep16 built through the program's public API.

``leaves`` maps the reference's leaf names to the program's parameters by the
order in which the model creates them (``reference.leaf_shapes`` is written in
that order) and the driver checks every shape. The router's correction bias
is a buffer the model's startup program fills from the layer's index: it is
no parameter, takes no gradient and is not among the leaves. The routers'
weights are leaves that take a zero gradient, as ``moe_topk`` gives one
wherever part of the experts is held (``config.json``, ``assumed.router``).
"""
from __future__ import annotations

import numpy as np

from .reference import correction_bias, leaf_shapes


def build_static(cfg, traffic, loads=None):
    """Program -> Executor: ``models.hybrid_ssm_moe`` with the next-token loss
    over all positions, Adam and bf16 AMP; with ``traffic["recompute"]`` each
    layer's activations are recomputed from its input in the backward
    (``RecomputeOptimizer``). ``loads`` receives the expert layers' ``Load``
    variables."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    if not hasattr(models, "hybrid_ssm_moe"):
        raise SystemExit("benchmark: the program in this checkout has no "
                         "models.hybrid_ssm_moe: it cannot run this "
                         "configuration")
    b, t, v = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    opt = cfg["optimizer"]
    pattern = cfg["hybrid_override_pattern"]
    biases = [np.asarray(correction_bias(cfg, i))
              for i, kind in enumerate(pattern) if kind == "E"]
    checkpoints = []
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, t], dtype="int64")
        labels = fluid.data(name="labels", shape=[b * t, 1], dtype="int64")
        logits = models.hybrid_ssm_moe(
            src, pattern, v, cfg["hidden_size"],
            mamba_heads=cfg["mamba_num_heads"],
            mamba_head_dim=cfg["mamba_head_dim"], n_groups=cfg["n_groups"],
            state_size=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
            chunk=cfg["chunk_size"], num_experts=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"],
            shared_dim=cfg["moe_shared_expert_intermediate_size"],
            held=[cfg["first_routed_expert_held"],
                  cfg["n_routed_experts_held"]],
            routed_scaling=cfg["routed_scaling_factor"],
            correction_bias=biases,
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], eps=cfg["norm_eps"], loads=loads,
            checkpoints=checkpoints)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * t, v]), labels))
        optimizer = fluid.optimizer.AdamOptimizer(
            opt["learning_rate"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"])
        if traffic.get("recompute"):
            optimizer = fluid.optimizer.RecomputeOptimizer(optimizer)
            optimizer._set_checkpoints(checkpoints)
        mp.decorate(optimizer).minimize(loss)
    names = [p.name for p in main.all_parameters()]
    return {"main": main, "startup": startup, "loss": loss,
            "leaves": dict(zip(leaf_shapes(cfg), names)),
            "moment": "%s_moment1_0", "moment_scale": 1.0 / (1 - opt["beta1"])}


def to_feed(batch):
    """The reference's batch in the shapes the static program declares."""
    rows, t = batch["labels"].shape
    return {"src": batch["src"],
            "labels": batch["labels"].reshape(rows * t, 1)}
