"""keye_vl2_a3b_ep8 built through the program's public API.

``leaves`` maps the reference's leaf names to the program's parameters by the
order in which the model creates them (``reference.leaf_shapes`` is written in
that order) and the driver checks every shape. The routers' weights are
leaves that take a zero gradient, as ``moe_topk`` gives one wherever part of
the experts is held (``config.json``, ``assumed.router``). The three position
components are a feed of the program.
"""
from __future__ import annotations

from .reference import leaf_shapes


def build_static(cfg, traffic, loads=None):
    """Program -> Executor: ``models.hybrid_ssm_moe`` over the ``S`` / ``E``
    pattern with the next-token loss over all positions plus the layers'
    indexer losses, Adam and bf16 AMP; with ``traffic["recompute"]`` each
    mixer's activations are recomputed from its input in the backward
    (``RecomputeOptimizer``). ``loads`` receives the expert layers' ``Load``
    variables."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    if not hasattr(fluid.layers, "attn_index_select"):
        raise SystemExit("benchmark: the program in this checkout has no "
                         "layers.attn_index_select (no indexed attention in "
                         "models.hybrid_ssm_moe): it cannot run this "
                         "configuration")
    b, t, v = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    opt, sa = cfg["optimizer"], cfg["sa_config"]
    checkpoints, index_losses = [], []
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, t], dtype="int64")
        labels = fluid.data(name="labels", shape=[b * t, 1], dtype="int64")
        pos = fluid.data(name="pos", shape=[3, b, t], dtype="int32")
        logits = models.hybrid_ssm_moe(
            src, cfg["hybrid_override_pattern"], v, cfg["hidden_size"],
            num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
            expert_dim=cfg["moe_intermediate_size"], shared_dim=0,
            held=[cfg["first_expert_held"], cfg["num_experts_held"]],
            routed_scaling=1.0, scoring="softmax", expert="swiglu",
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"], loads=loads,
            checkpoints=checkpoints, indexed={
                "positions": pos, "rope_theta": float(cfg["rope_theta"]),
                "rope_sections": cfg["rope_scaling"]["mrope_section"],
                "index_heads": sa["indexer_num_heads"],
                "index_dim": sa["indexer_head_dim"],
                "index_topk": sa["topk"],
                "index_rotary_dims": cfg["assumed"]["indexer_rotary_dims"],
                "index_losses": index_losses})
        ce = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * t, v]), labels))
        loss = fluid.layers.sums([ce] + index_losses)
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
            "labels": batch["labels"].reshape(rows * t, 1),
            "pos": batch["pos"].transpose(1, 0, 2)}
