"""resnet50 built through the program's public API for the static drivers:
``models.resnet`` with cross entropy, Momentum and bf16 AMP, as
``bench.py:_build_resnet50`` builds it."""
from __future__ import annotations

from .reference import leaf_shapes


def build_static(cfg, traffic):
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp
    from paddle_tpu import models

    b, size, ch = traffic["batch"], cfg["image_size"], cfg["image_channels"]
    opt = cfg["optimizer"]
    if (cfg["stage_blocks"], cfg["block"]) != ([3, 4, 6, 3], "bottleneck"):
        raise SystemExit("benchmark: the program's models.resnet has no "
                         "depth for stage_blocks %r" % (cfg["stage_blocks"],))
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        img = fluid.data(name="img", shape=[b, ch, size, size],
                         dtype="float32")
        label = fluid.data(name="label", shape=[b, 1], dtype="int64")
        pred = models.resnet50(img, class_dim=cfg["num_classes"],
                               data_format=cfg["data_format"])
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, label))
        optimizer = fluid.optimizer.MomentumOptimizer(
            learning_rate=opt["learning_rate"], momentum=opt["momentum"])
        mp.decorate(optimizer).minimize(loss)
    names = [p.name for p in main.all_parameters()
             if getattr(p, "trainable", True)]
    return {"main": main, "startup": startup, "loss": loss,
            "leaves": dict(zip(leaf_shapes(cfg), names)),
            "moment": "%s_velocity_0", "moment_scale": 1.0}


def to_feed(batch):
    return {"img": batch["img"],
            "label": batch["label"].reshape(batch["label"].shape[0], 1)}
