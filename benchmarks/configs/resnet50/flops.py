"""Model FLOPs of one ResNet training step, from the configuration's sizes.

A multiply-add counts as 2, nothing twice, no recomputation. Forward is every
convolution (2 x Cin x Cout x k x k x Hout x Wout) and the classifier. Backward
needs the gradient of both operands of each, twice the forward, except that the
first convolution needs none for the image. Batch norm, ReLU, pooling, softmax
and the optimizer are not counted.
"""
from __future__ import annotations


def conv_shapes(cfg):
    """(cin, cout, kernel, stride, input size) of every convolution, in the
    order the model creates them."""
    size = cfg["image_size"]
    convs = [(cfg["image_channels"], cfg["stem_width"], 7, 2, size)]
    size = size // 2 // 2          # stem stride 2, max-pool stride 2
    cin = cfg["stem_width"]
    exp = cfg["bottleneck_expansion"]
    for stage, (n, width) in enumerate(zip(cfg["stage_blocks"],
                                           cfg["stage_widths"])):
        for i in range(n):
            stride = 2 if i == 0 and stage > 0 else 1
            convs += [(cin, width, 1, 1, size),
                      (width, width, 3, stride, size),
                      (width, width * exp, 1, 1, size // stride)]
            if cin != width * exp or stride != 1:
                convs.append((cin, width * exp, 1, stride, size))
            cin, size = width * exp, size // stride
    return convs


def forward_flops_per_image(cfg):
    total = 0
    for cin, cout, k, stride, size in conv_shapes(cfg):
        out = size // stride
        total += 2 * cin * cout * k * k * out * out
    exp = cfg["bottleneck_expansion"]
    return total + 2 * cfg["stage_widths"][-1] * exp * cfg["num_classes"]


def flops_per_step(cfg, traffic):
    images = traffic["batch"] * traffic.get("replicas", 1)
    cin, cout, k, stride, size = conv_shapes(cfg)[0]
    stem = 2 * cin * cout * k * k * (size // stride) ** 2
    return images * (3 * forward_flops_per_image(cfg) - stem)
