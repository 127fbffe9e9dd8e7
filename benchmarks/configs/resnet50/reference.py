"""Plain reference of the resnet50 configuration: float32 ``jax.numpy`` and
``lax`` convolutions, no kernels, nothing imported from the program.

Bottleneck ResNet of arXiv:1512.03385 in training mode (batch statistics in
every batch norm), with the departures ``config.json`` lists. Each bottleneck
is wrapped in ``jax.checkpoint`` so that a whole batch, which batch norm needs
at once, fits beside nothing else on one chip; that recomputes, it does not
change the mathematics. ``cast`` is applied to both operands of every
convolution and of the classifier's product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def unit_names(cfg):
    """Names of the conv + batch-norm units in the order the program's model
    creates them: stem, then per bottleneck its three and its shortcut."""
    names = ["stem"]
    cin = cfg["stem_width"]
    exp = cfg["bottleneck_expansion"]
    for stage, (n, width) in enumerate(zip(cfg["stage_blocks"],
                                           cfg["stage_widths"])):
        for i in range(n):
            stride = 2 if i == 0 and stage > 0 else 1
            block = "s%d.b%d" % (stage, i)
            names += [block + ".c0", block + ".c1", block + ".c2"]
            if cin != width * exp or stride != 1:
                names.append(block + ".short")
            cin = width * exp
    return names


def leaf_shapes(cfg):
    from .flops import conv_shapes

    shapes = {}
    for unit, (cin, cout, k, _, _) in zip(unit_names(cfg), conv_shapes(cfg)):
        shapes[unit + ".w"] = (cout, cin, k, k)
        shapes[unit + ".scale"] = (cout,)
        shapes[unit + ".bias"] = (cout,)
    feat = cfg["stage_widths"][-1] * cfg["bottleneck_expansion"]
    shapes["fc.w"] = (feat, cfg["num_classes"])
    shapes["fc.b"] = (cfg["num_classes"],)
    return shapes


def init_params(key, cfg):
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        x = jax.random.normal(k, shape, jnp.float32)
        if name.endswith(".scale"):
            params[name] = 1.0 + 0.1 * x
        elif name.endswith(".bias") or name == "fc.b":
            params[name] = 0.1 * x
        elif name == "fc.w":
            params[name] = 0.01 * x
        else:   # He et al.'s initialisation for a convolution before ReLU
            params[name] = x * math.sqrt(2.0 / (shape[1] * shape[2] * shape[3]))
    return params


def make_batch(key, cfg, traffic):
    b = traffic["batch"] * traffic.get("replicas", 1)
    k1, k2 = jax.random.split(key)
    size, ch = cfg["image_size"], cfg["image_channels"]
    return {"img": jax.random.normal(k1, (b, ch, size, size), jnp.float32),
            "label": jax.random.randint(k2, (b,), 0, cfg["num_classes"],
                                        jnp.int32)}


def _conv_bn(x, params, unit, stride, eps, cast, relu):
    w = params[unit + ".w"]
    pad = (w.shape[2] - 1) // 2
    y = jax.lax.conv_general_dilated(
        cast(x), cast(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    mean = jnp.mean(y, (0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), (0, 2, 3), keepdims=True)
    y = (y - mean) * jax.lax.rsqrt(var + eps)
    y = y * params[unit + ".scale"][None, :, None, None] \
        + params[unit + ".bias"][None, :, None, None]
    return jax.nn.relu(y) if relu else y


def _bottleneck(x, params, block, stride, has_short, eps, cast):
    y = _conv_bn(x, params, block + ".c0", 1, eps, cast, True)
    y = _conv_bn(y, params, block + ".c1", stride, eps, cast, True)
    y = _conv_bn(y, params, block + ".c2", 1, eps, cast, False)
    if has_short:
        x = _conv_bn(x, params, block + ".short", stride, eps, cast, False)
    return jax.nn.relu(x + y)


def loss(params, batch, cfg, cast=lambda x: x):
    """Mean cross entropy of the softmax classifier over the batch."""
    eps = cfg["batch_norm_eps"]
    x = _conv_bn(batch["img"], params, "stem", 2, eps, cast, True)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), [(0, 0), (0, 0), (1, 1), (1, 1)])
    units = set(unit_names(cfg))
    for stage, n in enumerate(cfg["stage_blocks"]):
        for i in range(n):
            block = "s%d.b%d" % (stage, i)
            stride = 2 if i == 0 and stage > 0 else 1
            fn = functools.partial(
                _bottleneck, block=block, stride=stride,
                has_short=block + ".short" in units, eps=eps, cast=cast)
            x = jax.checkpoint(fn)(x, params)
    x = jnp.mean(x, (2, 3))
    logits = jnp.matmul(cast(x), cast(params["fc.w"])) + params["fc.b"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["label"][:, None], 1))
