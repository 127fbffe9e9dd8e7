"""A stand-in for a configuration that does not exist yet, for sizing the
check before the configuration's PR: a plain residual stack over a tree of
leaves whose names, shapes and order a JSON file gives, with a cross-entropy
head over ``tokens`` token ids. Each layer's branch runs under
``jax.checkpoint``. It has a reference's interface (``init_params``,
``make_batch``, ``loss``), so ``follow()`` and the sizing take it as they take
a cell's reference; it stands for the bytes of a model, not for its
mathematics.

The file: ``tokens``, ``hidden``, ``vocab_rows``, ``optimizer``,
``reference_rows_per_block``, ``pattern`` (one letter a layer) and ``kinds``
(letter -> the layer's leaves in order, each ``[name, shape, use]``). A
leaf's ``use`` says how the branch applies it to the activation ``y``
(``[tokens, width]``, resized to the width the leaf expects by cutting or
tiling):

- ``matmul`` ``[a, b]``: ``y = relu(y @ w)``;
- ``experts`` ``[e, a, b]``: the tokens in ``e`` equal groups, group ``i``
  through matrix ``i`` (a grouped product over the experts a chip holds);
- ``scale``, any shape: ``y = y * w`` with ``w`` flattened.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp


def load(path):
    with open(path) as f:
        cfg = json.load(f)
    traffic = {"pool": 3, "reference_rows_per_block":
               cfg.get("reference_rows_per_block")}
    return cfg, traffic


def layers_of(cfg):
    """[(prefix, leaves)] in the stack's order."""
    return [("l%d." % i, cfg["kinds"][letter])
            for i, letter in enumerate(cfg["pattern"])]


def leaf_shapes(cfg):
    """{leaf: (shape, use)} in the order the stack applies them."""
    h, v = cfg["hidden"], cfg["vocab_rows"]
    shapes = {"emb": ((v, h), "table")}
    for prefix, leaves in layers_of(cfg):
        for name, shape, use in leaves:
            shapes[prefix + name] = (tuple(shape), use)
    shapes["norm_f"] = ((h,), "scale")
    shapes["head"] = ((h, v), "matmul")
    return shapes


def parameter_count(cfg):
    return sum(math.prod(s) for s, _ in leaf_shapes(cfg).values())


def init_params(key, cfg):
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, (shape, use)) in zip(keys, shapes.items()):
        x = jax.random.normal(k, shape, jnp.float32)
        if use == "scale":
            params[name] = 1.0 + 0.02 * x
        elif use == "table":
            params[name] = x
        else:   # a product keeps the activation's size
            params[name] = x * shape[-2] ** -0.5
    return params


def make_batch(key, cfg, traffic):
    k1, k2 = jax.random.split(key)
    t, v = cfg["tokens"], cfg["vocab_rows"]
    return {"ids": jax.random.randint(k1, (t,), 0, v, jnp.int32),
            "labels": jax.random.randint(k2, (t,), 0, v, jnp.int32)}


def _resized(y, width):
    have = y.shape[-1]
    if have < width:
        y = jnp.tile(y, (1,) * (y.ndim - 1) + (-(-width // have),))
    return y[..., :width]


def _branch(x, leaves, params, prefix, cast):
    y = x
    for name, shape, use in leaves:
        w = params[prefix + name]
        if use == "scale":
            y = y * _resized(w.reshape(1, -1), y.shape[-1])
        elif use == "matmul":
            y = jax.nn.relu(jnp.matmul(cast(_resized(y, shape[0])), cast(w)))
        elif use == "experts":
            groups = _resized(y, shape[1]).reshape(shape[0], -1, shape[1])
            y = jax.nn.relu(jnp.einsum("etk,ekn->etn", cast(groups), cast(w))
                            ).reshape(-1, shape[2])
        else:
            raise ValueError("stand-in has no use %r for a leaf" % use)
    return _resized(y, x.shape[-1])


def loss(params, batch, cfg, cast=lambda x: x):
    """Mean cross entropy of the head's logits over the batch's tokens."""
    x = params["emb"][batch["ids"]]
    for prefix, leaves in layers_of(cfg):
        x = x + jax.checkpoint(
            lambda x, p, leaves=leaves, prefix=prefix:
            _branch(x, leaves, p, prefix, cast))(x, params)
    x = x * params["norm_f"]
    logits = jnp.matmul(cast(x), cast(params["head"]))
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], 1))
