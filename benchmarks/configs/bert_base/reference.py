"""Plain reference of the bert_base configuration: float32 ``jax.numpy``, no
kernels, nothing imported from the program.

It follows arXiv:1810.04805's encoder (post-layer-norm blocks, learned
positions, GELU) with the departures ``config.json`` lists: one embedding sum
(tokens + positions), a masked-LM head that is a single projection, no dropout.
The seeded weights and batches of a run are made here too, so the program and
the reference are given the same ones.

``cast`` is applied to both operands of every matrix multiplication: the
identity for the reference, a round trip through a narrower type for the
control that must fail the comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("q.w", "q.b", "k.w", "k.b", "v.w", "v.b", "o.w", "o.b",
                "ln1.scale", "ln1.bias", "f1.w", "f1.b", "f2.w", "f2.b",
                "ln2.scale", "ln2.bias")


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    v, p = cfg["vocab_size"], cfg["max_position_embeddings"]
    shapes = {"emb": (v, d), "pos": (p, d), "ln0.scale": (d,), "ln0.bias": (d,)}
    per_layer = {"q.w": (d, d), "k.w": (d, d), "v.w": (d, d), "o.w": (d, d),
                 "f1.w": (d, f), "f1.b": (f,), "f2.w": (f, d)}
    for i in range(cfg["num_hidden_layers"]):
        for leaf in LAYER_LEAVES:
            shapes["l%d.%s" % (i, leaf)] = per_layer.get(leaf, (d,))
    shapes["head.w"] = (d, v)
    shapes["head.b"] = (v,)
    return shapes


def init_params(key, cfg):
    std = cfg["assumed"]["initializer_range"]
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        x = std * jax.random.normal(k, shape, jnp.float32)
        params[name] = 1.0 + x if name.endswith(".scale") else x
    return params


def make_batch(key, cfg, traffic):
    b = traffic["batch"] * traffic.get("replicas", 1)
    t, v = traffic["seq_len"], cfg["vocab_size"]
    m = traffic.get("masked_positions")
    k1, k2, k3 = jax.random.split(key, 3)
    batch = {"src": jax.random.randint(k1, (b, t), 0, v, jnp.int32),
             "pos": jnp.tile(jnp.arange(t, dtype=jnp.int32), (b, 1))}
    if m:
        batch["mpos"] = jax.random.randint(k2, (b, m), 0, t, jnp.int32)
        batch["labels"] = jax.random.randint(k3, (b, m), 0, v, jnp.int32)
    else:
        batch["labels"] = jax.random.randint(k3, (b, t), 0, v, jnp.int32)
    return batch


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def loss(params, batch, cfg, cast=lambda x: x):
    """Mean masked-LM cross entropy over the batch's label positions."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    b, t = batch["src"].shape

    def mm(x, w):
        return jnp.matmul(cast(x), cast(w))

    x = params["emb"][batch["src"]] + params["pos"][batch["pos"]]
    x = _layer_norm(x, params["ln0.scale"], params["ln0.bias"], eps)
    for i in range(cfg["num_hidden_layers"]):
        p = {leaf: params["l%d.%s" % (i, leaf)] for leaf in LAYER_LEAVES}

        def heads(y):
            return y.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)

        q = heads(mm(x, p["q.w"]) + p["q.b"]) * (d // h) ** -0.5
        k = heads(mm(x, p["k.w"]) + p["k.b"])
        v = heads(mm(x, p["v.w"]) + p["v.b"])
        scores = jnp.einsum("bhqd,bhkd->bhqk", cast(q), cast(k))
        ctx = jnp.einsum("bhqk,bhkd->bhqd", cast(jax.nn.softmax(scores, -1)),
                         cast(v))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = _layer_norm(x + mm(ctx, p["o.w"]) + p["o.b"],
                        p["ln1.scale"], p["ln1.bias"], eps)
        ff = jax.nn.gelu(mm(x, p["f1.w"]) + p["f1.b"], approximate=False)
        x = _layer_norm(x + mm(ff, p["f2.w"]) + p["f2.b"],
                        p["ln2.scale"], p["ln2.bias"], eps)
    if "mpos" in batch:
        x = jnp.take_along_axis(x, batch["mpos"][:, :, None], axis=1)
    logits = mm(x, params["head.w"]) + params["head.b"]
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, batch["labels"][:, :, None], axis=-1)
    return -jnp.mean(picked)
