"""Plain reference of the lfm2_24b_a2b_ep8 configuration: float32
``jax.numpy``, no kernels, nothing imported from the program.

It follows the equations ISSUE 46 writes out, literally. Pre-norm residual
sublayers ``x = x + F(rms_norm(x))`` with ``F`` one of:

- ``C`` the doubly gated short convolution on ``u``: ``[B | C | z] = u
  W_in`` (2048 each, in that order), ``y_t = sum_{j=0..2} w[:, j] (B *
  z)_{t-2+j}`` per channel as three shifted products (causal, depthwise,
  zeros before position 0, no bias), output ``(C * y) W_out``; no activation;
- ``*`` grouped-query attention: ``q = u W_q`` to 32 heads of 64, ``k = u
  W_k``, ``v = u W_v`` to 8 heads of 64; q and k each through an RMS norm
  over the head's 64 dims with a weight [64], then rotary positions
  ``0..T-1`` on all 64 dims (rotate-half, pair ``i`` by ``pos x
  theta^(-i/32)``); a dense causal softmax of ``q . k / 8``, each K/V head
  shared by 4 query heads; output ``ctx W_o``;
- ``D`` ``(silu(u W1) * (u W3)) W2``;
- ``E`` sigmoid scores over all 64 experts, the 4 largest of score + bias (a
  buffer of zeros), weights ``s / (sum of the chosen s + 1e-6)`` times
  ``routed_scaling_factor``, the sum over the chosen experts that this chip
  holds (every held expert over every token, masked; no row buffer); no
  shared expert; the router's weight held where it starts.

After the last sublayer ``rms_norm(x)`` and the head, which is the embedding
table again (tied: one leaf, used twice) over the held vocabulary rows; the
loss is the mean next-token cross entropy over all positions.

Departures, none of which changes the arithmetic's meaning: each sublayer,
each block of ``QUERY_BLOCK`` query rows of attention, each held expert and
each block of ``HEAD_BLOCK`` positions of the head and its cross entropy run
under ``jax.checkpoint`` (the backward recomputes them, so that float32 at
8,192 tokens fits beside the follower's four trees); a query block's scores
and probabilities are made in one pass, so nothing of [T, T] is kept.

``cast`` is applied to both operands of every matrix multiplication that
the program makes in bfloat16 (both mixers' projections, attention's two
products, the feed-forward and expert products, the head) and to the three
streams as they enter the gated convolution (the op's operands; not the
router, which the program keeps in float32): the identity for the reference,
a round trip through a narrower type for the control that must fail the
comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256      # query rows of one checkpointed block of attention
HEAD_BLOCK = 2048      # positions of one checkpointed block of the head

KINDS = {
    # in the order the program creates them
    "C": ("in_w", "taps", "out_w"),
    "*": ("q", "q_norm", "k", "k_norm", "v", "o"),
    "D": ("w1", "w3", "w2"),
    # the router, then the routed experts' gate, down, up
    "E": ("router", "gate", "down", "up"),
}
# residual-branch outputs, scaled down by the number of sublayers
BRANCH_OUT = ("out_w", "o", "w2", "down")
ONES = ("norm", "norm_f", "q_norm", "k_norm")


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters.
    There is no ``head``: it is ``emb``."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    held, f = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    fd = cfg["intermediate_size"]
    of = {"norm": (c,),
          "in_w": (c, 3 * c), "taps": (c, cfg["conv_L_cache"]),
          "out_w": (c, c),
          "q": (c, h * hd), "q_norm": (hd,), "k": (c, hkv * hd),
          "k_norm": (hd,), "v": (c, hkv * hd), "o": (h * hd, c),
          "w1": (c, fd), "w3": (c, fd), "w2": (fd, c),
          "router": (c, cfg["num_experts"]), "gate": (held, c, f),
          "down": (held, f, c), "up": (held, c, f)}
    shapes = {"emb": (v, c)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        for leaf in ("norm",) + KINDS[kind]:
            shapes["l%d.%s" % (i, leaf)] = of[leaf]
    shapes["norm_f"] = (c,)
    return shapes


def init_params(key, cfg):
    """Seeded weights (``config.json``, ``assumed.initialisation``): every
    matrix N(0, ``initializer_range``), the table and the taps among them,
    the residual-branch outputs divided by sqrt(number of sublayers), norm
    weights 1."""
    std = cfg["assumed"]["initializer_range"]
    depth = len(cfg["hybrid_override_pattern"])
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        leaf = name.split(".", 1)[-1]
        if leaf in ONES:
            x = jnp.ones(shape, jnp.float32)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if leaf in BRANCH_OUT:
                x = x / math.sqrt(depth)
        params[name] = x
    return params


def make_batch(key, cfg, traffic):
    """Ids uniform over the held vocabulary rows; the label of a position is
    the next id, so every position has one; one document a sequence."""
    b = traffic["batch"] * traffic.get("replicas", 1)
    t = traffic["seq_len"]
    ids = jax.random.randint(key, (b, t + 1), 0, cfg["vocab_size"], jnp.int32)
    return {"src": ids[:, :-1], "labels": ids[:, 1:]}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _identity(x):
    return x


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- the doubly gated short convolution ---------------------------------------

def short_conv(u, p, cfg, mm, cast):
    """u [B, T, C] (normed) -> [B, T, C]: three shifted products."""
    c, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    t = u.shape[1]
    streams = cast(mm(u, p["in_w"]))
    b, gate, z = (streams[..., :c], streams[..., c:2 * c],
                  streams[..., 2 * c:])
    bz = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(bz[:, j:j + t] * p["taps"][:, j] for j in range(taps))
    return mm(gate * y, p["out_w"])


# -- grouped-query attention --------------------------------------------------

def rotary(x, theta):
    """x [B, T, H, hd], every dim rotated (rotate-half form), positions
    ``0..T-1``."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * inv)[None, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, p, cfg, mm, cast):
    """u [B, T, C] (normed) -> [B, T, C]."""
    b, t, _ = u.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    q = mm(u, p["q"]).reshape(b, t, h, hd)
    k = mm(u, p["k"]).reshape(b, t, hkv, hd)
    v = mm(u, p["v"]).reshape(b, t, hkv, hd)
    q = rotary(_rms_norm(q, p["q_norm"], eps), theta)
    k = rotary(_rms_norm(k, p["k_norm"], eps), theta)
    # a K/V head and the h / hkv query heads that share it
    q = q.reshape(b, t, hkv, h // hkv, hd)
    bq = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", cast(qb), cast(k)) * hd ** -0.5
        seen = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", cast(probs), cast(v))

    ctx = jax.lax.map(block, jnp.arange(t // bq))    # [T / bq, B, bq, g, r, hd]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h * hd)
    return mm(ctx, p["o"])


# -- feed-forward -------------------------------------------------------------

def _swiglu(u, w1, w3, w2, mm):
    return mm(_silu(mm(u, w1)) * mm(u, w3), w2)


def routing(u, p, cfg):
    """(idx [B, T, k], weight [B, T, k]): sigmoid scores over all experts,
    the k largest of score + bias (zeros), ``scaling x s / (sum of the
    chosen s + 1e-6)``. Float32, never cast; the router's weight is not
    trained on one rank alone (config.json, assumed.router): it takes a zero
    gradient."""
    s = jax.nn.sigmoid(jnp.matmul(u, jax.lax.stop_gradient(p["router"])))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, -1, keepdims=True)
                 + cfg["assumed"]["route_eps"])
    return idx, cfg["routed_scaling_factor"] * w


def routed_part(u, p, cfg, mm, first, held):
    """The sum over the chosen experts ``first .. first + held - 1``; ``p``
    holds those experts' matrices."""
    idx, w = routing(u, p, cfg)

    @jax.checkpoint
    def expert(u, gate, up, down, mask):
        return mask[..., None] * _swiglu(u, gate, up, down, mm)

    def add_one(out, held_expert):
        e, gate, up, down = held_expert
        mask = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return out + expert(u, gate, up, down, mask), None

    out, _ = jax.lax.scan(add_one, jnp.zeros_like(u), (
        jnp.arange(held), p["gate"], p["up"], p["down"]))
    return out


def experts(u, p, cfg, mm):
    return routed_part(u, p, cfg, mm, cfg["first_expert_held"],
                       cfg["num_experts_held"])


# -- the model ----------------------------------------------------------------

def _of_layer(params, i):
    prefix = "l%d." % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def sublayer(kind, x, p, cfg, mm, cast):
    u = _rms_norm(x, p["norm"], cfg["norm_eps"])
    if kind == "C":
        return x + short_conv(u, p, cfg, mm, cast)
    if kind == "*":
        return x + attention(u, p, cfg, mm, cast)
    if kind == "D":
        return x + _swiglu(u, p["w1"], p["w3"], p["w2"], mm)
    return x + experts(u, p, cfg, mm)


def loss(params, batch, cfg, cast=_identity):
    """Mean next-token cross entropy over all positions of the batch."""

    def mm(x, w):
        return jnp.matmul(cast(x), cast(w))

    x = params["emb"][batch["src"]]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = jax.checkpoint(
            lambda x, p, kind=kind: sublayer(kind, x, p, cfg, mm, cast))(
                x, _of_layer(params, i))
    d = x.shape[-1]
    rows = math.gcd(x.shape[0] * x.shape[1], HEAD_BLOCK)

    @jax.checkpoint
    def picked(args):
        """The summed log-probability of a block of positions' labels; the
        head is the table again."""
        xb, labels = args
        logits = mm(_rms_norm(xb, params["norm_f"], cfg["norm_eps"]),
                    params["emb"].T)
        logp = jax.nn.log_softmax(logits, -1)
        return jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    total = jnp.sum(jax.lax.map(picked, (
        x.reshape(-1, rows, d), batch["labels"].reshape(-1, rows))))
    return -total / batch["labels"].size
