"""Plain reference of the xing4_29b_a4b_ep8 configuration: float32
``jax.numpy``, no kernels, nothing imported from the program.

It follows the equations ISSUE 37 writes out, literally. The model keeps
``n = 4`` residual streams ``X [B, T, n, C]``, every one a copy of the
token's embedding at the start. Every sublayer ``F`` (latent attention ``L``,
the dense gated feed-forward layer ``D``, routed experts beside a shared one
``E``) sits inside its own manifold-constrained hyper-connection:

- ``x~ = vec(X_t) / sqrt(mean(vec(X_t)^2) + hc_eps)`` (no weight); ``[p | q |
  r] = x~ Phi``; ``H_pre = sigmoid(alpha_pre p + b_pre)``; ``H_post = 2
  sigmoid(alpha_post q + b_post)``; ``M = exp(clip(alpha_res mat(r) + b_res,
  -30, 30))``, then twenty times rows over ``rowsum + hc_eps`` and columns
  over ``colsum + hc_eps``: ``H_res``;
- ``h = sum_i H_pre[i] X[i]``; ``y = F(rms_norm(h))``; ``X'[i] = sum_j
  H_res[i, j] X[j] + H_post[i] y``.

Latent attention on ``u = rms_norm(h)``: ``c_q = rms_norm(u W_qa)``, ``[q_nope
| q_rope] = c_q W_qb`` (32 heads of 128 | 64); ``[c_kv | k_r] = u W_kva``
(512 | 64), ``[k_nope | v] = rms_norm(c_kv) W_kvb`` (32 heads of 128 | 128);
``k_r`` is ONE head for all 32; rotary (rotate-half, positions ``0..T-1``,
YaRN's 32 blended frequencies) on ``q_rope`` and ``k_r``; scores ``(q_nope .
k_nope + q_rope . k_r) 192^-0.5 (0.1 ln 64 + 1)^2``, causal softmax, context
``P v``, output ``ctx W_o``. Experts: sigmoid scores over all 64, the 4
largest of score + bias (a buffer of zeros), weights ``2 s / sum of the chosen
s``, the sum over the chosen experts that this chip holds, plus the shared
SwiGLU expert; the router's weight held where it starts. After the last
sublayer ``rms_norm(sum_i X[i])`` and an untied head over the held vocabulary
rows; the loss is the mean next-token cross entropy over all positions.

Departures, none of which changes the arithmetic's meaning: each sublayer
with its maps and mix, each block of ``QUERY_BLOCK`` query rows of attention,
each held expert and each block of ``HEAD_BLOCK`` positions of the head and
its cross entropy run under ``jax.checkpoint`` (the backward recomputes them,
so that float32 at 4,096 tokens fits beside the follower's four trees); a
query block's scores and probabilities are made in one pass, so nothing of
[T, T] is kept.

``cast`` is applied to both operands of every matrix multiplication that
the program makes in bfloat16 (the latent projections, attention's two
products, the feed-forward and expert products, the head; not the maps'
product, the mixes of the streams or the router, which the program keeps in
float32): the identity for the reference, a round trip through a narrower
type for the control that must fail the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256      # query rows of one checkpointed block of attention
HEAD_BLOCK = 2048      # positions of one checkpointed block of the head

HYPER = ("hc.phi", "hc.alpha", "hc.b_pre", "hc.b_post", "hc.b_res")
KINDS = {
    "L": ("q_a", "q_norm", "q_b", "kv_a", "kv_norm", "kv_b", "o"),
    "D": ("w1", "w3", "w2"),
    # in the order the program creates them: the routed experts' gate, down,
    # up, then the shared expert's
    "E": ("router", "gate", "down", "up", "s_w1", "s_w3", "s_w2"),
}
# residual-branch outputs, scaled down by the number of sublayers
BRANCH_OUT = ("o", "w2", "down", "s_w2")
ONES = ("norm", "norm_f", "q_norm", "kv_norm")


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters."""
    c, v, n = cfg["hidden_size"], cfg["vocab_size"], cfg["hc_mult"]
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, f = cfg["n_routed_experts_held"], cfg["moe_intermediate_size"]
    fs, fd = f * cfg["n_shared_experts"], cfg["intermediate_size"]
    of = {"hc.phi": (n * c, 2 * n + n * n), "hc.alpha": (3,),
          "hc.b_pre": (n,), "hc.b_post": (n,), "hc.b_res": (n, n),
          "norm": (c,),
          "q_a": (c, qr), "q_norm": (qr,), "q_b": (qr, h * (nope + rope)),
          "kv_a": (c, kvr + rope), "kv_norm": (kvr,),
          "kv_b": (kvr, h * (nope + vd)), "o": (h * vd, c),
          "w1": (c, fd), "w3": (c, fd), "w2": (fd, c),
          "router": (c, cfg["n_routed_experts"]), "gate": (held, c, f),
          "down": (held, f, c), "up": (held, c, f),
          "s_w1": (c, fs), "s_w3": (c, fs), "s_w2": (fs, c)}
    shapes = {"emb": (v, c)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        for leaf in HYPER + ("norm",) + KINDS[kind]:
            shapes["l%d.%s" % (i, leaf)] = of[leaf]
    shapes["norm_f"] = (c,)
    shapes["head"] = (c, v)
    return shapes


def init_params(key, cfg):
    """Seeded weights (``config.json``, ``assumed.initialisation`` and
    ``assumed.hyper_initialisation``): every matrix N(0,
    ``initializer_range``), the residual-branch outputs divided by
    sqrt(number of sublayers), norm weights 1; ``alpha`` 0.01, ``b_pre``
    logit(1 / n), ``b_post`` 0, ``b_res`` 0 on the diagonal and -8 off it."""
    std = cfg["assumed"]["initializer_range"]
    depth, n = len(cfg["hybrid_override_pattern"]), cfg["hc_mult"]
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        leaf = name.split(".", 1)[-1]
        if leaf in ONES:
            x = jnp.ones(shape, jnp.float32)
        elif leaf == "hc.alpha":
            x = jnp.full(shape, 0.01, jnp.float32)
        elif leaf == "hc.b_pre":
            x = jnp.full(shape, math.log((1 / n) / (1 - 1 / n)), jnp.float32)
        elif leaf == "hc.b_post":
            x = jnp.zeros(shape, jnp.float32)
        elif leaf == "hc.b_res":
            x = -8.0 * (1.0 - jnp.eye(n, dtype=jnp.float32))
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if leaf in BRANCH_OUT:
                x = x / math.sqrt(depth)
        params[name] = x
    return params


def make_batch(key, cfg, traffic):
    """Ids uniform over the held vocabulary rows; the label of a position is
    the next id, so every position has one; one document a sequence,
    positions 0..T-1."""
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


# -- the residual path --------------------------------------------------------

def hyper_maps(x, p, cfg):
    """(H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n, n]) from the
    streams x [B, T, n, C] and a sublayer's hyper-connection leaves."""
    b, t, n, c = x.shape
    eps = cfg["hc_eps"]
    flat = x.reshape(b, t, n * c)
    normed = flat / jnp.sqrt(jnp.mean(jnp.square(flat), -1, keepdims=True)
                             + eps)
    pqr = jnp.matmul(normed, p["hc.phi"])               # float32, never cast
    a_pre, a_post, a_res = p["hc.alpha"]
    h_pre = jax.nn.sigmoid(a_pre * pqr[..., :n] + p["hc.b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a_post * pqr[..., n:2 * n] + p["hc.b_post"])
    m = jnp.exp(jnp.clip(
        a_res * pqr[..., 2 * n:].reshape(b, t, n, n) + p["hc.b_res"],
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)    # rows
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)    # then columns
    return h_pre, h_post, m


def hyper_sublayer(x, p, cfg, fn):
    """X' = H_res X + H_post (x) fn(rms_norm(H_pre . X))."""
    h_pre, h_post, h_res = hyper_maps(x, p, cfg)
    h = jnp.einsum("btn,btnc->btc", h_pre, x)
    y = fn(_rms_norm(h, p["norm"], cfg["rms_norm_eps"]))
    return (jnp.einsum("btij,btjc->btic", h_res, x)
            + h_post[..., None] * y[:, :, None, :])


# -- latent attention ---------------------------------------------------------

def yarn_frequencies(cfg):
    """The 32 frequency pairs of the rotary part: scaled and unscaled
    frequencies blended by a linear ramp between the pairs that make
    ``beta_fast`` and ``beta_slow`` turns over the original positions."""
    rs, dim, base = (cfg["rope_scaling"], cfg["qk_rope_head_dim"],
                     float(cfg["rope_theta"]))
    span = rs["original_max_position_embeddings"]

    def pair_with(turns):
        return dim * math.log(span / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    lo = max(math.floor(pair_with(rs["beta_fast"])), 0)
    hi = min(math.ceil(pair_with(rs["beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = base ** (-2.0 * i / dim)
    m = 1.0 - jnp.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return (1.0 - m) * plain / rs["factor"] + m * plain


def _rotary(x, cfg):
    """x [B, T, H, 64] rotated, rotate-half form, positions 0..T-1; cos and
    sin times ``mscale / mscale_all_dim`` (1 here)."""
    rs = cfg["rope_scaling"]

    def attention_factor(m):
        return 0.1 * m * math.log(rs["factor"]) + 1.0

    ratio = attention_factor(rs["mscale"]) / attention_factor(
        rs["mscale_all_dim"])
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_frequencies(cfg)
    cos = (jnp.cos(ang) * ratio)[None, :, None, :]
    sin = (jnp.sin(ang) * ratio)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def score_scale(cfg):
    rs = cfg["rope_scaling"]
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def latent_attention(u, p, cfg, mm, cast):
    """u [B, T, C] (normed) -> [B, T, C]."""
    b, t, _ = u.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr = cfg["kv_lora_rank"]
    cq = _rms_norm(mm(u, p["q_a"]), p["q_norm"], eps)
    q = mm(cq, p["q_b"]).reshape(b, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], cfg)
    kva = mm(u, p["kv_a"])
    k_r = _rotary(kva[..., kvr:].reshape(b, t, 1, rope), cfg)[:, :, 0]
    kv = mm(_rms_norm(kva[..., :kvr], p["kv_norm"], eps),
            p["kv_b"]).reshape(b, t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = score_scale(cfg)
    bq = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(i):
        def cut(z):
            return jax.lax.dynamic_slice_in_dim(z, i * bq, bq, axis=1)

        # the rotary key is one head, read by all
        s = (jnp.einsum("bqhd,bkhd->bhqk", cast(cut(q_nope)), cast(k_nope))
             + jnp.einsum("bqhd,bkd->bhqk", cast(cut(q_rope)), cast(k_r))
             ) * scale
        seen = (i * bq + jnp.arange(bq))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", cast(probs), cast(v))

    ctx = jax.lax.map(block, jnp.arange(t // bq))       # [T / bq, B, bq, H, vd]
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h * vd)
    return mm(ctx, p["o"])


# -- feed-forward -------------------------------------------------------------

def _swiglu(u, w1, w3, w2, mm):
    return mm(_silu(mm(u, w1)) * mm(u, w3), w2)


def routing(u, p, cfg):
    """(idx [B, T, k], weight [B, T, k]): sigmoid scores over all experts,
    the k largest of score + bias (zeros), ``scaling x s / sum of the chosen
    s``. Float32, never cast; the router's weight is not trained on one rank
    alone (config.json, assumed.router): it takes a zero gradient."""
    s = jax.nn.sigmoid(jnp.matmul(u, jax.lax.stop_gradient(p["router"])))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, cfg["routed_scaling_factor"] * w


def routed_part(u, p, cfg, mm, first, held):
    """The sum over the chosen experts ``first .. first + held - 1``."""
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
    return (routed_part(u, p, cfg, mm, cfg["first_routed_expert_held"],
                        cfg["n_routed_experts_held"])
            + _swiglu(u, p["s_w1"], p["s_w3"], p["s_w2"], mm))


# -- the model ----------------------------------------------------------------

def _of_layer(params, i):
    prefix = "l%d." % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def sublayer_fn(kind, p, cfg, mm, cast):
    if kind == "L":
        return lambda u: latent_attention(u, p, cfg, mm, cast)
    if kind == "D":
        return lambda u: _swiglu(u, p["w1"], p["w3"], p["w2"], mm)
    return lambda u: experts(u, p, cfg, mm)


def loss(params, batch, cfg, cast=_identity):
    """Mean next-token cross entropy over all positions of the batch."""

    def mm(x, w):
        return jnp.matmul(cast(x), cast(w))

    emb = params["emb"][batch["src"]]
    x = jnp.broadcast_to(emb[:, :, None, :],
                         emb.shape[:2] + (cfg["hc_mult"], emb.shape[-1]))
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x = jax.checkpoint(
            lambda x, p, kind=kind: hyper_sublayer(
                x, p, cfg, sublayer_fn(kind, p, cfg, mm, cast)))(
                    x, _of_layer(params, i))
    x = jnp.sum(x, 2)
    d = x.shape[-1]
    rows = math.gcd(x.shape[0] * x.shape[1], HEAD_BLOCK)

    @jax.checkpoint
    def picked(args):
        """The summed log-probability of a block of positions' labels."""
        xb, labels = args
        logits = mm(_rms_norm(xb, params["norm_f"], cfg["rms_norm_eps"]),
                    params["head"])
        logp = jax.nn.log_softmax(logits, -1)
        return jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    total = jnp.sum(jax.lax.map(picked, (
        x.reshape(-1, rows, d), batch["labels"].reshape(-1, rows))))
    return -total / batch["labels"].size
