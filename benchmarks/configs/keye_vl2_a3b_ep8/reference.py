"""Plain reference of the keye_vl2_a3b_ep8 configuration: float32
``jax.numpy``, no kernels, nothing imported from the program.

It follows the layer equations ISSUE 35 writes out, literally. Every
published layer is the same two pre-norm residual halves:

- attention over the keys an indexer selects: ``u = RMSNorm(x)``; q, k, v
  projections (32 query heads over 4 key/value heads of 128, no bias);
  per-head RMS norms on q and k; rotary positions (rotate-half, 64 pairs
  ``theta^(-i/64)``, three position components in consecutive sections
  [16, 24, 24]); the indexer on ``stop_gradient(u)``: 16 query heads of 64,
  one LayerNormed key head, rotary on the first 32 dims of both, per-head
  weights ``(u Ww) 16^-0.5 64^-0.5``, index score ``I[t, s] = sum_h w[t, h]
  relu(qI[t, h] . kI[s])``; of each query the ``min(t + 1, 2048)`` causal
  keys with the largest score (``jax.lax.top_k`` on the masked scores of a
  query block: ties to the lower key); attention as an explicit masked
  softmax over that set, one set for all heads; and the indexer's loss
  ``(1/T) sum_t KL(pbar[t] || softmax over the set of I[t])`` with ``pbar``
  the head-averaged attention probabilities, held constant;
- routed experts: ``u2 = RMSNorm(x)``; ``softmax(u2 Wr)`` over all 128
  experts; the 8 largest, weights over their sum; the gated expert
  ``(silu(u2 Wg) * (u2 Wu)) Wd``; the sum over the chosen experts that this
  chip holds (a loop over the held ones, ``lax.scan``, with a 0/weight mask
  over all tokens); the router's weight held where it starts.

Embedding, the layers, a final RMSNorm and an untied head over the held
vocabulary rows; the loss is the mean next-token cross entropy over all
positions plus the sum of the layers' indexer losses.

Departures, none of which changes the arithmetic's meaning: each half of a
layer, each block of ``QUERY_BLOCK`` query rows of attention, each held
expert and each block of ``HEAD_BLOCK`` positions of the head and its cross
entropy run under ``jax.checkpoint`` (the backward recomputes them, so that
float32 at 16,384 tokens fits beside the follower's four trees: with blocks
of 512 query rows and the experts and the head whole,
``benchmarks/aot_sizing.py --reference`` sized the gradient program at 14.4
GiB beside 3.5 GiB of Adam's moments, and a Python loop over the experts let
the compiler make all sixteen experts' hidden rows again at once); ``0 log
0`` in the KL is 0
(``xlogy``); a query's scores, selection, attention and loss are made in
one pass over its block, so nothing of [T, T] is kept.

``cast`` is applied to both operands of every matrix multiplication (the
indexer's and the attention's products among them, not the router's, as in
the program): the identity for the reference, a round trip through a
narrower type for the control that must fail the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256      # query rows of one checkpointed block of attention
HEAD_BLOCK = 2048      # positions of one checkpointed block of the head

KINDS = {
    "S": ("q", "k", "v", "q_norm", "k_norm", "idx.q", "idx.k", "idx.w",
          "idx.ln_scale", "idx.ln_bias", "o"),
    # in the order the program creates them: gate, down, up
    "E": ("router", "gate", "down", "up"),
}
# residual-branch outputs, scaled down by the number of mixers
BRANCH_OUT = ("o", "down")
ONES = ("norm", "norm_f", "q_norm", "k_norm", "idx.ln_scale")


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held, f = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    of = {"q": (d, hq), "k": (d, hkv), "v": (d, hkv), "q_norm": (hd,),
          "k_norm": (hd,), "idx.q": (d, ih * idim), "idx.k": (d, idim),
          "idx.w": (d, ih), "idx.ln_scale": (idim,), "idx.ln_bias": (idim,),
          "o": (hq, d), "router": (d, cfg["num_experts"]),
          "gate": (held, d, f), "down": (held, f, d), "up": (held, d, f)}
    shapes = {"emb": (v, d)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        shapes["l%d.norm" % i] = (d,)
        for leaf in KINDS[kind]:
            shapes["l%d.%s" % (i, leaf)] = of[leaf]
    shapes["norm_f"] = (d,)
    shapes["head"] = (d, v)
    return shapes


def init_params(key, cfg):
    """Seeded weights: every matrix N(0, ``initializer_range``), the
    residual-branch outputs divided by sqrt(number of mixers), norm weights 1
    (``config.json``, ``assumed.initialisation``)."""
    std = cfg["assumed"]["initializer_range"]
    depth = len(cfg["hybrid_override_pattern"])
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        leaf = name.split(".", 1)[-1]
        if leaf in ONES:
            x = jnp.ones(shape, jnp.float32)
        elif leaf == "idx.ln_bias":
            x = jnp.zeros(shape, jnp.float32)
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if leaf in BRANCH_OUT:
                x = x / math.sqrt(depth)
        params[name] = x
    return params


def make_batch(key, cfg, traffic):
    """Ids uniform over the held vocabulary rows; the label of a position is
    the next id, so every position has one; text-only positions: the three
    components equal, 0..T-1. ``pos`` is [B, 3, T] here (rows first, as the
    harness cuts batches); the program's feed is [3, B, T]."""
    b = traffic["batch"] * traffic.get("replicas", 1)
    t = traffic["seq_len"]
    ids = jax.random.randint(key, (b, t + 1), 0, cfg["vocab_size"], jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, 3, t))
    return {"src": ids[:, :-1], "labels": ids[:, 1:], "pos": pos}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def _rotary(x, pos, theta, sections, dims):
    """x [B, T, H, hd], its first ``dims`` dims rotated (rotate-half form);
    pos [B, 3, T]; pair i reads the component whose consecutive section
    holds i (the last one past the sections' end)."""
    half = dims // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    comp = []
    for i in range(half):
        c = 0
        while c < len(sections) - 1 and i >= sum(sections[:c + 1]):
            c += 1
        comp.append(c)
    p = jnp.asarray(pos, jnp.float32)[:, jnp.asarray(comp), :]  # [B, half, T]
    ang = (jnp.swapaxes(p, 1, 2) * inv)[:, :, None, :]          # [B,T,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2, rest = x[..., :half], x[..., half:dims], x[..., dims:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _identity(x):
    return x


def _index_scores(qi, ki, w, cast):
    """I[b, t, s] = sum_h w[b, t, h] relu(qi[b, t, h] . ki[b, s])."""
    s = jnp.einsum("bqhd,bkd->bhqk", cast(qi), cast(ki))
    return jnp.sum(jnp.moveaxis(w, 2, 1)[..., None] * jax.nn.relu(s), 1)


def _select(index, rows, topk):
    """bool [B, R, T]: of query ``rows[r]`` the ``min(t + 1, topk)`` causal
    keys with the largest index score, ties to the lower key."""
    b, r, t = index.shape
    n_keep = min(topk, t)
    seen = rows[:, None] >= jnp.arange(t)[None, :]
    _, chosen = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), n_keep)
    # a query with fewer causal keys than topk keeps them all
    valid = jnp.arange(n_keep)[None, :] < (rows + 1)[:, None]
    return jnp.zeros((b, r, t), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None],
        chosen].set(jnp.broadcast_to(valid, chosen.shape))


def _selected_attention(q, k, v, qi, ki, w, topk, cast):
    """(context [B, T, H, hd], the indexer's loss): q [B, T, H, hd], k and v
    [B, T, Hkv, hd]; qi [B, T, Hi, di], ki [B, T, di], w [B, T, Hi]."""
    b, t, h, hd = q.shape
    hkv = k.shape[2]
    # query head h reads key/value head h // (H / Hkv)
    q = q.reshape(b, t, hkv, h // hkv, hd)
    bq = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(i):
        def cut(z):
            return jax.lax.dynamic_slice_in_dim(z, i * bq, bq, axis=1)

        # index scores of the block's queries against every key
        index = _index_scores(cut(qi), ki, cut(w), cast)        # [B, bq, T]
        sel = _select(index, i * bq + jnp.arange(bq), topk)
        # attention over the selected keys, one set for all heads
        s_a = jnp.einsum("bqngd,bknd->bngqk", cast(cut(q)), cast(k)) \
            * hd ** -0.5
        probs = jax.nn.softmax(
            jnp.where(sel[:, None, None], s_a, -jnp.inf), -1)
        ctx = jnp.einsum("bngqk,bknd->bqngd", cast(probs), cast(v))
        # the indexer's loss: KL(pbar || pi) over the selection
        pbar = jax.lax.stop_gradient(jnp.mean(probs, (1, 2)))    # [B, bq, T]
        logpi = jax.nn.log_softmax(jnp.where(sel, index, -jnp.inf), -1)
        kl = jnp.sum(jax.scipy.special.xlogy(pbar, pbar)
                     - pbar * jnp.where(sel, logpi, 0.0))
        return ctx, kl

    ctx, kl = jax.lax.map(block, jnp.arange(t // bq))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, t, h, hd)
    return ctx, jnp.sum(kl) / (b * t)


def _indexer(u, p, pos, cfg, mm):
    """(qi [B, T, Hi, di], ki [B, T, di], w [B, T, Hi]) from the normed
    hidden state, cut from the gradient."""
    b, t, _ = u.shape
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = cfg["rope_scaling"]["mrope_section"]
    sa = cfg["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    idx_dims = cfg["assumed"]["indexer_rotary_dims"]
    ub = jax.lax.stop_gradient(u)
    qi = _rotary(mm(ub, p["idx.q"]).reshape(b, t, ih, idim), pos, theta,
                 sections, idx_dims)
    ki = _layer_norm(mm(ub, p["idx.k"]), p["idx.ln_scale"],
                     p["idx.ln_bias"], eps)
    ki = _rotary(ki[:, :, None, :], pos, theta, sections, idx_dims)[:, :, 0]
    return qi, ki, mm(ub, p["idx.w"]) * (ih ** -0.5 * idim ** -0.5)


def selection(x, p, pos, cfg):
    """bool [B, T, T]: the keys each query of layer input ``x`` selects (for
    ``tools/sparse_select_diff.py``; ``loss`` makes the same a block at a
    time and keeps none)."""
    u = _rms_norm(x, p["norm"], cfg["rms_norm_eps"])
    qi, ki, w = _indexer(u, p, pos, cfg, jnp.matmul)
    t = x.shape[1]
    bq = math.gcd(t, QUERY_BLOCK)

    def block(i):
        def cut(z):
            return jax.lax.dynamic_slice_in_dim(z, i * bq, bq, axis=1)

        return _select(_index_scores(cut(qi), ki, cut(w), _identity),
                       i * bq + jnp.arange(bq), cfg["sa_config"]["topk"])

    sel = jax.lax.map(block, jnp.arange(t // bq))       # [T / bq, B, bq, T]
    return jnp.moveaxis(sel, 0, 1).reshape(x.shape[0], t, t)


def _attention_half(x, p, pos, cfg, mm, cast):
    """(x + attention, the indexer's loss of this layer)."""
    b, t, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = cfg["rope_scaling"]["mrope_section"]
    u = _rms_norm(x, p["norm"], eps)
    q = _rms_norm(mm(u, p["q"]).reshape(b, t, h, hd), p["q_norm"], eps)
    k = _rms_norm(mm(u, p["k"]).reshape(b, t, hkv, hd), p["k_norm"], eps)
    v = mm(u, p["v"]).reshape(b, t, hkv, hd)
    q = _rotary(q, pos, theta, sections, hd)
    k = _rotary(k, pos, theta, sections, hd)

    qi, ki, w = _indexer(u, p, pos, cfg, mm)
    ctx, li = _selected_attention(q, k, v, qi, ki, w,
                                  cfg["sa_config"]["topk"], cast)
    return x + mm(ctx.reshape(b, t, h * hd), p["o"]), li


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _experts_half(x, p, cfg, mm):
    n_top = cfg["num_experts_per_tok"]
    first, held = cfg["first_expert_held"], cfg["num_experts_held"]
    u = _rms_norm(x, p["norm"], cfg["rms_norm_eps"])
    # float32, never cast; the router's weight is not trained on one rank
    # alone (config.json, assumed.router): it takes a zero gradient
    probs = jax.nn.softmax(
        jnp.matmul(u, jax.lax.stop_gradient(p["router"])), -1)
    w, idx = jax.lax.top_k(probs, n_top)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)

    @jax.checkpoint
    def expert(u, gate, up, down, mask):
        return mask[..., None] * mm(_silu(mm(u, gate)) * mm(u, up), down)

    def add_one(out, held_expert):      # the chosen experts this chip holds
        e, gate, up, down = held_expert
        mask = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        return out + expert(u, gate, up, down, mask), None

    out, _ = jax.lax.scan(add_one, jnp.zeros_like(x), (
        jnp.arange(held), p["gate"], p["up"], p["down"]))
    return x + out


def _of_layer(params, i):
    prefix = "l%d." % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def loss(params, batch, cfg, cast=_identity):
    """Mean next-token cross entropy over all positions of the batch, plus
    the sum of the layers' indexer losses."""

    def mm(x, w):
        return jnp.matmul(cast(x), cast(w))

    pos = batch["pos"]
    x = params["emb"][batch["src"]]
    index_loss = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = _of_layer(params, i)
        if kind == "S":
            x, li = jax.checkpoint(
                lambda x, p: _attention_half(x, p, pos, cfg, mm, cast))(x, p)
            index_loss = index_loss + li
        else:
            x = jax.checkpoint(
                lambda x, p: _experts_half(x, p, cfg, mm))(x, p)
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
    return -total / batch["labels"].size + index_loss
