"""Plain reference of the kimi_linear_48b_a3b_ep32 configuration: float32
``jax.numpy``, no kernels, nothing imported from the program.

It follows the equations ISSUE 43 writes out, literally. Pre-norm residual
sublayers ``x = x + F(rms_norm(x))`` with ``F`` one of:

- ``K`` Kimi Delta Attention on ``u``: ``q = l2norm(silu(conv4(u W_q)))
  128^-0.5``, ``k = l2norm(silu(conv4(u W_k)))``, ``v = silu(conv4(u W_v))``
  (causal depthwise convolutions of 4 taps, the L2 norm over a head's 128
  dims); ``g = -exp(A_log_h) softplus(u W_f1 W_f2 + dt_bias)`` a channel,
  ``beta = sigmoid(u W_b)`` a head; a head's state ``S [128, 128]`` from zero,
  **position by position**: ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t))
  S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``; output ``(rms_norm(o_t) w
  * sigmoid(u W_g1 W_g2 + b_g)) W_o``. The recurrence IS the computation
  here, a ``lax.scan`` over the positions: no chunked form, no triangular
  solve, no cumulative decay, so none of what can go wrong in the program's
  form can go wrong here in the same way;
- ``L`` latent attention without a query latent and without positions: ``q
  = u W_q`` (32 heads of 128 | 64), ``[c_kv | k_r] = u W_kva`` (512 | 64),
  ``[k_nope | v] = rms_norm(c_kv) W_kvb`` (32 heads of 128 | 128), ``k_r``
  ONE head for all 32, scores ``(q . [k_nope | k_r]) 192^-0.5``, causal
  softmax, context ``P v``, output ``ctx W_o``;
- ``D`` ``(silu(u W1) * (u W3)) W2``;
- ``E`` sigmoid scores over all 256 experts, the 8 largest of score + bias (a
  buffer of zeros), weights ``2.446 s / sum of the chosen s``, the sum over
  the chosen experts that this chip holds, plus the shared SwiGLU expert; the
  router's weight held where it starts.

After the last sublayer ``rms_norm(x)`` and an untied head over the held
vocabulary rows; the loss is the mean next-token cross entropy over all
positions.

Departures, none of which changes the arithmetic's meaning: each sublayer,
each ``SCAN_BLOCK`` positions of the recurrence, each block of
``QUERY_BLOCK`` query rows of attention, each held expert and each block of
``HEAD_BLOCK`` positions of the head and its cross entropy run under
``jax.checkpoint`` (the backward recomputes them, so that float32 at 8,192
tokens fits beside the follower's four trees: the recurrence's backward keeps
a state every ``SCAN_BLOCK`` positions and makes the ones between again); a
query block's scores and probabilities are made in one pass, so nothing of
[T, T] is kept.

``cast`` is applied to both operands of every matrix multiplication that
the program makes in bfloat16 (the projections of both mixers, attention's
two products, the feed-forward and expert products, the head) and to q, k
and v as they enter the recurrence (the operands of the delta-rule op's
products; not the router, which the program keeps in float32): the identity
for the reference, a round trip through a narrower type for the control that
must fail the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64        # positions of one checkpointed block of the recurrence
QUERY_BLOCK = 256      # query rows of one checkpointed block of attention
HEAD_BLOCK = 2048      # positions of one checkpointed block of the head
L2_EPS = 1e-6          # under the square root of the L2 norms of q and k

KINDS = {
    # in the order the program creates them
    "K": ("q_w", "q_conv", "k_w", "k_conv", "v_w", "v_conv", "f_a", "f_b",
          "beta_w", "a_log", "dt_bias", "g_a", "g_b", "g_bias", "o_norm",
          "o"),
    "L": ("q", "kv_a", "kv_norm", "kv_b", "o"),
    "D": ("w1", "w3", "w2"),
    # the routed experts' gate, down, up, then the shared expert's
    "E": ("router", "gate", "down", "up", "s_w1", "s_w3", "s_w2"),
}
# residual-branch outputs, scaled down by the number of sublayers
BRANCH_OUT = ("o", "w2", "down", "s_w2")
ONES = ("norm", "norm_f", "kv_norm", "o_norm")


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr = cfg["kv_lora_rank"]
    lin = cfg["linear_attn_config"]
    hk, dk, taps = (lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    inner = hk * dk
    held, f = cfg["num_experts_held"], cfg["moe_intermediate_size"]
    fs, fd = f * cfg["num_shared_experts"], cfg["intermediate_size"]
    of = {"norm": (c,),
          "q_w": (c, inner), "k_w": (c, inner), "v_w": (c, inner),
          "q_conv": (inner, taps), "k_conv": (inner, taps),
          "v_conv": (inner, taps),
          "f_a": (c, dk), "f_b": (dk, inner), "beta_w": (c, hk),
          "a_log": (hk,), "dt_bias": (inner,),
          "g_a": (c, dk), "g_b": (dk, inner), "g_bias": (inner,),
          "o_norm": (dk,),
          "q": (c, h * (nope + rope)), "kv_a": (c, kvr + rope),
          "kv_norm": (kvr,), "kv_b": (kvr, h * (nope + vd)),
          "w1": (c, fd), "w3": (c, fd), "w2": (fd, c),
          "router": (c, cfg["num_experts"]), "gate": (held, c, f),
          "down": (held, f, c), "up": (held, c, f),
          "s_w1": (c, fs), "s_w3": (c, fs), "s_w2": (fs, c)}
    out_rows = {"K": inner, "L": h * vd}
    shapes = {"emb": (v, c)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        for leaf in ("norm",) + KINDS[kind]:
            shapes["l%d.%s" % (i, leaf)] = (
                (out_rows[kind], c) if leaf == "o" else of[leaf])
    shapes["norm_f"] = (c,)
    shapes["head"] = (c, v)
    return shapes


def init_params(key, cfg):
    """Seeded weights (``config.json``, ``assumed.initialisation`` and
    ``assumed.kda_initialisation``): every matrix N(0, ``initializer_range``),
    the residual-branch outputs divided by sqrt(number of sublayers), norm
    weights 1; ``A_log = ln U(1, 16)``, ``dt_bias`` the inverse softplus of
    ``exp(U(ln 1e-3, ln 1e-1))``, the output gate's bias 0."""
    std = cfg["assumed"]["initializer_range"]
    depth = len(cfg["hybrid_override_pattern"])
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        leaf = name.split(".", 1)[-1]
        if leaf in ONES:
            x = jnp.ones(shape, jnp.float32)
        elif leaf == "g_bias":
            x = jnp.zeros(shape, jnp.float32)
        elif leaf == "a_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                            math.log(1e-3), math.log(1e-1)))
            x = dt + jnp.log(-jnp.expm1(-dt))
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


def _l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def _identity(x):
    return x


def _silu(x):
    return x * jax.nn.sigmoid(x)


# -- Kimi Delta Attention -----------------------------------------------------

def causal_conv(x, w):
    """x [B, T, C], w [C, taps]: ``out[t] = sum_j w[:, j] x[t - (taps - 1) +
    j]``, positions before the sequence read as zero."""
    t, taps = x.shape[1], w.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """o [B, T, H, V]: the recurrence itself, a position at a time. q, k, g
    [B, T, H, K], v [B, T, H, V], beta [B, T, H]; the state [B, H, K, V]
    starts at zero. Each step: decay every channel's row of the state, read
    what the decayed state holds for ``k_t``, write ``beta_t`` of the
    difference to ``v_t`` along ``k_t``, read with ``q_t``."""
    b, t, h, dk = q.shape
    block = math.gcd(t, SCAN_BLOCK)

    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        held = jnp.sum(kt[..., None] * state, -2)
        u = bt[..., None] * (vt - held)
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.sum(qt[..., None] * state, -2)

    @jax.checkpoint
    def positions(state, inp):
        return jax.lax.scan(step, state, inp)

    def blocks(x):     # [B, T, ...] -> [T / block, block, B, ...]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape((t // block, block) + x.shape[1:])

    zero = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(positions, zero,
                        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def kda(u, p, cfg, mm, cast):
    """u [B, T, C] (normed) -> [B, T, C]."""
    b, t, _ = u.shape
    lin = cfg["linear_attn_config"]
    h, dk = lin["num_heads"], lin["head_dim"]

    def heads(x):
        return x.reshape(b, t, h, dk)

    q = heads(_silu(causal_conv(mm(u, p["q_w"]), p["q_conv"])))
    k = heads(_silu(causal_conv(mm(u, p["k_w"]), p["k_conv"])))
    v = heads(_silu(causal_conv(mm(u, p["v_w"]), p["v_conv"])))
    q, k, v = cast(q), cast(k), cast(v)
    g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        heads(mm(mm(u, p["f_a"]), p["f_b"]) + p["dt_bias"]))
    beta = jax.nn.sigmoid(mm(u, p["beta_w"]))
    o = delta_rule(_l2_norm(q) * dk ** -0.5, _l2_norm(k), v, g, beta)
    gate = jax.nn.sigmoid(heads(mm(mm(u, p["g_a"]), p["g_b"]) + p["g_bias"]))
    o = _rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]) * gate
    return mm(o.reshape(b, t, h * dk), p["o"])


# -- latent attention ---------------------------------------------------------

def latent_attention(u, p, cfg, mm, cast):
    """u [B, T, C] (normed) -> [B, T, C]. No query latent, no positions."""
    b, t, _ = u.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr = cfg["kv_lora_rank"]
    q = mm(u, p["q"]).reshape(b, t, h, nope + rope)
    q_nope, q_r = q[..., :nope], q[..., nope:]
    kva = mm(u, p["kv_a"])
    k_r = kva[..., kvr:]                                 # [B, T, rope]
    kv = mm(_rms_norm(kva[..., :kvr], p["kv_norm"], eps),
            p["kv_b"]).reshape(b, t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
    bq = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(i):
        def cut(z):
            return jax.lax.dynamic_slice_in_dim(z, i * bq, bq, axis=1)

        # the shared key is one head, read by all
        s = (jnp.einsum("bqhd,bkhd->bhqk", cast(cut(q_nope)), cast(k_nope))
             + jnp.einsum("bqhd,bkd->bhqk", cast(cut(q_r)), cast(k_r))
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
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_token"])
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
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


def shared_part(u, p, mm):
    return _swiglu(u, p["s_w1"], p["s_w3"], p["s_w2"], mm)


def experts(u, p, cfg, mm):
    return (routed_part(u, p, cfg, mm, cfg["first_expert_held"],
                        cfg["num_experts_held"]) + shared_part(u, p, mm))


# -- the model ----------------------------------------------------------------

def _of_layer(params, i):
    prefix = "l%d." % i
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def sublayer(kind, x, p, cfg, mm, cast):
    u = _rms_norm(x, p["norm"], cfg["rms_norm_eps"])
    if kind == "K":
        return x + kda(u, p, cfg, mm, cast)
    if kind == "L":
        return x + latent_attention(u, p, cfg, mm, cast)
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
        """The summed log-probability of a block of positions' labels."""
        xb, labels = args
        logits = mm(_rms_norm(xb, params["norm_f"], cfg["rms_norm_eps"]),
                    params["head"])
        logp = jax.nn.log_softmax(logits, -1)
        return jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))

    total = jnp.sum(jax.lax.map(picked, (
        x.reshape(-1, rows, d), batch["labels"].reshape(-1, rows))))
    return -total / batch["labels"].size
