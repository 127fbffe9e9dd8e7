"""Plain reference of the nemotron3_nano_ep16 configuration: float32
``jax.numpy``, no kernels, nothing imported from the program.

It follows the ``nemotron_h`` layer equations (ISSUE 27 writes them out):
every layer ``x <- x + mixer(RMSNorm(x))``, a final RMSNorm and an untied head;
the mixer by the pattern's letter: ``M`` Mamba-2 with the selective scan as
the LITERAL recurrence over positions (``lax.scan``; not the chunked
algorithm the program runs), ``E`` sigmoid top-6-of-128 routing with the sum
over the chosen experts that this chip holds (the router's weight held
where it starts), beside the shared expert, ``*``
causal grouped-query attention without positional encoding. Departures, none
of which changes the arithmetic's meaning: each layer, each segment of
``SCAN_SEGMENT`` positions of the scan and each block of ``QUERY_BLOCK`` query
rows of attention run under ``jax.checkpoint`` (the backward recomputes them,
so that float32 activations at 8,192 tokens fit beside the follower's four
trees); the experts are a loop over the held ones with a 0/weight mask over
all tokens; the scan's products are sums of elementwise products (exact
float32 on any backend).

``cast`` is applied to both operands of every matrix multiplication, and to
the operands of the scan's products (``dt x``, B, C), which the program feeds
to the MXU: the identity for the reference, a round trip through a narrower
type for the control that must fail the comparison.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SCAN_SEGMENT = 128     # positions a checkpointed segment of the scan holds
QUERY_BLOCK = 512      # query rows of one checkpointed block of attention

KINDS = {
    "M": ("in_proj", "conv.w", "conv.b", "dt_bias", "A_log", "D", "gnorm",
          "out_proj"),
    "E": ("router", "w1", "w2", "shared_up", "shared_down"),
    "*": ("q", "k", "v", "o"),
}
# residual-branch outputs, scaled down as rescale_prenorm_residual says
BRANCH_OUT = ("out_proj", "o", "w2", "shared_down")


def sizes(cfg):
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    inner, bc = heads * p, cfg["n_groups"] * cfg["ssm_state_size"]
    return {"inner": inner, "bc": bc, "conv": inner + 2 * bc,
            "proj": 2 * inner + 2 * bc + heads}


def leaf_shapes(cfg):
    """Leaf names in the order the program's model creates its parameters."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    s = sizes(cfg)
    heads = cfg["mamba_num_heads"]
    held, f = cfg["n_routed_experts_held"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    of = {"in_proj": (d, s["proj"]), "conv.w": (s["conv"], cfg["conv_kernel"]),
          "conv.b": (s["conv"],), "dt_bias": (heads,), "A_log": (heads,),
          "D": (heads,), "gnorm": (s["inner"],), "out_proj": (s["inner"], d),
          "router": (d, cfg["n_routed_experts"]), "w1": (held, d, f),
          "w2": (held, f, d), "shared_up": (d, fs), "shared_down": (fs, d),
          "q": (d, hq), "k": (d, hkv), "v": (d, hkv), "o": (hq, d)}
    shapes = {"emb": (v, d)}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        shapes["l%d.norm" % i] = (d,)
        for leaf in KINDS[kind]:
            shapes["l%d.%s" % (i, leaf)] = of[leaf]
    shapes["norm_f"] = (d,)
    shapes["head"] = (d, v)
    return shapes


def correction_bias(cfg, layer):
    """The router's selection-only bias of layer ``layer``: a buffer, the
    same in program and reference, made from the layer's index."""
    e = jnp.arange(cfg["n_routed_experts"], dtype=jnp.float32)
    return cfg["assumed"]["router_correction_bias_scale"] * jnp.cos(
        1.0 + layer + e)


def init_params(key, cfg):
    std = cfg["assumed"]["initializer_range"]
    depth = len(cfg["hybrid_override_pattern"])
    shapes = leaf_shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    params = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        leaf = name.split(".", 1)[-1]
        if leaf in ("norm", "gnorm", "norm_f", "D"):
            x = jnp.ones(shape, jnp.float32)
        elif leaf == "conv.b":
            x = jnp.zeros(shape, jnp.float32)
        elif leaf == "A_log":
            x = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif leaf == "dt_bias":
            lo, hi = cfg["time_step_min"], cfg["time_step_max"]
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32)
                         * (math.log(hi) - math.log(lo)) + math.log(lo))
            dt = jnp.maximum(dt, cfg["time_step_floor"])
            x = dt + jnp.log(-jnp.expm1(-dt))      # softplus's inverse
        else:
            x = std * jax.random.normal(k, shape, jnp.float32)
            if leaf in BRANCH_OUT and cfg["rescale_prenorm_residual"]:
                x = x / math.sqrt(depth)
        params[name] = x
    return params


def make_batch(key, cfg, traffic):
    """Ids uniform over the held vocabulary rows; the label of a position is
    the next id, so every position has one."""
    b = traffic["batch"] * traffic.get("replicas", 1)
    t = traffic["seq_len"]
    ids = jax.random.randint(key, (b, t + 1), 0, cfg["vocab_size"], jnp.int32)
    return {"src": ids[:, :-1], "labels": ids[:, 1:]}


def _rms_norm(x, w, eps, groups=1):
    g = x.reshape(x.shape[:-1] + (groups, x.shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(x.shape) * w


def _scan(x, dx, dt, a, bm, cm, d_skip):
    """The selective scan as its definition, position by position: x and
    dx = dt x [B, T, H, P], dt [B, T, H], a [H] (negative), bm and cm
    [B, T, G, N]. ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D x_t``."""
    bsz, t, h, p = x.shape
    g, n = bm.shape[2:]
    r = h // g

    def step(state, inp):
        xt, dtt, bt, ct, dxt = inp              # [B,H,P] [B,H] [B,G,N] ...
        bt, ct = jnp.repeat(bt, r, axis=1), jnp.repeat(ct, r, axis=1)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + dxt[..., None] * bt[:, :, None, :])
        y = jnp.sum(state * ct[:, :, None, :], -1) + d_skip[:, None] * xt
        return state, y

    seg = math.gcd(t, SCAN_SEGMENT)

    @jax.checkpoint
    def segment(state, inps):
        return jax.lax.scan(step, state, inps)

    def by_time(z):      # [B, T, ...] -> [T / seg, seg, B, ...]
        z = jnp.moveaxis(z, 1, 0)
        return z.reshape((t // seg, seg) + z.shape[1:])

    state = jnp.zeros((bsz, h, p, n), jnp.float32)
    _, ys = jax.lax.scan(segment, state,
                         tuple(by_time(z) for z in (x, dt, bm, cm, dx)))
    return jnp.moveaxis(ys.reshape((t,) + ys.shape[2:]), 0, 1)


def _mamba(u, p, cfg, mm, cast):
    b, t, _ = u.shape
    s = sizes(cfg)
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, k = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    proj = mm(u, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [s["inner"], s["inner"] + s["conv"]], -1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + t] * p["conv.w"][:, i]
                          for i in range(k)) + p["conv.b"])
    x, bm, cm = jnp.split(xbc, [s["inner"], s["inner"] + s["bc"]], -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    x = x.reshape(b, t, heads, hp)
    # the scan's products are the program's MXU products: their operands are
    # what a control narrows (dt x, B, C)
    y = _scan(x, cast(dt[..., None] * x), dt, -jnp.exp(p["A_log"]),
              cast(bm.reshape(b, t, g, n)), cast(cm.reshape(b, t, g, n)),
              p["D"])
    y = y.reshape(b, t, s["inner"]) * jax.nn.silu(z)
    return mm(_rms_norm(y, p["gnorm"], cfg["norm_eps"], g), p["out_proj"])


def _identity(x):
    return x


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def _moe(u, p, cfg, mm, layer):
    k = cfg["num_experts_per_tok"]
    first, held = cfg["first_routed_expert_held"], cfg["n_routed_experts_held"]
    # float32, never cast; the router's weight is not trained on one rank
    # alone (config.json, assumed.router): it takes a zero gradient
    s = jax.nn.sigmoid(jnp.matmul(u, jax.lax.stop_gradient(p["router"])))
    _, idx = jax.lax.top_k(s + correction_bias(cfg, layer), k)
    w = jnp.take_along_axis(s, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = cfg["routed_scaling_factor"] * w
    out = mm(_relu2(mm(u, p["shared_up"])), p["shared_down"])
    for e in range(held):         # the chosen experts that this chip holds
        mask = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
        out = out + mask[..., None] * mm(_relu2(mm(u, p["w1"][e])),
                                         p["w2"][e])
    return out


def _attention(u, p, cfg, mm, cast):
    b, t, _ = u.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def heads(y, n):
        return y.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q = heads(mm(u, p["q"]), h) * hd ** -0.5
    k = jnp.repeat(heads(mm(u, p["k"]), hkv), h // hkv, axis=1)
    v = jnp.repeat(heads(mm(u, p["v"]), hkv), h // hkv, axis=1)
    bq = math.gcd(t, QUERY_BLOCK)
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=2)
        scores = jnp.einsum("bhqd,bhkd->bhqk", cast(qi), cast(k))
        seen = (i * bq + jnp.arange(bq))[:, None] >= key_pos[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", cast(probs), cast(v))

    ctx = jax.lax.map(block, jnp.arange(t // bq))        # [nb, B, H, bq, hd]
    ctx = ctx.transpose(1, 0, 3, 2, 4).reshape(b, t, h * hd)
    return mm(ctx, p["o"])


def loss(params, batch, cfg, cast=_identity):
    """Mean next-token cross entropy over all positions of the batch."""
    eps = cfg["norm_eps"]

    def mm(x, w):
        return jnp.matmul(cast(x), cast(w))

    x = params["emb"][batch["src"]]
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        prefix = "l%d." % i
        p = {k[len(prefix):]: v for k, v in params.items()
             if k.startswith(prefix)}

        @jax.checkpoint
        def layer(x, p, kind=kind, i=i):
            u = _rms_norm(x, p["norm"], eps)
            if kind == "M":
                return x + _mamba(u, p, cfg, mm, cast)
            if kind == "E":
                return x + _moe(u, p, cfg, mm, i)
            return x + _attention(u, p, cfg, mm, cast)

        x = layer(x, p)
    logits = mm(_rms_norm(x, params["norm_f"], eps), params["head"])
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    return -jnp.mean(picked)
