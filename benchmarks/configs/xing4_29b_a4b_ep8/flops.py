"""Model FLOPs of one training step of the latent-attention decoder on
hyper-connected residual streams, and the operations and bytes of its new
kernels, from the configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted (a sublayer's under ``RecomputeOptimizer``, the gradient ops' second
forwards). Backward is twice the forward, so a step is three forwards.
Counted: every projection (the latent ones, the feed-forward layers, the
shared expert, the router, the head), attention's scores at 192 and context
at 128 over the causal pairs (what the mathematics needs, whatever kernel
implements it), the routed experts' three products AT THE EXPECTED LOAD, and
the residual path's products: the maps' ``x~ Phi`` and the multiply-adds of
``H_pre . X`` and ``H_res X + H_post (x) y``. Not counted: lookups, norms,
rotary, activations, softmax, sigmoids, the Sinkhorn rounds, the optimizer.
"""
from __future__ import annotations


def expected_slots(cfg, tokens):
    """Routed slots a layer that land on the held experts under uniform
    routing: 4096 x 4 x 8 / 64 = 2048 in the cell."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
            / cfg["n_routed_experts"])


def causal_pairs(seq_len):
    """(query, key) pairs with key <= query in one sequence."""
    return seq_len * (seq_len + 1) // 2


def attend_ops_and_bytes(cfg, tokens, itemsize=2, seq_len=None):
    """(FLOPs, bytes) of ONE layer's attention forward: scores at nope + rope
    dims and context at the value dim over the causal pairs for every head;
    q, the per-head keys, v and the context moved once, the shared rotary
    key once for all heads."""
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    seq_len = seq_len or tokens
    seqs = tokens // seq_len
    flops = 2 * seqs * causal_pairs(seq_len) * h * (nope + rope + vd)
    moved = tokens * (h * (nope + rope) + h * nope + 2 * h * vd
                      + rope) * itemsize
    return flops, moved


def mhc_ops_and_bytes(cfg, tokens, itemsize=4, y_itemsize=2):
    """(FLOPs, bytes) of ONE sublayer's residual path forward: the maps'
    product ``[n C] x [n C, 2 n + n^2]`` and the ``n + n^2 + n``
    multiply-adds an element of a stream's width of ``H_pre . X`` and ``H_res
    X + H_post (x) y``. Bytes: the streams read once for the maps and ``h``,
    read and written once for the mix (float32), ``h`` written, ``y`` read,
    ``Phi`` read."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    outs = 2 * n + n * n
    flops = 2 * tokens * (n * c * outs + (2 * n + n * n) * c)
    moved = (tokens * (3 * n * c + c) * itemsize + tokens * c * y_itemsize
             + n * c * outs * itemsize)
    return flops, moved


def experts_ops_and_bytes(cfg, tokens, itemsize=2):
    """(FLOPs, bytes) of ONE ``moe_topk`` forward's grouped products at the
    expected load: three products D x F a slot (gate, up, down); bytes: the
    held experts' three matrices read once, a slot's row read and written at
    width D, its two hidden rows written and the gated one read at width
    F."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    slots = expected_slots(cfg, tokens)
    flops = 3 * 2 * slots * d * f
    moved = (3 * cfg["n_routed_experts_held"] * d * f
             + slots * (2 * d + 3 * f)) * itemsize
    return flops, moved


def forward_flops(cfg, tokens, seq_len):
    c, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f = cfg["moe_intermediate_size"]
    latent = (c * qr + qr * h * (nope + rope) + c * (kvr + rope)
              + kvr * h * (nope + vd) + h * vd * c)
    per_kind = {
        "L": 2 * tokens * latent
        + attend_ops_and_bytes(cfg, tokens, seq_len=seq_len)[0],
        "D": 2 * tokens * 3 * c * cfg["intermediate_size"],
        "E": 2 * tokens * c * (cfg["n_routed_experts"]
                               + 3 * f * cfg["n_shared_experts"])
        + experts_ops_and_bytes(cfg, tokens)[0],
    }
    pattern = cfg["hybrid_override_pattern"]
    return (sum(per_kind[k] for k in pattern)
            + len(pattern) * mhc_ops_and_bytes(cfg, tokens)[0]
            + 2 * tokens * c * v)


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops(cfg, tokens, t)
