"""Model FLOPs of one training step of the short-convolution / attention
decoder, and the operations and bytes of what the cell's roofline shares are
taken against, from the configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted (a sublayer's under ``RecomputeOptimizer``, the gradient ops' second
forwards). Backward is twice the forward, so a step is three forwards.
Counted: every projection (both mixers', the dense feed-forward layer, the
router, the tied head), the gated convolution's two gates and three taps,
attention's scores and context at the true head dim of 64 over the causal
pairs (what the mathematics needs, whatever kernel implements it and however
wide its tiles) and the routed experts' three products AT THE EXPECTED LOAD.
Not counted: lookups, norms, rotary positions, activations, softmax, the
optimizer.
"""
from __future__ import annotations


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def expected_slots(cfg, tokens):
    """Routed slots a layer that land on the held experts under uniform
    routing: 8192 x 4 x 8 / 64 = 4096 in the cell."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts_held"]
            / cfg["num_experts"])


def causal_pairs(seq_len):
    """(query, key) pairs with key <= query in one sequence."""
    return seq_len * (seq_len + 1) // 2


def gate_ops_and_bytes(cfg, tokens, itemsize=2):
    """(FLOPs, bytes) of ONE ``short_conv_gate`` forward: for a position and
    a channel the gate ``B * z`` (1), the taps' products and their sum
    (``taps`` multiplies, ``taps - 1`` adds) and the gate ``C * y`` (1);
    bytes: the three streams read and the result written once, 4 x [tokens,
    hidden], and the taps in float32. Nothing between them: a form that
    keeps a stream or the convolved one in HBM moves more than is counted."""
    c, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    flops = tokens * c * (2 * taps + 1)
    moved = 4 * tokens * c * itemsize + c * taps * 4
    return flops, moved


def attend_ops_and_bytes(cfg, tokens, itemsize=2, seq_len=None):
    """(FLOPs, bytes) of ONE layer's attention forward: scores and context
    at the head dim over the causal pairs for every query head; q and the
    context moved once at ``heads x head dim``, k and v once at the K/V
    heads."""
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    seq_len = seq_len or tokens
    seqs = tokens // seq_len
    flops = 2 * seqs * causal_pairs(seq_len) * h * (hd + hd)
    moved = tokens * (2 * h * hd + 2 * hkv * hd) * itemsize
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
    moved = (3 * cfg["num_experts_held"] * d * f
             + slots * (2 * d + 3 * f)) * itemsize
    return flops, moved


def forward_flops(cfg, tokens, seq_len):
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  head_dim(cfg))
    per_kind = {
        "C": 2 * tokens * (c * 3 * c + c * c)
        + gate_ops_and_bytes(cfg, tokens)[0],
        "*": 2 * tokens * (2 * c * h * hd + 2 * c * hkv * hd)
        + attend_ops_and_bytes(cfg, tokens, seq_len=seq_len)[0],
        "D": 2 * tokens * 3 * c * cfg["intermediate_size"],
        "E": 2 * tokens * c * cfg["num_experts"]
        + experts_ops_and_bytes(cfg, tokens)[0],
    }
    return (sum(per_kind[k] for k in cfg["hybrid_override_pattern"])
            + 2 * tokens * c * v)


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops(cfg, tokens, t)
