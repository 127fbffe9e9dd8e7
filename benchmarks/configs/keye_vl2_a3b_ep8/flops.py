"""Model FLOPs of one training step of the sparse-attention MoE decoder, and
the operations and bytes of its new kernels, from the configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted (a mixer's under ``RecomputeOptimizer``, the gradient ops' second
forwards, nor the indexer loss's second pass over scores it needs again).
Backward is twice the forward, so a step is three forwards. Counted: every
projection (the indexer's among them), the index scores ONCE over the causal
pairs, attention's scores and context over the SELECTED pairs (what the
mathematics needs, whatever kernel implements it: a kernel that masks every
causal block reads low against this count, one that skips blocks higher), the
gated experts' three products AT THE EXPECTED LOAD, the router, the head. Not
counted: lookups, norms, rotary, activations, softmax, the selection's
counting passes, the indexer's loss, the optimizer.
"""
from __future__ import annotations


def expected_slots(cfg, tokens):
    """Routed slots a layer that land on the held experts under uniform
    routing: 16384 x 8 x 16 / 128 = 16384 in the cell."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["num_experts_held"]
            / cfg["num_experts"])


def causal_pairs(seq_len):
    """(query, key) pairs with key <= query in one sequence."""
    return seq_len * (seq_len + 1) // 2


def selected_pairs(cfg, seq_len):
    """Pairs a sequence's queries attend: query t selects min(t + 1, topk)
    keys. 31.5 M of the 134.2 M causal pairs at 16,384 tokens."""
    k = min(cfg["sa_config"]["topk"], seq_len)
    return causal_pairs(k) + (seq_len - k) * k


def index_ops_and_bytes(cfg, tokens, itemsize=4, seq_len=None):
    """(FLOPs, bytes) of ONE layer's index scores over the causal pairs:
    heads x dim multiply-adds a pair. Bytes: the indexer's queries, key and
    weights read once in float32 and the selection written once, a byte a
    (query, key) pair of the square."""
    sa = cfg["sa_config"]
    h, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    seq_len = seq_len or tokens
    seqs = tokens // seq_len
    flops = 2 * seqs * causal_pairs(seq_len) * h * d
    moved = tokens * (h * d + d + h) * itemsize + seqs * seq_len * seq_len
    return flops, moved


def attend_ops_and_bytes(cfg, tokens, itemsize=2, seq_len=None):
    """(FLOPs, bytes) of ONE layer's selected attention forward: scores and
    context over the selected pairs for every query head; q, k, v read and
    the context written once."""
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    seq_len = seq_len or tokens
    seqs = tokens // seq_len
    flops = 2 * 2 * seqs * selected_pairs(cfg, seq_len) * h * hd
    moved = tokens * (2 * h + 2 * hkv) * hd * itemsize
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
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    sa = cfg["sa_config"]
    index_proj = sa["indexer_head_dim"] * (sa["indexer_num_heads"] + 1) \
        + sa["indexer_num_heads"]
    per_kind = {
        "S": 2 * tokens * d * (2 * hq + 2 * hkv + index_proj)
        + index_ops_and_bytes(cfg, tokens, seq_len=seq_len)[0]
        + attend_ops_and_bytes(cfg, tokens, seq_len=seq_len)[0],
        "E": 2 * tokens * d * cfg["num_experts"]
        + experts_ops_and_bytes(cfg, tokens)[0],
    }
    layers = sum(per_kind[k] for k in cfg["hybrid_override_pattern"])
    return layers + 2 * tokens * d * v


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops(cfg, tokens, t)
