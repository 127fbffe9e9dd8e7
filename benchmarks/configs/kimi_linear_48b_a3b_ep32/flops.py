"""Model FLOPs of one training step of the delta-rule / latent-attention
decoder, and the operations and bytes of its new kernel, from the
configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted (a sublayer's under ``RecomputeOptimizer``, the gradient ops' second
forwards). Backward is twice the forward, so a step is three forwards.
Counted: every projection (both mixers', the feed-forward layers, the shared
expert, the router, the head), the three convolutions' taps, the delta rule's
products at chunk 64 over the causal pairs inside a chunk and with the
carried state, attention's scores at 192 and context at 128 over the causal
pairs (what the mathematics needs, whatever kernel implements it) and the
routed experts' three products AT THE EXPECTED LOAD. Not counted: lookups,
norms, activations, gates' exponentials, softmax, the optimizer.
"""
from __future__ import annotations

KDA_CHUNK = 64     # the chunk the delta rule's operations are counted at


def expected_slots(cfg, tokens):
    """Routed slots a layer that land on the held experts under uniform
    routing: 8192 x 8 x 8 / 256 = 2048 in the cell."""
    return (tokens * cfg["num_experts_per_token"] * cfg["num_experts_held"]
            / cfg["num_experts"])


def causal_pairs(seq_len):
    """(query, key) pairs with key <= query in one sequence."""
    return seq_len * (seq_len + 1) // 2


def attend_ops_and_bytes(cfg, tokens, itemsize=2, seq_len=None):
    """(FLOPs, bytes) of ONE layer's attention forward: scores at nope + rope
    dims and context at the value dim over the causal pairs for every head;
    q, the per-head keys, v and the context moved once, the shared key head
    once for all heads."""
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    seq_len = seq_len or tokens
    seqs = tokens // seq_len
    flops = 2 * seqs * causal_pairs(seq_len) * h * (nope + rope + vd)
    moved = tokens * (h * (nope + rope) + h * nope + 2 * h * vd
                      + rope) * itemsize
    return flops, moved


def kda_ops_and_bytes(cfg, tokens, itemsize=2):
    """(FLOPs, bytes) of ONE ``kda_chunk`` forward, counted at chunks of
    ``KDA_CHUNK`` = C positions whatever chunk or kernel the program runs.
    For a head and a chunk, with K = V the head dim: the two decayed Gram
    matrices over the pairs inside the chunk (``k_t . k_s`` for s < t, ``q_t
    . k_s`` for s <= t), forward substitution for the unit lower-triangular
    [C, C] inverse (C^3 / 3), its two products with the chunk's C rows of
    width K and V, ``B U`` over the pairs, and three products of C rows
    with the [K, V] state (what the state holds for the rows' keys, what it
    holds for their queries, the rows written into it). Bytes: the operands
    and the result once, q, k, v and the raw decay projection [tokens, H K],
    the raw write strength [tokens, H], o [tokens, H V]; no chunk state, no
    intermediate: a kernel that keeps them in VMEM reads the same count."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    c = KDA_CHUNK
    within, strict = c * (c + 1) // 2, c * (c - 1) // 2
    a_chunk = (2 * d * (strict + within)          # the two Gram matrices
               + 2 * c ** 3 // 6                  # forward substitution
               + 2 * within * (d + d)             # the inverse's two products
               + 2 * within * d                   # B U
               + 3 * 2 * c * d * d)               # with the carried state
    flops = (tokens // c) * h * a_chunk
    moved = tokens * (h * (5 * d) + h) * itemsize
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
    c, v, h = (cfg["hidden_size"], cfg["vocab_size"],
               cfg["num_attention_heads"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kvr, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    lin = cfg["linear_attn_config"]
    inner, rank = lin["num_heads"] * lin["head_dim"], lin["head_dim"]
    kda = (4 * c * inner + 2 * (c * rank + rank * inner)
           + c * lin["num_heads"] + 3 * inner * lin["short_conv_kernel_size"])
    latent = (c * h * (nope + rope) + c * (kvr + rope)
              + kvr * h * (nope + vd) + h * vd * c)
    per_kind = {
        "K": 2 * tokens * kda + kda_ops_and_bytes(cfg, tokens)[0],
        "L": 2 * tokens * latent
        + attend_ops_and_bytes(cfg, tokens, seq_len=seq_len)[0],
        "D": 2 * tokens * 3 * c * cfg["intermediate_size"],
        "E": 2 * tokens * c * (cfg["num_experts"]
                               + 3 * f * cfg["num_shared_experts"])
        + experts_ops_and_bytes(cfg, tokens)[0],
    }
    return (sum(per_kind[k] for k in cfg["hybrid_override_pattern"])
            + 2 * tokens * c * v)


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops(cfg, tokens, t)
