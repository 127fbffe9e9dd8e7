"""Model FLOPs of one training step of the hybrid state-space MoE, and the
operations and bytes of its two new kernels, from the configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted (neither the scan's, which its gradient op recomputes inside, nor a
layer's under ``RecomputeOptimizer``). Backward is twice the forward, so a
step is three forwards. Counted: every projection, the scan's four products
of the chunked algorithm, the routed experts AT THE EXPECTED LOAD (tokens x
top-k x held / experts slots a layer: what uniform routing gives this chip),
the shared expert, the router, attention's causal half of scores and context,
the head. Not counted: lookups, norms, the convolution's taps, activations,
softmax, the optimizer.
"""
from __future__ import annotations

from .reference import sizes


def expected_slots(cfg, tokens):
    """Routed slots a layer that land on the held experts under uniform
    routing: 8192 x 6 x 8 / 128 = 3072 in the cell."""
    return (tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
            / cfg["n_routed_experts"])


def scan_ops_and_bytes(cfg, tokens, itemsize=2):
    """(FLOPs, bytes) of ONE ``ssd_chunk_scan`` forward over ``tokens``
    positions by the chunked algorithm: C B^T inside chunks, its masked
    product with dt x, the chunk-end states, the carried states' output.
    Bytes: x, B, C read and y written in the MXU type, dt in float32, and
    the chunk-end states written and read once in float32; the [Q, Q] tiles
    are the algorithm's own and are not counted as traffic."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    flops = 2 * tokens * (q * g * n + q * h * p + 2 * h * p * n)
    moved = (tokens * (2 * h * p + 2 * g * n) * itemsize + tokens * h * 4
             + 2 * (tokens // q) * h * p * n * 4)
    return flops, moved


def experts_ops_and_bytes(cfg, tokens, itemsize=2):
    """(FLOPs, bytes) of ONE ``moe_topk`` forward's grouped products at the
    expected load: two products D x F a slot; bytes: the held experts'
    weights read once, a slot's row read and written at width D and its
    hidden row written and read at width F."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    slots = expected_slots(cfg, tokens)
    flops = 2 * 2 * slots * d * f
    moved = (2 * cfg["n_routed_experts_held"] * d * f
             + slots * (2 * d + 2 * f)) * itemsize
    return flops, moved


def forward_flops(cfg, tokens, seq_len):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    s = sizes(cfg)
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_kind = {
        "M": 2 * tokens * d * (s["proj"] + s["inner"])
        + scan_ops_and_bytes(cfg, tokens)[0],
        "E": 2 * tokens * d * (cfg["n_routed_experts"]
                               + 2 * cfg["moe_shared_expert_intermediate_size"])
        + experts_ops_and_bytes(cfg, tokens)[0],
        # causal: half of the T x T scores and of the context
        "*": 2 * tokens * d * (2 * hq + 2 * hkv) + 2 * tokens * seq_len * hq,
    }
    layers = sum(per_kind[k] for k in cfg["hybrid_override_pattern"])
    return layers + 2 * tokens * d * v


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops(cfg, tokens, t)
