"""Model FLOPs of one BERT training step, from the configuration's sizes.

A multiply-add counts as 2, nothing is counted twice, recomputation is not
counted. Forward: per token and layer 4 projections D x D, two feed-forward
matmuls D x F, and attention's scores and context (T x D each); the head is
D x V at the positions the loss reads. Backward is twice the forward (every
matmul needs the gradient of both operands), so a step is three forwards.
Embedding lookups, softmax, layer norm, GELU and the optimizer are not counted.
"""
from __future__ import annotations


def forward_flops_per_token(cfg, seq_len, head_positions):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = 2 * 4 * d * d + 2 * 2 * d * f + 2 * 2 * seq_len * d
    head = 2 * d * cfg["vocab_size"] * head_positions / seq_len
    return cfg["num_hidden_layers"] * per_layer + head


def flops_per_step(cfg, traffic):
    """FLOPs of one step over the GLOBAL batch of the traffic."""
    t = traffic["seq_len"]
    head_positions = traffic.get("masked_positions") or t
    tokens = traffic["batch"] * traffic.get("replicas", 1) * t
    return 3 * forward_flops_per_token(cfg, t, head_positions) * tokens
