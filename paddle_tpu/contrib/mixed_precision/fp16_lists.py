"""Op lists steering mixed-precision rewriting.

Parity: /root/reference/python/paddle/fluid/contrib/mixed_precision/
fp16_lists.py:20 (AutoMixedPrecisionLists; white/black/gray sets).
TPU-first difference: the low-precision dtype is bfloat16, whose 8-bit
exponent makes the reference's fp16 overflow-driven black-listing less
critical — but the list semantics are kept so user overrides port over.
"""
from __future__ import annotations

import copy


class AutoMixedPrecisionLists:
    """Merge built-in white/black lists with user-supplied overrides."""

    def __init__(self, custom_white_list=None, custom_black_list=None):
        self._custom_white_list = custom_white_list
        self._custom_black_list = custom_black_list
        self.white_list = copy.copy(white_list)
        self.black_list = copy.copy(black_list)
        self.gray_list = copy.copy(gray_list)
        self.fp32_slots = fp32_slots
        self._update_list()

    def _update_list(self):
        if self._custom_white_list and self._custom_black_list:
            for op_name in self._custom_white_list:
                if op_name in self._custom_black_list:
                    raise ValueError(
                        "Custom white list overlap custom black list: %s"
                        % op_name)
        if self._custom_white_list:
            for op_name in self._custom_white_list:
                if op_name in self.black_list:
                    self.black_list.remove(op_name)
                self.white_list.add(op_name)
        if self._custom_black_list:
            for op_name in self._custom_black_list:
                if op_name in self.white_list:
                    self.white_list.remove(op_name)
                self.black_list.add(op_name)


# MXU-bound ops: always run in bf16 (reference fp16_lists.py white_list)
white_list = {
    "conv2d",
    "conv3d",
    "conv2d_transpose",
    "matmul",
    "mul",
    # q, k, v reach the kernels in bf16 (MXU operands); scores, softmax
    # statistics and accumulators are float32 inside them
    "flash_attention",
    # x, B, C are the scan's MXU operands; see fp32_slots for the rest
    "ssd_chunk_scan",
    # q, k, v and the raw gate projections as the products before them make
    # them; the gates' activations, the decays and the L2 norms are float32
    # inside the op; see fp32_slots for the decay's leaves
    "kda_chunk",
    # the held experts' grouped products; see fp32_slots for the router
    "moe_topk",
    # the attention's own q, k as its kernels multiply them; see fp32_slots
    # for the indexer's operands. ``attn_index_project`` and
    # ``attn_index_select`` are on no list: every operand of an op the
    # rewrite does not know stays float32, which is what a choice needs
    "attn_index_loss",
}

# input slots of white- and gray-list ops that stay float32: what sets a
# decay or a choice must not pass through the low type
fp32_slots = {
    "ssd_chunk_scan": frozenset(("A", "D", "DtBias")),
    "kda_chunk": frozenset(("ALog", "DtBias")),
    "moe_topk": frozenset(("X", "RouterW", "Bias")),
    "attn_index_loss": frozenset(("QI", "KI", "W", "LSE")),
    # the taps weigh in float32, as the op sums them
    "short_conv_gate": frozenset(("W",)),
}

# numerically sensitive reductions/losses/normalizations: keep f32
# (reference fp16_lists.py black_list; normalization moved here from the
# reference's gray set — the TPU policy keeps stats math in f32, which
# costs nothing on bandwidth-bound elementwise ops)
black_list = {
    "exp",
    "square",
    "log",
    "mean",
    "sum",
    "cos_sim",
    "softmax",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "cross_entropy",
    "cross_entropy2",
    "batch_norm",
    "layer_norm",
    "rms_norm",
    "instance_norm",
    "group_norm",
    # the residual streams and the maps that mix them (flat norm, product,
    # sigmoids, Sinkhorn rounds): float32 in and out
    "mhc_pre",
    "mhc_post",
}

# follow their inputs (reference gray_list)
gray_list = {
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "elementwise_mod",
    "elementwise_floordiv",
    "tanh",
    "sigmoid",
    "lookup_table",
    "top_k",
    "pool2d",
    "pool3d",
    "dropout",
    "relu",
    "relu6",
    "leaky_relu",
    "soft_relu",
    "flatten2",
    "stack",
    "unstack",
    "uniform_random_batch_size_like",
    "gaussian_random",
    "gaussian_random_batch_size_like",
    "slice",
    "rank",
    "scale",
    "transpose2",
    "reshape2",
    "gather",
    "fill_constant",
    "get_tensor_from_selected_rows",
    "sign",
    "cast",
    "causal_conv1d",
    # the projection's three streams as the product before them makes them;
    # the two gates and the taps' sum are float32 inside the op, and the
    # taps stay float32 (fp32_slots)
    "short_conv_gate",
}
