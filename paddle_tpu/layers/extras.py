"""Wave-3 layer APIs.

Parity: the remaining single-op wrappers and small compositions from
/root/reference/python/paddle/fluid/layers/ (nn.py, loss.py, tensor.py,
control_flow.py, detection.py) — each docstring names its op/source.
"""
from __future__ import annotations

import numpy as np

from .. import framework
from ..layer_helper import LayerHelper

__all__ = [
    "reverse", "pixel_shuffle", "shuffle_channel", "space_to_depth",
    "temporal_shift", "shard_index", "multiplex", "crop", "crop_tensor",
    "affine_channel", "unfold", "affine_grid", "selu", "mean_iou",
    "bilinear_tensor_product", "cos_sim", "bpr_loss",
    "teacher_student_sigmoid_loss", "sigmoid_focal_loss", "row_conv",
    "fsp_matrix", "hash", "unique", "edit_distance", "warpctc",
    "ctc_greedy_decoder", "rank", "size", "is_empty", "sum",
    "scatter_nd", "pad_constant_like", "add_position_encoding",
    "dice_loss", "npair_loss", "while_loop", "case", "switch_case",
    "gru_unit", "lstm_unit", "py_func", "double_buffer",
    "image_resize_short", "gaussian_random_batch_size_like",
    "sequence_reverse", "get_tensor_from_selected_rows",
    "merge_selected_rows", "lod_reset",
]


def _simple(op_type, x, attrs=None, dtype=None, out_slot="Out"):
    helper = LayerHelper(op_type, input=x)
    out = helper.create_variable_for_type_inference(dtype or x.dtype)
    helper.append_op(op_type, inputs={"X": [x]},
                     outputs={out_slot: [out]}, attrs=attrs or {},
                     infer_shape=False)
    return out


def reverse(x, axis):
    return _simple("reverse", x, {"axis": axis if isinstance(
        axis, (list, tuple)) else [axis]})


def pixel_shuffle(x, upscale_factor):
    return _simple("pixel_shuffle", x, {"upscale_factor": upscale_factor})


def shuffle_channel(x, group, name=None):
    return _simple("shuffle_channel", x, {"group": group})


def space_to_depth(x, blocksize, name=None):
    return _simple("space_to_depth", x, {"blocksize": blocksize})


def temporal_shift(x, seg_num, shift_ratio=0.25, name=None):
    return _simple("temporal_shift", x, {"seg_num": seg_num,
                                         "shift_ratio": shift_ratio})


def shard_index(input, index_num, nshards, shard_id, ignore_value=-1):
    return _simple("shard_index", input,
                   {"index_num": index_num, "nshards": nshards,
                    "shard_id": shard_id, "ignore_value": ignore_value})


def multiplex(inputs, index):
    helper = LayerHelper("multiplex", input=inputs[0])
    out = helper.create_variable_for_type_inference(inputs[0].dtype)
    helper.append_op("multiplex",
                     inputs={"X": list(inputs), "Ids": [index]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def crop(x, shape=None, offsets=None, name=None):
    helper = LayerHelper("crop", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    attrs = {}
    if isinstance(shape, framework.Variable):
        inputs["Y"] = [shape]
    else:
        attrs["shape"] = list(shape or [])
    attrs["offsets"] = list(offsets or [])
    helper.append_op("crop", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs, infer_shape=False)
    return out


def crop_tensor(x, shape=None, offsets=None, name=None):
    return crop(x, shape, offsets, name)


def affine_channel(x, scale=None, bias=None, data_layout="NCHW", name=None,
                   act=None):
    from .tensor import fill_constant

    helper = LayerHelper("affine_channel", input=x)
    c = int(x.shape[1 if data_layout == "NCHW" else -1])
    if scale is None:
        scale = fill_constant([c], x.dtype, 1.0)
    if bias is None:
        bias = fill_constant([c], x.dtype, 0.0)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("affine_channel",
                     inputs={"X": [x], "Scale": [scale], "Bias": [bias]},
                     outputs={"Out": [out]},
                     attrs={"data_layout": data_layout},
                     infer_shape=False)
    return out


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    pads = paddings if isinstance(paddings, (list, tuple)) and \
        len(paddings) == 4 else _pair(paddings) * 2
    return _simple("unfold", x,
                   {"kernel_sizes": _pair(kernel_sizes),
                    "strides": _pair(strides),
                    "paddings": list(pads),
                    "dilations": _pair(dilations)}, out_slot="Y")


def affine_grid(theta, out_shape, name=None):
    helper = LayerHelper("affine_grid", input=theta)
    out = helper.create_variable_for_type_inference(theta.dtype)
    inputs = {"Theta": [theta]}
    attrs = {}
    if isinstance(out_shape, framework.Variable):
        inputs["OutputShape"] = [out_shape]
    else:
        attrs["output_shape"] = [int(v) for v in out_shape]
    helper.append_op("affine_grid", inputs=inputs,
                     outputs={"Output": [out]}, attrs=attrs,
                     infer_shape=False)
    return out


def selu(x, scale=None, alpha=None, name=None):
    attrs = {}
    if scale is not None:
        attrs["scale"] = scale
    if alpha is not None:
        attrs["alpha"] = alpha
    return _simple("selu", x, attrs)


def mean_iou(input, label, num_classes):
    helper = LayerHelper("mean_iou", input=input)
    miou = helper.create_variable_for_type_inference("float32")
    wrong = helper.create_variable_for_type_inference("int32")
    correct = helper.create_variable_for_type_inference("int32")
    helper.append_op("mean_iou",
                     inputs={"Predictions": [input], "Labels": [label]},
                     outputs={"OutMeanIou": [miou], "OutWrong": [wrong],
                              "OutCorrect": [correct]},
                     attrs={"num_classes": num_classes},
                     infer_shape=False)
    return miou, wrong, correct


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    helper = LayerHelper("bilinear_tensor_product", input=x,
                         param_attr=param_attr, bias_attr=bias_attr)
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[size, int(x.shape[1]), int(y.shape[1])], dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [x], "Y": [y], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[1, size], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    helper.append_op("bilinear_tensor_product", inputs=inputs,
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim", input=X)
    out = helper.create_variable_for_type_inference(X.dtype)
    xn = helper.create_variable_for_type_inference(X.dtype)
    yn = helper.create_variable_for_type_inference(X.dtype)
    helper.append_op("cos_sim", inputs={"X": [X], "Y": [Y]},
                     outputs={"Out": [out], "XNorm": [xn], "YNorm": [yn]},
                     infer_shape=False)
    if X.shape is not None:
        out.shape = (int(X.shape[0]), 1)
    return out


def bpr_loss(input, label, name=None):
    helper = LayerHelper("bpr_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("bpr_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]}, infer_shape=False)
    return out


def teacher_student_sigmoid_loss(input, label, soft_max_up_bound=15.0,
                                 soft_max_lower_bound=-15.0):
    helper = LayerHelper("teacher_student_sigmoid_loss", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("teacher_student_sigmoid_loss",
                     inputs={"X": [input], "Label": [label]},
                     outputs={"Y": [out]},
                     attrs={"soft_max_up_bound": soft_max_up_bound,
                            "soft_max_lower_bound": soft_max_lower_bound},
                     infer_shape=False)
    return out


def sigmoid_focal_loss(x, label, fg_num, gamma=2.0, alpha=0.25):
    helper = LayerHelper("sigmoid_focal_loss", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("sigmoid_focal_loss",
                     inputs={"X": [x], "Label": [label],
                             "FgNum": [fg_num]},
                     outputs={"Out": [out]},
                     attrs={"gamma": gamma, "alpha": alpha},
                     infer_shape=False)
    return out


def row_conv(input, future_context_size, param_attr=None, act=None):
    helper = LayerHelper("row_conv", input=input,
                         param_attr=param_attr)
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[future_context_size + 1, int(input.shape[-1])],
        dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("row_conv",
                     inputs={"X": [input], "Filter": [w]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def fsp_matrix(x, y):
    helper = LayerHelper("fsp", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("fsp", inputs={"X": [x], "Y": [y]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def hash(input, hash_size, num_hash=1, name=None):
    return _simple("hash", input, {"mod_by": hash_size,
                                   "num_hash": num_hash}, dtype="int64")


def unique(x, dtype="int32"):
    helper = LayerHelper("unique", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    index = helper.create_variable_for_type_inference(dtype)
    helper.append_op("unique", inputs={"X": [x]},
                     outputs={"Out": [out], "Index": [index]},
                     infer_shape=False)
    return out, index


def edit_distance(input, label, normalized=True, ignored_tokens=None,
                  input_length=None, label_length=None):
    helper = LayerHelper("edit_distance", input=input)
    out = helper.create_variable_for_type_inference("float32")
    seq_num = helper.create_variable_for_type_inference("int64")
    helper.append_op("edit_distance",
                     inputs={"Hyps": [input], "Refs": [label]},
                     outputs={"Out": [out], "SequenceNum": [seq_num]},
                     attrs={"normalized": normalized},
                     infer_shape=False)
    return out, seq_num


def warpctc(input, label, blank=0, norm_by_times=False,
            input_length=None, label_length=None):
    helper = LayerHelper("warpctc", input=input)
    loss = helper.create_variable_for_type_inference(input.dtype)
    inputs = {"Logits": [input], "Label": [label]}
    if input_length is not None:
        inputs["LogitsLength"] = [input_length]
    helper.append_op("warpctc",
                     inputs=inputs,
                     outputs={"Loss": [loss]},
                     attrs={"blank": blank,
                            "norm_by_times": norm_by_times},
                     infer_shape=False)
    loss.shape = (int(input.shape[0]) if len(input.shape) == 3 else 1, 1)
    return loss


def ctc_greedy_decoder(input, blank, name=None):
    """argmax over classes then CTC alignment (reference
    ctc_greedy_decoder = top_k + ctc_align)."""
    from .nn import argmax

    ids = argmax(input, axis=-1)
    helper = LayerHelper("ctc_align", input=input)
    out = helper.create_variable_for_type_inference("int64")
    out.lod_level = 1
    helper.append_op("ctc_align", inputs={"Input": [ids]},
                     outputs={"Output": [out]},
                     attrs={"blank": blank, "merge_repeated": True},
                     infer_shape=False)
    return out


def rank(input):
    """Static rank as a constant tensor (reference layers/nn.py rank)."""
    from .tensor import fill_constant

    return fill_constant([1], "int32", len(input.shape))


def size(input):
    """Runtime element count (handles dynamic -1 dims via the shape op,
    unlike a compile-time constant which would go negative)."""
    from .nn import reduce_prod, shape
    from .tensor import cast

    return cast(reduce_prod(cast(shape(input), "int64")), "int64")


def is_empty(x, cond=None):
    from .control_flow import equal
    from .tensor import assign, cast, fill_constant

    zero = fill_constant([1], "int64", 0)
    out = equal(cast(size(x), "int64"), zero)
    if cond is not None:
        assign(out, output=cond)
        return cond
    return out


def sum(x):
    """Elementwise sum of a LIST of tensors (reference layers.sum ->
    sum op; distinct from reduce_sum)."""
    xs = x if isinstance(x, (list, tuple)) else [x]
    helper = LayerHelper("sum", input=xs[0])
    out = helper.create_variable_for_type_inference(xs[0].dtype)
    helper.append_op("sum", inputs={"X": list(xs)},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def scatter_nd(index, updates, shape, name=None):
    """zeros(shape) with updates scattered (reference scatter_nd =
    scatter_nd_add onto zeros)."""
    from .nn import scatter_nd_add
    from .tensor import fill_constant

    zero = fill_constant(list(shape), updates.dtype, 0.0)
    return scatter_nd_add(zero, index, updates)


def pad_constant_like(x, y, pad_value=0.0, name=None):
    """Pad y to x's shape (reference pad_constant_like_op)."""
    from .nn import pad

    paddings = []
    for xs, ys in zip(x.shape, y.shape):
        if int(xs) < 0 or int(ys) < 0:
            raise ValueError(
                "pad_constant_like requires static shapes; got %s vs %s"
                % (x.shape, y.shape))
        paddings.extend([0, int(xs) - int(ys)])
    return pad(y, paddings, pad_value)


def add_position_encoding(input, alpha, beta, name=None):
    """Sinusoidal position encoding added in-graph (reference
    add_position_encoding_op)."""
    from . import tensor as lt
    from .nn import elementwise_add
    from .ops import scale

    T, D = int(input.shape[1]), int(input.shape[2])
    pos = np.arange(T)[:, None]
    dim = np.arange(D // 2)[None, :]
    inv = 1.0 / np.power(10000.0, 2 * dim / D)
    enc = np.zeros((T, D), np.float32)
    enc[:, 0::2] = np.sin(pos * inv)
    enc[:, 1::2] = np.cos(pos * inv)
    # [1, T, D]: broadcast over the (possibly dynamic) batch dim
    enc_var = lt.assign(enc[None])
    return elementwise_add(scale(input, scale=alpha),
                           scale(enc_var, scale=beta))


def dice_loss(input, label, epsilon=1e-5):
    """(reference layers/nn.py dice_loss): one-hot the class labels,
    per-sample dice, then mean."""
    from .nn import one_hot, reduce_mean, reduce_sum
    from .ops import scale
    from .tensor import cast

    depth = int(input.shape[-1])
    # one_hot squeezes label's trailing 1-dim: [..., 1] -> [..., depth]
    label_oh = cast(one_hot(label, depth), input.dtype)
    reduce_dim = list(range(1, len(input.shape)))
    inse = reduce_sum(input * label_oh, dim=reduce_dim)
    denom = reduce_sum(input, dim=reduce_dim) + \
        reduce_sum(label_oh, dim=reduce_dim)
    dice = scale(inse, 2.0) / (denom + epsilon)
    return reduce_mean(scale(dice, -1.0, bias=1.0))


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    """(reference layers/loss.py npair_loss composition)."""
    from .loss import softmax_with_cross_entropy
    from .nn import matmul, reduce_mean, reduce_sum, transpose
    from .ops import scale
    from .tensor import cast

    reg = reduce_mean(reduce_sum(anchor * anchor, dim=1)) + \
        reduce_mean(reduce_sum(positive * positive, dim=1))
    sim = matmul(anchor, transpose(positive, [1, 0]))
    n = int(anchor.shape[0])
    lab = cast(labels, "int64")
    from .nn import reshape

    ce = softmax_with_cross_entropy(sim, reshape(lab, [n, 1]))
    return reduce_mean(ce) + scale(reg, l2_reg / 2.0)


def while_loop(cond, body, loop_vars, is_test=False, name=None):
    """Functional while (reference layers/control_flow.py while_loop)
    built on the While op: loop vars thread through assigns."""
    from .control_flow import While
    from .tensor import assign

    c = cond(*loop_vars)
    w = While(c)
    with w.block():
        new_vars = body(*loop_vars)
        if not isinstance(new_vars, (list, tuple)):
            new_vars = [new_vars]
        for old, new in zip(loop_vars, new_vars):
            assign(new, output=old)
        assign(cond(*loop_vars), output=c)
    return list(loop_vars)


def case(pred_fn_pairs, default=None, name=None):
    """First-true-wins select chain (reference layers/control_flow.py
    case; both branches evaluate — XLA select semantics)."""
    helper = LayerHelper("case")
    if default is None:
        raise ValueError("case requires a default fn here")
    result = default()
    for pred, fn in reversed(pred_fn_pairs):
        val = fn()
        out = helper.create_variable_for_type_inference(val.dtype)
        helper.append_op("where",
                         inputs={"Condition": [pred], "X": [val],
                                 "Y": [result]},
                         outputs={"Out": [out]}, infer_shape=False)
        result = out
    return result


def switch_case(branch_index, branch_fns, default=None, name=None):
    from .control_flow import equal  # noqa: F401
    from .tensor import fill_constant

    pairs = []
    helper = LayerHelper("switch_case")
    for idx, fn in (branch_fns.items() if isinstance(branch_fns, dict)
                    else enumerate(branch_fns)):
        iconst = fill_constant([1], branch_index.dtype, int(idx))
        eq = helper.create_variable_for_type_inference("bool")
        helper.append_op("equal",
                         inputs={"X": [branch_index], "Y": [iconst]},
                         outputs={"Out": [eq]}, infer_shape=False)
        pairs.append((eq, fn))
    return case(pairs, default=default or pairs[-1][1])


def gru_unit(input, hidden, size, param_attr=None, bias_attr=None,
             activation="tanh", gate_activation="sigmoid",
             origin_mode=False):
    """(reference layers/rnn.py gru_unit over the gru_unit op)."""
    helper = LayerHelper("gru_unit", input=input, param_attr=param_attr,
                         bias_attr=bias_attr)
    dtype = helper.input_dtype()
    d = size // 3
    w = helper.create_parameter(attr=helper.param_attr, shape=[d, 3 * d],
                                dtype=dtype)
    inputs = {"Input": [input], "HiddenPrev": [hidden], "Weight": [w]}
    if bias_attr is not False:
        b = helper.create_parameter(attr=helper.bias_attr,
                                    shape=[1, 3 * d], dtype=dtype,
                                    is_bias=True)
        inputs["Bias"] = [b]
    gate = helper.create_variable_for_type_inference(dtype)
    rhp = helper.create_variable_for_type_inference(dtype)
    hid = helper.create_variable_for_type_inference(dtype)
    helper.append_op("gru_unit", inputs=inputs,
                     outputs={"Gate": [gate], "ResetHiddenPrev": [rhp],
                              "Hidden": [hid]},
                     attrs={"origin_mode": origin_mode},
                     infer_shape=False)
    b = int(hidden.shape[0])
    hid.shape = (b, d)
    rhp.shape = (b, d)
    gate.shape = (b, 3 * d)
    return hid, rhp, gate


def lstm_unit(x_t, hidden_t_prev, cell_t_prev, forget_bias=0.0,
              param_attr=None, bias_attr=None, name=None):
    """(reference layers/rnn.py lstm_unit: fc + lstm_unit op)."""
    from .nn import fc
    from .tensor import concat

    helper = LayerHelper("lstm_unit", input=x_t)
    d = int(cell_t_prev.shape[-1])
    merged = concat([x_t, hidden_t_prev], axis=1)
    gates = fc(merged, size=4 * d, param_attr=param_attr,
               bias_attr=bias_attr)
    c = helper.create_variable_for_type_inference(x_t.dtype)
    h = helper.create_variable_for_type_inference(x_t.dtype)
    helper.append_op("lstm_unit",
                     inputs={"X": [gates], "C_prev": [cell_t_prev]},
                     outputs={"C": [c], "H": [h]},
                     attrs={"forget_bias": forget_bias},
                     infer_shape=False)
    c.shape = tuple(cell_t_prev.shape)
    h.shape = tuple(cell_t_prev.shape)
    return h, c


def py_func(func, x, out, backward_func=None, skip_vars_in_backward_input=None):
    """Delegates to the full py_func layer (nn.py) backed by the real
    py_func op with backward-callable support (py_func_op.cc); this
    round-2 forward-only shim kept its export slot here."""
    from .nn import py_func as _py_func_full

    return _py_func_full(func, x, out, backward_func=backward_func,
                         skip_vars_in_backward_input=
                         skip_vars_in_backward_input)


def double_buffer(reader, place=None, name=None):
    """Device double-buffering is built into DataLoader
    (use_double_buffer=True); graph-side this is identity."""
    return reader


def image_resize_short(input, out_short_len, resample="BILINEAR"):
    from .nn import image_resize

    h, w = int(input.shape[2]), int(input.shape[3])
    short = min(h, w)
    scale = out_short_len / float(short)
    return image_resize(input, out_shape=[int(round(h * scale)),
                                          int(round(w * scale))],
                        resample=resample)


def gaussian_random_batch_size_like(input, shape, mean=0.0, std=1.0,
                                    seed=0, dtype="float32"):
    from .nn import gaussian_random

    shape = list(shape)
    shape[0] = int(input.shape[0])
    return gaussian_random(shape, mean=mean, std=std, seed=seed,
                           dtype=dtype)


def sequence_reverse(x, name=None):
    """Reverse each sequence (LoD) — needs_lod op composition via the
    reverse op on equal-length, else host path."""
    helper = LayerHelper("sequence_reverse", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.lod_level = getattr(x, "lod_level", 0)
    helper.append_op("sequence_reverse", inputs={"X": [x]},
                     outputs={"Y": [out]}, infer_shape=False)
    return out


def get_tensor_from_selected_rows(x, name=None):
    return _simple("get_tensor_from_selected_rows", x)


def merge_selected_rows(x, name=None):
    helper = LayerHelper("merge_selected_rows", input=x)
    out = helper.main_program.current_block().create_var(
        name=framework.unique_name.generate("merged_sr"),
        type="selected_rows", dtype=x.dtype)
    helper.append_op("merge_selected_rows", inputs={"X": [x]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def lod_reset(x, y=None, target_lod=None):
    """Re-stamp a tensor's LoD (reference lod_reset_op)."""
    helper = LayerHelper("lod_reset", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.lod_level = 1
    inputs = {"X": [x]}
    attrs = {}
    if y is not None:
        inputs["Y"] = [y]
    else:
        attrs["target_lod"] = [int(v) for v in (target_lod or [])]
    helper.append_op("lod_reset", inputs=inputs, outputs={"Out": [out]},
                     attrs=attrs, infer_shape=False)
    out.shape = tuple(x.shape)
    return out


def linear_chain_crf(input, label, param_attr=None, length=None):
    """CRF negative log-likelihood (reference layers/nn.py
    linear_chain_crf over linear_chain_crf_op). Dense [B, T, K] input;
    creates the [K+2, K] transition parameter. `length` is not yet
    honored — pad with the repeated last label (the NLL of the padded
    tail is then constant wrt the emissions)."""
    if length is not None:
        raise NotImplementedError(
            "linear_chain_crf(length=...) is not supported yet; pad "
            "labels with the repeated final label instead")
    helper = LayerHelper("linear_chain_crf", input=input,
                         param_attr=param_attr)
    dtype = helper.input_dtype()
    k = int(input.shape[-1])
    trans = helper.create_parameter(attr=helper.param_attr,
                                    shape=[k + 2, k], dtype=dtype)
    alpha = helper.create_variable_for_type_inference(dtype)
    em_exps = helper.create_variable_for_type_inference(dtype)
    tr_exps = helper.create_variable_for_type_inference(dtype)
    ll = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "linear_chain_crf",
        inputs={"Emission": [input], "Transition": [trans],
                "Label": [label]},
        outputs={"Alpha": [alpha], "EmissionExps": [em_exps],
                 "TransitionExps": [tr_exps], "LogLikelihood": [ll]},
        infer_shape=False)
    ll.shape = (int(input.shape[0]) if len(input.shape) == 3 else 1, 1)
    return ll


def crf_decoding(input, param_attr, label=None, length=None):
    """Viterbi decode using the transition learned by
    linear_chain_crf (reference layers/nn.py crf_decoding)."""
    helper = LayerHelper("crf_decoding", input=input)
    # reuse the transition parameter created by linear_chain_crf
    from ..param_attr import ParamAttr

    name = param_attr.name if isinstance(param_attr, ParamAttr) else None
    blk = helper.main_program.global_block()
    trans = None
    if name:
        trans = blk._find_var_recursive(name)
    if trans is None:
        k = int(input.shape[-1])
        matches = [p for p in blk.all_parameters
                   if p.shape and len(p.shape) == 2
                   and p.shape[0] == k + 2 and p.shape[1] == k]
        # most recently created wins (the CRF layer built just before);
        # pass a NAMED param_attr to disambiguate multiple CRFs
        trans = matches[-1] if matches else None
    if trans is None:
        raise ValueError("crf_decoding: no transition parameter found; "
                         "run linear_chain_crf first or name the param")
    out = helper.create_variable_for_type_inference("int64")
    inputs = {"Emission": [input], "Transition": [trans]}
    if label is not None:
        inputs["Label"] = [label]
    helper.append_op("crf_decoding", inputs=inputs,
                     outputs={"ViterbiPath": [out]}, infer_shape=False)
    out.shape = tuple(input.shape[:-1])
    return out


__all__ += ["linear_chain_crf", "crf_decoding"]


def sequence_slice(input, offset, length, name=None):
    """(reference sequence_ops sequence_slice layer over the host op)."""
    helper = LayerHelper("sequence_slice", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    out.lod_level = 1
    out.shape = (-1,) + tuple(input.shape[1:])
    helper.append_op("sequence_slice",
                     inputs={"X": [input], "Offset": [offset],
                             "Length": [length]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def sequence_unpad(x, length, name=None):
    helper = LayerHelper("sequence_unpad", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    out.lod_level = 1
    out.shape = (-1,) + tuple(x.shape[2:])
    helper.append_op("sequence_unpad",
                     inputs={"X": [x], "Length": [length]},
                     outputs={"Out": [out]}, infer_shape=False)
    return out


def im2sequence(input, filter_size=1, stride=1, padding=0,
                input_image_size=None, out_stride=1, name=None):
    if input_image_size is not None:
        raise NotImplementedError(
            "im2sequence with per-sample input_image_size is not "
            "supported yet; crop/pad to a uniform size upstream")

    def _pair(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v]

    helper = LayerHelper("im2sequence", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    pads = _pair(padding)
    helper.append_op(
        "im2sequence", inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"kernels": _pair(filter_size), "strides": _pair(stride),
               "paddings": pads * 2 if len(pads) == 2 else pads,
               "out_stride": _pair(out_stride)},
        infer_shape=False)
    return out


def grid_sampler(x, grid, name=None):
    helper = LayerHelper("grid_sampler", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("grid_sampler",
                     inputs={"X": [x], "Grid": [grid]},
                     outputs={"Output": [out]}, infer_shape=False)
    out.shape = (int(x.shape[0]), int(x.shape[1]),
                 int(grid.shape[1]), int(grid.shape[2]))
    return out


def soft_relu(x, threshold=40.0, name=None):
    """log(1 + exp(min(x, threshold))) (reference soft_relu)."""
    from .nn import elementwise_max, elementwise_min
    from .ops import exp, log, scale
    from .tensor import fill_constant

    capped = elementwise_min(
        x, fill_constant([1], x.dtype, float(threshold)))
    capped = elementwise_max(
        capped, fill_constant([1], x.dtype, -float(threshold)))
    return log(scale(exp(capped), scale=1.0, bias=1.0))


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_lod=True,
          print_phase="both"):
    """(reference layers/control_flow.py Print over the print host op)."""
    helper = LayerHelper("print", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "print", inputs={"In": [input]}, outputs={"Out": [out]},
        attrs={"first_n": first_n, "message": message or "",
               "summarize": summarize,
               "print_tensor_name": print_tensor_name,
               "print_tensor_type": print_tensor_type,
               "print_tensor_shape": print_tensor_shape,
               "print_tensor_lod": print_tensor_lod,
               "print_phase": print_phase.upper()},
        infer_shape=False)
    out.shape = tuple(input.shape or ())
    return out


def gather_tree(ids, parents):
    helper = LayerHelper("gather_tree", input=ids)
    out = helper.create_variable_for_type_inference(ids.dtype)
    helper.append_op("gather_tree",
                     inputs={"Ids": [ids], "Parents": [parents]},
                     outputs={"Out": [out]}, infer_shape=False)
    out.shape = tuple(ids.shape)
    return out


def random_crop(x, shape, seed=None):
    helper = LayerHelper("random_crop", input=x)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("random_crop", inputs={"X": [x]},
                     outputs={"Out": [out]},
                     attrs={"shape": [int(s) for s in shape],
                            "seed": int(seed or 0)},
                     infer_shape=False)
    out.shape = tuple(x.shape[:len(x.shape) - len(shape)]) + \
        tuple(int(s) for s in shape)
    return out


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    helper = LayerHelper("spectral_norm", input=weight)
    h = int(weight.shape[dim])
    w = 1
    for i, s in enumerate(weight.shape):
        if i != dim:
            w *= int(s)
    # random init (reference uses Normal(0,1)): a CONSTANT init would
    # zero out against weights orthogonal to the all-ones vector and
    # divide by sigma=0
    from ..initializer import NormalInitializer

    u = helper.main_program.global_block().create_var(
        name=framework.unique_name.generate("spectral_norm_u"),
        shape=(h,), dtype="float32", persistable=True)
    u.stop_gradient = True
    helper.set_variable_initializer(u, NormalInitializer(0.0, 1.0))
    v = helper.main_program.global_block().create_var(
        name=framework.unique_name.generate("spectral_norm_v"),
        shape=(w,), dtype="float32", persistable=True)
    v.stop_gradient = True
    helper.set_variable_initializer(v, NormalInitializer(0.0, 1.0))
    out = helper.create_variable_for_type_inference(weight.dtype)
    helper.append_op("spectral_norm",
                     inputs={"Weight": [weight], "U": [u], "V": [v]},
                     outputs={"Out": [out], "UOut": [u], "VOut": [v]},
                     attrs={"dim": dim, "power_iters": power_iters,
                            "eps": eps},
                     infer_shape=False)
    out.shape = tuple(weight.shape)
    return out


def data_norm(input, act=None, epsilon=1e-4, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=False):
    helper = LayerHelper("data_norm", input=input, act=act)
    d = int(input.shape[-1])
    # reference nn.py data_norm defaults (batch_size=1e4, batch_sum=0,
    # batch_square=1e4), overridable via a param_attr dict; the stats are
    # persistable and UPDATED BY THE GRAD OP each backward pass (see
    # ops/misc_ops.py _data_norm_grad_maker) — test-mode programs never
    # run backward, so stats stay frozen, matching the reference.
    size_default, sum_default, sq_default = 1e4, 0.0, 1e4
    if param_attr and isinstance(param_attr, dict):
        size_default = param_attr.get("batch_size", 1e4)
        sum_default = param_attr.get("batch_sum", 0.0)
        sq_default = param_attr.get("batch_square", 1e4)
    # trainable=True parameters like the reference (their presence on the
    # grad path is what triggers the stat-updating grad op; no optimizer
    # update ever applies to them because the grad op rebinds the vars
    # in-place instead of emitting @GRAD outputs)
    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    def stat_param(tag, value):
        return helper.create_parameter(
            attr=ParamAttr(
                name=framework.unique_name.generate("dn_%s" % tag),
                initializer=ConstantInitializer(float(value))),
            shape=[d], dtype="float32")

    size = stat_param("size", size_default)
    ssum = stat_param("sum", sum_default)
    sqsum = stat_param("sqsum", sq_default)
    out = helper.create_variable_for_type_inference(input.dtype)
    means = helper.create_variable_for_type_inference("float32")
    scales = helper.create_variable_for_type_inference("float32")
    helper.append_op("data_norm",
                     inputs={"X": [input], "BatchSize": [size],
                             "BatchSum": [ssum],
                             "BatchSquareSum": [sqsum]},
                     outputs={"Y": [out], "Means": [means],
                              "Scales": [scales]},
                     attrs={"epsilon": epsilon}, infer_shape=False)
    out.shape = tuple(input.shape)
    return helper.append_activation(out)


def center_loss(input, label, num_classes, alpha, param_attr=None,
                update_center=True):
    from .tensor import fill_constant
    from ..initializer import ConstantInitializer
    from ..param_attr import ParamAttr

    helper = LayerHelper("center_loss", input=input)
    d = int(input.shape[-1])
    # reference loss.py center_loss: centers via create_parameter with
    # the caller's param_attr, zero-filled by default
    centers = helper.create_parameter(
        attr=param_attr if param_attr is not None else ParamAttr(
            name=framework.unique_name.generate("centers")),
        shape=[num_classes, d], dtype="float32",
        default_initializer=ConstantInitializer(0.0), stop_gradient=True)
    rate = alpha if isinstance(alpha, framework.Variable) else \
        fill_constant([1], "float32", float(alpha))
    diff = helper.create_variable_for_type_inference(input.dtype)
    loss = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "center_loss",
        inputs={"X": [input], "Label": [label], "Centers": [centers],
                "CenterUpdateRate": [rate]},
        outputs={"CentersOut": [centers], "SampleCenterDiff": [diff],
                 "Loss": [loss]},
        attrs={"cluster_num": num_classes,
               "need_update": update_center},
        infer_shape=False)
    loss.shape = (int(input.shape[0]), 1)
    return loss


def tensor_array_to_tensor(input, axis=0, name=None, use_stack=False,
                           dtype="float32"):
    """NOTE: the array's element shapes are runtime information, so the
    returned Variable has no static shape — set `out.shape` manually
    before feeding it to shape-inferring layers."""
    helper = LayerHelper("tensor_array_to_tensor", input=None)
    out = helper.main_program.current_block().create_var(
        name=framework.unique_name.generate("ta2t"), dtype=dtype)
    idx = helper.main_program.current_block().create_var(
        name=framework.unique_name.generate("ta2t_idx"), dtype="int32")
    helper.append_op("tensor_array_to_tensor",
                     inputs={"X": [input]},
                     outputs={"Out": [out], "OutIndex": [idx]},
                     attrs={"axis": axis, "use_stack": use_stack},
                     infer_shape=False)
    return out, idx


def adaptive_pool3d(input, pool_size, pool_type="max",
                    require_index=False, name=None):
    if require_index:
        raise NotImplementedError(
            "adaptive_pool3d(require_index=True) (mask output) is not "
            "supported yet")

    def _triple(v):
        return list(v) if isinstance(v, (list, tuple)) else [v, v, v]

    helper = LayerHelper("adaptive_pool3d", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "pool3d", inputs={"X": [input]}, outputs={"Out": [out]},
        attrs={"pooling_type": pool_type, "ksize": _triple(pool_size),
               "adaptive": True})
    return out


__all__ += ["sequence_slice", "sequence_unpad", "im2sequence",
            "grid_sampler", "soft_relu", "Print", "gather_tree",
            "random_crop", "spectral_norm", "data_norm", "center_loss",
            "tensor_array_to_tensor", "adaptive_pool3d"]


def flash_attention(q, k, v, causal=False, scale=0.0, lengths=None,
                    num_heads=0, select=None, return_lse=False):
    """Fused attention over q [B, H, S, D] and k, v [B, H_kv, S, D], H_kv
    dividing H (the multihead hot path; fewer K/V heads are shared —
    reference fused/multihead_matmul_op.cu; v's head dim may differ from
    q's and k's, and is then the context's), or over token-major q, k, v
    [B, T, H*hd] with ``num_heads``, as the projections leave them: the
    layout is the operands' rank, and the context comes back in it (no
    head split or merge in the program). Lowers to the Pallas flash
    kernels on TPU (which ones is the op's choice, from the shapes);
    ``apply_sequence_parallel`` rewrites it to ring attention over an
    'sp' mesh axis for long-context training. ``lengths`` ([B] int)
    masks padded keys inside the kernel. ``select`` ([B, S, S] int8, as
    ``attn_index_select`` makes it) is a per-query key selection shared by
    the heads, applied inside the streaming kernels beside the causal mask
    (head-major operands). The op also binds ``LSE``, the residual its grad
    op reads, so the backward runs no second forward; ``return_lse`` hands
    it out too (with a selection: [B*H, S, 1] float32 wherever the op
    runs)."""
    helper = LayerHelper("flash_attention", input=q)
    out = helper.create_variable_for_type_inference(q.dtype)
    lse = helper.create_variable_for_type_inference("float32",
                                                    stop_gradient=True)
    ins = {"Q": [q], "K": [k], "V": [v]}
    if lengths is not None:
        ins["Lengths"] = [lengths]
    if select is not None:
        ins["Select"] = [select]
    # no shape inference: it would trace the kernels at build time (and
    # count a path that no step runs); Out has Q's shape with V's last axis
    helper.append_op(
        "flash_attention", inputs=ins,
        outputs={"Out": [out], "LSE": [lse]},
        attrs={"causal": bool(causal), "scale": float(scale),
               "num_heads": int(num_heads)},
        infer_shape=False)
    if not framework.in_dygraph_mode():
        out.shape = tuple(q.shape[:-1]) + (v.shape[-1],)
    return (out, lse) if return_lse else out


def switch_moe(input, num_experts, hidden_dim, capacity_factor=1.0,
               num_groups=1, param_attr=None, name=None):
    """Switch-routed mixture-of-experts FFN over [T, D] tokens: top-1
    gating with fixed per-expert capacity (overflow dropped, GShard /
    Switch-Transformer semantics). The reference snapshot has no MoE;
    this is the Program surface that ``apply_expert_parallel`` shards
    over an 'ep' mesh axis (experts device-local, two all_to_alls route
    token slots — parallel/moe.py)."""
    helper = LayerHelper("moe", input=input, param_attr=param_attr,
                         name=name)
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    gate_w = helper.create_parameter(
        attr=helper.param_attr, shape=[d, num_experts], dtype=dtype)
    w_in = helper.create_parameter(
        attr=helper.param_attr, shape=[num_experts, d, hidden_dim],
        dtype=dtype)
    w_out = helper.create_parameter(
        attr=helper.param_attr, shape=[num_experts, hidden_dim, d],
        dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "moe",
        inputs={"X": [input], "GateW": [gate_w], "WIn": [w_in],
                "WOut": [w_out]},
        outputs={"Out": [out]},
        attrs={"shard_axis": "", "num_groups": int(num_groups),
               "capacity_factor": float(capacity_factor)})
    return out


__all__ += ["flash_attention", "switch_moe"]


def rms_norm(input, scale=True, epsilon=1e-5, groups=1, gate=None,
             param_attr=None, name=None, gating="silu_before"):
    """Root-mean-square norm over the last axis: ``x * rsqrt(mean(x^2) +
    epsilon) * Scale`` (no mean, no bias). ``groups`` > 1 norms each of
    that many equal groups of the last axis; with ``gate`` the normed value
    is ``x * silu(gate)`` (``gating`` ``silu_before``: Mamba-2's gated
    norm), or the result is multiplied by ``sigmoid(gate)``
    (``sigmoid_after``: the delta-rule mixer's output gate). Float32
    statistics under AMP (black list)."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("rms_norm", input=input, param_attr=param_attr,
                         name=name)
    inputs = {"X": [input]}
    if scale:
        inputs["Scale"] = [helper.create_parameter(
            attr=helper.param_attr, shape=[int(input.shape[-1])],
            dtype=input.dtype,
            default_initializer=ConstantInitializer(1.0))]
    if gate is not None:
        inputs["Gate"] = [gate]
    out = helper.create_variable_for_type_inference(input.dtype)
    attrs = {"epsilon": float(epsilon), "groups": int(groups)}
    if gating != "silu_before":
        attrs["gating"] = gating
    helper.append_op("rms_norm", inputs=inputs, outputs={"Y": [out]},
                     attrs=attrs)
    return out


def causal_conv1d(input, kernel_size=4, bias=True, act=None,
                  param_attr=None, bias_attr=None, name=None):
    """Depthwise causal convolution along time over [B, T, C]: weight
    [C, kernel_size] (its last tap weighs the current position), bias [C];
    ``act`` None or "silu"."""
    helper = LayerHelper("causal_conv1d", input=input,
                         param_attr=param_attr, bias_attr=bias_attr,
                         name=name)
    c = int(input.shape[-1])
    inputs = {"X": [input], "W": [helper.create_parameter(
        attr=helper.param_attr, shape=[c, int(kernel_size)],
        dtype=input.dtype)]}
    if bias:
        inputs["Bias"] = [helper.create_parameter(
            attr=helper.bias_attr, shape=[c], dtype=input.dtype,
            is_bias=True)]
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op("causal_conv1d", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"activation": act or ""})
    return out


def short_conv_gate(input, kernel_size=3, param_attr=None, name=None):
    """The doubly gated short convolution over one projection's result
    [B, T, 3C], its three streams ``B | C | z`` read by offset: ``C *
    causal_conv1d(B * z)`` with taps [C, kernel_size] (the last weighs the
    current position), no bias, no activation; [B, T, C]
    (ops/ssm_ops.py). The gradient op keeps nothing but these inputs."""
    helper = LayerHelper("short_conv_gate", input=input,
                         param_attr=param_attr, name=name)
    c = int(input.shape[-1]) // 3
    if 3 * c != int(input.shape[-1]):
        raise ValueError("short_conv_gate: %d channels are no three streams"
                         % int(input.shape[-1]))
    w = helper.create_parameter(attr=helper.param_attr,
                                shape=[c, int(kernel_size)],
                                dtype=input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op("short_conv_gate", inputs={"X": [input], "W": [w]},
                     outputs={"Out": [out]}, infer_shape=False)
    if not framework.in_dygraph_mode():
        out.shape = tuple(input.shape[:-1]) + (c,)
    return out


def ssd_chunk_scan(x, dt, A, B, C, D=None, dt_bias=None, chunk=128):
    """Mamba-2's selective scan by chunks (ops/ssm_ops.py): x [B, T, H, P],
    raw step sizes dt [B, T, H], A / D / dt_bias [H] (A negative), B and C
    [B, T, G, N]; from a zero state
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``
    with ``dt_t = softplus(dt + dt_bias)``, taken inside the op in float32.
    The gradient op keeps nothing but these inputs and recomputes inside."""
    helper = LayerHelper("ssd_chunk_scan", input=x)
    ins = {"X": [x], "Dt": [dt], "A": [A], "B": [B], "C": [C]}
    if D is not None:
        ins["D"] = [D]
    if dt_bias is not None:
        ins["DtBias"] = [dt_bias]
    out = helper.create_variable_for_type_inference(x.dtype)
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op("ssd_chunk_scan", inputs=ins, outputs={"Out": [out]},
                     attrs={"chunk": int(chunk)}, infer_shape=False)
    if not framework.in_dygraph_mode():
        out.shape = tuple(x.shape)
    return out


def kda_chunk(q, k, v, g, beta, a_log, dt_bias, chunk=64):
    """Kimi Delta Attention's core by chunks (ops/kda_ops.py): q, k [B, T,
    H, K] and v [B, T, H, V] after their convolutions, the raw gate
    projections g [B, T, H, K] and beta [B, T, H], the decay's leaves a_log
    [H] and dt_bias [H K]; from a zero state ``S_t = (I - beta_t k_t k_t^T)
    Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` with q
    and k L2-normed over K (q then times ``K^-0.5``), ``g_t =
    -exp(a_log) softplus(g + dt_bias)`` and ``beta_t = sigmoid(beta)``, all
    taken inside the op in float32. The gradient op keeps nothing but these
    inputs and recomputes inside."""
    helper = LayerHelper("kda_chunk", input=v)
    out = helper.create_variable_for_type_inference(v.dtype)
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op(
        "kda_chunk",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
                "ALog": [a_log], "DtBias": [dt_bias]},
        outputs={"Out": [out]},
        attrs={"chunk": int(chunk)}, infer_shape=False)
    if not framework.in_dygraph_mode():
        out.shape = tuple(v.shape)
    return out


def moe_topk(input, num_experts, k, hidden_dim, held=None, scaling=1.0,
             norm_topk=True, correction_bias=None, return_load=False,
             param_attr=None, name=None, scoring="sigmoid",
             expert="relu2", route_eps=None):
    """Routed experts over [T, D] tokens, without drops: scores over all
    ``num_experts`` (``scoring``: ``sigmoid`` of each logit, or a
    ``softmax`` over all of them), the ``k`` largest of score + correction
    bias chosen (the bias enters the choice only), weights ``scaling *
    s / (sum of the chosen s + route_eps)`` (``norm_topk``; ``route_eps``
    None: the op's own 1e-20), expert ``relu(u W1)^2 W2``
    (``expert`` ``relu2``, not gated) or ``(silu(u W1) * (u W3)) W2``
    (``swiglu``: a third matrix ``W3``, created after the other two; the
    op reads the form from whether it is bound).
    ``held = [first, count]`` are the experts this program
    holds (all by default): their weights alone are created and their
    part of the sum alone is computed, which is what one rank of an
    expert-parallel deployment does (ops/moe_ops.py). ``correction_bias``
    is an array [num_experts] or None (zeros): a buffer, not a parameter.
    A program that holds part of the experts sees only their pull on the
    router, so there the router's weight takes a zero gradient and stays
    where it is. With ``return_load`` also returns ``Load`` [count + 1] int32: the
    slots each held expert received and whether the exact slower branch
    ran, for a fetch."""
    import numpy as np

    from ..initializer import NumpyArrayInitializer

    helper = LayerHelper("moe_topk", input=input, param_attr=param_attr,
                         name=name)
    dtype = helper.input_dtype()
    t, d = int(input.shape[0]), int(input.shape[-1])
    first, count = held if held is not None else (0, num_experts)
    router_w = helper.create_parameter(
        attr=helper.param_attr, shape=[d, num_experts], dtype=dtype)
    w1 = helper.create_parameter(
        attr=helper.param_attr, shape=[count, d, hidden_dim], dtype=dtype)
    w2 = helper.create_parameter(
        attr=helper.param_attr, shape=[count, hidden_dim, d], dtype=dtype)
    bias = helper.create_global_variable(
        name=framework.unique_name.generate("moe_correction_bias"),
        shape=[num_experts], dtype=dtype, persistable=True)
    bias.stop_gradient = True
    value = (np.zeros([num_experts]) if correction_bias is None
             else np.asarray(correction_bias))
    helper.set_variable_initializer(
        bias, NumpyArrayInitializer(value.astype(dtype)))
    out = helper.create_variable_for_type_inference(dtype)
    load = helper.create_variable_for_type_inference("int32",
                                                     stop_gradient=True)
    inputs = {"X": [input], "RouterW": [router_w], "Bias": [bias],
              "W1": [w1], "W2": [w2]}
    if expert not in ("relu2", "swiglu"):
        raise ValueError("moe_topk: no expert %r" % (expert,))
    if expert == "swiglu":
        inputs["W3"] = [helper.create_parameter(
            attr=helper.param_attr, shape=[count, d, hidden_dim],
            dtype=dtype)]
    attrs = {"k": int(k), "held": [int(first), int(count)],
             "scaling": float(scaling), "norm_topk": bool(norm_topk),
             "scoring": scoring}
    if route_eps is not None:
        attrs["route_eps"] = float(route_eps)
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op(
        "moe_topk", inputs=inputs,
        outputs={"Out": [out], "Load": [load]}, attrs=attrs,
        infer_shape=False)
    if not framework.in_dygraph_mode():
        out.shape, load.shape = (t, d), (count + 1,)
    return (out, load) if return_load else out


def yarn_inv_freq(dim, theta, factor, original_positions, beta_fast=32.0,
                  beta_slow=1.0):
    """YaRN's ``dim / 2`` frequencies for ``rotary_embedding(inv_freq=)``:
    pair ``i`` blends the scaled and the unscaled one, ``(1 - m_i)
    theta^(-2i/dim) / factor + m_i theta^(-2i/dim)`` with ``m_i = 1 -
    clip((i - lo) / (hi - lo), 0, 1)``; ``lo`` and ``hi`` are the pairs that
    make ``beta_fast`` and ``beta_slow`` turns over ``original_positions``
    (floor and ceiling, within the head)."""
    import math

    def pair_of(turns):
        return (dim * math.log(original_positions / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if hi == lo:
        hi += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    m = 1.0 - np.clip((i - lo) / (hi - lo), 0.0, 1.0)
    return [float(f) for f in (1.0 - m) * plain / factor + m * plain]


def yarn_mscale(factor, mscale=1.0):
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 without
    scaling): a model whose ``mscale`` equals its ``mscale_all_dim`` leaves
    cos and sin alone and multiplies the scores' scale by its square."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_embedding(input, positions=None, theta=10000.0, sections=None,
                     rotary_dims=0, inv_freq=None, offset=0):
    """Rotary positions on [B, T, H, hd], rotate-half form: frequency pair
    ``i`` of the ``rotary_dims / 2`` pairs (``rotary_dims`` 0: the head from
    dim ``offset`` on; the rotated slice starts there) turns by ``pos *
    theta^(-i / pairs)``, or by ``pos * inv_freq[i]`` where the frequencies
    are given (``yarn_inv_freq``). ``positions`` [3, B, T]
    int holds three components (temporal, height, width) and ``sections``
    says how many pairs each takes, in order (None: all from the first);
    text has the three equal; without ``positions`` every row is one
    document, ``0..T-1`` (ops/sparse_attn_ops.py)."""
    helper = LayerHelper("rotary_embedding", input=input)
    out = helper.create_variable_for_type_inference(input.dtype)
    ins = {"X": [input]}
    if positions is not None:
        ins["Pos"] = [positions]
    helper.append_op(
        "rotary_embedding", inputs=ins, outputs={"Out": [out]},
        attrs={"theta": float(theta), "sections": list(sections or []),
               "rotary_dims": int(rotary_dims), "offset": int(offset),
               "inv_freq": [float(f) for f in inv_freq or ()]})
    return out


def attn_index_project(input, positions, heads, dim, theta=10000.0,
                       sections=None, rotary_dims=0, epsilon=1e-6,
                       param_attr=None, name=None):
    """The lightning indexer's operands from the normed hidden state
    [B, T, D], which takes no gradient from here: queries QI
    [B, T, heads, dim], one key head KI [B, T, dim] (LayerNorm after its
    projection), rotary positions on the first ``rotary_dims`` dims of
    both, and the heads' weights W [B, T, heads] (scaled by ``heads^-0.5
    dim^-0.5``). Float32 at full precision, under AMP too. Parameters in
    order: the queries', the key's and the weights' matrices, the
    LayerNorm's scale and bias."""
    from ..initializer import ConstantInitializer

    helper = LayerHelper("attn_index_project", input=input,
                         param_attr=param_attr, name=name)
    dtype = helper.input_dtype()
    b, t, d = (int(n) for n in input.shape)

    def matrix(cols):
        return helper.create_parameter(attr=helper.param_attr,
                                       shape=[d, cols], dtype=dtype)

    wq, wk, ww = matrix(heads * dim), matrix(dim), matrix(heads)
    ln_scale = helper.create_parameter(
        attr=helper.param_attr, shape=[dim], dtype=dtype,
        default_initializer=ConstantInitializer(1.0))
    ln_bias = helper.create_parameter(
        attr=helper.param_attr, shape=[dim], dtype=dtype,
        default_initializer=ConstantInitializer(0.0))
    qi, ki, w = (helper.create_variable_for_type_inference(dtype)
                 for _ in range(3))
    helper.append_op(
        "attn_index_project",
        inputs={"X": [input], "WQ": [wq], "WK": [wk], "WW": [ww],
                "LnScale": [ln_scale], "LnBias": [ln_bias],
                "Pos": [positions]},
        outputs={"QI": [qi], "KI": [ki], "W": [w]},
        attrs={"heads": int(heads), "theta": float(theta),
               "sections": list(sections or []),
               "rotary_dims": int(rotary_dims), "epsilon": float(epsilon)},
        infer_shape=False)
    if not framework.in_dygraph_mode():
        qi.shape, ki.shape, w.shape = ((b, t, heads, dim), (b, t, dim),
                                       (b, t, heads))
    return qi, ki, w


def attn_index_select(qi, ki, w, topk):
    """Select [B, T, T] int8 from ``attn_index_project``'s results: 1 where
    query t attends key s, the ``min(t + 1, topk)`` causal keys with the
    largest index score ``sum_h w[t, h] relu(qi[t, h] . ki[s])``, ties to
    the lower s; exact, no gradient. ``flash_attention``'s ``select``."""
    helper = LayerHelper("attn_index_select", input=qi)
    select = helper.create_variable_for_type_inference("int8",
                                                       stop_gradient=True)
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op(
        "attn_index_select", inputs={"QI": [qi], "KI": [ki], "W": [w]},
        outputs={"Select": [select]}, attrs={"topk": int(topk)},
        infer_shape=False)
    if not framework.in_dygraph_mode():
        b, t = int(qi.shape[0]), int(qi.shape[1])
        select.shape = (b, t, t)
    return select


def attn_index_loss(qi, ki, w, select, q, k, lse, scale):
    """The indexer's loss [1]: the mean over queries of ``KL(pbar || pi)``
    over the selection, ``pi`` the softmax of the index scores there and
    ``pbar`` the attention's head-averaged probabilities, made again from
    its q [B, H, T, hd], k [B, Hkv, T, hd], ``scale`` and the ``lse`` its
    forward returned (``flash_attention(..., return_lse=True)``) and held
    constant. Only ``qi``, ``ki``, ``w`` take a gradient from it."""
    helper = LayerHelper("attn_index_loss", input=qi)
    loss = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "attn_index_loss",
        inputs={"QI": [qi], "KI": [ki], "W": [w], "Select": [select],
                "Q": [q], "K": [k], "LSE": [lse]},
        outputs={"Loss": [loss]}, attrs={"scale": float(scale)},
        infer_shape=False)
    if not framework.in_dygraph_mode():
        loss.shape = (1,)
    return loss


def mhc_pre(x, sinkhorn_iters=20, epsilon=1e-6, clamp=(-30.0, 30.0),
            name=None):
    """The read side of manifold-constrained hyper-connections around one
    sublayer, over the streams x [B, n, T, C], stream-major
    (ops/hyper_connection_ops.py):
    returns ``(h, h_post, h_res)``: the sublayer's input ``h`` [B, T, C] =
    ``sum_i H_pre[i] x[i]``, and the maps ``mhc_post`` writes the sublayer's
    output back with, ``H_post`` [B, n, T] and the Sinkhorn-normalised
    ``H_res`` [B, n, n, T]. Parameters in order: ``Phi`` [n C, 2 n + n^2]
    (N(0, 0.02)), ``alpha`` [3] (pre, post, res; 0.01), ``b_pre`` [n]
    (logit(1 / n): ``h`` starts as the streams' mean), ``b_post`` [n] (0:
    ``H_post`` starts at 1), ``b_res`` [n, n] (0 on the diagonal, -8 off it:
    ``H_res`` starts near the identity).
    Float32 under AMP (black list)."""
    import math

    from ..initializer import (ConstantInitializer, NormalInitializer,
                               NumpyArrayInitializer)

    helper = LayerHelper("mhc_pre", input=x, name=name)
    B, n, T, C = (int(d) for d in x.shape)

    def leaf(shape, init):
        return helper.create_parameter(attr=helper.param_attr, shape=shape,
                                       dtype="float32",
                                       default_initializer=init)

    b_res = np.full((n, n), -8.0, "float32")
    np.fill_diagonal(b_res, 0.0)
    ins = {"X": [x],
           "Phi": [leaf([n * C, 2 * n + n * n],
                        NormalInitializer(0.0, 0.02))],
           "Alpha": [leaf([3], ConstantInitializer(0.01))],
           "BPre": [leaf([n], ConstantInitializer(-math.log(n - 1.0)))],
           "BPost": [leaf([n], ConstantInitializer(0.0))],
           "BRes": [leaf([n, n], NumpyArrayInitializer(b_res))]}
    h, h_post, h_res = (helper.create_variable_for_type_inference("float32")
                        for _ in range(3))
    # no shape inference: it would trace (and count) the op at build time
    helper.append_op(
        "mhc_pre", inputs=ins,
        outputs={"H": [h], "HPost": [h_post], "HRes": [h_res]},
        attrs={"sinkhorn_iters": int(sinkhorn_iters),
               "epsilon": float(epsilon), "clamp_min": float(clamp[0]),
               "clamp_max": float(clamp[1])},
        infer_shape=False)
    if not framework.in_dygraph_mode():
        h.shape, h_post.shape, h_res.shape = (B, T, C), (B, n, T), (B, n, n, T)
    return h, h_post, h_res


def mhc_post(x, h_res, h_post, y):
    """The write side: the streams after a sublayer whose output is y
    [B, T, C], ``x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y``."""
    helper = LayerHelper("mhc_post", input=x)
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "mhc_post",
        inputs={"X": [x], "HRes": [h_res], "HPost": [h_post], "Y": [y]},
        outputs={"Out": [out]})
    return out


__all__ += ["rms_norm", "causal_conv1d", "short_conv_gate", "ssd_chunk_scan",
            "kda_chunk",
            "moe_topk",
            "rotary_embedding", "yarn_inv_freq", "yarn_mscale",
            "attn_index_project", "attn_index_select", "attn_index_loss",
            "mhc_pre", "mhc_post"]


def chunk_eval(input, label, chunk_scheme, num_chunk_types,
               excluded_chunk_types=None, seq_length=None):
    """Chunk-detection precision/recall/F1 (reference layers/nn.py:866
    -> chunk_eval_op; the NER evaluation layer)."""
    helper = LayerHelper("chunk_eval", input=input)

    def mk(dtype):
        return helper.create_variable_for_type_inference(
            dtype, stop_gradient=True)

    precision, recall, f1 = mk("float32"), mk("float32"), mk("float32")
    n_infer, n_label, n_correct = mk("int64"), mk("int64"), mk("int64")
    inputs = {"Inference": [input], "Label": [label]}
    if seq_length is not None:
        inputs["SeqLength"] = [seq_length]
    helper.append_op(
        "chunk_eval", inputs=inputs,
        outputs={"Precision": [precision], "Recall": [recall],
                 "F1-Score": [f1], "NumInferChunks": [n_infer],
                 "NumLabelChunks": [n_label],
                 "NumCorrectChunks": [n_correct]},
        attrs={"num_chunk_types": int(num_chunk_types),
               "chunk_scheme": chunk_scheme,
               "excluded_chunk_types": list(excluded_chunk_types or [])},
        infer_shape=False)
    for v in (precision, recall, f1):
        v.shape, v.dtype = (1,), "float32"
    for v in (n_infer, n_label, n_correct):
        v.shape, v.dtype = (1,), "int64"
    return precision, recall, f1, n_infer, n_label, n_correct


__all__ += ["chunk_eval"]
