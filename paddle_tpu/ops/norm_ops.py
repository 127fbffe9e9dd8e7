"""Normalization + dropout ops.

Parity: /root/reference/paddle/fluid/operators/{batch_norm_op.cc,
layer_norm_op.cc, instance_norm_op.cc, group_norm_op.cc, dropout_op.cc,
lrn_op.cc}. batch_norm keeps the reference's five-output contract
(Y, MeanOut/VarianceOut in-place running stats, SavedMean/SavedVariance);
running-stat updates are data outputs rather than buffer mutation — the
executor rebinds them, which is the functional XLA-native way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import RNG_SEED_ATTR, In, Out, register_op


@register_op(
    "batch_norm",
    inputs=[
        In("X"),
        In("Scale"),
        In("Bias"),
        In("Mean", no_grad=True),
        In("Variance", no_grad=True),
        In("MomentumTensor", dispensable=True, no_grad=True),
    ],
    outputs=[
        Out("Y"),
        Out("MeanOut", is_ref=True, no_grad=True),
        Out("VarianceOut", is_ref=True, no_grad=True),
        Out("SavedMean", no_grad=True),
        Out("SavedVariance", no_grad=True),
        # cuDNN-only scratch in the reference (dispensable there too);
        # the kernel returns None for it and inference-pruned programs
        # never bind it — surfaced by the ISSUE-12 verifier
        Out("ReserveSpace", dispensable=True, no_grad=True),
    ],
    attrs={
        "momentum": 0.9,
        "epsilon": 1e-5,
        "is_test": False,
        "data_layout": "NCHW",
        "use_global_stats": False,
        "trainable_statistics": False,
        "fuse_with_relu": False,
        "use_mkldnn": False,
    },
)
def _batch_norm(ins, attrs):
    x = ins["X"]
    scale, bias = ins["Scale"], ins["Bias"]
    mean, var = ins["Mean"], ins["Variance"]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    use_global = attrs.get("is_test", False) or attrs.get("use_global_stats", False)

    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if use_global:
        use_mean, use_var = mean, var
        saved_mean = mean
        saved_inv_std = jax.lax.rsqrt(var + eps)
        mean_out, var_out = mean, var
    else:
        # sync-BN (reference sync_batch_norm_op.cu / sync_batch_norm_pass):
        # when marked and running inside a mapped mesh axis, batch
        # statistics average across the axis before normalization
        axis_name = None
        if attrs.get("_sync_stats"):
            from .collective_ops import axis_for_ring

            axis_name = axis_for_ring(attrs.get("_sync_ring_id", 0))
        if axis_name is not None:
            local_mean = jnp.mean(x, axis=red_axes)
            local_sq = jnp.mean(jnp.square(x), axis=red_axes)
            use_mean = jax.lax.pmean(local_mean, axis_name)
            use_var = jax.lax.pmean(local_sq, axis_name) -                 jnp.square(use_mean)
        else:
            use_mean = jnp.mean(x, axis=red_axes)
            use_var = jnp.mean(jnp.square(x - use_mean.reshape(bshape)),
                               axis=red_axes)
        saved_mean = use_mean
        saved_inv_std = jax.lax.rsqrt(use_var + eps)
        mean_out = mean * momentum + use_mean * (1 - momentum)
        var_out = var * momentum + use_var * (1 - momentum)

    inv_std = jax.lax.rsqrt(use_var + eps)
    y = (x - use_mean.reshape(bshape)) * (scale * inv_std).reshape(bshape) + bias.reshape(bshape)
    return {
        "Y": y,
        "MeanOut": mean_out,
        "VarianceOut": var_out,
        "SavedMean": saved_mean,
        "SavedVariance": saved_inv_std,  # reference saves inverse std
        "ReserveSpace": None,
    }


@register_op(
    "layer_norm",
    inputs=[In("X"), In("Scale", dispensable=True), In("Bias", dispensable=True)],
    outputs=[Out("Y"), Out("Mean", no_grad=True), Out("Variance", no_grad=True)],
    attrs={"epsilon": 1e-5, "begin_norm_axis": 1},
)
def _layer_norm(ins, attrs):
    x = ins["X"]
    eps = attrs.get("epsilon", 1e-5)
    axis = attrs.get("begin_norm_axis", 1)
    red = tuple(range(axis, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    if ins.get("Scale") is not None:
        y = y * ins["Scale"].reshape((1,) * axis + x.shape[axis:])
    if ins.get("Bias") is not None:
        y = y + ins["Bias"].reshape((1,) * axis + x.shape[axis:])
    lead = 1
    for d in x.shape[:axis]:
        lead *= d
    return {
        "Y": y,
        "Mean": mean.reshape(lead),
        "Variance": var.reshape(lead),
    }


@register_op(
    "instance_norm",
    inputs=[In("X"), In("Scale", dispensable=True), In("Bias", dispensable=True)],
    outputs=[Out("Y"), Out("SavedMean", no_grad=True),
             Out("SavedVariance", no_grad=True)],
    attrs={"epsilon": 1e-5},
)
def _instance_norm(ins, attrs):
    x = ins["X"]
    eps = attrs.get("epsilon", 1e-5)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=red, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    bshape = (1, -1) + (1,) * (x.ndim - 2)
    if ins.get("Scale") is not None:
        y = y * ins["Scale"].reshape(bshape)
    if ins.get("Bias") is not None:
        y = y + ins["Bias"].reshape(bshape)
    n, c = x.shape[0], x.shape[1]
    return {
        "Y": y,
        "SavedMean": mean.reshape(n * c),
        "SavedVariance": jax.lax.rsqrt(var + eps).reshape(n * c),
    }


@register_op(
    "group_norm",
    inputs=[In("X"), In("Scale", dispensable=True), In("Bias", dispensable=True)],
    outputs=[Out("Y"), Out("Mean", no_grad=True), Out("Variance", no_grad=True)],
    attrs={"epsilon": 1e-5, "groups": 1, "data_layout": "NCHW"},
)
def _group_norm(ins, attrs):
    x = ins["X"]
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    red = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=red, keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=red, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale") is not None:
        y = y * ins["Scale"].reshape(bshape)
    if ins.get("Bias") is not None:
        y = y + ins["Bias"].reshape(bshape)
    return {"Y": y, "Mean": mean.reshape(n, g), "Variance": var.reshape(n, g)}


@register_op(
    "dropout",
    inputs=[In("X"), In("Seed", dispensable=True, no_grad=True)],
    outputs=[Out("Out"), Out("Mask", no_grad=True)],
    attrs={
        "dropout_prob": 0.5,
        "is_test": False,
        "fix_seed": False,
        "seed": 0,
        "dropout_implementation": "downgrade_in_infer",
    },
    needs_rng=True,
)
def _dropout(ins, attrs):
    x = ins["X"]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        out = x if impl == "upscale_in_train" else x * (1.0 - p)
        return {"Out": out, "Mask": None}
    key = jax.random.PRNGKey(ins[RNG_SEED_ATTR])
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(p >= 1.0, jnp.zeros_like(x), x * mask / (1.0 - p))
    else:
        out = x * mask
    return {"Out": out, "Mask": mask}


@register_op(
    "lrn",
    inputs=[In("X")],
    outputs=[Out("Out"), Out("MidOut", no_grad=True)],
    attrs={"n": 5, "alpha": 1e-4, "beta": 0.75, "k": 1.0, "data_format": "NCHW"},
)
def _lrn(ins, attrs):
    x = ins["X"]
    n = attrs.get("n", 5)
    alpha, beta, k = attrs.get("alpha", 1e-4), attrs.get("beta", 0.75), attrs.get("k", 1.0)
    half = n // 2
    sq = jnp.square(x)
    padded = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    mid = k + alpha * sum(
        padded[:, i : i + x.shape[1]] for i in range(n)
    )
    return {"Out": x / jnp.power(mid, beta), "MidOut": mid}


@register_op(
    "l2_normalize",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"axis": -1, "epsilon": 1e-10},
)
def _l2_normalize(ins, attrs):
    x = ins["X"]
    sq = jnp.sum(jnp.square(x), axis=attrs.get("axis", -1), keepdims=True)
    return {"Out": x * jax.lax.rsqrt(jnp.maximum(sq, attrs.get("epsilon", 1e-10)))}


@register_op(
    "rms_norm",
    inputs=[In("X"), In("Scale", dispensable=True),
            In("Gate", dispensable=True)],
    outputs=[Out("Y")],
    attrs={"epsilon": 1e-5, "groups": 1, "gating": "silu_before"},
)
def _rms_norm(ins, attrs):
    """Root-mean-square norm over the last axis (no mean, no bias):
    ``x * rsqrt(mean(x^2) + eps) * Scale``. With ``Gate`` and ``gating``
    ``silu_before`` the normed value is ``x * silu(Gate)`` (Mamba-2's gated
    norm); with ``sigmoid_after`` the result is multiplied by
    ``sigmoid(Gate)`` (the delta-rule mixer's output gate); with ``groups``
    > 1 the mean runs over each of that many equal groups of the last axis.
    The statistics are float32 whatever the input's type (AMP black list)."""
    x = ins["X"]
    dtype = x.dtype
    x = x.astype(jnp.float32)
    gate = ins.get("Gate")
    gating = attrs.get("gating", "silu_before")
    if gate is not None and gating not in ("silu_before", "sigmoid_after"):
        raise ValueError("rms_norm: no gating %r" % (gating,))
    if gate is not None and gating == "silu_before":
        x = x * jax.nn.silu(gate.astype(jnp.float32))
    groups = int(attrs.get("groups", 1) or 1)
    shape = x.shape
    g = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                          + attrs.get("epsilon", 1e-5))
    y = g.reshape(shape)
    if ins.get("Scale") is not None:
        y = y * ins["Scale"].astype(jnp.float32)
    if gate is not None and gating == "sigmoid_after":
        y = y * jax.nn.sigmoid(gate.astype(jnp.float32))
    return {"Y": y.astype(dtype)}
