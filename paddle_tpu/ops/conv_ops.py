"""Convolution / pooling / interpolation ops.

Parity: /root/reference/paddle/fluid/operators/{conv_op.cc, conv_cudnn_op.cu,
conv_transpose_op.cc, pool_op.cc, interpolate_op.cc}. All lower to
lax.conv_general_dilated / lax.reduce_window — XLA maps these straight to
the MXU (convs) and VPU (pooling), replacing the reference's
cuDNN-algorithm-search machinery (no algo cache needed: XLA picks layouts).
NCHW is kept as the logical layout; XLA relayouts internally for TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.registry import In, Out, register_op


def _norm_pads(paddings, n=2):
    # [p] -> [(p,p)...]; [ph, pw] -> [(ph,ph),(pw,pw)]; [t,b,l,r] -> pairs
    p = list(paddings)
    if len(p) == n:
        return [(x, x) for x in p]
    if len(p) == 2 * n:
        return [(p[2 * i], p[2 * i + 1]) for i in range(n)]
    if len(p) == 1:
        return [(p[0], p[0])] * n
    raise ValueError("bad paddings %r" % (paddings,))


def _conv_nd(x, w, strides, paddings, dilations, groups, data_format="NCHW",
             padding_algorithm="EXPLICIT"):
    n = x.ndim - 2
    if padding_algorithm == "SAME":
        pads = "SAME"
    elif padding_algorithm == "VALID":
        pads = "VALID"
    else:
        pads = _norm_pads(paddings, n)
    # NHWC lowers NATIVELY via dimension numbers (channels-last is the
    # TPU conv engine's preferred layout — no transposes around the op;
    # the filter stays OIHW, the framework's storage layout)
    if data_format in ("NHWC", "NDHWC"):
        spec = (data_format, "OIHW" if n == 2 else "OIDHW", data_format)
    else:
        spec = (("NCHW", "OIHW", "NCHW") if n == 2
                else ("NCDHW", "OIDHW", "NCDHW"))
    dn = lax.conv_dimension_numbers(x.shape, w.shape, spec)
    return lax.conv_general_dilated(
        x,
        w,
        window_strides=tuple(strides),
        padding=pads,
        rhs_dilation=tuple(dilations),
        dimension_numbers=dn,
        feature_group_count=groups,
    )


_CONV_ATTRS = {
    "strides": [1, 1],
    "paddings": [0, 0],
    "dilations": [1, 1],
    "groups": 1,
    "use_cudnn": True,
    "use_mkldnn": False,
    "data_format": "NCHW",
    "padding_algorithm": "EXPLICIT",
    "exhaustive_search": False,
    "fuse_relu_before_depthwise_conv": False,
    "workspace_size_MB": 512,
}


@register_op(
    "conv2d",
    inputs=[In("Input"), In("Filter"), In("Bias", dispensable=True),
            In("ResidualData", dispensable=True)],
    outputs=[Out("Output")],
    attrs=dict(_CONV_ATTRS),
)
def _conv2d(ins, attrs):
    data_format = attrs.get("data_format", "NCHW")
    if data_format == "AnyLayout":
        data_format = "NCHW"
    out = _conv_nd(
        ins["Input"],
        ins["Filter"],
        attrs.get("strides", [1, 1]),
        attrs.get("paddings", [0, 0]),
        attrs.get("dilations", [1, 1]),
        attrs.get("groups", 1),
        data_format,
        attrs.get("padding_algorithm", "EXPLICIT"),
    )
    if ins.get("Bias") is not None:
        bshape = ((1, -1, 1, 1) if data_format != "NHWC"
                  else (1, 1, 1, -1))
        out = out + ins["Bias"].reshape(bshape)
    return {"Output": out}


@register_op(
    "depthwise_conv2d",
    inputs=[In("Input"), In("Filter")],
    outputs=[Out("Output")],
    attrs=dict(_CONV_ATTRS),
)
def _depthwise_conv2d(ins, attrs):
    x, w = ins["Input"], ins["Filter"]
    # one group per input channel — channel axis depends on layout
    groups = (x.shape[-1]
              if attrs.get("data_format", "NCHW") == "NHWC"
              else x.shape[1])
    out = _conv_nd(
        x, w,
        attrs.get("strides", [1, 1]),
        attrs.get("paddings", [0, 0]),
        attrs.get("dilations", [1, 1]),
        groups,
        attrs.get("data_format", "NCHW"),
        attrs.get("padding_algorithm", "EXPLICIT"),
    )
    return {"Output": out}


@register_op(
    "conv3d",
    inputs=[In("Input"), In("Filter")],
    outputs=[Out("Output")],
    attrs={**_CONV_ATTRS, "strides": [1, 1, 1], "paddings": [0, 0, 0],
           "dilations": [1, 1, 1]},
)
def _conv3d(ins, attrs):
    data_format = attrs.get("data_format", "NCHW")
    if data_format in ("NCHW", "AnyLayout"):  # 2d-named default attr
        data_format = "NCDHW"
    out = _conv_nd(
        ins["Input"], ins["Filter"],
        attrs.get("strides", [1, 1, 1]),
        attrs.get("paddings", [0, 0, 0]),
        attrs.get("dilations", [1, 1, 1]),
        attrs.get("groups", 1),
        data_format,
        attrs.get("padding_algorithm", "EXPLICIT"),
    )
    return {"Output": out}


@register_op(
    "conv2d_transpose",
    inputs=[In("Input"), In("Filter")],
    outputs=[Out("Output")],
    attrs={**_CONV_ATTRS, "output_size": [], "output_padding": []},
)
def _conv2d_transpose(ins, attrs):
    x, w = ins["Input"], ins["Filter"]  # w: [in_c, out_c/groups, kh, kw]
    strides = tuple(attrs.get("strides", [1, 1]))
    pads = _norm_pads(attrs.get("paddings", [0, 0]), 2)
    dilations = tuple(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    # Gradient-of-conv formulation: transposed conv = lhs-dilated conv with
    # flipped kernel, mirroring conv2d_transpose_op.cc's GEMM+col2im.
    kh = (w.shape[2] - 1) * dilations[0] + 1
    kw = (w.shape[3] - 1) * dilations[1] + 1
    pad_t = kh - 1 - pads[0][0]
    pad_b = kh - 1 - pads[0][1]
    pad_l = kw - 1 - pads[1][0]
    pad_r = kw - 1 - pads[1][1]
    w_flip = jnp.flip(w, axis=(2, 3))
    if groups > 1:
        in_c = w.shape[0]
        w_flip = w_flip.reshape(groups, in_c // groups, *w.shape[1:])
        w_flip = jnp.concatenate(
            [jnp.swapaxes(w_flip[g], 0, 1) for g in range(groups)], axis=0
        )  # [out_c, in_c/groups, kh, kw]
    else:
        w_flip = jnp.swapaxes(w_flip, 0, 1)
    dn = lax.conv_dimension_numbers(x.shape, w_flip.shape, ("NCHW", "OIHW", "NCHW"))
    out = lax.conv_general_dilated(
        x,
        w_flip,
        window_strides=(1, 1),
        padding=[(pad_t, pad_b), (pad_l, pad_r)],
        lhs_dilation=strides,
        rhs_dilation=dilations,
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    return {"Output": out}


_POOL_ATTRS = {
    "pooling_type": "max",
    "ksize": [1, 1],
    "strides": [1, 1],
    "paddings": [0, 0],
    "global_pooling": False,
    "exclusive": True,
    "adaptive": False,
    "ceil_mode": False,
    "use_cudnn": True,
    "use_mkldnn": False,
    "data_format": "NCHW",
    "padding_algorithm": "EXPLICIT",
}


def _ceil_extra_pads(spatial, ksize, strides, pads, ceil_mode):
    """Per-dim (lo, hi) pads; ceil_mode adds extra hi pad so the output
    size follows ceil((H + pl + ph - k)/s) + 1 (reference pooling.cc)."""
    out = []
    for size, k, s, (lo, hi) in zip(spatial, ksize, strides, pads):
        if ceil_mode:
            n_out = -(-(size + lo + hi - k) // s) + 1  # ceil div
            extra = (n_out - 1) * s + k - (size + lo + hi)
            hi += max(0, extra)
        out.append((lo, hi))
    return out


def _pool_impl(x, attrs, ndim):
    """Rank-generic max/avg pooling over the ``ndim`` spatial dims of an
    NC... (or, with data_format=NHWC/NDHWC, N...C) tensor. Covers
    ceil_mode (extra hi padding), exclusive avg (valid-element count via
    a ones reduce_window), and adaptive pooling."""
    ptype = attrs.get("pooling_type", "max")
    nhwc = attrs.get("data_format", "NCHW") in ("NHWC", "NDHWC")
    sp0 = 1 if nhwc else 2  # first spatial axis
    spatial_axes = tuple(range(sp0, sp0 + ndim))
    if attrs.get("global_pooling", False) or (
        attrs.get("adaptive", False) and list(attrs.get("ksize")) == [1] * ndim
    ):
        f = jnp.max if ptype == "max" else jnp.mean
        return f(x, axis=spatial_axes, keepdims=True)
    if attrs.get("adaptive", False):
        osize = attrs["ksize"]
        # adaptive pooling via even split (requires divisibility, the
        # common CNN case; reference supports ragged windows)
        new_shape = list(x.shape[:sp0])
        red_axes = []
        for i, o in enumerate(osize):
            new_shape += [o, x.shape[sp0 + i] // o]
            red_axes.append(sp0 + 2 * i + 1)
        new_shape += list(x.shape[sp0 + ndim:])
        f = jnp.max if ptype == "max" else jnp.mean
        return f(x.reshape(new_shape), axis=tuple(red_axes))
    ksize = tuple(attrs["ksize"])
    strides = tuple(attrs.get("strides", [1] * ndim))
    pads = _norm_pads(attrs.get("paddings", [0] * ndim), ndim)
    pads = _ceil_extra_pads(x.shape[sp0:sp0 + ndim], ksize, strides, pads,
                            attrs.get("ceil_mode", False))
    if nhwc:
        pad_cfg = [(0, 0)] + list(pads) + [(0, 0)]
        dims = (1,) + ksize + (1,)
        strd = (1,) + strides + (1,)
    else:
        pad_cfg = [(0, 0), (0, 0)] + list(pads)
        dims = (1, 1) + ksize
        strd = (1, 1) + strides
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, dims, strd, pad_cfg)
    s = lax.reduce_window(x, 0.0, lax.add, dims, strd, pad_cfg)
    if attrs.get("exclusive", True):
        shp = x.shape[sp0:sp0 + ndim]
        ones = jnp.ones(shp, dtype=x.dtype)
        ones = ones[(None,) + (slice(None),) * ndim + (None,)] if nhwc \
            else ones[(None, None)]
        cnt = lax.reduce_window(ones, 0.0, lax.add, dims, strd, pad_cfg)
        return s / cnt
    return s / float(np.prod(ksize))


def _pool2d_impl(x, attrs):
    return _pool_impl(x, attrs, 2)


@register_op(
    "pool2d",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs=dict(_POOL_ATTRS),
)
def _pool2d(ins, attrs):
    return {"Out": _pool2d_impl(ins["X"], attrs)}


@register_op(
    "pool3d",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={**_POOL_ATTRS, "ksize": [1, 1, 1], "strides": [1, 1, 1],
           "paddings": [0, 0, 0]},
)
def _pool3d(ins, attrs):
    return {"Out": _pool_impl(ins["X"], attrs, 3)}


@register_op(
    "interpolate",
    inputs=[In("X"), In("OutSize", dispensable=True, no_grad=True),
            In("Scale", dispensable=True, no_grad=True)],
    outputs=[Out("Out")],
    attrs={"out_h": -1, "out_w": -1, "scale": 0.0, "interp_method": "bilinear",
           "align_corners": True, "align_mode": 1, "data_layout": "NCHW"},
)
def _interpolate(ins, attrs):
    x = ins["X"]
    n, c, h, w = x.shape
    oh, ow = attrs.get("out_h", -1), attrs.get("out_w", -1)
    scale = attrs.get("scale", 0.0)
    if scale and scale > 0:
        oh, ow = int(h * scale), int(w * scale)
    method = attrs.get("interp_method", "bilinear")
    align = attrs.get("align_corners", True)
    if method == "nearest":
        # align_corners: ratio=(in-1)/(out-1), index=round(i*ratio)
        # (reference interpolate_op.h NearestNeighborInterpolate)
        if align and oh > 1:
            ridx = jnp.round(jnp.arange(oh) * ((h - 1) / (oh - 1))).astype(jnp.int32)
        else:
            ridx = jnp.floor(jnp.arange(oh) * (h / oh)).astype(jnp.int32)
        if align and ow > 1:
            cidx = jnp.round(jnp.arange(ow) * ((w - 1) / (ow - 1))).astype(jnp.int32)
        else:
            cidx = jnp.floor(jnp.arange(ow) * (w / ow)).astype(jnp.int32)
        out = x[:, :, ridx][:, :, :, cidx]
        return {"Out": out}
    # bilinear
    if align and oh > 1:
        rs = jnp.linspace(0.0, h - 1, oh)
    else:
        align_mode = attrs.get("align_mode", 1)
        if align_mode == 0:
            rs = jnp.clip((jnp.arange(oh) + 0.5) * (h / oh) - 0.5, 0, h - 1)
        else:
            rs = jnp.clip(jnp.arange(oh) * (h / oh), 0, h - 1)
    if align and ow > 1:
        cs = jnp.linspace(0.0, w - 1, ow)
    else:
        align_mode = attrs.get("align_mode", 1)
        if align_mode == 0:
            cs = jnp.clip((jnp.arange(ow) + 0.5) * (w / ow) - 0.5, 0, w - 1)
        else:
            cs = jnp.clip(jnp.arange(ow) * (w / ow), 0, w - 1)
    r0 = jnp.floor(rs).astype(jnp.int32)
    c0 = jnp.floor(cs).astype(jnp.int32)
    r1 = jnp.minimum(r0 + 1, h - 1)
    c1 = jnp.minimum(c0 + 1, w - 1)
    ar = (rs - r0)[None, None, :, None].astype(x.dtype)
    ac = (cs - c0)[None, None, None, :].astype(x.dtype)
    g = lambda ri, ci: x[:, :, ri][:, :, :, ci]
    out = (
        g(r0, c0) * (1 - ar) * (1 - ac)
        + g(r1, c0) * ar * (1 - ac)
        + g(r0, c1) * (1 - ar) * ac
        + g(r1, c1) * ar * ac
    )
    return {"Out": out}


@register_op(
    "grid_sampler",
    inputs=[In("X"), In("Grid")],
    outputs=[Out("Output")],
    attrs={"align_corners": True, "mode": "bilinear", "padding_mode": "zeros"},
)
def _grid_sampler(ins, attrs):
    x, grid = ins["X"], ins["Grid"]  # x: NCHW, grid: NHW2 in [-1,1]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1

    def sample(yi, xi):
        yi_c = jnp.clip(yi, 0, h - 1)
        xi_c = jnp.clip(xi, 0, w - 1)
        batch = jnp.arange(n)[:, None, None]
        v = x[batch, :, yi_c, xi_c]  # N,H,W,C
        mask = ((yi >= 0) & (yi < h) & (xi >= 0) & (xi < w))[..., None]
        return v * mask.astype(v.dtype)

    wx = (gx - x0)[..., None]
    wy = (gy - y0)[..., None]
    out = (
        sample(y0, x0) * (1 - wy) * (1 - wx)
        + sample(y0, x1) * (1 - wy) * wx
        + sample(y1, x0) * wy * (1 - wx)
        + sample(y1, x1) * wy * wx
    )
    return {"Output": jnp.transpose(out, (0, 3, 1, 2))}
