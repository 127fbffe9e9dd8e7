"""Block-tuned implicit-GEMM conv2d as a Pallas TPU kernel.

Parity intent: the reference's conv hot path is cuDNN algorithm search
(operators/conv_cudnn_op.cu) plus hand-fused conv+bias+relu
(operators/fused/conv_fusion_op.cu). This is the TPU-native analog:
one kernel computes conv(+folded scale/shift)(+residual)(+relu) for
the NHWC ResNet hot shapes, expressed as KH*KW accumulated MXU
matmuls over [block_h * W_out, Cin] x [Cin, block_n] tiles — the
im2col never materializes in HBM, and the elementwise epilogue runs
in VMEM on the accumulator, saving one full activation round-trip.

Blocking lesson from the flash-attention kernels (BASELINE.md r4):
block size is the whole game. block_h is chosen so the GEMM M-dim
(block_h * W_out) lands in the 448-1024 row range and block_n caps at
256 lanes; K = Cin per tap (128-aligned for every ResNet stage except
the 3-channel stem, which stays on XLA).

Grid = (B, H_out/block_h, Cout/block_n), all parallel: the full
KH*KW*Cin reduction happens inside one grid instance, so the fp32
accumulator lives in registers/VMEM with no cross-step carry.

Scope: stride 1 and 2, square kernels (1x1/3x3 are the ResNet mix),
groups=1, NHWC. Everything else routes to lax.conv_general_dilated.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.place import compute_platform


def _kernel(x_ref, w_ref, scale_ref, shift_ref, *rest,
            block_h, w_out, kh, kw, stride, relu, has_residual):
    from jax.experimental import pallas as pl

    if has_residual:
        res_ref, o_ref = rest
    else:
        (o_ref,) = rest
    i = pl.program_id(1)
    h0 = i * block_h * stride
    cin = x_ref.shape[3]
    bn = o_ref.shape[3]
    rows = block_h * w_out
    acc = jnp.zeros((rows, bn), jnp.float32)
    # input rows needed for output rows [i*bh, i*bh+bh) at tap r:
    # h*stride + r  ->  contiguous span of (bh-1)*stride + 1 rows
    span = (block_h - 1) * stride + 1
    for r in range(kh):
        xs_full = x_ref[0, pl.ds(h0 + r, span), :, :]
        for c in range(kw):
            if stride == 1:
                xs = jax.lax.slice(
                    xs_full, (0, c, 0),
                    (block_h, c + w_out, cin))    # [bh, w_out, cin]
            else:
                # Mosaic only supports unit strides in extract_
                # strided_slice: decimate via reshape instead. Rows:
                # pad span (2bh-1) to 2bh, fold the stride into a new
                # axis, keep phase 0. Cols: same on the width axis.
                wspan = c + (w_out - 1) * stride + 1
                xs = jax.lax.slice(
                    xs_full, (0, c, 0), (span, wspan, cin))
                xs = jnp.pad(xs, ((0, 2 * block_h - span),
                                  (0, 2 * w_out - (wspan - c)), (0, 0)))
                xs = xs.reshape(block_h, 2, 2 * w_out, cin)[:, 0]
                xs = xs.reshape(block_h, w_out, 2, cin)[:, :, 0]
            acc += jax.lax.dot_general(
                xs.reshape(rows, cin), w_ref[r, c],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    y = acc * scale_ref[:] + shift_ref[:]
    if has_residual:
        y = y + res_ref[0].reshape(rows, bn).astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.reshape(block_h, w_out, bn).astype(o_ref.dtype)


def _pick_block_h(h_out, w_out):
    """Largest divisor of h_out keeping the GEMM M-dim <= ~1024 rows."""
    best = 1
    for bh in range(1, h_out + 1):
        if h_out % bh == 0 and bh * w_out <= 1024:
            best = bh
    return best


def _pick_block_n(cout):
    for bn in (256, 128, cout):
        if cout % bn == 0:
            return bn
    return cout


def conv2d_bn_act(x, w, scale=None, shift=None, *, stride=1, padding=0,
                  relu=False, residual=None, block_h=None, block_n=None,
                  interpret=None):
    """Fused conv(+scale/shift)(+residual)(+relu), NHWC.

    x: [B, H, W, Cin]; w: [KH, KW, Cin, Cout]; scale/shift: [Cout]
    (pass None for a pure conv); residual: [B, H_out, W_out, Cout].
    Returns [B, H_out, W_out, Cout] in x.dtype.
    """
    from jax.experimental import pallas as pl

    if stride not in (1, 2):
        # the kernel's decimation path folds the stride into a
        # hard-coded factor-2 reshape (_kernel: pad-to-2bh + keep
        # phase 0); any other stride would run to completion with
        # wrong output instead of failing
        raise ValueError("conv2d_bn_act supports stride 1 or 2, got %r"
                         % (stride,))
    if interpret is None:
        interpret = compute_platform() != "tpu"
    B, H, W, Cin = x.shape
    KH, KW, _, Cout = w.shape
    if padding:
        x = jnp.pad(x, ((0, 0), (padding, padding), (padding, padding),
                        (0, 0)))
        H, W = H + 2 * padding, W + 2 * padding
    H_out = (H - KH) // stride + 1
    W_out = (W - KW) // stride + 1
    bh = block_h or _pick_block_h(H_out, W_out)
    bn = block_n or _pick_block_n(Cout)
    if H_out % bh or Cout % bn:
        raise ValueError("block_h/block_n must divide H_out/Cout")
    if scale is None:
        scale = jnp.ones((Cout,), jnp.float32)
    if shift is None:
        shift = jnp.zeros((Cout,), jnp.float32)
    scale2 = jnp.asarray(scale, jnp.float32).reshape(1, Cout)
    shift2 = jnp.asarray(shift, jnp.float32).reshape(1, Cout)

    kernel = functools.partial(
        _kernel, block_h=bh, w_out=W_out, kh=KH, kw=KW, stride=stride,
        relu=relu, has_residual=residual is not None)
    in_specs = [
        # full (padded) image rows for one batch element: halo slicing
        # happens inside the kernel (overlap is not expressible with
        # blocked index maps)
        pl.BlockSpec((1, H, W, Cin), lambda b, i, n: (b, 0, 0, 0)),
        pl.BlockSpec((KH, KW, Cin, bn), lambda b, i, n: (0, 0, 0, n)),
        pl.BlockSpec((1, bn), lambda b, i, n: (0, n)),
        pl.BlockSpec((1, bn), lambda b, i, n: (0, n)),
    ]
    args = [x, w, scale2, shift2]
    if residual is not None:
        in_specs.append(
            pl.BlockSpec((1, bh, W_out, bn), lambda b, i, n: (b, i, 0, n)))
        args.append(residual)
    out = pl.pallas_call(
        kernel,
        grid=(B, H_out // bh, Cout // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, W_out, bn),
                               lambda b, i, n: (b, i, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, H_out, W_out, Cout), x.dtype),
        interpret=interpret,
    )(*args)
    return out


def _xla_conv_nhwc(x, w, stride, padding):
    from jax import lax

    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(padding, padding), (padding, padding)],
        dimension_numbers=dn)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def pallas_conv(x, w, stride=1, padding=0):
    """Differentiable pallas conv, NHWC x [B,H,W,Cin], w HWIO.

    Forward runs the pallas implicit-GEMM kernel; backward uses XLA's
    conv transpose forms (the bwd shapes flip the win class — e.g. an
    expansion conv's dx is a reduction conv, where XLA measured faster;
    see BASELINE.md round-5 table)."""
    return conv2d_bn_act(x, w, stride=stride, padding=padding)


def _pallas_conv_fwd(x, w, stride, padding):
    return pallas_conv(x, w, stride, padding), (x, w)


def _pallas_conv_bwd(stride, padding, res, g):
    x, w = res
    _, vjp = jax.vjp(
        lambda x, w: _xla_conv_nhwc(x, w, stride, padding), x, w)
    return vjp(g)


pallas_conv.defvjp(_pallas_conv_fwd, _pallas_conv_bwd)


def route_pallas(flag_value, x_shape, w_shape, stride, groups, dilations,
                 data_format):
    """Routing decision for the conv op: 'off' never; 'all' any viable
    shape; 'auto' only the measured-win class (stride-1 1x1 expansion
    convs, Cout >= 2*Cin — the shapes where the fused epilogue beats
    XLA 1.4-1.5x on v5e; every other class measured at or below parity,
    BASELINE.md round 5)."""
    if flag_value == "off" or not pallas_conv_viable(
            x_shape, w_shape, stride, groups, dilations, data_format):
        return False
    if flag_value == "all":
        return True
    KH, KW, Cin, Cout = w_shape
    return KH == 1 and stride == 1 and Cout >= 2 * Cin


def pallas_conv_viable(x_shape, w_shape, stride, groups, dilations,
                       data_format):
    """True when the pallas kernel covers this conv (NHWC, groups=1,
    square small kernel, 128-aligned Cin, stride 1/2)."""
    if data_format != "NHWC" or groups != 1:
        return False
    if any(d != 1 for d in dilations):
        return False
    KH, KW, Cin, _ = w_shape
    if KH != KW or KH not in (1, 3):
        return False
    if Cin % 128:
        return False          # the 3-channel stem stays on XLA
    return stride in (1, 2)
