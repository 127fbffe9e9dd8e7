"""Fused optimizer update over the flat param/state buffer — Pallas.

The cross-replica sharded-update rewrite (PR 6) already proved the
kernel boundary: an optimizer instance's state flattened into ONE
buffer, updated by elementwise math. This module is the single-chip
half of that story: ONE kernel launch applies sgd / momentum / adam /
adamw across every param element, replacing the per-param op chain
(~4 HBM round trips per param per elementwise pass) with a blocked
streaming pass over the flat buffer — the memory-bound optimizer phase
becomes one pipelined read-modify-write.

Layout contract (enforced by the rewrite pass, core/fusion.py): flat
arrays are zero-padded to a multiple of ``LANE_PAD`` (= 8 sublanes x
128 lanes) so the kernel can view them as [rows, 128] tiles; scalars
(learning rate, beta pows) ride in SMEM. The update math is the SAME
jnp expression sequence as ops/optimizer_ops.py — sqrt/mul/add/div
only, each correctly rounded, so the pallas kernel, the XLA fallback
(``use_pallas=False``), and the per-param op chain are bit-identical.

Off-TPU (same rule as flash_attention: ``compute_platform()``) the
update is the XLA lowering of the same expressions: XLA fuses the flat
elementwise chain into one loop there; tests run the kernels in
interpret mode via ``force_pallas=True`` where the math is numpy-exact.
On a TPU the kernel is the path, and a build failure raises.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ...core.place import compute_platform

# flat buffers are padded to a multiple of this so [rows, 128] tiling
# always satisfies the TPU (8, 128) tile rule
LANE_PAD = 8 * 128

# preferred row-block: 2048 x 128 x 4B = 1MB VMEM per operand stream
_BLOCK_ROWS = 2048

FUSED_OPTIMIZERS = ("sgd", "momentum", "adam", "adamw")


def _update_math(op_type: str, attrs: Dict, p, g, lr, sa=None, sb=None,
                 b1pow=None, b2pow=None):
    """The optimizer update as pure elementwise expressions — ONE
    definition shared by the pallas kernel body and the XLA fallback,
    mirroring ops/optimizer_ops.py term for term (same operation
    order => bit-identical results).

    Returns (p_out, state_a_out, state_b_out)."""
    if op_type == "sgd":
        return p - lr * g, None, None
    if op_type == "momentum":
        mu = attrs.get("mu", 0.9)
        v = mu * sa + g
        if attrs.get("use_nesterov", False):
            p_out = p - (g + mu * v) * lr
        else:
            p_out = p - lr * v
        return p_out, v, None
    if op_type in ("adam", "adamw"):
        b1 = attrs.get("beta1", 0.9)
        b2 = attrs.get("beta2", 0.999)
        eps = attrs.get("epsilon", 1e-8)
        m1 = b1 * sa + (1 - b1) * g
        m2 = b2 * sb + (1 - b2) * jnp.square(g)
        lr_t = lr * jnp.sqrt(1 - b2pow) / (1 - b1pow)
        p_out = p - lr_t * m1 / (jnp.sqrt(m2) + eps)
        if op_type == "adamw":
            wd = attrs.get("weight_decay", 0.01)
            p_out = p_out - lr * wd * p
        return p_out, m1, m2
    raise ValueError("fused optimizer does not support %r" % op_type)


def _n_states(op_type: str) -> int:
    return {"sgd": 0, "momentum": 1, "adam": 2, "adamw": 2}[op_type]


def _kernel(*refs, op_type, attrs, n_state, has_pows):
    """One [block_rows, 128] tile: load every operand stream, apply the
    shared update math, store the outputs. Scalars come from SMEM."""
    k = 0
    p_ref = refs[k]; k += 1                             # noqa: E702
    g_ref = refs[k]; k += 1                             # noqa: E702
    lr_ref = refs[k]; k += 1                            # noqa: E702
    sa_ref = sb_ref = None
    if n_state >= 1:
        sa_ref = refs[k]; k += 1                        # noqa: E702
    if n_state >= 2:
        sb_ref = refs[k]; k += 1                        # noqa: E702
    b1_ref = b2_ref = None
    if has_pows:
        b1_ref = refs[k]; k += 1                        # noqa: E702
        b2_ref = refs[k]; k += 1                        # noqa: E702
    outs = refs[k:]

    p = p_ref[...]
    g = g_ref[...].astype(p.dtype)
    lr = lr_ref[0]
    sa = sa_ref[...] if sa_ref is not None else None
    sb = sb_ref[...] if sb_ref is not None else None
    b1pow = b1_ref[0] if b1_ref is not None else None
    b2pow = b2_ref[0] if b2_ref is not None else None

    p_out, sa_out, sb_out = _update_math(op_type, attrs, p, g, lr, sa,
                                         sb, b1pow, b2pow)
    outs[0][...] = p_out.astype(outs[0].dtype)
    j = 1
    if sa_out is not None:
        outs[j][...] = sa_out.astype(outs[j].dtype)
        j += 1
    if sb_out is not None:
        outs[j][...] = sb_out.astype(outs[j].dtype)


def _block_rows(rows: int) -> int:
    """Largest divisor of ``rows`` that is <= _BLOCK_ROWS and a
    multiple of 8 (sublane rule). ``rows`` is a multiple of 8 by the
    LANE_PAD contract, so 8 always qualifies."""
    b = min(_BLOCK_ROWS, rows)
    b -= b % 8
    while b > 8 and rows % b:
        b -= 8
    return max(b, 8)


def fused_optimizer_update(op_type: str, attrs: Dict, param, grad, lr,
                           state_a=None, state_b=None, beta1_pow=None,
                           beta2_pow=None,
                           force_pallas: Optional[bool] = None):
    """Apply one fused optimizer step over flat [padded] arrays.

    ``param``/``grad`` (and the state buffers) are flat, zero-padded to
    a multiple of ``LANE_PAD``; scalars are 0-d/1-element arrays.
    Returns ``(param_out, state_a_out, state_b_out)`` (None where the
    optimizer carries no such state). Routes to the pallas kernel
    where the computation runs on a TPU (or under ``force_pallas`` —
    interpret mode — in tests); the XLA path is the same math on the
    same flat buffer, which XLA fuses into one loop.
    """
    n_state = _n_states(op_type)
    has_pows = op_type in ("adam", "adamw")
    lr = jnp.asarray(lr).reshape(())
    scalars = [lr.reshape(1)]
    if has_pows:
        if beta1_pow is None or beta2_pow is None:
            raise ValueError("%s needs beta pow accumulators" % op_type)
        scalars += [jnp.asarray(beta1_pow).reshape(1).astype(param.dtype),
                    jnp.asarray(beta2_pow).reshape(1).astype(param.dtype)]

    on_tpu = compute_platform() == "tpu"
    use_pallas = on_tpu if force_pallas is None else bool(force_pallas)
    if use_pallas and param.size % LANE_PAD == 0 and param.size > 0:
        return _pallas_update(op_type, attrs, param, grad, scalars,
                              state_a, state_b, n_state, has_pows,
                              interpret=not on_tpu)
    # XLA path: identical expressions over the same flat buffers
    b1pow = scalars[1][0] if has_pows else None
    b2pow = scalars[2][0] if has_pows else None
    return _update_math(op_type, attrs, param,
                        grad.astype(param.dtype), lr,
                        state_a, state_b, b1pow, b2pow)


def _pallas_update(op_type, attrs, param, grad, scalars, state_a,
                   state_b, n_state, has_pows, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = param.size // 128
    br = _block_rows(rows)
    grid = (rows // br,)
    tile = pl.BlockSpec((br, 128), lambda i: (i, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)

    args = [param.reshape(rows, 128), grad.reshape(rows, 128),
            scalars[0]]
    in_specs = [tile, tile, smem]
    if n_state >= 1:
        args.append(state_a.reshape(rows, 128))
        in_specs.append(tile)
    if n_state >= 2:
        args.append(state_b.reshape(rows, 128))
        in_specs.append(tile)
    if has_pows:
        args += scalars[1:]
        in_specs += [smem, smem]

    n_out = 1 + n_state
    kernel = functools.partial(_kernel, op_type=op_type,
                               attrs=dict(attrs), n_state=n_state,
                               has_pows=has_pows)
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[tile] * n_out,
        out_shape=[jax.ShapeDtypeStruct((rows, 128), param.dtype)
                   for _ in range(n_out)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
    flat = [o.reshape(-1) for o in outs]
    p_out = flat[0]
    sa_out = flat[1] if n_state >= 1 else None
    sb_out = flat[2] if n_state >= 2 else None
    return p_out, sa_out, sb_out
