"""Manifold-constrained hyper-connections: ``n`` residual streams that every
sublayer reads through one map and writes through two.

The streams are kept stream-major, ``X [B, n, T, C]``: a stream is a plane
``[T, C]`` of whole (8, 128) tiles, where ``[T, n, C]`` would pad ``n = 4``
to a tile's 8 rows or be copied into this layout and back around every op
(the TPU compiler's choice, read in a first sizing of the step). Around a
sublayer ``F`` (``X_t`` the ``n`` streams of token ``t``) they become

- ``mhc_pre``: ``x~ = vec(X_t) / sqrt(mean(vec(X_t)^2) + eps)`` (a flat RMS
  norm over the ``n C`` values of a token, no weight); ``[p | q | r] = x~
  Phi`` (``Phi [n C, n + n + n^2]``, float32 at full precision); ``H_pre =
  sigmoid(alpha_pre p + b_pre)``, ``H_post = 2 sigmoid(alpha_post q +
  b_post)``, ``M = exp(clip(alpha_res mat(r) + b_res, lo, hi))`` made doubly
  stochastic by ``iters`` Sinkhorn rounds (rows over ``rowsum + eps``, then
  columns over ``colsum + eps``), ``H_res = M``; and the sublayer's input
  ``h = sum_i H_pre[i] X[i]``.
- ``mhc_post``: ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` with ``y =
  F(norm(h))``.

Everything here is float32 (AMP black list): the maps decide how the
streams mix, and the stream is what every later layer reads. The gradient
ops are the automatic VJPs of these functions, through the Sinkhorn rounds
too. Inner ``jax.named_scope``s tell ``maps`` (norm, product, sigmoids,
rounds) from ``mix`` (the passes over the stream) in a trace.

The maps of a token are 24 numbers beside a stream of 14,336: they are made
and handed on with the tokens along the last (lane) axis, ``H_post [B, n,
T]``, ``H_res [B, n, n, T]``, so that a Sinkhorn round is a few dense
elementwise passes and not 4 x 4 tiles padded to the lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op

_HI = jax.lax.Precision.HIGHEST


def sinkhorn(m, iters, eps):
    """``m [n, n, ...]`` (row, column, ...) positive -> near doubly stochastic: ``iters`` times
    rows (over the sum along axis 1), then columns (along axis 0)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, 1, keepdims=True) + eps)
        m = m / (jnp.sum(m, 0, keepdims=True) + eps)
    return m


def maps(x, phi, alpha, b_pre, b_post, b_res, iters, eps, lo, hi):
    """(H_pre [B, n, T], H_post [B, n, T], H_res [B, n, n, T]) of the streams
    x [B, n, T, C], float32, the tokens along the last axis."""
    f32 = jnp.float32
    B, n, T, C = x.shape
    x, alpha = x.astype(f32), alpha.astype(f32)
    phi = phi.astype(f32).reshape(n, C, -1)     # row i C + c of Phi: [i, c]
    # x~ Phi = (vec(X) Phi) / rms, a stream's plane at a time: neither the
    # normed streams nor a [T, n C] copy of them is ever written
    inv = jax.lax.rsqrt(jnp.sum(jnp.mean(jnp.square(x), -1), 1) / n + eps)
    pqr = sum(jnp.dot(x[:, i], phi[i], precision=_HI) for i in range(n))
    pqr = jnp.swapaxes(pqr * inv[..., None], 1, 2)        # [B, 2n + n^2, T]
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n]
                           + b_pre.astype(f32)[:, None])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n]
                                  + b_post.astype(f32)[:, None])
    m = jnp.exp(jnp.clip(
        alpha[2] * pqr[:, 2 * n:].reshape(B, n, n, T)
        + b_res.astype(f32)[:, :, None], lo, hi))
    return h_pre, h_post, jnp.moveaxis(
        sinkhorn(jnp.moveaxis(m, 0, 2), iters, eps), 2, 0)


def mix_in(x, h_pre):
    """h [B, T, C] = sum_i H_pre[i] X[i]."""
    return jnp.sum(h_pre[..., None] * x.astype(jnp.float32), 1)


def mix_out(x, h_res, h_post, y):
    """X' [B, n, T, C] = H_res X + H_post (x) y: n + 1 multiply-adds an
    element, one pass (static slices: no gather, no scatter)."""
    f32 = jnp.float32
    x, y = x.astype(f32), y.astype(f32)
    out = h_post[..., None] * y[:, None]
    for j in range(x.shape[1]):
        out = out + h_res[:, :, j, :, None] * x[:, j][:, None]
    return out


_MAP_ATTRS = {"sinkhorn_iters": 20, "epsilon": 1e-6, "clamp_min": -30.0,
              "clamp_max": 30.0}


_PRE_INPUTS = ("X", "Phi", "Alpha", "BPre", "BPost", "BRes")
_PRE_OUTPUTS = ("H", "HPost", "HRes")


def _pre(x, phi, alpha, b_pre, b_post, b_res, attrs):
    """(h, H_post, H_res) of ``mhc_pre``, with its inner scopes."""
    with jax.named_scope("maps"):
        h_pre, h_post, h_res = maps(
            x, phi, alpha, b_pre, b_post, b_res,
            int(attrs.get("sinkhorn_iters", 20)),
            float(attrs.get("epsilon", 1e-6)),
            float(attrs.get("clamp_min", -30.0)),
            float(attrs.get("clamp_max", 30.0)))
    with jax.named_scope("mix"):
        return mix_in(x, h_pre), h_post, h_res


def _mhc_pre_grad(ins, attrs):
    """The VJP of ``mhc_pre`` from its inputs (the forward runs again inside,
    the Sinkhorn rounds with it): an op of its own so that the forward op's
    count of sublayers is not the gradient's too."""
    outs, vjp = jax.vjp(lambda *a: _pre(*a, attrs),
                        *(ins[n] for n in _PRE_INPUTS))
    cts = tuple(jnp.zeros_like(o) if ins.get(n + "@GRAD") is None
                else ins[n + "@GRAD"].astype(o.dtype)
                for n, o in zip(_PRE_OUTPUTS, outs))
    return {n + "@GRAD": g.astype(ins[n].dtype)
            for n, g in zip(_PRE_INPUTS, vjp(cts))}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "mhc_pre_grad",
    inputs=[In(n) for n in _PRE_INPUTS]
    + [In(n + "@GRAD", dispensable=True) for n in _PRE_OUTPUTS],
    outputs=[Out(n + "@GRAD", dispensable=True) for n in _PRE_INPUTS],
    attrs=dict(_MAP_ATTRS),
    grad=None,
)(_mhc_pre_grad)


@register_op(
    "mhc_pre",
    inputs=[In(n) for n in _PRE_INPUTS],
    outputs=[Out(n) for n in _PRE_OUTPUTS],
    attrs=dict(_MAP_ATTRS),
)
def _mhc_pre(ins, attrs):
    """X [B, n, T, C] the streams; Phi [n C, 2 n + n^2] (row ``i C + c``
    reads stream ``i``'s value ``c``); Alpha [3]
    (``alpha_pre, alpha_post, alpha_res``); BPre, BPost [n]; BRes [n, n].
    ``H`` [B, T, C] is the sublayer's input, ``HPost`` [B, n, T] and
    ``HRes`` [B, n, n, T] (``[b, i, j, t]``) are what ``mhc_post`` writes the sublayer's output
    back with. Float32 out. Each trace of the op counts
    ``kernels.mhc_sublayers`` (a sublayer recomputed in the backward is
    traced, and counted, again)."""
    from .. import observability as _obs

    if _obs.enabled():
        _obs.inc("kernels.mhc_sublayers")
    return dict(zip(_PRE_OUTPUTS, _pre(*(ins[n] for n in _PRE_INPUTS),
                                       attrs)))


@register_op(
    "mhc_post",
    inputs=[In("X"), In("HRes"), In("HPost"), In("Y")],
    outputs=[Out("Out")],
)
def _mhc_post(ins, attrs):
    """Out [B, n, T, C] = HRes X + HPost (x) Y: the streams after a
    sublayer whose output is Y [B, T, C]. Float32 out."""
    with jax.named_scope("mix"):
        return {"Out": mix_out(ins["X"], ins["HRes"], ins["HPost"],
                               ins["Y"])}
