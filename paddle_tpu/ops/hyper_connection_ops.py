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
streams mix, and the stream is what every later layer reads.

The four ops (``mhc_pre``, ``mhc_post`` and a gradient op each) have two
forms, and ``hyper_path`` reads the choice from what an op can see. Where the
computation runs on a TPU and the streams fill whole tiles, every pass over
the streams is a kernel of ``ops/pallas/hyper_connection.py`` whose block
holds all ``n`` streams of a tile of tokens: ``mhc_pre`` one pass (norm,
products, ``H_pre`` and ``h``), ``mhc_post`` one, ``mhc_post_grad`` one
(``dX``, ``dy``, ``dH_res`` and ``dH_post`` from one reading of the streams
and their cotangent), ``mhc_pre_grad`` two (``dH_pre`` is a sum over a whole
row that ``dX`` needs); between them the sigmoids, the Sinkhorn rounds and
their ``jax.vjp`` stay XLA's, on ``[24, T]`` arrays (``maps_of``). Everywhere
else, the CPU and every shape the blocks cannot take, ``maps``, ``mix_in``,
``mix_out`` and their ``jax.vjp`` run as plain XLA: the kernels are measured
and tested against them. Inner ``jax.named_scope``s tell ``mix`` (a pass
over the streams: in the kernels' form the norm and the products too, which
ride in the same pass) from ``maps`` (everything on the small arrays; in the
XLA form the norm and the products as well) in a trace.

The maps of a token are 24 numbers beside a stream of 14,336: they are made
and handed on with the tokens along the last (lane) axis, ``H_post [B, n,
T]``, ``H_res [B, n, n, T]``, so that a Sinkhorn round is a few dense
elementwise passes and not 4 x 4 tiles padded to the lanes.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op
from .pallas import hyper_connection as _kernels

# the module: the package's attribute of that name is the function
_fa = importlib.import_module(".pallas.flash_attention", __package__)
_HI = jax.lax.Precision.HIGHEST


def hyper_path(x):
    """"pallas" | "xla": which form the four ops take over the streams x
    [B, n, T, C]. The kernels where the computation runs on a TPU (asked
    through ``ops.pallas.flash_attention``, as ``benchmarks/aot_sizing.py``
    answers there) and the streams fill the kernels' blocks
    (``ops.pallas.hyper_connection.fits``)."""
    on_tpu = _fa.compute_platform() == "tpu"
    return "pallas" if on_tpu and _kernels.fits(x) else "xla"


def sinkhorn(m, iters, eps):
    """``m [n, n, ...]`` (row, column, ...) positive -> near doubly stochastic: ``iters`` times
    rows (over the sum along axis 1), then columns (along axis 0)."""
    for _ in range(iters):
        m = m / (jnp.sum(m, 1, keepdims=True) + eps)
        m = m / (jnp.sum(m, 0, keepdims=True) + eps)
    return m


def maps_of(pqr, alpha, b_pre, b_post, b_res, iters, eps, lo, hi):
    """(H_pre [B, n, T], H_post [B, n, T], H_res [B, n, n, T]) of the scaled
    products ``pqr = x~ Phi`` [B, 2 n + n^2, T]: the sigmoids and the
    Sinkhorn rounds, on small arrays."""
    f32 = jnp.float32
    B, _, T = pqr.shape
    n = b_pre.shape[0]
    alpha = alpha.astype(f32)
    h_pre = jax.nn.sigmoid(alpha[0] * pqr[:, :n]
                           + b_pre.astype(f32)[:, None])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * pqr[:, n:2 * n]
                                  + b_post.astype(f32)[:, None])
    m = jnp.exp(jnp.clip(
        alpha[2] * pqr[:, 2 * n:].reshape(B, n, n, T)
        + b_res.astype(f32)[:, :, None], lo, hi))
    return h_pre, h_post, jnp.moveaxis(
        sinkhorn(jnp.moveaxis(m, 0, 2), iters, eps), 2, 0)


def maps(x, phi, alpha, b_pre, b_post, b_res, iters, eps, lo, hi):
    """``maps_of`` the streams x [B, n, T, C], float32, the tokens along the
    last axis."""
    f32 = jnp.float32
    B, n, T, C = x.shape
    x = x.astype(f32)
    phi = phi.astype(f32).reshape(n, C, -1)     # row i C + c of Phi: [i, c]
    # x~ Phi = (vec(X) Phi) / rms, a stream's plane at a time: neither the
    # normed streams nor a [T, n C] copy of them is ever written
    inv = jax.lax.rsqrt(jnp.sum(jnp.mean(jnp.square(x), -1), 1) / n + eps)
    pqr = sum(jnp.dot(x[:, i], phi[i], precision=_HI) for i in range(n))
    pqr = jnp.swapaxes(pqr * inv[..., None], 1, 2)        # [B, 2n + n^2, T]
    return maps_of(pqr, alpha, b_pre, b_post, b_res, iters, eps, lo, hi)


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


def _map_attrs(attrs):
    """(rounds, epsilon, the clamp's two ends) of an op's attrs."""
    return (int(attrs.get("sinkhorn_iters", 20)),
            float(attrs.get("epsilon", 1e-6)),
            float(attrs.get("clamp_min", -30.0)),
            float(attrs.get("clamp_max", 30.0)))


def _pre(x, phi, alpha, b_pre, b_post, b_res, attrs):
    """(h, H_post, H_res) of ``mhc_pre`` in the XLA form, with its inner
    scopes."""
    with jax.named_scope("maps"):
        h_pre, h_post, h_res = maps(x, phi, alpha, b_pre, b_post, b_res,
                                    *_map_attrs(attrs))
    with jax.named_scope("mix"):
        return mix_in(x, h_pre), h_post, h_res


def _phi_rows(phi, n):
    """Phi [n C, K] as the kernels read it, [n, K, C] float32."""
    return jnp.swapaxes(phi.astype(jnp.float32).reshape(n, -1, phi.shape[1]),
                        1, 2)


def _tokens_minor(a):
    """A per-token array of the kernels, [B, T, k] -> [B, k, T]."""
    return jnp.swapaxes(a, 1, 2)


def _pre_kernels(x, phi, alpha, b_pre, b_post, b_res, attrs):
    """``_pre`` in the kernels' form: one pass over the streams, then the
    maps of the products it leaves."""
    f32 = jnp.float32
    n = x.shape[1]
    iters, eps, lo, hi = _map_attrs(attrs)
    with jax.named_scope("maps"):
        phi = _phi_rows(phi, n)
        # alpha_pre and b_pre over the first n of the K products' lanes
        ab = jnp.pad(jnp.stack([jnp.broadcast_to(alpha.astype(f32)[0], (n,)),
                                b_pre.astype(f32)]),
                     ((0, 0), (0, phi.shape[1] - n)))
    with jax.named_scope("mix"):
        h, pqr = _kernels.pre_forward(x, phi, ab, eps=eps)
    with jax.named_scope("maps"):
        _, h_post, h_res = maps_of(_tokens_minor(pqr), alpha, b_pre, b_post,
                                   b_res, iters, eps, lo, hi)
    return h, h_post, h_res


def _pre_grad_kernels(x, phi, alpha, b_pre, b_post, b_res, cts, attrs):
    """The gradients of ``_pre_kernels``' inputs from the cotangents ``cts``
    of (h, H_post, H_res): a pass that reads the streams and ``dh`` for the
    products, the norm and ``dH_pre``; the maps' ``jax.vjp`` on the small
    arrays; a pass that reads them again and writes ``dX`` and ``dPhi``.

    With ``u = x Phi``, ``inv = rsqrt(mean(x^2) + eps)`` and ``pqr = u
    inv``: ``du = dpqr inv``, and what reaches the mean of squares comes
    back as ``g x`` with ``g = -(dpqr . pqr) inv^2 / (n C)``."""
    B, n, T, C = x.shape
    iters, eps, lo, hi = _map_attrs(attrs)
    dh, dh_post, dh_res = cts
    with jax.named_scope("maps"):
        rows = _phi_rows(phi, n)
    with jax.named_scope("mix"):
        pqr, inv, dh_pre = _kernels.pre_grad_reads(x, rows, dh, eps=eps)
    with jax.named_scope("maps"):
        (h_pre, _, _), vjp = jax.vjp(
            lambda *a: maps_of(*a, iters, eps, lo, hi),
            _tokens_minor(pqr), alpha, b_pre, b_post, b_res)
        dpqr, *small = vjp((_tokens_minor(dh_pre), dh_post, dh_res))
        dpqr = _tokens_minor(dpqr)
        g = -jnp.sum(dpqr * pqr, -1, keepdims=True) * inv * inv / (n * C)
        hg = jnp.concatenate([_tokens_minor(h_pre), g], -1)
    with jax.named_scope("mix"):
        dx, dphi = _kernels.pre_grad_writes(x, rows, dh, dpqr * inv, hg)
    with jax.named_scope("maps"):
        dphi = jnp.swapaxes(jnp.sum(dphi, 0), 1, 2).reshape(phi.shape)
    return (dx, dphi, *small)


def _mhc_pre_grad(ins, attrs):
    """The gradient of ``mhc_pre`` from its inputs (the forward's maps run
    again inside, the Sinkhorn rounds with them): an op of its own so that
    the forward op's count of sublayers is not the gradient's too. In the
    XLA form the ``jax.vjp`` of ``_pre``; in the kernels' form two passes
    over the streams around the maps' ``jax.vjp``."""
    f32 = jnp.float32
    args = tuple(ins[n] for n in _PRE_INPUTS)
    B, n, T, C = ins["X"].shape
    cts = tuple(jnp.zeros(shape, f32) if ins.get(name + "@GRAD") is None
                else ins[name + "@GRAD"].astype(f32)
                for name, shape in zip(
                    _PRE_OUTPUTS, ((B, T, C), (B, n, T), (B, n, n, T))))
    if hyper_path(ins["X"]) == "pallas":
        grads = _pre_grad_kernels(*args, cts, attrs)
    else:
        grads = jax.vjp(lambda *a: _pre(*a, attrs), *args)[1](cts)
    return {name + "@GRAD": g.astype(ins[name].dtype)
            for name, g in zip(_PRE_INPUTS, grads)}


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
    traced, and counted, again) and, by the form it took (``hyper_path``),
    ``kernels.mhc{path=pallas|xla}``."""
    from .. import observability as _obs

    path = hyper_path(ins["X"])
    if _obs.enabled():
        _obs.inc("kernels.mhc_sublayers")
        _obs.inc("kernels.mhc", path=path)
    form = _pre_kernels if path == "pallas" else _pre
    return dict(zip(_PRE_OUTPUTS, form(*(ins[n] for n in _PRE_INPUTS),
                                       attrs)))


_POST_INPUTS = ("X", "HRes", "HPost", "Y")


def _coefficients(h_res, h_post):
    """The kernels' [B, T, n n + n]: ``H_res[i, j]`` of a token at column
    ``i n + j``, then ``H_post[i]``."""
    B, n, _, T = h_res.shape
    return jnp.concatenate(
        [_tokens_minor(h_res.astype(jnp.float32).reshape(B, n * n, T)),
         _tokens_minor(h_post.astype(jnp.float32))], -1)


def _mhc_post_grad(ins, attrs):
    """The gradient of ``mhc_post`` from its inputs: in the XLA form the
    ``jax.vjp`` of ``mix_out``; in the kernels' form one pass that reads the
    streams, ``Y`` and the cotangent once and writes ``dX`` and ``dY`` a
    block at a time, ``dH_res`` and ``dH_post`` summed beside them."""
    x, h_res, h_post, y = (ins[n] for n in _POST_INPUTS)
    d = ins["Out@GRAD"].astype(jnp.float32)
    if hyper_path(x) == "pallas":
        B, n, T, _ = x.shape
        with jax.named_scope("maps"):
            coef = _coefficients(h_res, h_post)
        with jax.named_scope("mix"):
            dx, dcoef, dy = _kernels.post_backward(x, coef, y, d)
        with jax.named_scope("maps"):
            dcoef = _tokens_minor(dcoef)
            grads = (dx, dcoef[:, :n * n].reshape(B, n, n, T),
                     dcoef[:, n * n:], dy)
    else:
        with jax.named_scope("mix"):
            grads = jax.vjp(mix_out, x, h_res, h_post, y)[1](d)
    return {n + "@GRAD": g.astype(ins[n].dtype)
            for n, g in zip(_POST_INPUTS, grads)}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "mhc_post_grad",
    inputs=[In(n) for n in _POST_INPUTS] + [In("Out@GRAD")],
    outputs=[Out(n + "@GRAD", dispensable=True) for n in _POST_INPUTS],
    grad=None,
)(_mhc_post_grad)


@register_op(
    "mhc_post",
    inputs=[In(n) for n in _POST_INPUTS],
    outputs=[Out("Out")],
)
def _mhc_post(ins, attrs):
    """Out [B, n, T, C] = HRes X + HPost (x) Y: the streams after a
    sublayer whose output is Y [B, T, C]. Float32 out."""
    x, h_res, h_post, y = (ins[n] for n in _POST_INPUTS)
    if hyper_path(x) == "pallas":
        with jax.named_scope("maps"):
            coef = _coefficients(h_res, h_post)
        with jax.named_scope("mix"):
            return {"Out": _kernels.post_forward(x, coef, y)}
    with jax.named_scope("mix"):
        return {"Out": mix_out(x, h_res, h_post, y)}
