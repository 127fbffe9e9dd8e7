"""The passes of manifold-constrained hyper-connections over the streams as
Pallas TPU kernels: a block holds all ``n`` streams of a tile of tokens, and
whatever is made from it is made while it is in VMEM, so each pass reads the
streams (and their cotangent) from HBM once and no plane of them is copied
out or back.

``ops/hyper_connection_ops.py`` holds the ops, the equations, the XLA form of
the same passes and the small-array work between them (the sigmoids, the
Sinkhorn rounds and their gradient); it calls here where ``hyper_path`` says
so. The streams are ``x [B, n, T, C]`` float32. **What belongs to a token
rides with the tokens on sublanes**: ``[B, T, k]`` arrays (``k`` = 24
products, 20 coefficients, ...) whose column ``[tT, 1]`` spreads over a
row's lanes as a softmax's row maximum does; the ops transpose them from and
to the maps' own token-minor layout outside, on arrays of a few hundred KB.
Phi arrives as ``[n, K, C]`` (``K = 2 n + n^2`` on sublanes: 1.4 MB at the
cell's shape where ``[C, K]`` would pad K to 128 lanes and 7.3 MB). Every
product is float32 at full precision, as in the XLA form.

Five kernels:

- ``mhc_pre_fwd`` (whole rows of C): the flat norm's squares, ``x Phi``,
  ``H_pre`` of the tile's own tokens and ``h = sum_i H_pre[i] x[i]``; gives
  ``h`` and the scaled products ``pqr [B, T, K]``.
- ``mhc_pre_reads``: the gradient's first pass: the same squares and
  products, and ``dH_pre[i] = sum_c dh x[i]``; gives ``pqr``, the norm's
  ``inv [B, T, 1]`` and ``dH_pre [B, T, n]``.
- ``mhc_pre_writes``: its second: ``dx[i] = H_pre[i] dh + du Phi[i]^T + g
  x[i]`` a block at a time, ``dPhi[i] = du^T x[i]`` summed over the token
  tiles in a block that stays in VMEM.
- ``mhc_post_fwd`` (C tiled): ``x'[i] = sum_j H_res[i, j] x[j] + H_post[i]
  y``.
- ``mhc_post_bwd`` (C tiled): ``dx[j] = sum_i H_res[i, j] d[i]``, ``dy =
  sum_i H_post[i] d[i]``, and ``dH_res[i, j] = sum_c d[i] x[j]``,
  ``dH_post[i] = sum_c d[i] y`` summed over the C tiles of a token tile.

No kernel states a ``vmem_limit_bytes``: the blocks are sized under the
default scoped limit (the latent-attention cell's step has hung on a Mosaic
call that asked for more, PERF.md section 7). Each entry sits behind one
``jax.jit``: the sublayers of a program, and the forward ops a recomputing
optimizer emits again, share one lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# contracting dimensions of a @ b, a @ b^T and a^T @ b
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# the entries below that hold a kernel (a test or a rehearsal off the chip
# wraps each with ``interpret=True``)
ENTRIES = ("pre_forward", "pre_grad_reads", "pre_grad_writes",
           "post_forward", "post_backward")

# a block's tokens where it holds whole rows of C, and the bytes one
# operand's block may take there ([n, tT, C] float32); where C is tiled, a
# block's tokens and lanes
ROW_TOKENS = 64
_ROW_BLOCK_BYTES = 2 << 20
TILE_TOKENS, TILE_LANES = 64, 1792


def fits(x):
    """Whether the kernels' blocks take these streams: float32, tokens in
    whole tiles of ``ROW_TOKENS``, rows of whole 128-lane tiles."""
    _, _, T, C = x.shape
    return bool(x.dtype == _F32 and T % ROW_TOKENS == 0 and C % 128 == 0)


def _row_tokens(n, C):
    """Tokens of a block of whole rows: the most, up to ``ROW_TOKENS`` and
    by halves down to a sublane tile, whose ``[n, tT, C]`` float32 block
    stays under ``_ROW_BLOCK_BYTES`` (four such operands, each twice for the
    pipeline, under the 16 MiB a Mosaic call gets unasked)."""
    tT = ROW_TOKENS
    while tT > 8 and 4 * n * tT * C > _ROW_BLOCK_BYTES:
        tT //= 2
    return tT


def _lanes(C):
    """The C tile: ``TILE_LANES`` where it divides C, else the largest
    multiple of 128 under it that does."""
    return next(c for c in range(min(TILE_LANES, C), 0, -128) if C % c == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=_F32)


def _row_sum(v):
    return jnp.sum(v, axis=1, keepdims=True)


def _norm_and_products(x_ref, phi_ref, eps, each=None):
    """(inv [tT, 1], pqr [tT, K]) of a block of whole rows: the flat RMS
    norm's ``rsqrt`` and ``(x Phi) inv``; ``each(i, x_i)`` sees every
    stream's plane while it is loaded."""
    n, tT, C = x_ref.shape
    sq = jnp.zeros((tT, 1), _F32)
    u = jnp.zeros((tT, phi_ref.shape[1]), _F32)
    for i in range(n):
        xi = x_ref[i]
        sq = sq + _row_sum(xi * xi)
        u = u + _dot(xi, phi_ref[i], _NT)
        if each is not None:
            each(i, xi)
    inv = jax.lax.rsqrt(sq / (n * C) + eps)
    return inv, u * inv


def _pre_fwd_kernel(x_ref, phi_ref, ab_ref, h_ref, pqr_ref, hpre_ref, *,
                    eps):
    """``ab`` [2, K]: ``alpha_pre`` and ``b_pre`` over the first n lanes.
    ``hpre_ref`` is scratch: a map's column is read back from it."""
    n = x_ref.shape[0]
    _, pqr = _norm_and_products(x_ref, phi_ref, eps)
    pqr_ref[...] = pqr
    hpre_ref[...] = jax.nn.sigmoid(ab_ref[0:1, :] * pqr + ab_ref[1:2, :])
    h = hpre_ref[:, 0:1] * x_ref[0]
    for i in range(1, n):
        h = h + hpre_ref[:, i:i + 1] * x_ref[i]
    h_ref[...] = h


def _pre_reads_kernel(x_ref, phi_ref, dh_ref, pqr_ref, inv_ref, dhpre_ref, *,
                      eps):
    dh = dh_ref[...]

    def each(i, xi):
        dhpre_ref[:, i:i + 1] = _row_sum(dh * xi)

    inv_ref[...], pqr_ref[...] = _norm_and_products(x_ref, phi_ref, eps,
                                                    each)


def _pre_writes_kernel(x_ref, phi_ref, dh_ref, du_ref, hg_ref, dx_ref,
                       dphi_ref):
    """``hg`` [tT, n + 1]: ``H_pre`` and, last, the norm's factor ``g``.
    ``dphi_ref`` [n, K, C] stays in VMEM over a batch row's token tiles."""
    n = x_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    dh, du = dh_ref[...], du_ref[...]
    g = hg_ref[:, n:n + 1]
    for i in range(n):
        xi = x_ref[i]
        dx_ref[i] = (hg_ref[:, i:i + 1] * dh + _dot(du, phi_ref[i], _NN)
                     + g * xi)
        dphi_ref[i] += _dot(du, xi, _TN)


def _post_fwd_kernel(x_ref, coef_ref, y_ref, out_ref):
    """``coef`` [tT, n n + n]: ``H_res[i, j]`` at column ``i n + j``, then
    ``H_post[i]``."""
    n = x_ref.shape[0]
    y = y_ref[...].astype(_F32)
    for i in range(n):
        acc = coef_ref[:, n * n + i:n * n + i + 1] * y
        for j in range(n):
            acc = acc + coef_ref[:, i * n + j:i * n + j + 1] * x_ref[j]
        out_ref[i] = acc


def _post_bwd_kernel(x_ref, coef_ref, y_ref, d_ref, dx_ref, dcoef_ref,
                     dy_ref):
    """``dcoef_ref`` [tT, n n + n] stays in VMEM over a token tile's C
    tiles."""
    n = x_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    y = y_ref[...].astype(_F32)
    dy = jnp.zeros(y.shape, _F32)
    for i in range(n):
        di = d_ref[i]
        at = n * n + i
        dy = dy + coef_ref[:, at:at + 1] * di
        dcoef_ref[:, at:at + 1] += _row_sum(di * y)
        for j in range(n):
            at = i * n + j
            dcoef_ref[:, at:at + 1] += _row_sum(di * x_ref[j])
    dy_ref[...] = dy.astype(dy_ref.dtype)
    for j in range(n):
        dx = coef_ref[:, j:j + 1] * d_ref[0]
        for i in range(1, n):
            dx = dx + coef_ref[:, i * n + j:i * n + j + 1] * d_ref[i]
        dx_ref[j] = dx


def _row_specs(x, phi, tokens):
    """(grid, block specs by operand kind) of the kernels that hold whole
    rows of C: the grid is ``(batch, token tile)``; ``tokens`` a block's,
    None for ``_row_tokens``'s."""
    B, n, T, C = x.shape
    tT = tokens or _row_tokens(n, C)

    def per_token(width):
        return pl.BlockSpec((None, tT, width), lambda b, t: (b, t, 0))

    return (B, T // tT), dict(
        x=pl.BlockSpec((None, n, tT, C), lambda b, t: (b, 0, t, 0)),
        phi=pl.BlockSpec(phi.shape, lambda b, t: (0, 0, 0)),
        per_token=per_token, tokens=tT)


def _shape(shape, dtype=_F32):
    return jax.ShapeDtypeStruct(shape, dtype)


@functools.partial(jax.jit, static_argnames=("eps", "tokens", "interpret"))
def pre_forward(x, phi, ab, *, eps, tokens=None, interpret=False):
    """(h [B, T, C], pqr [B, T, K]) of the streams x, Phi as [n, K, C] and
    ``ab`` [2, K] (``_pre_fwd_kernel``)."""
    B, n, T, C = x.shape
    K = phi.shape[1]
    grid, s = _row_specs(x, phi, tokens)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[s["x"], s["phi"], pl.BlockSpec(ab.shape,
                                                 lambda b, t: (0, 0))],
        out_specs=[s["per_token"](C), s["per_token"](K)],
        out_shape=[_shape((B, T, C)), _shape((B, T, K))],
        scratch_shapes=[pltpu.VMEM((s["tokens"], K), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mhc_pre_fwd",
    )(x, phi, ab)


@functools.partial(jax.jit, static_argnames=("eps", "tokens", "interpret"))
def pre_grad_reads(x, phi, dh, *, eps, tokens=None, interpret=False):
    """The gradient's first pass: (pqr [B, T, K], inv [B, T, 1], dH_pre
    [B, T, n]) from the streams and ``dh`` [B, T, C]."""
    B, n, T, C = x.shape
    K = phi.shape[1]
    grid, s = _row_specs(x, phi, tokens)
    return pl.pallas_call(
        functools.partial(_pre_reads_kernel, eps=eps),
        grid=grid,
        in_specs=[s["x"], s["phi"], s["per_token"](C)],
        out_specs=[s["per_token"](K), s["per_token"](1), s["per_token"](n)],
        out_shape=[_shape((B, T, K)), _shape((B, T, 1)), _shape((B, T, n))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="mhc_pre_reads",
    )(x, phi, dh)


@functools.partial(jax.jit, static_argnames=("tokens", "interpret"))
def pre_grad_writes(x, phi, dh, du, hg, *, tokens=None, interpret=False):
    """The gradient's second pass: (dx [B, n, T, C], dPhi [B, n, K, C], a
    batch row's own sum) from ``du`` [B, T, K] (the products' cotangent,
    scaled) and ``hg`` [B, T, n + 1] (``H_pre``, then the norm's factor)."""
    B, n, T, C = x.shape
    K = phi.shape[1]
    grid, s = _row_specs(x, phi, tokens)
    return pl.pallas_call(
        _pre_writes_kernel,
        grid=grid,
        in_specs=[s["x"], s["phi"], s["per_token"](C), s["per_token"](K),
                  s["per_token"](n + 1)],
        out_specs=[s["x"], pl.BlockSpec((None, n, K, C),
                                        lambda b, t: (b, 0, 0, 0))],
        out_shape=[_shape(x.shape), _shape((B, n, K, C))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="mhc_pre_writes",
    )(x, phi, dh, du, hg)


def _tile_specs(x, tile):
    """(grid, block specs) of the kernels that tile C: the grid is ``(batch,
    token tile, C tile)``; ``tile`` a block's (tokens, lanes), None for
    ``TILE_TOKENS`` and ``_lanes``'s."""
    B, n, T, C = x.shape
    tT, tC = tile or (TILE_TOKENS, _lanes(C))
    return (B, T // tT, C // tC), dict(
        x=pl.BlockSpec((None, n, tT, tC), lambda b, t, c: (b, 0, t, c)),
        y=pl.BlockSpec((None, tT, tC), lambda b, t, c: (b, t, c)),
        coef=pl.BlockSpec((None, tT, n * n + n), lambda b, t, c: (b, t, 0)))


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def post_forward(x, coef, y, *, tile=None, interpret=False):
    """x' [B, n, T, C] from the streams, ``coef`` [B, T, n n + n]
    (``_post_fwd_kernel``) and the sublayer's output y [B, T, C]."""
    grid, s = _tile_specs(x, tile)
    return pl.pallas_call(
        _post_fwd_kernel,
        grid=grid,
        in_specs=[s["x"], s["coef"], s["y"]],
        out_specs=s["x"],
        out_shape=_shape(x.shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="mhc_post_fwd",
    )(x, coef, y)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def post_backward(x, coef, y, d, *, tile=None, interpret=False):
    """(dx [B, n, T, C], dcoef [B, T, n n + n], dy in y's type) from the
    forward's operands and the cotangent d [B, n, T, C] of x'."""
    grid, s = _tile_specs(x, tile)
    return pl.pallas_call(
        _post_bwd_kernel,
        grid=grid,
        in_specs=[s["x"], s["coef"], s["y"], s["x"]],
        out_specs=[s["x"], s["coef"], s["y"]],
        out_shape=[_shape(x.shape), _shape(coef.shape),
                   _shape(y.shape, y.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mhc_post_bwd",
    )(x, coef, y, d)
