"""Kimi Delta Attention's chunked delta rule (arXiv:2510.26692) as Pallas TPU
kernels: a chunk's ``[C, C]`` tiles, the triangular inverse and the carried
``[V, K]`` states live in VMEM, and HBM sees the operands and the result.

``ops/kda_ops.py`` holds the op, the gates both of its forms share, the
equations and the XLA form of the same algorithm; it calls here where
``kda_path`` says so. Every function below takes the gates' results: q, k
``[B, T, H, K]`` and v ``[B, T, H, V]`` as the op receives them (the L2
norms of q and k are taken here), the log-decays g ``[B, T, H, K]`` <= 0 and
the write strengths beta ``[B, T, H]`` float32, T a whole number of chunks.

Two kernels over the grid ``(batch, chunk, heads / HEADS)`` with the chunk
axis sequential and the states of ALL heads in one float32 scratch, so that
a step's beta block ``[C, H]`` and its gradient's are one block for all the
heads of a chunk. A head is a 128-lane slice of the token-major operands
``[B, T, H*K]``: nothing is transposed on either side of a call.

- ``kda_fwd``: for each head of the step the in-chunk cumulative decays
  ``G`` (a product with a triangle of ones), the two decayed Gram matrices,
  ``(I + Diag(beta) A)^-1``, the rows written ``U = T (beta v) - T (beta k
  e^G) S``, the outputs and ``S <- e^{G_C} S + (k e^{G_C - G})^T U``; with
  ``save`` also the state that enters each chunk, which the backward needs.
- ``kda_bwd``: the chunks in reverse with ``dS`` carried in the scratch;
  makes a chunk's tiles again from the operands and the entering state and
  gives dq, dk, dv, dg and dbeta.

**No exponent is ever positive**, as in the XLA form, by other means. A
pair (t, s), s < t, of a chunk lies in different halves of exactly one
aligned block of 2m positions, m = C/2 ... ``BASE``, or in one block of
``BASE``. At level m every position has one reference row, the first of the
upper half of its block of 2m: ``e^{G_t - G_s} = e^{-|G_t - R|} e^{-|R -
G_s|}`` for t above and s below it, so ONE array ``e^{-|G - R_m|}`` serves
both sides, and a level is one masked product of ``[C, K]`` operands in the
MXU's type. Pairs inside a block of ``BASE`` positions are summed channel
by channel with the exponent ``G_t - G_s`` itself, a shift along the
positions a distance, in float32. The gradient of a Gram matrix needs no
derivative of any reference: ``dG = a da - b db`` with da, db the sums
against the same decays.

The inverse of ``I + M`` (M strictly lower): blocks of 8 by ``(I - L)(I +
L^2)(I + L^4)`` (exact, L nilpotent; powers of an 8 x 8 block stay small),
then pairs of blocks by ``[[P, 0], [X, R]]^-1 = [[P', 0], [-R' X P', R']]``,
block forward substitution, float32 products at ``HIGHEST``. Its gradient
is ``-(T^T dT T^T)``.

Float32: the decays, their sums and exponentials, the norms, the inverse
and its products, the carried states. The MXU's other operands have v's
type (bf16 under AMP), sums are float32, as in the XLA form.

Each kernel sits behind one ``jax.jit``: the layers of a program share one
lowering.
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

BASE = 8        # positions whose pairs are summed channel by channel
HEADS = 4       # heads of a grid step
L2_EPS = 1e-6   # under the square root of the L2 norms of q and k


def fits(q, v, chunk):
    """Whether the kernels' blocks take these operands: heads of whole
    128-lane tiles in whole steps, a chunk that is a power of two of whole
    sublane tiles and divides T, bf16 or float32."""
    _, T, H, K = q.shape
    return bool(K % 128 == 0 and v.shape[-1] % 128 == 0 and H % HEADS == 0
                and chunk in (16, 32, 64, 128) and T % chunk == 0
                and v.dtype in (jnp.bfloat16, jnp.float32))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _dot32(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HI,
                               preferred_element_type=_F32)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _iotas(C):
    return (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0),
            jax.lax.broadcasted_iota(jnp.int32, (C, C), 1))


def _levels(C):
    """log2 of the half sizes m = C/2 ... BASE."""
    return range(C.bit_length() - 2, BASE.bit_length() - 2, -1)


def _level_mask(rows, cols, lg):
    """t and s in one aligned block of 2m and in different halves of it
    (m = 2^lg); with s < t that is t above, s below."""
    return ((rows >> (lg + 1)) == (cols >> (lg + 1))) \
        & ((rows >> lg) > (cols >> lg))


def _level_decay(G, lg):
    """``e^{-|G - R|}`` [C, K] with R the row of G at the first position of
    the upper half of each aligned block of 2m = 2^(lg + 1) positions."""
    C, K = G.shape
    m = 1 << lg
    R = jnp.concatenate([
        jnp.broadcast_to(G[j * 2 * m + m:j * 2 * m + m + 1], (2 * m, K))
        for j in range(C // (2 * m))], axis=0)
    return jnp.exp(-jnp.abs(G - R))


def _band_decay(G, d):
    """``e^{G_t - G_{t-d}}`` [C, K] where t - d lies in t's block of BASE
    positions, 0 elsewhere."""
    inside = (jax.lax.broadcasted_iota(jnp.int32, G.shape, 0)
              & (BASE - 1)) >= d
    return jnp.exp(jnp.where(inside, G - pltpu.roll(G, d, 0), -jnp.inf))


def _place(rows, cols, d, column):
    """[C, C] with ``column`` [C, 1] on the d-th diagonal under the main."""
    return jnp.where(cols == rows - d, column, 0.0)


def _pick(rows, cols, d, M):
    """The d-th diagonal under the main of M [C, C] as a column [C, 1]."""
    return _rowsum(jnp.where(cols == rows - d, M, 0.0))


class _Chunk:
    """What a head's chunk is made of, from its operands alone: both
    kernels build it, the backward reads more of it."""

    def __init__(self, q, k, v, g, beta, tri, rows, cols):
        C, K = g.shape
        self.mxu = mxu = v.dtype
        q, k, self.v = q.astype(_F32), k.astype(_F32), v.astype(_F32)
        self.scale = float(K) ** -0.5
        self.rq = jax.lax.rsqrt(_rowsum(q * q) + L2_EPS)
        self.rk = jax.lax.rsqrt(_rowsum(k * k) + L2_EPS)
        self.nq = q * self.rq
        self.qn, self.kn = self.nq * self.scale, k * self.rk
        self.beta = beta
        G = _dot32(tri, g)                          # in-chunk cumulative sums
        self.E = jnp.exp(G)
        self.last = G[C - 1:]                       # [1, K]
        self.Eend = jnp.exp(self.last - G)
        self.Kd, self.Qd = self.kn * self.E, self.qn * self.E
        self.Ke = self.kn * self.Eend
        # the Gram matrices: A strict, P with the diagonal
        A = jnp.zeros((C, C), _F32)
        P = _place(rows, cols, 0, _rowsum(self.qn * self.kn))
        self.levels = []
        for lg in _levels(C):
            El = _level_decay(G, lg)
            X, Y = (self.kn * El).astype(mxu), (self.qn * El).astype(mxu)
            YX = jnp.concatenate([Y, X], axis=0)
            both = _dot(YX, X, _NT)                 # [2C, C]
            mask = _level_mask(rows, cols, lg)
            P = P + jnp.where(mask, both[:C], 0.0)
            A = A + jnp.where(mask, both[C:], 0.0)
            self.levels.append((El, X, YX, mask))
        self.bands = []
        for d in range(1, BASE):
            F = _band_decay(G, d)
            ks = pltpu.roll(self.kn, d, 0) * F      # k_{t-d} e^{G_t-G_{t-d}}
            A = A + _place(rows, cols, d, _rowsum(self.kn * ks))
            P = P + _place(rows, cols, d, _rowsum(self.qn * ks))
            self.bands.append((F, ks))
        self.A, self.P = A, P
        self.T = _inverse(beta * A, rows, cols)
        self.bKd, self.bv = beta * self.Kd, beta * self.v
        self.W = _dot32(self.T, self.bKd)           # [C, K]
        self.U0 = _dot32(self.T, self.bv)           # [C, V]

    def written(self, state):
        """The rows the chunk writes, ``U0 - W S``, for the state [V, K]
        that enters it (in the MXU's type)."""
        return self.U0 - _dot(self.W.astype(self.mxu), state, _NT)


def _inverse(M, rows, cols):
    """``(I + M)^-1`` for strictly lower-triangular M [C, C] float32."""
    C = M.shape[0]
    eye = (rows == cols).astype(_F32)
    L = jnp.where((rows >> 3) == (cols >> 3), M, 0.0)
    L2 = _dot32(L, L)
    inv = _dot32(_dot32(eye - L, eye + L2), eye + _dot32(L2, L2))
    for lg in range(3, C.bit_length() - 1):
        off = jnp.where(((rows >> (lg + 1)) == (cols >> (lg + 1)))
                        & ((rows >> lg) != (cols >> lg)), M, 0.0)
        inv = inv - _dot32(_dot32(inv, off), inv)
    return inv


def _tri(C):
    rows, cols = _iotas(C)
    return (cols <= rows).astype(_F32), rows, cols


def _head_beta(tile, head):
    """Column ``head`` of the beta block [C, H] as [C, 1]."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return _rowsum(jnp.where(lanes == head, tile, 0.0))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                K, V, save):
    st_ref, s_ref = rest if save else (None,) + rest
    C = q_ref.shape[1]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[j] = jnp.zeros(s_ref.shape[1:], _F32)

    tri, rows, cols = _tri(C)
    betas = beta_ref[0]
    for h in range(HEADS):
        kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        c = _Chunk(q_ref[0, :, kl], k_ref[0, :, kl], v_ref[0, :, vl],
                   g_ref[0, :, kl], _head_beta(betas, j * HEADS + h),
                   tri, rows, cols)
        st = s_ref[j, h]                                    # [V, K]
        if save:
            st_ref[0, 0, h] = st
        sm = st.astype(c.mxu)
        u = c.written(sm).astype(c.mxu)
        o = _dot(c.Qd.astype(c.mxu), sm, _NT) \
            + _dot(c.P.astype(c.mxu), u, _NN)
        o_ref[0, :, vl] = o.astype(o_ref.dtype)
        s_ref[j, h] = st * jnp.exp(c.last) \
            + _dot(u, c.Ke.astype(c.mxu), _TN)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ds_ref, *, K, V):
    C = q_ref.shape[1]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        ds_ref[j] = jnp.zeros(ds_ref.shape[1:], _F32)

    @pl.when(j == 0)
    def _():
        dbeta_ref[...] = jnp.zeros(dbeta_ref.shape, _F32)

    tri, rows, cols = _tri(C)
    betas = beta_ref[0]
    lanes = jax.lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    for h in range(HEADS):
        kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
        head = j * HEADS + h
        c = _Chunk(q_ref[0, :, kl], k_ref[0, :, kl], v_ref[0, :, vl],
                   g_ref[0, :, kl], _head_beta(betas, head),
                   tri, rows, cols)
        mxu = c.mxu
        st, dst = st_ref[0, 0, h], ds_ref[j, h]             # [V, K]
        sm, dsm = st.astype(mxu), dst.astype(mxu)
        u = c.written(sm).astype(mxu)
        do = do_ref[0, :, vl].astype(mxu)
        Pm, Kem = c.P.astype(mxu), c.Ke.astype(mxu)
        # o = Qd S + P u;  S' = e^last S + Ke^T u;  u = U0 - W S
        du = _dot(Pm, do, _TN) + _dot(Kem, dsm, _NT)        # [C, V]
        dP = jnp.where(cols <= rows, _dot(do, u, _NT), 0.0)
        dQd = _dot(do, sm, _NN)                             # [C, K]
        dKe = _dot(u, dsm, _NN)
        dum = du.astype(mxu)
        dW = -_dot(dum, sm, _NN)
        decay = jnp.exp(c.last)
        ds_ref[j, h] = dst * decay \
            + _dot(do, c.Qd.astype(mxu), _TN) \
            - _dot(dum, c.W.astype(mxu), _TN)
        dlast = jnp.sum(dst * st, axis=0, keepdims=True) * decay \
            + jnp.sum(dKe * c.Ke, axis=0, keepdims=True)    # [1, K]
        # U0 = T (beta v), W = T (beta Kd), T = (I + beta A)^-1
        dbv, dbKd = _dot32(c.T, du, _TN), _dot32(c.T, dW, _TN)
        dT = _dot32(du, c.bv, _NT) + _dot32(dW, c.bKd, _NT)
        dM = -jnp.where(cols < rows,
                        _dot32(_dot32(c.T, dT, _TN), c.T, _NT), 0.0)
        dbeta = _rowsum(dM * c.A) + _rowsum(dbv * c.v) \
            + _rowsum(dbKd * c.Kd)
        dbeta_ref[0] += jnp.where(lanes == head, dbeta, 0.0)
        dA = c.beta * dM
        dv_ref[0, :, vl] = (c.beta * dbv).astype(dv_ref.dtype)
        dKd = c.beta * dbKd
        dkn = dKd * c.E + dKe * c.Eend
        dqn = dQd * c.E
        dG = dKd * c.Kd + dQd * c.Qd - dKe * c.Ke
        # the Gram matrices: da, db against the same decays, dG = a da - b db
        for El, X, YX, mask in c.levels:
            both = jnp.concatenate([jnp.where(mask, dP, 0.0).astype(mxu),
                                    jnp.where(mask, dA, 0.0).astype(mxu)],
                                   axis=0)                  # [2C, C]
            above = _dot(both, X, _NN)                      # [2C, K]
            da_p, da_a = El * above[:C], El * above[C:]
            db = El * _dot(both, YX, _TN)                   # [C, K]
            dqn = dqn + da_p
            dkn = dkn + da_a + db
            dG = dG + c.qn * da_p + c.kn * (da_a - db)
        c0 = _pick(rows, cols, 0, dP)
        dqn = dqn + c0 * c.kn
        dkn = dkn + c0 * c.qn
        for d, (F, ks) in enumerate(c.bands, 1):
            ca, cp = _pick(rows, cols, d, dA), _pick(rows, cols, d, dP)
            da_a, da_p = ca * ks, cp * ks
            db = pltpu.roll((ca * c.kn + cp * c.qn) * F, C - d, 0)
            dqn = dqn + da_p
            dkn = dkn + da_a + db
            dG = dG + c.qn * da_p + c.kn * (da_a - db)
        # G = cumulative sums of g; the chunk's last row also fed ``last``
        dG = dG + jnp.where(
            jax.lax.broadcasted_iota(jnp.int32, dG.shape, 0) == C - 1,
            dlast, 0.0)
        dg_ref[0, :, kl] = _dot32(tri, dG, _TN)
        dnq = dqn * c.scale
        dq_ref[0, :, kl] = (c.rq * (dnq - c.nq * _rowsum(dnq * c.nq))
                            ).astype(dq_ref.dtype)
        dk_ref[0, :, kl] = (c.rk * (dkn - c.kn * _rowsum(dkn * c.kn))
                            ).astype(dk_ref.dtype)


def _flat(a):
    """[B, T, H, D] -> [B, T, H*D]: a head is a lane tile."""
    return a.reshape(a.shape[:2] + (-1,))


def _specs(dims, chunk, reverse=False):
    B, T, H, K, V = dims
    N = T // chunk

    def at(n):
        return N - 1 - n if reverse else n

    def tokens(width):
        return pl.BlockSpec((1, chunk, HEADS * width),
                            lambda b, n, j: (b, at(n), j))

    betas = pl.BlockSpec((1, chunk, H), lambda b, n, j: (b, at(n), 0))
    states = pl.BlockSpec((1, 1, HEADS, V, K),
                          lambda b, n, j: (b, at(n), j, 0, 0))
    return tokens, betas, states


_PARAMS = dict(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "save", "interpret"))
def forward(q, k, v, g, beta, *, chunk, save=False, interpret=False):
    """o [B, T, H, V]; with ``save`` also the state that enters each chunk,
    [B, T / chunk, H, V, K] float32."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    tokens, betas, states = _specs((B, T, H, K, V), chunk)
    out_shape = [jax.ShapeDtypeStruct((B, T, H * V), v.dtype)]
    out_specs = [tokens(V)]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((B, T // chunk, H, V, K), _F32))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, V=V, save=save),
        grid=(B, T // chunk, H // HEADS),
        in_specs=[tokens(K), tokens(K), tokens(V), tokens(K), betas],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H // HEADS, HEADS, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name="kda_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)),
      beta.astype(_F32))
    o = out[0].reshape(B, T, H, V)
    return (o, out[1]) if save else o


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def backward(q, k, v, g, beta, do, *, chunk, interpret=False):
    """(dq, dk, dv, dg, dbeta) of ``forward``'s o for its cotangent do."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    _, entering = forward(q, k, v, g, beta, chunk=chunk, save=True,
                          interpret=interpret)
    tokens, betas, states = _specs((B, T, H, K, V), chunk, reverse=True)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, V=V),
        grid=(B, T // chunk, H // HEADS),
        in_specs=[tokens(K), tokens(K), tokens(V), tokens(K), betas,
                  tokens(V), states],
        out_specs=[tokens(K), tokens(K), tokens(V), tokens(K), betas],
        out_shape=[jax.ShapeDtypeStruct((B, T, H * K), q.dtype),
                   jax.ShapeDtypeStruct((B, T, H * K), k.dtype),
                   jax.ShapeDtypeStruct((B, T, H * V), v.dtype),
                   jax.ShapeDtypeStruct((B, T, H * K), _F32),
                   jax.ShapeDtypeStruct((B, T, H), _F32)],
        scratch_shapes=[pltpu.VMEM((H // HEADS, HEADS, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name="kda_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)),
      beta.astype(_F32), _flat(do.astype(v.dtype)), entering)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def delta_rule(q, k, v, g, beta, chunk, interpret=False):
    """The kernels' delta rule, differentiable: o [B, T, H, V]."""
    return forward(q, k, v, g, beta, chunk=chunk, interpret=interpret)


def _rule_fwd(q, k, v, g, beta, chunk, interpret):
    return (forward(q, k, v, g, beta, chunk=chunk, interpret=interpret),
            (q, k, v, g, beta))


def _rule_bwd(chunk, interpret, kept, do):
    return backward(*kept, do, chunk=chunk, interpret=interpret)


delta_rule.defvjp(_rule_fwd, _rule_bwd)
