"""Kimi Delta Attention's chunked delta rule (arXiv:2510.26692) as Pallas TPU
kernels: a chunk's ``[C, C]`` tiles, the triangular inverse and the carried
``[V, K]`` states live in VMEM, and HBM sees the operands and the result.

``ops/kda_ops.py`` holds the op, the gates both of its forms share, the
equations and the XLA form of the same algorithm; it calls here where
``kda_path`` says so. Every function below takes the gates' results: q, k
``[B, T, H, K]`` and v ``[B, T, H, V]`` as the op receives them (the L2
norms of q and k are taken here), the log-decays g ``[B, T, H, K]`` <= 0 and
the write strengths beta ``[B, T, H]`` float32, T a whole number of chunks.

Three kernels over the grid ``(batch, chunk, heads / HEADS)`` with the chunk
axis sequential and the states of ALL heads in one float32 scratch, so that
a step's beta block ``[C, H]`` and its gradient's are one block for all the
heads of a chunk. A head is a 128-lane slice of the token-major operands
``[B, T, H*K]``: nothing is transposed on either side of a call.

- ``kda_fwd``, the forward op: for each head of the step the in-chunk
  cumulative decays ``G`` (a product with a triangle of ones), the two
  decayed Gram matrices, ``T = (I + Diag(beta) A)^-1``, the rows written ``U
  = T (beta v) - T (beta k e^G) S``, the outputs and ``S <- e^{G_C} S + (k
  e^{G_C - G})^T U``.
- ``kda_states``, the gradient op's pass over the chunks in order: the same
  without what only the outputs need (no q, no ``P``, no ``o``). It keeps, of
  every (chunk, head), the state that enters it and ``T``, ``A``, ``W = T
  (beta k e^G)`` and ``U0 = T (beta v)``: 144 KiB at a chunk of 64, alive
  inside the gradient op only.
- ``kda_bwd``: the chunks in reverse with ``dS`` carried in the scratch;
  reads what the state pass kept, makes from the operands what is
  elementwise in them and ``P`` (the state pass has no q), no inverse, and
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
    """What a head's chunk is made of. From its operands alone and
    elementwise (but for one product with the triangle): the norms, the
    decays and the operands they scale, without the q side where q is None.
    The Gram matrices (``grams``) and the inverse with its two products
    (``solve``) are made where a kernel asks for them: the backward is
    handed what the state pass made of them instead (``kept``)."""

    def __init__(self, q, k, v, g, beta, tri, rows, cols):
        C, K = g.shape
        self.mxu = v.dtype
        self.rows, self.cols = rows, cols
        k, self.v = k.astype(_F32), v.astype(_F32)
        self.rk = jax.lax.rsqrt(_rowsum(k * k) + L2_EPS)
        self.kn = k * self.rk
        if q is not None:
            q = q.astype(_F32)
            self.scale = float(K) ** -0.5
            self.rq = jax.lax.rsqrt(_rowsum(q * q) + L2_EPS)
            self.nq = q * self.rq
            self.qn = self.nq * self.scale
        self.beta = beta
        self.G = G = _dot32(tri, g)                 # in-chunk cumulative sums
        self.E = jnp.exp(G)
        self.last = G[C - 1:]                       # [1, K]
        self.Eend = jnp.exp(self.last - G)
        self.Kd = self.kn * self.E
        if q is not None:
            self.Qd = self.qn * self.E
        self.Ke = self.kn * self.Eend

    def grams(self, of_q, of_k):
        """(P, A) [C, C] float32, None where not asked for: the decayed
        Gram matrices of q against k with the diagonal and of k against k
        strictly under it. A level is ONE product for those asked for.
        The decays of the levels and of the bands stay (``levels``,
        ``bands``): the Gram matrices' gradient is taken against them."""
        rows, cols, mxu = self.rows, self.cols, self.mxu
        C = self.kn.shape[0]
        A = jnp.zeros((C, C), _F32) if of_k else None
        P = _place(rows, cols, 0, _rowsum(self.qn * self.kn)) if of_q else None
        self.levels = []
        for lg in _levels(C):
            El = _level_decay(self.G, lg)
            X = (self.kn * El).astype(mxu)
            YX = jnp.concatenate([(self.qn * El).astype(mxu), X], axis=0) \
                if of_q else X
            left = YX if of_k else YX[:C]
            both = _dot(left, X, _NT)               # [2C, C] for both
            mask = _level_mask(rows, cols, lg)
            if of_q:
                P = P + jnp.where(mask, both[:C], 0.0)
            if of_k:
                A = A + jnp.where(mask, both[-C:], 0.0)
            self.levels.append((El, X, YX, mask))
        self.bands = []
        for d in range(1, BASE):
            F = _band_decay(self.G, d)
            ks = pltpu.roll(self.kn, d, 0) * F      # k_{t-d} e^{G_t-G_{t-d}}
            if of_k:
                A = A + _place(rows, cols, d, _rowsum(self.kn * ks))
            if of_q:
                P = P + _place(rows, cols, d, _rowsum(self.qn * ks))
            self.bands.append((F, ks))
        return P, A

    def solve(self, A):
        """``T = (I + Diag(beta) A)^-1`` and its products with the operands,
        ``W = T (beta Kd)`` [C, K] and ``U0 = T (beta v)`` [C, V]."""
        T = _inverse(self.beta * A, self.rows, self.cols)
        self.kept(T, A, _dot32(T, self.beta * self.Kd),
                  _dot32(T, self.beta * self.v))

    def kept(self, T, A, W, U0):
        """W in the MXU's type, which is all any product reads of it."""
        self.T, self.A, self.U0 = T, A, U0
        self.W = W.astype(self.mxu)

    def written(self, state):
        """The rows the chunk writes, ``U0 - W S``, for the state [V, K]
        that enters it (in the MXU's type)."""
        return self.U0 - _dot(self.W, state, _NT)

    def leaving(self, state, u):
        """The state [V, K] float32 that leaves the chunk, ``e^{G_C} S +
        u^T Ke``, for the one that entered and the rows written (in the
        MXU's type)."""
        return state * jnp.exp(self.last) \
            + _dot(u, self.Ke.astype(self.mxu), _TN)


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


def _head(refs, h, K, V, betas, head, tri, rows, cols):
    """The chunk of head ``h`` of a step from the operands' blocks (q, k, v,
    g), q None in the state pass."""
    kl, vl = slice(h * K, (h + 1) * K), slice(h * V, (h + 1) * V)
    q_ref, k_ref, v_ref, g_ref = refs
    return _Chunk(None if q_ref is None else q_ref[0, :, kl], k_ref[0, :, kl],
                  v_ref[0, :, vl], g_ref[0, :, kl], _head_beta(betas, head),
                  tri, rows, cols)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, s_ref, *, K, V):
    C = q_ref.shape[1]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[j] = jnp.zeros(s_ref.shape[1:], _F32)

    tri, rows, cols = _tri(C)
    betas = beta_ref[0]
    for h in range(HEADS):
        c = _head((q_ref, k_ref, v_ref, g_ref), h, K, V, betas,
                  j * HEADS + h, tri, rows, cols)
        P, A = c.grams(True, True)
        c.solve(A)
        st = s_ref[j, h]                                    # [V, K]
        sm = st.astype(c.mxu)
        u = c.written(sm).astype(c.mxu)
        o = _dot(c.Qd.astype(c.mxu), sm, _NT) + _dot(P.astype(c.mxu), u, _NN)
        o_ref[0, :, h * V:(h + 1) * V] = o.astype(o_ref.dtype)
        s_ref[j, h] = c.leaving(st, u)


def _states_kernel(k_ref, v_ref, g_ref, beta_ref, st_ref, ta_ref, w_ref,
                   u0_ref, s_ref, *, K, V):
    C = k_ref.shape[1]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[j] = jnp.zeros(s_ref.shape[1:], _F32)

    tri, rows, cols = _tri(C)
    betas = beta_ref[0]
    for h in range(HEADS):
        c = _head((None, k_ref, v_ref, g_ref), h, K, V, betas,
                  j * HEADS + h, tri, rows, cols)
        c.solve(c.grams(False, True)[1])
        st = s_ref[j, h]                                    # [V, K]
        st_ref[0, 0, h] = st
        ta_ref[0, 0, h] = jnp.concatenate([c.T, c.A], axis=1)
        w_ref[0, 0, h] = c.W
        u0_ref[0, 0, h] = c.U0
        s_ref[j, h] = c.leaving(st, c.written(st.astype(c.mxu)).astype(c.mxu))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, st_ref, ta_ref,
                w_ref, u0_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                ds_ref, *, K, V):
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
        c = _head((q_ref, k_ref, v_ref, g_ref), h, K, V, betas, head,
                  tri, rows, cols)
        P, _ = c.grams(True, False)
        ta = ta_ref[0, 0, h]
        c.kept(ta[:, :C], ta[:, C:], w_ref[0, 0, h], u0_ref[0, 0, h])
        mxu = c.mxu
        st, dst = st_ref[0, 0, h], ds_ref[j, h]             # [V, K]
        sm, dsm = st.astype(mxu), dst.astype(mxu)
        u = c.written(sm).astype(mxu)
        do = do_ref[0, :, vl].astype(mxu)
        Pm, Kem = P.astype(mxu), c.Ke.astype(mxu)
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
            - _dot(dum, c.W, _TN)
        dlast = jnp.sum(dst * st, axis=0, keepdims=True) * decay \
            + jnp.sum(dKe * c.Ke, axis=0, keepdims=True)    # [1, K]
        # U0 = T (beta v), W = T (beta Kd), T = (I + beta A)^-1
        dbv, dbKd = _dot32(c.T, du, _TN), _dot32(c.T, dW, _TN)
        dT = _dot32(du, c.beta * c.v, _NT) + _dot32(dW, c.beta * c.Kd, _NT)
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

    def tiles(rows, width):
        """A [rows, width] tile a (chunk, head) of [B, N, H, rows, width]."""
        return pl.BlockSpec((1, 1, HEADS, rows, width),
                            lambda b, n, j: (b, at(n), j, 0, 0))

    betas = pl.BlockSpec((1, chunk, H), lambda b, n, j: (b, at(n), 0))
    return tokens, betas, tiles


def _kept(dims, chunk, mxu, reverse=False):
    """(shapes, specs) of what the state pass keeps of every (chunk, head)
    for the backward: the state that enters the chunk [V, K], ``T | A``
    side by side [C, 2C] and ``U0`` [C, V], float32, and ``W`` [C, K] in
    the MXU's type."""
    B, T, H, K, V = dims
    tiles = _specs(dims, chunk, reverse)[2]
    sizes = ((V, K, _F32), (chunk, 2 * chunk, _F32), (chunk, K, mxu),
             (chunk, V, _F32))
    return ([jax.ShapeDtypeStruct((B, T // chunk, H, r, w), t)
             for r, w, t in sizes], [tiles(r, w) for r, w, _ in sizes])


_PARAMS = dict(dimension_semantics=("parallel", "arbitrary", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def forward(q, k, v, g, beta, *, chunk, interpret=False):
    """o [B, T, H, V]."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    tokens, betas, _ = _specs((B, T, H, K, V), chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, K=K, V=V),
        grid=(B, T // chunk, H // HEADS),
        in_specs=[tokens(K), tokens(K), tokens(V), tokens(K), betas],
        out_specs=tokens(V),
        out_shape=jax.ShapeDtypeStruct((B, T, H * V), v.dtype),
        scratch_shapes=[pltpu.VMEM((H // HEADS, HEADS, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name="kda_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)),
      beta.astype(_F32)).reshape(B, T, H, V)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def states(k, v, g, beta, *, chunk, interpret=False):
    """The gradient op's pass over the chunks in order, which makes no
    output: what ``_kept`` lists, [B, T / chunk, H, ...] each."""
    B, T, H, K = k.shape
    V = v.shape[-1]
    dims = (B, T, H, K, V)
    tokens, betas, _ = _specs(dims, chunk)
    out_shape, out_specs = _kept(dims, chunk, v.dtype)
    return pl.pallas_call(
        functools.partial(_states_kernel, K=K, V=V),
        grid=(B, T // chunk, H // HEADS),
        in_specs=[tokens(K), tokens(V), tokens(K), betas],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((H // HEADS, HEADS, V, K), _F32)],
        compiler_params=pltpu.CompilerParams(**_PARAMS),
        interpret=interpret, name="kda_states",
    )(_flat(k), _flat(v), _flat(g.astype(_F32)), beta.astype(_F32))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def backward(q, k, v, g, beta, do, *, chunk, interpret=False):
    """(dq, dk, dv, dg, dbeta) of ``forward``'s o for its cotangent do:
    ``states``, then the chunks in reverse."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    dims = (B, T, H, K, V)
    kept = states(k, v, g, beta, chunk=chunk, interpret=interpret)
    tokens, betas, _ = _specs(dims, chunk, reverse=True)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, K=K, V=V),
        grid=(B, T // chunk, H // HEADS),
        in_specs=[tokens(K), tokens(K), tokens(V), tokens(K), betas,
                  tokens(V)] + _kept(dims, chunk, v.dtype, reverse=True)[1],
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
      beta.astype(_F32), _flat(do.astype(v.dtype)), *kept)
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
