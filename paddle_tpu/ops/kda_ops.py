"""Kimi Delta Attention's core (arXiv:2510.26692): a gated delta rule whose
decay differs by channel, with its gradient op.

A head keeps a state ``S [K, V]``, zero at the start, and at each position

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with a log-decay ``g_t <= 0`` for every one of the K channels of a head and
a write strength ``beta_t`` in (0, 1). The op computes the chunkwise form:
with ``G`` the cumulative sums of ``g`` inside a chunk of C positions and
``u_t = beta_t (v_t - k_t^T Diag(exp(g_t)) S_{t-1})`` the row that position
t writes,

    (I + Diag(beta) A) U = Diag(beta) (V - (K . exp(G)) S_0),
    A[t, s] = sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])   for s < t,
    O = (Q . exp(G)) S_0 + B U,   B[t, s] = the same sum with q_t, s <= t,
    S_C = Diag(exp(G_C)) S_0 + (K . exp(G_C - G))^T U,

so a chunk costs products, the inverse of one unit lower-triangular [C, C]
matrix, and one step of a recurrence over the chunks.

Two forms of it, chosen by ``kda_path`` from what the op sees: on a TPU,
where the heads fill whole lane tiles, the Pallas kernels of
``ops/pallas/kda.py``, which keep a chunk's tiles and the states in VMEM
and, in the gradient op, make a chunk's inverse once, in a state pass whose
tiles the backward kernel reads (their module has how); everywhere else the XLA einsums below, which the
CPU's tests run and the kernels are held to. Both take the gates here
(``gates``) and both keep what follows.

**No exponent is ever positive** (the einsums' way; the kernels reach the
same by other means). ``A`` and ``B`` are sums of ``exp(G_t -
G_s)`` with ``s <= t``; splitting that into ``exp(G_t) exp(-G_s)`` makes
them one product but ``exp(-G_s)`` leaves float32 once a chunk's decays
sum past -88, which strong gates reach while the recurrence runs on
untroubled. Here a chunk is cut into sub-blocks of ``SUB`` positions: a
pair of different sub-blocks is a product of ``a_t exp(G_t - R)`` and ``b_s
exp(R - G_s)`` with ``R`` the cumulative sum at the first position of t's
sub-block, which lies between the two, so both exponents are <= 0; a
sub-block against itself is summed channel by channel with the exponent
``G_t - G_s`` itself (``_decayed_gram``, ``_own_gram``). What underflows
there is smaller than float32 can add to the terms beside it.

Float32 whatever the operands' type: the gates (softplus, sigmoid), the
log-decays, their cumulative sums and every exponential of them, the L2
norms of q and k, the triangular inverse and its two products, the carried
state. q, k, v arrive in the AMP type and are the MXU's operands, as are
the rows and states cast to it where a product is not named above;
accumulation is float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op
from .pallas import kda as _kernels
from .ssm_ops import _chunks, _fa, _pad_time

_HI = jax.lax.Precision.HIGHEST
SUB = 8               # positions of a sub-block of a chunk
HEADS_A_PASS = 4      # heads whose chunk rows are made together
L2_EPS = _kernels.L2_EPS   # under the square root of the L2 norms of q, k


def kda_path(q, v, chunk=64):
    """"pallas" | "xla_chunked": which form of the delta rule these
    operands take. The kernels where the computation runs on a TPU (asked
    through ``ops.pallas.flash_attention``, as ``benchmarks/aot_sizing.py``
    answers there) and the padded length, the chunk and the heads fill the
    kernels' blocks (``ops.pallas.kda.fits``)."""
    C, pad = _chunks(q.shape[1], chunk)
    padded = jax.ShapeDtypeStruct(
        (q.shape[0], q.shape[1] + pad) + q.shape[2:], q.dtype)
    on_tpu = _fa.compute_platform() == "tpu"
    return "pallas" if on_tpu and _kernels.fits(padded, v, C) \
        else "xla_chunked"


def _sub_block(C):
    """The largest sub-block of at most ``SUB`` positions that tiles C."""
    return next(c for c in range(min(SUB, C), 0, -1) if C % c == 0)


def _pairs(c, strict):
    """[c, c] bool: position s is before position t (or is t, unless
    ``strict``)."""
    return jnp.tril(jnp.ones((c, c), bool), -1 if strict else 0)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _own_gram(a, b, G, strict):
    """M [c, c, X] with ``M[t, s] = sum_d a[t, d] b[s, d] exp(G[t, d] - G[s,
    d])`` for ``s <= t`` (``s < t`` if ``strict``), 0 above: a sub-block
    against itself, the exponent the difference itself. a, b, G [c, D, X]
    float32 with everything that is no position or channel (batch, chunk,
    sub-block, head) along the minor axis X, so that the sum over the
    channels adds whole vectors. Its gradient is written out below: two
    more such sums, nothing of [c, c, D, X] kept or made twice."""
    decay = jnp.exp(jnp.where(_pairs(a.shape[0], strict)[:, :, None, None],
                              G[:, None] - G[None, :], -jnp.inf))
    return jnp.sum(a[:, None] * b[None, :] * decay, 2)


def _own_gram_fwd(a, b, G, strict):
    return _own_gram(a, b, G, strict), (a, b, G)


def _own_gram_bwd(strict, kept, dM):
    """``da[t] = sum_s dM[t, s] b[s] E[t, s]``, ``db[s] = sum_t dM[t, s]
    a[t] E[t, s]`` and, because G enters through ``E = exp(G_t - G_s)``
    alone, ``dG = a da - b db``. The second sum is built with s leading: as
    written it shares no array with the first, which XLA would otherwise
    make once, whole, for both."""
    a, b, G = kept
    keep = _pairs(a.shape[0], strict)
    by_t = jnp.exp(jnp.where(keep[:, :, None, None],
                             G[:, None] - G[None, :], -jnp.inf))
    da = jnp.sum(dM[:, :, None] * b[None, :] * by_t, 1)
    by_s = jnp.exp(jnp.where(keep.T[:, :, None, None],
                             G[None, :] - G[:, None], -jnp.inf))
    db = jnp.sum(jnp.swapaxes(dM, 0, 1)[:, :, None] * a[None, :] * by_s, 1)
    return da, db, a * da - b * db


_own_gram.defvjp(_own_gram_fwd, _own_gram_bwd)


def _decayed_gram(a, b, G, strict, mxu):
    """M [B, N, H, C, C] float32 with ``M[t, s] = sum_d a_t[d] b_s[d]
    exp(G_t[d] - G_s[d])`` for ``s <= t`` (``s < t`` if ``strict``) and 0
    above. a, b, G [B, N, C, H, D] float32, G non-increasing along C."""
    f32 = jnp.float32
    Bsz, N, C, H, D = G.shape
    c = _sub_block(C)
    n = C // c

    def blocks(x):
        return x.reshape(Bsz, N, n, c, H, D)

    ab, bb, Gb = blocks(a), blocks(b), blocks(G)

    def minor(x):      # [b,n,i,c,h,d] -> [c, d, (b n i h)]
        return x.transpose(3, 5, 0, 1, 2, 4).reshape(c, D, -1)

    own = _own_gram(minor(ab), minor(bb), minor(Gb), strict)
    own = own.reshape(c, c, Bsz, N, n, H).transpose(2, 3, 5, 4, 0, 1)
    if n == 1:                                              # [b,n,h,i,t,s]
        return own.reshape(Bsz, N, H, C, C)
    # sub-block i of t against sub-block j < i of s, through R = G at i's
    # first position: G_t - R <= 0 and R - G_s <= 0
    R = Gb[:, :, :, :1]                                     # [b,n,i,1,h,d]
    a_hat = (ab * jnp.exp(Gb - R)).astype(mxu)
    earlier = jnp.tril(jnp.ones((n, n), bool), -1)[:, :, None, None, None]
    b_hat = (bb[:, :, None] * jnp.exp(jnp.where(
        earlier, R[:, :, :, None] - Gb[:, :, None], -jnp.inf))).astype(mxu)
    off = jnp.einsum("bnithd,bnijshd->bnhitjs", a_hat, b_hat,
                     preferred_element_type=f32)
    # the sub-blocks' own pairs beside them, placed by a mask: no product
    # may round them
    same = jnp.eye(n, dtype=bool)[:, None, :, None]         # [i,1,j,1]
    return jnp.where(same, own[..., None, :], off).reshape(Bsz, N, H, C, C)


def _rows_inverse(L):
    """``(I + L)^-1`` by forward substitution, a row at a time, with the
    matrices of the batch along the minor axis: a row is sums of products
    of whole vectors, whatever C is."""
    C = L.shape[-1]
    low = jnp.moveaxis(L.reshape((-1, C, C)), 0, -1)        # [t, s, m]
    eye = jnp.eye(C, dtype=L.dtype)
    rows = []
    for t in range(C):
        rows.append(eye[t][:, None] - sum(
            (low[t, s] * rows[s] for s in range(t)),
            jnp.zeros((C, low.shape[-1]), L.dtype)))
    return jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(L.shape)


def _block_inverse(L):
    """``(I + L)^-1`` of a strictly lower-triangular L [..., C, C]: blocks
    of at most ``SUB`` rows by forward substitution, a pair of blocks by
    ``[[P, 0], [X, R]]^-1 = [[P', 0], [-R' X P', R']]``, which is forward
    substitution by blocks: no power of L is formed, nothing cancels."""
    C = L.shape[-1]
    if C <= SUB or C % 2:
        return _rows_inverse(L)
    h = C // 2
    halves = _block_inverse(jnp.stack([L[..., :h, :h], L[..., h:, h:]]))
    top, bottom = halves[0], halves[1]
    corner = -jnp.matmul(jnp.matmul(bottom, L[..., h:, :h], precision=_HI),
                         top, precision=_HI)
    return jnp.concatenate([
        jnp.concatenate([top, jnp.zeros_like(top)], -1),
        jnp.concatenate([corner, bottom], -1)], -2)


@jax.custom_vjp
def inv_unit_lower(L):
    """``(I + L)^-1`` for strictly lower-triangular L [..., C, C], float32.
    Its gradient is two products with the inverse, ``-(T^T dT T^T)``: the
    substitution is not differentiated through."""
    return _block_inverse(L)


def _inv_fwd(L):
    inv = _block_inverse(L)
    return inv, inv


def _inv_bwd(inv, d_inv):
    t = jnp.swapaxes(inv, -1, -2)
    d = -jnp.matmul(jnp.matmul(t, d_inv, precision=_HI), t, precision=_HI)
    return (jnp.tril(d, -1),)


inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def gates(g, beta, a_log, dt_bias):
    """(log-decays [B, T, H, K] <= 0, write strengths [B, T, H]) in float32
    from the raw projections: ``-exp(a_log_h) softplus(g + dt_bias)`` and
    ``sigmoid(beta)``. dt_bias [H K] or [H, K]."""
    f32 = jnp.float32
    raw = g.astype(f32) + dt_bias.astype(f32).reshape(g.shape[2:])
    return (-jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(raw),
            jax.nn.sigmoid(beta.astype(f32)))


def l2norm(x):
    """x / sqrt(sum x^2 + L2_EPS) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def kda_chunk(q, k, v, g, beta, a_log, dt_bias, chunk=64):
    """o [B, T, H, V] of Kimi Delta Attention from a zero state, by chunks
    of ``chunk`` positions (the module's docstring has the equations).
    q, k [B, T, H, K] and v [B, T, H, V] after their convolutions: q and k
    take their L2 norm over K here, q then ``K^-0.5``.
    g [B, T, H, K] and beta [B, T, H] are the raw gate projections, a_log
    [H] and dt_bias [H K] the decay's leaves (``gates``). A length that is
    no multiple of the chunk is padded with positions of g = 0 and beta = 0
    (decay 1, nothing written), which change nothing before them.
    Differentiable in either form (``kda_path``); each trace counts
    ``kernels.kda_chunk{path=pallas|xla_chunked}``."""
    from .. import observability as _obs

    path = kda_path(q, v, chunk)
    if _obs.enabled():
        _obs.inc("kernels.kda_chunk", path=path)
    if path == "xla_chunked":
        return _xla_chunked(q, k, v, g, beta, a_log, dt_bias, chunk)
    T = q.shape[1]
    C, pad = _chunks(T, chunk)
    g, beta = gates(g, beta, a_log, dt_bias)
    if pad:
        q, k, v, g, beta = (_pad_time(a, pad) for a in (q, k, v, g, beta))
    return _kernels.delta_rule(q, k, v, g, beta, C)[:, :T]


def _xla_chunked(q, k, v, g, beta, a_log, dt_bias, chunk):
    """The delta rule over whole chunks in XLA einsums, three stages.
    What a chunk needs of itself alone (``_chunk_rows``: the
    gates, the norms, the Gram matrices, the inverse and its products) is
    made ``HEADS_A_PASS`` heads at a time (where that divides H), each
    pass made again from its inputs in the backward: what a pass keeps for
    its gradient, a few dozen arrays of the operands' size, is alive for
    one pass at a time. The recurrence over the chunks then runs once,
    all heads together: its steps are few and small, and a step costs the
    same whether it carries 8 heads or 32. The outputs are two products
    over all chunks at once."""
    f32, mxu = jnp.float32, v.dtype
    Bsz, T, H, K = q.shape
    V = v.shape[-1]
    per = HEADS_A_PASS if H % HEADS_A_PASS == 0 else H
    operands = (q, k, v, g, beta, a_log, dt_bias.reshape(H, K))
    if per == H:
        rows = _chunk_rows(*operands, chunk)
    else:
        axes = (2, 2, 2, 2, 2, 0, 0)        # the operands' head axis

        def passes(x, axis):    # -> [passes, ..., per, ...]
            shape = x.shape[:axis] + (H // per, per) + x.shape[axis + 1:]
            return jnp.moveaxis(x.reshape(shape), axis, 0)

        def heads(x, axis):     # [passes, ..., per, ...] -> [..., H, ...]
            x = jnp.moveaxis(x, 0, axis)
            return x.reshape(x.shape[:axis] + (H,) + x.shape[axis + 2:])

        rows = jax.lax.map(
            jax.checkpoint(lambda xs: _chunk_rows(*xs, chunk)),
            tuple(passes(x, axis) for x, axis in zip(operands, axes)))
        rows = tuple(heads(x, axis) for x, axis in zip(rows, ROWS_HEAD_AXIS))
    w, u0, k_end, end, q_in, pairs = rows

    def step(state, xs):
        w_n, u0_n, k_end_n, end_n = xs
        # the state is float32 where it is carried; the rows written and
        # the state that entered leave the step in the MXU's type, which is
        # all the products after it read
        entered = state.astype(mxu)
        u = (u0_n - jnp.einsum("bhtk,bhkv->bthv", w_n, entered,
                               preferred_element_type=f32)).astype(mxu)
        new = jnp.exp(end_n)[..., None] * state + jnp.einsum(
            "bhks,bshv->bhkv", k_end_n, u, preferred_element_type=f32)
        return new, (u, entered)

    _, (u, entering) = jax.lax.scan(
        step, jnp.zeros((Bsz, H, K, V), f32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (w, u0, k_end, end)))
    u, entering = jnp.moveaxis(u, 0, 1), jnp.moveaxis(entering, 0, 1)
    o = jnp.einsum("bnhtk,bnhkv->bnthv", q_in, entering,
                   preferred_element_type=f32) \
        + jnp.einsum("bnhts,bnshv->bnthv", pairs, u,
                     preferred_element_type=f32)
    return o.reshape(Bsz, -1, H, V)[:, :T].astype(v.dtype)


# the head axis of each of ``_chunk_rows``' results
ROWS_HEAD_AXIS = (2, 3, 2, 2, 2, 2)


def _chunk_rows(q, k, v, g, beta, a_log, dt_bias, chunk):
    """What each chunk gives the recurrence and the outputs, for the heads
    given, with N chunks of C positions: ``w`` [B, N, H, C, K] and ``u0``
    [B, N, C, H, V] (the rows written are ``u0 - w S`` for the state S that
    enters the chunk), ``k_end`` [B, N, H, K, C] and ``end`` [B, N, H, K]
    (the state that leaves is ``exp(end) S + k_end u``), ``q_in`` [B, N, H,
    C, K] and ``pairs`` [B, N, H, C, C] (the outputs are ``q_in S + pairs
    u``). The MXU's operands in its type, head-major with the contraction
    last, as the CPU's dot takes a low-precision left operand; ``u0`` and
    ``end`` float32. dt_bias [H, K]."""
    f32, mxu = jnp.float32, v.dtype
    Bsz, T, H, K = q.shape
    C, pad = _chunks(T, chunk)
    N = (T + pad) // C
    g, beta = gates(g, beta, a_log, dt_bias)
    qn, kn, v32 = l2norm(q) * float(K) ** -0.5, l2norm(k), v.astype(f32)

    def chunked(x):
        return _pad_time(x, pad).reshape((Bsz, N, C) + x.shape[2:])

    qn, kn, v32, g, beta = (chunked(x) for x in (qn, kn, v32, g, beta))
    G = jnp.cumsum(g, axis=2)                               # [b,n,c,h,k]
    A = _decayed_gram(kn, kn, G, True, mxu)                 # [b,n,h,c,c]
    pairs = _decayed_gram(qn, kn, G, False, mxu)
    inv = inv_unit_lower(A * beta.transpose(0, 1, 3, 2)[..., None])
    decayed = jnp.exp(G)
    bt = beta[..., None]
    w = jnp.einsum("bnhts,bnshk->bnhtk", inv, bt * kn * decayed,
                   precision=_HI)
    u0 = jnp.einsum("bnhts,bnshv->bnthv", inv, bt * v32, precision=_HI)
    end = G[:, :, -1]                                       # [b,n,h,k]
    k_end = (kn * jnp.exp(end[:, :, None] - G)).transpose(0, 1, 3, 4, 2)
    q_in = (qn * decayed).transpose(0, 1, 3, 2, 4)
    return (w.astype(mxu), u0, k_end.astype(mxu), end, q_in.astype(mxu),
            pairs.astype(mxu))


SLOTS = ("Q", "K", "V", "G", "Beta", "ALog", "DtBias")


def _kda(v, attrs):
    """``kda_chunk`` over an op's input slots ``v``."""
    return kda_chunk(*(v[n] for n in SLOTS),
                     chunk=int(attrs.get("chunk", 64)))


def _kda_chunk_grad(ins, attrs):
    """The gradients from the op's inputs alone: nothing but q, k, v, the
    raw gates and the decay's leaves lives from the forward to the backward.
    ``jax.vjp`` of the function in either form. The kernels' form is then
    the state pass, which keeps each chunk's entering state, inverse and the
    inverse's products, and the backward kernel that reads them, between
    the gates and their gradient. The XLA form
    runs again behind an optimization barrier, so that XLA cannot fold the
    copy into the forward op's and keep its per-chunk intermediates alive
    until here."""
    vals = tuple(ins[n] for n in SLOTS)
    if kda_path(ins["Q"], ins["V"], int(attrs.get("chunk", 64))) \
            == "xla_chunked":
        vals = jax.lax.optimization_barrier(vals)
    out, vjp = jax.vjp(lambda *vals: _kda(dict(zip(SLOTS, vals)), attrs),
                       *vals)
    grads = vjp(ins["Out@GRAD"].astype(out.dtype))
    return {n + "@GRAD": g for n, g in zip(SLOTS, grads)}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "kda_chunk_grad",
    inputs=[In(n) for n in SLOTS] + [In("Out@GRAD")],
    outputs=[Out(n + "@GRAD", dispensable=True) for n in SLOTS],
    attrs={"chunk": 64},
    grad=None,
)(_kda_chunk_grad)


@register_op(
    "kda_chunk",
    inputs=[In(n) for n in SLOTS],
    outputs=[Out("Out")],
    attrs={"chunk": 64},
)
def _kda_chunk(ins, attrs):
    """Kimi Delta Attention over [B, T, H, K] (see ``kda_chunk`` above for
    the equations and shapes). The gates'
    activations and the L2 norms are taken inside, in float32, so that no
    decay passes through the AMP type."""
    return {"Out": _kda(ins, attrs)}
