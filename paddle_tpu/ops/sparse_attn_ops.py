"""Learned sparse attention: rotary positions, and the indexer that chooses
each query's keys (DeepSeek sparse attention's lightning indexer).

- ``rotary_embedding``: rotate-half rotary positions over the leading
  ``rotary_dims`` of a head, from three position components (temporal,
  height, width) that take consecutive sections of the frequency pairs.
- ``attn_index_project``: the indexer's queries, its one key head
  (LayerNorm, rotary on part of the head) and its per-head weights from a
  hidden state that is cut from the gradient.
- ``attn_index_select``: the index scores ``I[t, s] = sum_h w[t, h]
  relu(qI[t, h] . kI[s])`` over the causal keys and, of each query, the
  ``topk`` largest (ties to the lower key), as the int8 [B, T, T] selection
  the streaming attention kernels read. No gradient.
- ``attn_index_loss``: the indexer's loss, ``KL(pbar || softmax over the
  selection of I)`` with ``pbar`` the head-averaged attention probabilities
  (from the attention's own q, k and the log-sum-exp its forward returned,
  held constant), and its gradient op for the indexer's operands.

Everything that sets the choice is float32 at full precision: a selection
that differs from the exact one is a different function, not a rounding.
The projections and the scores' transposed products are float32 products at
``Precision.HIGHEST`` (on the TPU six bfloat16 partial products of the
operands' three bfloat16 pieces, accumulated in float32). The index scores
themselves, contraction d = 64 on a 128-deep array, are those same six
partial products as ONE bfloat16 product over the pieces laid side by side
along the contraction (``split3``, ``pack_keys``, ``packed_scores``):
contraction 6 d, three full passes where ``HIGHEST`` makes six half-filled
ones, nothing dropped and float32 accumulation; one path for every d.
Nothing of [T, T] is made whole but the int8 selection: scores are made a
block of query rows at a time, over the keys up to the block's causal group
only, and the ``topk``-th largest of a row is found as an exact threshold
(a search over the bits of the scores' order-preserving integer form: 32
counting passes, where a sort of whole rows would take ~100), with ties cut
by position the same way. Inner ``jax.named_scope``s tell ``score``, ``select`` and
``loss`` apart in a trace.
"""
from __future__ import annotations

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import In, Out, register_op

_HI = jax.lax.Precision.HIGHEST
# query rows a block of the selection / of the loss holds ([rows, keys]
# float32 tiles for each of the indexer's / the attention's heads)
SELECT_ROWS = 512
LOSS_ROWS = 256
# causal groups: a group of query rows meets the keys up to its own end only
GROUPS = 8


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------


def rotary_angles(pos, theta, sections, pairs, inv_freq=None):
    """[B, T, pairs] float32 angles: pair ``i`` has frequency
    ``theta ** (-i / pairs)``, or ``inv_freq[i]`` where the frequencies are
    given (scaled ones, YaRN's blend), and reads the position component
    whose consecutive section holds ``i`` (the last one past the sections'
    end). ``pos`` [3, B, T] int."""
    if inv_freq is None:
        inv = theta ** (-jnp.arange(pairs, dtype=jnp.float32) / pairs)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ends = np.cumsum(np.asarray(sections, np.int64))
    comp = np.minimum(np.searchsorted(ends, np.arange(pairs), side="right"),
                      len(ends) - 1)
    p = jnp.moveaxis(pos.astype(jnp.float32)[comp], 0, -1)    # [B, T, pairs]
    return p * inv


def rotary(x, pos, theta, sections, rotary_dims=0, inv_freq=None, offset=0):
    """x [B, T, H, hd] with ``rotary_dims`` of a head's dims (0: all that
    follow) rotated from dim ``offset`` on, rotate-half form within that
    slice: ``x * cos + rotate_half(x) * sin``; float32 inside."""
    rd = int(rotary_dims) or x.shape[-1] - offset
    half = rd // 2
    ang = rotary_angles(pos, theta, sections, half, inv_freq)[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., offset:offset + half], xf[..., offset + half:offset + rd]
    parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., offset + rd:]]
    if offset:   # without one the trace is what it was
        parts.insert(0, xf[..., :offset])
    return jnp.concatenate(parts, -1).astype(x.dtype)


@register_op(
    "rotary_embedding",
    inputs=[In("X"), In("Pos", dispensable=True, no_grad=True)],
    outputs=[Out("Out")],
    attrs={"theta": 10000.0, "sections": [], "rotary_dims": 0,
           "inv_freq": [], "offset": 0},
)
def _rotary_embedding(ins, attrs):
    """X [B, T, H, hd]; Pos [3, B, T] int (temporal, height, width; a feed;
    unbound: one document a row, every component ``0..T-1``);
    ``sections``: how many frequency pairs each component takes, in order
    (empty: all from component 0); ``rotary_dims``: how many dims of a head
    rotate (0: all from ``offset`` on), starting at dim ``offset``;
    ``inv_freq``: the pairs' frequencies, one each (empty: ``theta^(-i /
    pairs)``)."""
    x = ins["X"]
    offset = int(attrs.get("offset", 0))
    rd = int(attrs.get("rotary_dims", 0)) or x.shape[-1] - offset
    sections = list(attrs.get("sections") or [rd // 2])
    inv_freq = list(attrs.get("inv_freq") or []) or None
    if inv_freq is not None and len(inv_freq) != rd // 2:
        raise ValueError("rotary_embedding: %d frequencies for %d pairs"
                         % (len(inv_freq), rd // 2))
    pos = ins.get("Pos")
    if pos is None:
        B, T = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, B, T))
    return {"Out": rotary(x, pos, float(attrs.get("theta", 1e4)), sections,
                          rd, inv_freq, offset)}


# ---------------------------------------------------------------------------
# the indexer
# ---------------------------------------------------------------------------


def index_project(x, wq, wk, ww, ln_scale, ln_bias, pos, heads, theta,
                  sections, rotary_dims, eps):
    """(qI [B, T, heads, d], kI [B, T, d], w [B, T, heads]) from the hidden
    state x [B, T, D], which takes no gradient from here."""
    f32 = jnp.float32
    x = jax.lax.stop_gradient(x.astype(f32))
    B, T, _ = x.shape
    d = wk.shape[1]
    q = jnp.dot(x, wq.astype(f32), precision=_HI).reshape(B, T, heads, d)
    k = jnp.dot(x, wk.astype(f32), precision=_HI)
    mean = jnp.mean(k, -1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), -1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(var + eps) * ln_scale + ln_bias
    q = rotary(q, pos, theta, sections, rotary_dims)
    k = rotary(k[:, :, None, :], pos, theta, sections, rotary_dims)[:, :, 0]
    w = jnp.dot(x, ww.astype(f32), precision=_HI) * (heads ** -0.5
                                                     * d ** -0.5)
    return q, k, w


@register_op(
    "attn_index_project",
    inputs=[In("X", no_grad=True), In("WQ"), In("WK"), In("WW"),
            In("LnScale"), In("LnBias"), In("Pos", no_grad=True)],
    outputs=[Out("QI"), Out("KI"), Out("W")],
    attrs={"heads": 1, "theta": 10000.0, "sections": [], "rotary_dims": 0,
           "epsilon": 1e-6},
)
def _attn_index_project(ins, attrs):
    """X [B, T, D] (the layer's normed hidden state; cut from the gradient:
    the indexer learns from its own loss alone); WQ [D, heads * d], WK
    [D, d] (one key head, LayerNorm ``LnScale``/``LnBias`` [d] after it), WW
    [D, heads] (the heads' weights, scaled by ``heads^-0.5 d^-0.5``); rotary
    positions on the first ``rotary_dims`` dims of queries and key. Float32
    at full precision (no AMP list names the op, so its operands stay
    float32)."""
    d = ins["WK"].shape[1]
    rd = int(attrs.get("rotary_dims", 0)) or d
    with jax.named_scope("score"):
        q, k, w = index_project(
            ins["X"], ins["WQ"], ins["WK"], ins["WW"], ins["LnScale"],
            ins["LnBias"], ins["Pos"], int(attrs.get("heads", 1)),
            float(attrs.get("theta", 1e4)),
            list(attrs.get("sections") or [rd // 2]), rd,
            float(attrs.get("epsilon", 1e-6)))
    return {"QI": q, "KI": k, "W": w}


def split3(x):
    """float32 ``x`` as three bfloat16 pieces (hi, mid, lo) with
    ``hi + mid + lo == x`` to float32's last bit: each piece is the bfloat16
    nearest to what the ones before it leave, and the differences are exact
    in float32. ``reduce_precision`` rounds in place: a convert to bfloat16
    and back may be folded away (XLA keeps excess precision where it may),
    which would leave ``mid`` and ``lo`` zero."""
    def nearest(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    hi = nearest(x)
    mid = nearest(x - hi)
    lo = nearest(x - hi - mid)
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def pack_keys(ki):
    """bfloat16 [S, 6 d]: the keys' pieces ``[hi | mid | hi | mid | lo | hi]``
    along the contraction, against the queries' ``[hi | hi | mid | mid | hi |
    lo]``: the six partial products a float32 product at ``HIGHEST`` keeps
    (hi.hi, hi.mid, mid.hi, mid.mid, hi.lo, lo.hi) in one bfloat16 product
    of contraction 6 d, accumulated in float32 as the MXU accumulates any
    contraction. At d = 64 that is three full passes of a 128-deep array
    where ``HIGHEST`` makes six half-filled ones."""
    hi, mid, lo = split3(ki)
    return jnp.concatenate([hi, mid, hi, mid, lo, hi], -1)


@jax.custom_vjp
def packed_scores(qi, ki, w, ks):
    """``index_scores(qi, ki, w)`` with ``ks = pack_keys(ki)`` made by the
    caller, once for all its blocks of query rows. The gradient goes to qi,
    ki and w; ks takes none."""
    return _packed_scores_fwd(qi, ki, w, ks)[0]


def _packed_scores_fwd(qi, ki, w, ks):
    hi, mid, lo = split3(qi)
    qs = jnp.concatenate([hi, hi, mid, mid, hi, lo], -1)
    s = jnp.einsum("rhk,sk->hrs", qs, ks,
                   preferred_element_type=jnp.float32)
    out = jnp.sum(jax.nn.relu(s) * w.T[:, :, None], 0) + 0.0
    return out, (s, qi, ki, w)


def _packed_scores_bwd(res, g):
    """dq = ds . k and dk = ds^T . q with ds[h, r, s] = g[r, s] w[r, h]
    [s_hrs > 0], float32 products of qi and ki themselves: the packed
    product's automatic transpose would multiply ds, rounded to bfloat16,
    by the pieces. Their contraction (keys; heads x rows) fills the array
    and their operand ds is made once a product, so ``HIGHEST`` stays: its
    six terms packed two side by side (``[k_hi | k_mid]``, four passes) pay
    for four makings of ds what they save in passes (``PERF.md``, PR 36)."""
    s, qi, ki, w = res
    ds = jnp.where(s > 0, g[None] * w.T[:, :, None], 0.0)
    dq = jnp.einsum("hrs,sd->rhd", ds, ki, precision=_HI)
    dk = jnp.einsum("hrs,rhd->sd", ds, qi, precision=_HI)
    dw = jnp.sum(jax.nn.relu(s) * g[None], -1).T
    return dq, dk, dw, None


packed_scores.defvjp(_packed_scores_fwd, _packed_scores_bwd)


def index_scores(qi, ki, w):
    """I [R, S] float32 = sum_h w[r, h] relu(qi[r, h] . ki[s]) for query
    rows qi [R, H, d], w [R, H] and keys ki [S, d]. The products are float32
    at full precision, as ``pack_keys`` makes them; the sum over heads is
    elementwise (no second matrix product to round it), and ``+ 0.0`` makes
    every zero a positive one: the selection orders bit patterns."""
    return packed_scores(qi, ki, w, pack_keys(ki))


def _ordered(x):
    """float32 -> uint32 with the same order (finite values are > 0)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(0x80000000))


def select_rows(scores, row0, topk):
    """int8 [R, S]: of query row ``row0 + r`` the ``min(t + 1, topk)`` causal
    keys (s <= t) with the largest score, ties to the lower s; exact."""
    R, S = scores.shape
    t = row0 + jnp.arange(R, dtype=jnp.int32)
    causal = jnp.arange(S, dtype=jnp.int32)[None, :] <= t[:, None]
    keys = jnp.where(causal, _ordered(scores), jnp.uint32(0))
    want = jnp.minimum(t + 1, topk)                              # [R]

    def narrow(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(keys >= cand[:, None], -1, dtype=jnp.int32)
        return jnp.where(n >= want, cand, thr)

    # the largest value that ``want`` keys reach: the want-th largest key
    thr = jax.lax.fori_loop(0, 32, narrow, jnp.zeros((R,), jnp.uint32))
    above = keys > thr[:, None]
    tied = (keys == thr[:, None]) & causal
    room = want - jnp.sum(above, -1, dtype=jnp.int32)            # >= 1

    def by_position():
        """The first ``room`` tied keys of a row: those before the largest
        bound ``m`` with at most ``room`` tied keys below it, found bit by
        bit as the threshold was (a cumulative sum over a row costs the TPU
        tens of milliseconds a block, and a step then takes as long as its
        rows happen to tie)."""
        pos = jnp.arange(S, dtype=jnp.int32)[None, :]

        def widen(i, m):
            cand = m | (jnp.int32(1) << (S.bit_length() - 1 - i))
            n = jnp.sum(tied & (pos < cand[:, None]), -1, dtype=jnp.int32)
            return jnp.where(n <= room, cand, m)

        m = jax.lax.fori_loop(0, S.bit_length(), widen,
                              jnp.zeros((R,), jnp.int32))
        return above | (tied & (pos < m[:, None]))

    # ties across the threshold are rare: only then are positions counted
    exact = jnp.all(jnp.sum(tied, -1, dtype=jnp.int32) == room)
    return jax.lax.cond(exact, lambda: above | tied, by_position).astype(
        jnp.int8)


def _row_plan(T, rows):
    """[(first row, last row + 1, keys)] of the causal groups, and the rows
    of a block: blocks of at most ``rows`` rows that divide a group."""
    block = math.gcd(T, rows)
    groups = math.gcd(T // block, GROUPS)
    per = T // groups
    return [(g * per, (g + 1) * per, (g + 1) * per)
            for g in range(groups)], block


def _blocks(x, lo, hi, block):
    """Rows lo..hi of x as [blocks, block, ...]."""
    return x[lo:hi].reshape(((hi - lo) // block, block) + x.shape[1:])


def select_mask(qi, ki, w, topk):
    """int8 [T, T] selection of one sequence: qi [T, H, d], ki [T, d],
    w [T, H]."""
    T = qi.shape[0]
    groups, block = _row_plan(T, SELECT_ROWS)
    with jax.named_scope("score"):
        ks = pack_keys(ki)
    parts = []
    for lo, hi, keys in groups:
        def one(args, keys=keys):
            q_b, w_b, row0 = args
            with jax.named_scope("score"):
                scores = packed_scores(q_b, ki[:keys], w_b, ks[:keys])
            with jax.named_scope("select"):
                return select_rows(scores, row0, topk)

        starts = lo + block * jnp.arange((hi - lo) // block, dtype=jnp.int32)
        m = jax.lax.map(one, (_blocks(qi, lo, hi, block),
                              _blocks(w, lo, hi, block), starts))
        parts.append(jnp.pad(m.reshape(hi - lo, keys),
                             ((0, 0), (0, T - keys))))
    return jnp.concatenate(parts, 0)


@register_op(
    "attn_index_select",
    inputs=[In("QI", no_grad=True), In("KI", no_grad=True),
            In("W", no_grad=True)],
    outputs=[Out("Select", no_grad=True)],
    attrs={"topk": 1},
    grad=None,
)
def _attn_index_select(ins, attrs):
    """QI [B, T, H, d], KI [B, T, d], W [B, T, H] (``attn_index_project``)
    -> Select [B, T, T] int8, 1 where query t attends key s: the
    ``min(t + 1, topk)`` causal keys with the largest index score, ties to
    the lower s. One selection a query, for all attention heads: the
    ``flash_attention`` op's ``Select`` input. No gradient."""
    f32 = jnp.float32
    qi, ki, w = (ins[s].astype(f32) for s in ("QI", "KI", "W"))
    topk = int(attrs.get("topk", 1))
    return {"Select": jnp.stack([select_mask(qi[b], ki[b], w[b], topk)
                                 for b in range(qi.shape[0])])}


def _block_kl(scores, on, q_b, k, lse_b, scale):
    """(sum over the block of pbar (log pbar - log pi), pi - pbar):
    ``pi`` the softmax of the index scores over the selection ``on``,
    ``pbar`` the mean over heads of the attention probabilities
    ``exp(scale q . k - lse)`` there. q_b [R, Hkv, G, hd], k [S, Hkv, hd],
    lse_b [Hkv, G, R]."""
    f32 = jnp.float32
    logpi = jax.nn.log_softmax(jnp.where(on, scores, -jnp.inf), -1)
    s = jnp.einsum("rkgd,skd->kgrs", q_b, k,
                   preferred_element_type=f32) * scale
    heads = s.shape[0] * s.shape[1]
    pbar = jnp.where(on, jnp.sum(jnp.exp(s - lse_b[..., None]), (0, 1))
                     / heads, 0.0)
    kl = jnp.sum(jax.scipy.special.xlogy(pbar, pbar)
                 - pbar * jnp.where(on, logpi, 0.0))
    return kl, jnp.where(on, jnp.exp(logpi), 0.0) - pbar


# one batch row of the loss's operands, heads grouped by their shared K/V
# head: q [T, Hkv, G, hd], k [T, Hkv, hd], lse [Hkv, G, T]
_Row = collections.namedtuple("_Row", "qi ki w sel q k lse")


def _loss_operands(ins):
    """A ``_Row`` for each batch row."""
    f32 = jnp.float32
    q, k = ins["Q"], ins["K"]                  # [B, H, T, hd], [B, Hkv, T, hd]
    B, H, T, hd = q.shape
    hkv = k.shape[1]
    lse = ins["LSE"].astype(f32).reshape(B, hkv, H // hkv, T)
    for b in range(B):
        yield _Row(ins["QI"][b].astype(f32), ins["KI"][b].astype(f32),
                   ins["W"][b].astype(f32), ins["Select"][b],
                   jnp.moveaxis(q[b], 1, 0).reshape(T, hkv, H // hkv, hd),
                   jnp.moveaxis(k[b], 1, 0), lse[b])


def _loss_blocks(row):
    """(keys, (qi, w, select, q, lse) as [blocks, rows, ...]) of each causal
    group of one batch row's operands."""
    groups, block = _row_plan(row.qi.shape[0], LOSS_ROWS)
    lse_t = jnp.moveaxis(row.lse, -1, 0)                        # [T, Hkv, G]
    for lo, hi, keys in groups:
        yield keys, tuple(
            _blocks(x, lo, hi, block)
            for x in (row.qi, row.w, row.sel[:, :keys], row.q, lse_t))


def index_loss(ins, scale):
    """The indexer's loss of a layer, the mean over all B * T queries."""
    total, count = jnp.zeros((), jnp.float32), 0
    for row in _loss_operands(ins):
        ki, k = row.ki, row.k
        with jax.named_scope("score"):
            ks = pack_keys(ki)
        count += row.qi.shape[0]
        for keys, xs in _loss_blocks(row):
            def one(args, keys=keys):
                q_b, w_b, sel_b, qa_b, lse_b = args
                with jax.named_scope("score"):
                    scores = packed_scores(q_b, ki[:keys], w_b, ks[:keys])
                with jax.named_scope("loss"):
                    return _block_kl(scores, sel_b != 0, qa_b, k[:keys],
                                     jnp.moveaxis(lse_b, 0, -1), scale)[0]

            total = total + jnp.sum(jax.lax.map(one, xs))
    return total / count


def index_loss_grad(ins, scale, g):
    """(dQI, dKI, dW) of ``g * index_loss``: ``dI = g (pi - pbar) / (B T)``
    on the selection, through the scores' own products."""
    dqs, dks, dws = [], [], []
    rows = list(_loss_operands(ins))
    count = sum(row.qi.shape[0] for row in rows)
    for row in rows:
        qi, ki, w, k = row.qi, row.ki, row.w, row.k
        with jax.named_scope("score"):
            ks = pack_keys(ki)
        dk = jnp.zeros(ki.shape, jnp.float32)
        dq_parts, dw_parts = [], []
        for keys, xs in _loss_blocks(row):
            def one(dk_g, args, keys=keys):
                q_b, w_b, sel_b, qa_b, lse_b = args
                with jax.named_scope("score"):
                    scores, vjp = jax.vjp(packed_scores, q_b, ki[:keys],
                                          w_b, ks[:keys])
                with jax.named_scope("loss"):
                    diff = _block_kl(scores, sel_b != 0, qa_b, k[:keys],
                                     jnp.moveaxis(lse_b, 0, -1), scale)[1]
                with jax.named_scope("score"):
                    dq_b, dk_b, dw_b, _ = vjp(diff * (g / count))
                return dk_g + dk_b, (dq_b, dw_b)

            dk_g, (dq_g, dw_g) = jax.lax.scan(
                one, jnp.zeros((keys, ki.shape[1]), jnp.float32), xs)
            dk = dk.at[:keys].add(dk_g)
            dq_parts.append(dq_g.reshape((-1,) + qi.shape[1:]))
            dw_parts.append(dw_g.reshape((-1,) + w.shape[1:]))
        dqs.append(jnp.concatenate(dq_parts, 0))
        dks.append(dk)
        dws.append(jnp.concatenate(dw_parts, 0))
    return jnp.stack(dqs), jnp.stack(dks), jnp.stack(dws)


_LOSS_INPUTS = [In("QI"), In("KI"), In("W"), In("Select", no_grad=True),
                In("Q", no_grad=True), In("K", no_grad=True),
                In("LSE", no_grad=True)]


def _attn_index_loss_grad(ins, attrs):
    """dQI, dKI, dW from one more pass over the blocks: scores, ``pi`` and
    ``pbar`` are made again a block at a time and the scores' products
    transposed; no forward's [T, T] residual is kept."""
    g = ins["Loss@GRAD"].astype(jnp.float32).reshape(())
    dq, dk, dw = index_loss_grad(ins, float(attrs.get("scale", 1.0)), g)
    return {"QI@GRAD": dq.astype(ins["QI"].dtype),
            "KI@GRAD": dk.astype(ins["KI"].dtype),
            "W@GRAD": dw.astype(ins["W"].dtype)}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "attn_index_loss_grad",
    inputs=_LOSS_INPUTS + [In("Loss@GRAD")],
    outputs=[Out("QI@GRAD", dispensable=True),
             Out("KI@GRAD", dispensable=True),
             Out("W@GRAD", dispensable=True)],
    attrs={"scale": 1.0},
    grad=None,
)(_attn_index_loss_grad)


@register_op(
    "attn_index_loss",
    inputs=_LOSS_INPUTS,
    outputs=[Out("Loss")],
    attrs={"scale": 1.0},
)
def _attn_index_loss(ins, attrs):
    """Loss [1] float32 = mean over queries t of ``sum_{s in S_t} pbar[t, s]
    (log pbar[t, s] - log pi[t, s])``: ``pi`` the softmax over the selection
    S_t (``Select`` [B, T, T]) of the index scores of QI, KI, W; ``pbar`` the
    mean over the attention heads of ``exp(scale Q . K - LSE)``, from the
    attention's own Q [B, H, T, hd], K [B, Hkv, T, hd] (bf16 under AMP, as
    its kernels multiply them) and the ``LSE`` [B * H, T, 1] its forward
    returned; held constant. QI, KI, W and LSE stay float32 under AMP
    (``fp16_lists.fp32_slots``). Gradients go to QI, KI, W alone."""
    loss = index_loss(ins, float(attrs.get("scale", 1.0)))
    return {"Loss": loss.reshape(1)}
