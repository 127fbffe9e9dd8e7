"""Flash attention as Pallas TPU kernels — forward AND backward.

Parity intent: the reference hand-fuses attention for inference in CUDA
(operators/fused/multihead_matmul_op.cu, math/bert_encoder_functor.cu);
this is the TPU-native equivalent, done the flash way so the S x S
score matrix never materializes in HBM. Two sets of kernels, chosen by
``flash_attention`` from the shapes it is given (``_plan``):

**Streaming** (long S):

- forward: grid = (batch*heads, q_blocks, k_blocks) with the K
  dimension iterated sequentially ("arbitrary") so the running-softmax
  scratch (m, l, acc in VMEM) persists across K steps; each step does
  two MXU matmuls (Q@K^T, P@V) on [block_q, block_k] tiles streamed
  HBM->VMEM by pallas; the log-sum-exp accumulation is float32
  regardless of input dtype. The forward also emits the per-row
  logsumexp (LSE), the only O(S) residual the backward needs.
- backward (FlashAttention-2 style): probabilities are RECOMPUTED
  blockwise from (Q, K, LSE) instead of stored, so training memory is
  O(S·D) instead of the O(S²) attention matrix a dense VJP carries.
  ONE kernel (``_flash_bwd_fused_kernel``) makes s, the masks, p, dp and
  ds of a (Q block, K block) tile once and accumulates dQ, dK and dV
  from them, five matmuls a tile: grid (K/V head, Q block, query head
  of the group x K block); dQ of a (query head, Q block) in a [bq, D]
  float32 scratch over its K blocks; dK and dV of the whole K/V head,
  [S, D] and [S, Dv] float32, resident in VMEM for all of it and cast
  and written once, the sum over the group's query heads with them.
  MXU operands stay in the dtype they arrive in (p and ds are cast to
  it), accumulation, scores, exp, LSE and delta are float32. A causal
  step above the diagonal computes nothing and its index maps name the
  last K block it needed again, so nothing is copied for it. It runs
  for calls with shared K/V heads whose accumulators fit
  ``STREAM_VMEM_BUDGET`` (``_fused_bwd_fits``, from the shapes alone,
  which also says why one K/V head a query head does not take it yet);
  for every other call the older pair runs: dQ iterates K blocks per Q
  block, dK/dV iterates Q blocks per K block, each making the tile
  again (seven matmuls). All consume the dense precomputed
  delta = rowsum(dO ∘ O) (an elementwise pass XLA fuses).

**Short** (S <= 1024 with the default blocks: a head's whole [S, S]
score tile fits VMEM): grid = (batch*heads / heads-per-step,); one
block over K means a plain softmax (no running max, no rescale); MXU
operands stay in the dtype they arrive in (bf16 under AMP) with
float32 accumulation, and scores, max, sum, LSE are float32 in VMEM;
the per-head code of a step is unrolled so that one head's VPU passes
overlap the next one's matmuls; ONE backward kernel yields dQ, dK, dV
from one S/P/dP (five matmuls a head instead of seven). Both kernels
work on the transposed tile [Tk, Tq], which keeps LSE and delta
lane-dense rows and transposes only [S, D]-sized operands.

**Token-major** (the short path for operands as the projections leave
them, ``[B, T, H*hd]``): the same per-head math on blocks of ``rows``
batch rows x T x ``lanes`` of the ``H*hd`` axis (``lanes`` a multiple of
128 that holds whole heads; ``_tokens_blocks``), each head's ``hd`` lanes
taken inside the kernel, the context written back in the same layout and
``delta = rowsum(dO * O)`` computed inside the backward kernel: no head
split or merge, and no layout copy, stands between the projections'
matmuls and the kernels. Token-major operands the short path does not
take are split into heads and merged again around the
head-major kernels, inside ``flash_attention``.

**Shared K/V heads** (grouped-query attention): K and V may have fewer
heads than Q, ``H_kv`` dividing ``H``. The streaming kernels' index maps
read the shared head (row ``b // group`` of the [B * H_kv] rows), nothing
is repeated in HBM, and the backward kernel (the dK/dV kernel of the
pair) runs over the group's query heads in its last grid axis so that
their sums happen in its accumulators. Such a call takes the streaming
kernels at any length (the short ones take one head count).

**Selection** (learned sparse attention): the streaming kernels take an
optional ``select`` operand, [B, S, S] int8, one tile a (Q block, K block)
pair shared by all the heads of a batch row, and hide the keys it leaves
out beside the causal mask, forward and backward. Without it the kernels,
their operands and ``_plan`` are unchanged.

Where the computation runs on a TPU (``core.place.compute_platform``)
the public entry builds the kernels, and a kernel Mosaic refuses
raises. Elsewhere it computes the identical dense math, so programs
are portable and CI (CPU) still exercises the call sites; tests run the
kernels in interpret mode on CPU where the math is exact.

Numerics, measured on v5e: with float32 inputs both this kernel and
XLA's dense attention run the MXU's default (bfloat16-pass) precision;
against an fp64 oracle the forward kernel's max error is ~2e-3
(non-causal) / ~8e-3 (causal) and the dense path's is ~3e-3 / ~1e-2 —
the flash accumulation is slightly MORE accurate, and the two agree
within their mutual rounding.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from jax.experimental.pallas import tpu as pltpu

from ...core.place import compute_platform

NEG_INF = -1e30


def _causal_mask(s, qi, ki, block_q, block_k):
    """Mask the score tile with absolute positions (shared by the
    forward and every backward kernel — one definition to extend for
    sliding-window/padding variants)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _causal_block_needed(qi, ki, block_q, block_k):
    """False only when the whole tile lies above the diagonal."""
    return ki * block_k <= qi * block_q + block_q - 1


def _kv_len_mask(s, ki, block_k, len_val):
    """Padding mask: key positions >= len_val (per batch row) are
    invisible — the kernel-side form of the reference's additive
    src_slf_attn_bias (0 / -inf over padded keys)."""
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(k_pos < len_val, s, NEG_INF)


def _select_mask(s, sel_ref):
    """Selection mask: query row r sees key column c of this tile only where
    the int8 tile of ``Select`` [B, S, S] is non-zero; one tile serves all
    the heads of a batch row."""
    return jnp.where(sel_ref[0].astype(jnp.int32) != 0, s, NEG_INF)


def _group(q, k):
    """Query heads that share one K/V head: ``H / H_kv`` (1 = plain
    multi-head attention)."""
    H, H_kv = q.shape[1], k.shape[1]
    if H % H_kv:
        raise ValueError("flash_attention: %d query heads over %d key/value "
                         "heads" % (H, H_kv))
    return H // H_kv


def _dense_attention(q, k, v, causal, scale, lengths=None, select=None,
                     with_lse=False):
    """The dense math. ``with_lse``: (out, [B*H, S, 1] float32 log-sum-exp
    of the visible scores, in the streaming kernels' shape): a selected call
    hands its LSE on wherever it runs (the indexer's loss reads it)."""
    group = _group(q, k)
    if group > 1:   # the dense math repeats the shared heads; no kernel does
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s,
                      NEG_INF)
    if select is not None:
        s = jnp.where((select != 0)[:, None], s, NEG_INF)
    if lengths is not None:
        S_kv = k.shape[2]
        vis = jnp.arange(S_kv)[None, None, None, :] < \
            lengths.astype(jnp.int32)[:, None, None, None]
        s = jnp.where(vis, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    if lengths is not None:
        # zero-length rows output ZEROS, matching the pallas kernels
        out = jnp.where(
            (lengths.astype(jnp.int32) > 0)[:, None, None, None],
            out, 0.0)
    out = out.astype(q.dtype)
    if not with_lse:
        return out
    B, H, S, _ = q.shape
    return out, jax.scipy.special.logsumexp(s, axis=-1).reshape(B * H, S, 1)


def _dense_lse(q, k, causal, scale, lengths_bh=None):
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[1]
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None], s, NEG_INF)
    if lengths_bh is not None:   # [BH] — already repeated per head
        S_kv = k.shape[1]
        vis = jnp.arange(S_kv)[None, None, :] < \
            lengths_bh.astype(jnp.int32)[:, None, None]
        s = jnp.where(vis, s, NEG_INF)
    return jax.scipy.special.logsumexp(s, axis=-1)[..., None]  # [BH,S,1]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _flash_kernel(*refs, scale, causal, block_q, block_k, nk, has_len,
                  has_sel=False):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref), refs = refs[:3], refs[3:]
    len_ref, refs = (refs[0], refs[1:]) if has_len else (None, refs)
    sel_ref, refs = (refs[0], refs[1:]) if has_sel else (None, refs)
    o_ref, lse_ref, m_ref, l_ref, acc_ref = refs
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    bi = pl.program_id(0)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale      # [bq, d]
        k = k_ref[0].astype(jnp.float32)              # [bk, d]
        s = jax.lax.dot_general(q, k,
                                (((1,), (1,)), ((), ())))  # [bq, bk]
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        if has_len:
            s = _kv_len_mask(s, ki, block_k, len_ref[bi, 0])
        if has_sel:
            s = _select_mask(s, sel_ref)

        m_prev = m_ref[:]                             # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                        # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())))
        m_ref[:] = m_new

    need = None
    if causal:
        # skip K blocks entirely above the diagonal — ~2x less work
        need = _causal_block_needed(qi, ki, block_q, block_k)
    if has_len:
        # skip K blocks entirely past the padded tail
        in_len = ki * block_k < len_ref[bi, 0]
        need = in_len if need is None else jnp.logical_and(need, in_len)
    if need is not None:
        pl.when(need)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[:] + jnp.log(l_safe)          # [bq, 1]


def count_backward(path):
    """``kernels.flash_attention_grad{path=fused|split|short|dense}``: one
    count a trace of attention's backward, made at the branch that was
    taken (here, and in the grad op for the dense math's automatic VJP)."""
    from ... import observability as obs

    if obs.enabled():
        obs.inc("kernels.flash_attention_grad", path=path)


def _no_tangent(lengths):
    """The cotangent of the int ``lengths`` argument (None or [B])."""
    from jax.dtypes import float0

    return (None if lengths is None
            else np.zeros(lengths.shape, dtype=float0))


def _len_bh(lengths, B, H):
    """[B] lengths -> [B*H, 1] int32 (one row per grid batch step)."""
    return jnp.repeat(lengths.astype(jnp.int32), H).reshape(B * H, 1)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   lengths=None, select=None):
    """Returns (out [B,H,S,Dv], lse [B*H, S, 1] float32); ``v``'s head dim
    ``Dv`` may differ from ``D``, the one q and k share. ``select``
    [B, S, S] int8 (whole blocks only: ``_plan`` sees to that)."""
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    S_kv, Dv = k.shape[2], v.shape[3]
    group = _group(q, k)
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S != S_kv or S % bq or S % bk:
        # ragged tail, or rectangular cross-attention Q/K — the kernel
        # grid assumes square S; dense math handles both exactly
        q3 = q.reshape(B * H, S, D)
        k3 = jnp.repeat(k, group, axis=1).reshape(B * H, S_kv, D)
        lbh = (None if lengths is None
               else jnp.repeat(lengths.astype(jnp.int32), H))
        return (_dense_attention(q, k, v, causal, scale, lengths),
                _dense_lse(q3, k3, causal, scale, lbh))
    nq, nk = S // bq, S // bk
    q3 = q.reshape(B * H, S, D)
    # row b of q3 is head b % H of batch row b // H; its K/V head is row
    # b // group of the [B * H_kv] rows: the index map reads the shared
    # head, nothing is repeated in HBM
    k3 = k.reshape(B * H // group, S, D)
    v3 = v.reshape(B * H // group, S, Dv)

    has_len = lengths is not None
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, nk=nk,
                               has_len=has_len, has_sel=select is not None)
    in_specs = [
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b // group, j, 0)),
        ]
    args = [q3, k3, v3]
    if has_len:
        # whole [BH,1] array in SMEM (scalar per batch row — a
        # (1,1) VMEM block would violate the TPU (8,128) tile rule)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_len_bh(lengths, B, H))
    if select is not None:
        in_specs.append(pl.BlockSpec((1, bq, bk),
                                     lambda b, i, j: (b // H, i, j)))
        args.append(select)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            # [BH, S, 1]: last block dim = full array dim (exempt from
            # the /128 lane rule), penultimate bq satisfies the /8 rule
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, S, Dv), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk,
                         has_len, has_sel=False):
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), refs = (refs[:6],
                                                               refs[6:])
    len_ref, refs = (refs[0], refs[1:]) if has_len else (None, refs)
    sel_ref, refs = (refs[0], refs[1:]) if has_sel else (None, refs)
    dq_ref, dq_acc = refs
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    bi = pl.program_id(0)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                               # [bq, 1]
        delta = delta_ref[0]                           # [bq, 1]

        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        if has_len:
            s = _kv_len_mask(s, ki, block_k, len_ref[bi, 0])
        if has_sel:
            s = _select_mask(s, sel_ref)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)                          # [bq, bk]
        dq_acc[:] += scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())))           # [bq, d]

    need = None
    if causal:
        need = _causal_block_needed(qi, ki, block_q, block_k)
    if has_len:
        in_len = ki * block_k < len_ref[bi, 0]
        need = in_len if need is None else jnp.logical_and(need, in_len)
    if need is not None:
        pl.when(need)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, nq,
                          has_len, group=1, has_sel=False):
    """dK and dV of one K/V head: the last grid axis runs over the
    ``group`` query heads that share it and, inside each, the Q blocks, so
    the sums over the group happen in the accumulators."""
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), refs = (refs[:6],
                                                               refs[6:])
    len_ref, refs = (refs[0], refs[1:]) if has_len else (None, refs)
    sel_ref, refs = (refs[0], refs[1:]) if has_sel else (None, refs)
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    step = pl.program_id(2)
    qi = step % nq if group > 1 else step
    ki = pl.program_id(1)
    bi = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                               # [bq, 1]
        delta = delta_ref[0]                           # [bq, 1]

        s = scale * jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        if has_len:
            s = _kv_len_mask(s, ki, block_k, len_ref[bi, 0])
        if has_sel:
            s = _select_mask(s, sel_ref)
        p = jnp.exp(s - lse)                           # [bq, bk]
        dv_acc[:] += jax.lax.dot_general(              # p^T @ do
            p, do, (((0,), (0,)), ((), ())))           # [bk, d]
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta)                          # [bq, bk]
        dk_acc[:] += scale * jax.lax.dot_general(      # ds^T @ q
            ds, q, (((0,), (0,)), ((), ())))           # [bk, d]

    need = None
    if causal:
        # rows strictly above this K block see none of it
        need = _causal_block_needed(qi, ki, block_q, block_k)
    if has_len:
        in_len = ki * block_k < len_ref[bi, 0]
        need = in_len if need is None else jnp.logical_and(need, in_len)
    if need is not None:
        pl.when(need)(_accumulate)
    else:
        _accumulate()

    @pl.when(step == group * nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# What the one-kernel streaming backward may ask of VMEM: a K/V head's dK and
# dV stay there in float32 while its query heads and Q blocks pass, so the
# need grows with S (16 MiB of accumulators at S = 16,384, D = 128). Beyond
# it the dQ and dK+dV pair, whose scratch does not grow with S, runs instead.
STREAM_VMEM_BUDGET = 96 << 20


def _lanes(d):
    """A minor dim as VMEM holds it: whole 128-lane tiles."""
    return -(-d // 128) * 128


def _fused_bwd_vmem_bytes(S, D, Dv, bq, bk, itemsize):
    """VMEM of ``_flash_bwd_fused_kernel``: the float32 dK and dV of a whole
    K/V head, their output blocks (double-buffered), the operands' blocks
    (double-buffered: q, dO, dQ, k, v, a selection's tile) and the float32
    [bq, bk] temporaries (s, p, dp, ds and their operand-dtype copies)."""
    D, Dv = _lanes(D), _lanes(Dv)
    return (S * (D + Dv) * (4 + 2 * itemsize)
            + 2 * ((2 * bq + bk) * D + (bq + bk) * Dv) * itemsize
            + 2 * bq * bk + bq * D * 4 + 6 * bq * bk * 4)


def _fused_bwd_fits(S, D, Dv, bq, bk, itemsize, group):
    """Whether a streaming backward call is the one kernel (each score tile
    made once) or the dQ and dK+dV pair: from the shapes alone. The one
    kernel must ask Mosaic for its VMEM (``vmem_limit_bytes``), which the
    pair never does, and that request, not the kernel's body, is what hung
    one kind of step on the v5e: the latent-attention cell's (one K/V head
    a query head, beside that model's expert sublayers) never came back
    from its first call with it, whatever the head dims; the pair hung the
    same step when given a request, and the one kernel ran it when, at
    smaller head dims, it needed none (PERF.md section 6, PR 38: what in
    that step the request meets is not known). So the one kernel runs
    where whole steps with its request have run on the chip: calls with
    shared K/V heads (``group`` query heads a K/V head, > 1); every other
    call keeps the pair."""
    return group > 1 and _fused_bwd_vmem_bytes(
        S, D, Dv, bq, bk, itemsize) <= STREAM_VMEM_BUDGET


def _flash_bwd_fused_kernel(*refs, scale, causal, block_q, block_k, nq, nk,
                            has_len, group, has_sel):
    """dQ, dK and dV of one K/V head from ONE s, p, dp, ds a (Q block, K
    block) tile: five matmuls where the dQ and dK+dV pair makes seven, and
    every pass over the [bq, bk] float32 tile once. Grid (K/V head, Q block,
    query head of the group x K block): dQ of a (query head, Q block)
    accumulates over its K blocks in ``dq_acc``; dK and dV of the whole head,
    [nk, bk, D] float32, stay in VMEM for all of it, the sum over the group
    with them, and are cast and written at the head's last step. MXU
    operands in the dtype they arrive in (p and ds cast to it), float32
    accumulation; scores, exp, lse, delta float32."""
    from jax.experimental import pallas as pl

    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), refs = (refs[:6],
                                                               refs[6:])
    len_ref, refs = (refs[0], refs[1:]) if has_len else (None, refs)
    sel_ref, refs = (refs[0], refs[1:]) if has_sel else (None, refs)
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
    step = pl.program_id(2)
    ki = step % nk
    qi = pl.program_id(1)
    bi = pl.program_id(0)
    f32 = jnp.float32

    @pl.when(jnp.logical_and(qi == 0, step == 0))
    def _init_head():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(ki == 0)
    def _init_rows():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        if has_len:
            s = _kv_len_mask(s, ki, block_k, len_ref[bi, 0])
        if has_sel:
            s = _select_mask(s, sel_ref)
        p = jnp.exp(s - lse_ref[0])                        # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        ds = (p * (dp - delta_ref[0])).astype(q.dtype)     # [bq, bk]
        dv_acc[ki] += jax.lax.dot_general(                 # p^T dO
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)                    # [bk, dv]
        dk_acc[ki] += jax.lax.dot_general(                 # ds^T q
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)                    # [bk, d]
        dq_acc[...] += jax.lax.dot_general(                # ds k
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)                    # [bq, d]

    need = None
    if causal:
        need = _causal_block_needed(qi, ki, block_q, block_k)
    if has_len:
        in_len = ki * block_k < len_ref[bi, 0]
        need = in_len if need is None else jnp.logical_and(need, in_len)
    if need is not None:
        pl.when(need)(_accumulate)
    else:
        _accumulate()

    @pl.when(ki == nk - 1)
    def _finish_rows():
        dq_ref[0] = (scale * dq_acc[...]).astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(qi == nq - 1, step == group * nk - 1))
    def _finish_head():
        dk_ref[0] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                    block_k, interpret, lengths=None, select=None):
    """dQ, dK, dV of the streaming path: one kernel that makes each score
    tile once for calls with shared K/V heads whose float32 dK and dV fit
    VMEM (``_fused_bwd_fits``), the dQ and dK+dV pair for the rest."""
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    Dv = v.shape[3]      # the head dim of v, the context and its cotangent
    group = _group(q, k)
    H_kv = H // group
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq, nk = S // bq, S // bk
    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H_kv, S, D)
    v3 = v.reshape(B * H_kv, S, Dv)
    do3 = g.reshape(B * H, S, Dv)
    o3 = out.reshape(B * H, S, Dv)
    # delta = rowsum(dO ∘ O): one fused elementwise pass, O(S·D)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [BH, S, 1]

    has_len = lengths is not None
    has_sel = select is not None
    # lengths: the whole array in SMEM, one row a grid batch step (query
    # heads for the dQ kernel, K/V heads for the others)
    len_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] if has_len else []
    kv_len = [_len_bh(lengths, B, H_kv)] if has_len else []
    sel = [select] if has_sel else []
    operands = (q3, k3, v3, do3, lse, delta)
    flags = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                 has_len=has_len, has_sel=has_sel)

    fused = _fused_bwd_fits(S, D, Dv, bq, bk, q.dtype.itemsize, group)
    count_backward("fused" if fused else "split")
    if fused:
        def q_rows(b, i, t):
            # K/V head b, step t: query head t // nk of its group
            return (b * group + t // nk, i, 0)

        def k_block(i, t):
            # the K block of step t; a causal step above the diagonal, which
            # computes nothing, names the last block it needed: no new copy
            j = t % nk
            return jnp.minimum(j, (i * bq + bq - 1) // bk) if causal else j

        def k_rows(b, i, t):
            return (b, k_block(i, t), 0)

        # dK, dV as [.., nk, bk, D]: the kernel indexes whole K blocks
        dq, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_fused_kernel, nq=nq, nk=nk,
                              group=group, **flags),
            grid=(B * H_kv, nq, group * nk),
            in_specs=[
                pl.BlockSpec((1, bq, D), q_rows),
                pl.BlockSpec((1, bk, D), k_rows),
                pl.BlockSpec((1, bk, Dv), k_rows),
                pl.BlockSpec((1, bq, Dv), q_rows),
                pl.BlockSpec((1, bq, 1), q_rows),
                pl.BlockSpec((1, bq, 1), q_rows),
            ] + len_specs + [
                pl.BlockSpec((1, bq, bk),
                             lambda b, i, t: (b // H_kv, i, k_block(i, t)))
            ] * has_sel,
            out_specs=[
                pl.BlockSpec((1, bq, D), q_rows),
                pl.BlockSpec((1, nk, bk, D), lambda b, i, t: (b, 0, 0, 0)),
                pl.BlockSpec((1, nk, bk, Dv), lambda b, i, t: (b, 0, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                jax.ShapeDtypeStruct((B * H_kv, nk, bk, D), k.dtype),
                jax.ShapeDtypeStruct((B * H_kv, nk, bk, Dv), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((nk, bk, D), jnp.float32),
                pltpu.VMEM((nk, bk, Dv), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_fused_bwd_vmem_bytes(
                    S, D, Dv, bq, bk, q.dtype.itemsize) + (8 << 20)),
            interpret=interpret,
            name="flash_stream_bwd",
        )(*operands, *kv_len, *sel)
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape))

    dq_kernel = functools.partial(_flash_bwd_dq_kernel, nk=nk, **flags)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ] + len_specs + [
            pl.BlockSpec((1, bq, bk), lambda b, i, j: (b // H, i, j))
        ] * has_sel,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, *([_len_bh(lengths, B, H)] if has_len else []), *sel)

    dkv_kernel = functools.partial(_flash_bwd_dkv_kernel, nq=nq, group=group,
                                   **flags)

    def q_rows(b, j, t):
        # K/V head b, step t: query head t // nq of its group, Q block t % nq
        return (b * group + t // nq, t % nq, 0) if group > 1 else (b, t, 0)

    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H_kv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), q_rows),
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, Dv), q_rows),
            pl.BlockSpec((1, bq, 1), q_rows),
            pl.BlockSpec((1, bq, 1), q_rows),
        ] + len_specs + [
            pl.BlockSpec((1, bq, bk),
                         lambda b, j, t: (b // H_kv, t % nq, j))
        ] * has_sel,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H_kv, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H_kv, S, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands, *kv_len, *sel)

    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


# ---------------------------------------------------------------------------
# short sequences: the whole score tile of a head lives in VMEM
# ---------------------------------------------------------------------------

# What the short path may ask of VMEM (v5e has 128 MiB; Mosaic's default
# scoped limit of 16 MiB is raised to what the shapes need).
SHORT_VMEM_BUDGET = 64 << 20
# A grid step takes up to this many query rows (heads x T) and heads: the
# per-head code is unrolled, so that one head's VPU passes overlap the
# next one's matmuls. Measured on the v5e (PERF.md section 6, PR 25):
# 16 heads at T = 128, 8 at 512, 4 at 1024; more is slower at 1024.
_SHORT_ROWS = 4096
_SHORT_MAX_HEADS = 16


def _short_vmem_bytes(heads, T, D, itemsize):
    """VMEM the backward kernel needs (the forward needs less): eight
    [heads, T, D] blocks, double-buffered, and one head's float32
    [T, T] temporaries (S, P, dP, dS and their operand-dtype copies)."""
    return 2 * 8 * heads * T * D * itemsize + 6 * T * T * 4


def _short_heads(BH, T, D, itemsize):
    """Heads a grid step works on, or 0 where the short path does not
    apply: the largest divisor of ``BH`` within ``_SHORT_ROWS`` rows,
    ``_SHORT_MAX_HEADS`` and the VMEM budget."""
    for heads in range(min(_SHORT_MAX_HEADS, max(1, _SHORT_ROWS // T), BH),
                       0, -1):
        if BH % heads == 0 and _short_vmem_bytes(
                heads, T, D, itemsize) <= SHORT_VMEM_BUDGET:
            return heads
    return 0


def _short_scores(q, k, len_val, scale, causal):
    """One head's masked scores, transposed: K (scale Q)^T, float32
    [Tk, Tq], with (scale Q). The softmax scale rides on the [T, D]
    operand, in its dtype, not on the [T, T] tile (for bf16 and a
    power-of-two scale, head dim 64 among them, the product is exact;
    the dense lowering scales q the same way). All short kernels work
    in this orientation: the softmax statistics, the LSE and delta are
    lane-dense rows [1, Tq], reductions run down the sublanes, and only
    [T, D]-sized operands are ever transposed."""
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if causal or len_val is not None:
        k_pos = jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        if causal:
            q_pos = jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
            st = jnp.where(q_pos >= k_pos, st, NEG_INF)
        if len_val is not None:
            st = jnp.where(k_pos < len_val, st, NEG_INF)
    return st, q


def _short_fwd_math(q, k, v, len_val, scale, causal):
    """One head's context, transposed, and its LSE from q, k, v [T, D]:
    (O^T float32 [D, Tq], LSE [1, Tq])."""
    st, _ = _short_scores(q, k, len_val, scale, causal)
    m = jnp.max(st, axis=0, keepdims=True)                 # [1, Tq]
    pt = jnp.exp(st - m)                                   # plain softmax
    l = jnp.sum(pt, axis=0, keepdims=True)                 # >= 1
    ot = jax.lax.dot_general(                              # V^T P^T
        v, pt.astype(v.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) / l            # [D, Tq]
    lse = m + jnp.log(l)
    if len_val is not None:
        # a row with no visible key puts out zeros and takes no
        # gradient (P recomputed from this LSE is 0), as in the
        # streaming kernels
        ot = jnp.where(len_val > 0, ot, 0.0)
        lse = jnp.where(len_val > 0, lse, -NEG_INF)
    return ot, lse


def _short_bwd_math(q, k, v, do, lse, delta, len_val, scale, causal):
    """One head's gradients from ONE S/P/dP: five matmuls, as the
    streaming path's one backward kernel makes a tile. Returns float32
    (dQ^T / scale [D, Tq], dK, dV [Tk, D])."""
    f32 = jnp.float32
    st, q = _short_scores(q, k, len_val, scale, causal)
    pt = jnp.exp(st - lse)                                 # [Tk, Tq]
    dv = jax.lax.dot_general(                              # P^T dO
        pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=f32)
    dpt = jax.lax.dot_general(                             # V dO^T
        v, do, (((1,), (1,)), ((), ())), preferred_element_type=f32)
    dst = (pt * (dpt - delta)).astype(q.dtype)
    dk = jax.lax.dot_general(                              # dS^T (scale Q)
        dst, q, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    dqt = jax.lax.dot_general(                             # K^T dS^T
        k, dst, (((0,), (0,)), ((), ())), preferred_element_type=f32)
    return dqt, dk, dv


def _short_fwd_kernel(*refs, scale, causal, has_len, heads):
    from jax.experimental import pallas as pl

    if has_len:
        q_ref, k_ref, v_ref, len_ref, o_ref, lse_ref = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref), len_ref = refs, None
    base = pl.program_id(0) * heads
    for g in range(heads):
        len_val = None if len_ref is None else len_ref[base + g, 0]
        ot, lse = _short_fwd_math(q_ref[g], k_ref[g], v_ref[g], len_val,
                                  scale, causal)
        o_ref[g] = jnp.transpose(ot).astype(o_ref.dtype)
        lse_ref[g] = lse


def _short_bwd_kernel(*refs, scale, causal, has_len, heads):
    """dQ, dK, dV of ``heads`` heads, one S/P/dP each."""
    from jax.experimental import pallas as pl

    if has_len:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, len_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dk_ref, dv_ref), len_ref = refs, None
    base = pl.program_id(0) * heads
    for g in range(heads):
        len_val = None if len_ref is None else len_ref[base + g, 0]
        dqt, dk, dv = _short_bwd_math(
            q_ref[g], k_ref[g], v_ref[g], do_ref[g], lse_ref[g],
            delta_ref[g], len_val, scale, causal)
        dv_ref[g] = dv.astype(dv_ref.dtype)
        dk_ref[g] = dk.astype(dk_ref.dtype)
        dq_ref[g] = (scale * jnp.transpose(dqt)).astype(dq_ref.dtype)


def _short_params(heads, T, D, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_short_vmem_bytes(heads, T, D, itemsize)
        + (8 << 20))


def _short_forward(q, k, v, causal, scale, heads, interpret,
                   lengths=None):
    """Returns (out [B,H,T,D], lse [B*H, 1, T] float32)."""
    from jax.experimental import pallas as pl

    B, H, T, D = q.shape
    BH = B * H
    block = pl.BlockSpec((heads, T, D), lambda b: (b, 0, 0))
    row = pl.BlockSpec((heads, 1, T), lambda b: (b, 0, 0))
    has_len = lengths is not None
    in_specs = [block, block, block]
    args = [x.reshape(BH, T, D) for x in (q, k, v)]
    if has_len:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_len_bh(lengths, B, H))
    out, lse = pl.pallas_call(
        functools.partial(_short_fwd_kernel, scale=scale, causal=causal,
                          has_len=has_len, heads=heads),
        grid=(BH // heads,),
        in_specs=in_specs,
        out_specs=[block, row],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), q.dtype),
                   jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)],
        compiler_params=_short_params(heads, T, D, q.dtype.itemsize),
        interpret=interpret,
        name="flash_short_fwd",
    )(*args)
    return out.reshape(B, H, T, D), lse


def _short_backward(q, k, v, out, lse, g, causal, scale, heads,
                    interpret, lengths=None):
    from jax.experimental import pallas as pl

    count_backward("short")
    B, H, T, D = q.shape
    BH = B * H
    # delta = rowsum(dO * O), one fused pass of XLA's, kept as rows
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(BH, 1, T)
    block = pl.BlockSpec((heads, T, D), lambda b: (b, 0, 0))
    row = pl.BlockSpec((heads, 1, T), lambda b: (b, 0, 0))
    has_len = lengths is not None
    in_specs = [block, block, block, block, row, row]
    args = [x.reshape(BH, T, D) for x in (q, k, v, g)] + [lse, delta]
    if has_len:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_len_bh(lengths, B, H))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_short_bwd_kernel, scale=scale, causal=causal,
                          has_len=has_len, heads=heads),
        grid=(BH // heads,),
        in_specs=in_specs,
        out_specs=[block, block, block],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), x.dtype)
                   for x in (q, k, v)],
        compiler_params=_short_params(heads, T, D, q.dtype.itemsize),
        interpret=interpret,
        name="flash_short_bwd",
    )(*args)
    shape = (B, H, T, D)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


# ---------------------------------------------------------------------------
# short sequences, token-major: q, k, v and the context as [B, T, H*hd]
# ---------------------------------------------------------------------------

# Elements of one [rows, T, lanes] block, and the heads a grid step
# unrolls. Measured on the v5e for one layer's forward + backward
# (PERF.md section 6, PR 29): the widest block of whole heads wins at
# every length up to these (T = 512: 768 lanes; T = 1024: 384). More
# batch rows a step gain a few hundredths of a millisecond a layer at
# T <= 256 and nothing above, and every process traces and lowers the
# unrolled per-head code again before a compilation cache can answer
# (~2 s a head of the four-chip cell's first step), so a step stops at
# 12 heads.
_TOKENS_BLOCK = 512 * 768
_TOKENS_MAX_HEADS = 12


def _tokens_vmem_bytes(rows, T, lanes, itemsize):
    """VMEM of the token-major backward kernel: eight [rows, T, lanes]
    blocks, double-buffered, one head's float32 [T, T] temporaries and a
    batch row's float32 results before they are stored."""
    return (2 * 8 * rows * T * lanes * itemsize + 6 * T * T * 4
            + 4 * T * lanes * 4)


def _tokens_blocks(B, T, H, D, itemsize):
    """``(rows, lanes)`` of the token-major short kernels for [B, T, H*D]
    operands, or None where they do not apply (a head dim that neither
    divides 128 nor is a multiple of it; blocks over the VMEM budget):
    ``lanes`` is the widest divisor of ``H*D`` that is whole heads and a
    multiple of 128 within ``_TOKENS_BLOCK``, ``rows`` the most batch rows
    that still fit it (and ``_TOKENS_MAX_HEADS``)."""
    if 128 % D and D % 128:
        return None
    E = H * D
    for lanes in range(E, 0, -D):
        if E % lanes or lanes % 128 or T * lanes > _TOKENS_BLOCK:
            continue
        most = min(_TOKENS_BLOCK // (T * lanes),
                   max(1, _TOKENS_MAX_HEADS // (lanes // D)), B)
        rows = next(r for r in range(most, 0, -1) if B % r == 0)
        if _tokens_vmem_bytes(rows, T, lanes, itemsize) \
                <= SHORT_VMEM_BUDGET:
            return rows, lanes
    return None


def _heads_of(refs, i, hd):
    """h -> head ``h``'s lanes of batch row ``i`` of each block in
    ``refs``. Whole lane tiles are sliced off the references; a head
    narrower than that is sliced off the row's loaded value (the faster
    of the two on the v5e at every length)."""
    if hd % 128 == 0:
        return lambda h: [ref[i, :, h * hd:(h + 1) * hd] for ref in refs]
    whole = [ref[i] for ref in refs]
    return lambda h: [x[:, h * hd:(h + 1) * hd] for x in whole]


def _store_row(ref, i, per_head, transposed):
    """Batch row ``i`` of ``ref`` from its heads' float32 results
    ([T, hd] each, or [hd, T] where ``transposed``): whole lane tiles are
    stored one by one, narrower ones concatenated first (and transposed
    once)."""
    hd = per_head[0].shape[0 if transposed else 1]
    if hd % 128:
        per_head = [jnp.concatenate(per_head, axis=0 if transposed else 1)]
        hd = ref.shape[2]
    for h, x in enumerate(per_head):
        ref[i, :, h * hd:(h + 1) * hd] = (
            jnp.transpose(x) if transposed else x).astype(ref.dtype)


def _tokens_fwd_kernel(*refs, scale, causal, has_len, rows, heads, hd):
    from jax.experimental import pallas as pl

    if has_len:
        q_ref, k_ref, v_ref, len_ref, o_ref, lse_ref = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref), len_ref = refs, None
    base = pl.program_id(0) * rows
    for i in range(rows):
        len_val = None if len_ref is None else len_ref[base + i, 0]
        take = _heads_of((q_ref, k_ref, v_ref), i, hd)
        ots = []
        for h in range(heads):
            ot, lse_ref[i, h] = _short_fwd_math(*take(h), len_val, scale,
                                                causal)
            ots.append(ot)
        _store_row(o_ref, i, ots, True)


def _tokens_bwd_kernel(*refs, scale, causal, has_len, rows, heads, hd):
    """dQ, dK, dV of ``rows`` x ``heads`` heads; delta = rowsum(dO * O)
    is made here, as a lane-dense row: the [T, hd] product is transposed
    and summed down the sublanes."""
    from jax.experimental import pallas as pl

    if has_len:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, len_ref,
         dq_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
         dq_ref, dk_ref, dv_ref), len_ref = refs, None
    base = pl.program_id(0) * rows
    for i in range(rows):
        len_val = None if len_ref is None else len_ref[base + i, 0]
        take = _heads_of((q_ref, k_ref, v_ref, do_ref, o_ref), i, hd)
        dqts, dks, dvs = [], [], []
        for h in range(heads):
            q, k, v, do, o = take(h)
            lse = lse_ref[i, h]
            delta = jnp.sum(
                jnp.transpose(do.astype(jnp.float32) * o.astype(jnp.float32)),
                axis=0, keepdims=True)
            dqt, dk, dv = _short_bwd_math(q, k, v, do, lse, delta, len_val,
                                          scale, causal)
            dqts.append(scale * dqt)
            dks.append(dk)
            dvs.append(dv)
        _store_row(dv_ref, i, dvs, False)
        _store_row(dk_ref, i, dks, False)
        _store_row(dq_ref, i, dqts, True)


def _tokens_call(kernel, name, blocks_in, lse, lengths, causal, scale,
                 num_heads, blocks, interpret):
    """One token-major kernel over the grid (B / rows, H*hd / lanes).
    The forward (``lse`` None) writes the context and the LSE; the
    backward reads the LSE and writes dQ, dK, dV."""
    from jax.experimental import pallas as pl

    q = blocks_in[0]
    B, T, E = q.shape
    rows, lanes = blocks
    hd = E // num_heads
    heads = lanes // hd
    block = pl.BlockSpec((rows, T, lanes), lambda b, j: (b, 0, j))
    row = pl.BlockSpec((rows, heads, 1, T), lambda b, j: (b, j, 0, 0))
    like_q = jax.ShapeDtypeStruct((B, T, E), q.dtype)
    in_specs, operands = [block] * len(blocks_in), list(blocks_in)
    if lse is None:
        out_specs, out_shape = [block, row], [like_q, jax.ShapeDtypeStruct(
            (B, num_heads, 1, T), jnp.float32)]
    else:
        in_specs.append(row)
        operands.append(lse)
        out_specs, out_shape = [block] * 3, [like_q] * 3
    has_len = lengths is not None
    if has_len:   # the whole [B, 1] array in SMEM, a scalar a batch row
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(lengths.astype(jnp.int32).reshape(B, 1))
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, causal=causal,
                          has_len=has_len, rows=rows, heads=heads, hd=hd),
        grid=(B // rows, E // lanes),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_tokens_vmem_bytes(
                rows, T, lanes, q.dtype.itemsize) + (8 << 20)),
        interpret=interpret,
        name=name,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_tokens(q, k, v, lengths, causal, scale, num_heads, blocks,
                  interpret):
    """(out [B, T, H*hd], lse [B, H, 1, T] float32) of the token-major
    short kernels; ``blocks`` as ``_tokens_blocks`` gives them."""
    return tuple(_tokens_call(
        _tokens_fwd_kernel, "flash_short_fwd", (q, k, v), None, lengths,
        causal, scale, num_heads, blocks, interpret))


def _flash_tokens_fwd(q, k, v, lengths, causal, scale, num_heads, blocks,
                      interpret):
    out, lse = _flash_tokens(q, k, v, lengths, causal, scale, num_heads,
                             blocks, interpret)
    return (out, lse), (q, k, v, lengths, out, lse)


def _flash_tokens_bwd(causal, scale, num_heads, blocks, interpret, res,
                      cts):
    q, k, v, lengths, out, lse = res
    count_backward("short")
    grads = _tokens_call(
        _tokens_bwd_kernel, "flash_short_bwd",
        (q, k, v, cts[0].astype(q.dtype), out), lse, lengths, causal,
        scale, num_heads, blocks, interpret)
    return tuple(grads) + (_no_tangent(lengths),)


_flash_tokens.defvjp(_flash_tokens_fwd, _flash_tokens_bwd)


def split_heads(x, num_heads):
    """[B, T, H*hd] -> [B, H, T, hd]."""
    B, T, E = x.shape
    return x.reshape(B, T, num_heads, E // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    """[B, H, T, hd] -> [B, T, H*hd]."""
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


# ---------------------------------------------------------------------------
# custom VJP plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, lengths, causal, scale, block_q, block_k, heads,
           interpret):
    """(out, lse). ``lengths`` is None or [B] int. ``heads`` > 0: the
    short path, that many heads a grid step (lse [B*H, 1, S]); 0: the
    streaming kernels (lse [B*H, S, 1]). The LSE is a residual for the
    backward: it takes no cotangent."""
    if heads:
        return _short_forward(q, k, v, causal, scale, heads, interpret,
                              lengths)
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, lengths)


def _flash_fwd(q, k, v, lengths, causal, scale, block_q, block_k, heads,
               interpret):
    out, lse = _flash(q, k, v, lengths, causal, scale, block_q, block_k,
                      heads, interpret)
    return (out, lse), (q, k, v, lengths, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, heads, interpret, res,
               cts):
    q, k, v, lengths, out, lse = res
    g = cts[0]
    dlen = _no_tangent(lengths)
    if heads:
        return _short_backward(q, k, v, out, lse, g, causal, scale,
                               heads, interpret, lengths) + (dlen,)
    S = q.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S != k.shape[2] or S % bq or S % bk:
        # ragged tail / rectangular: dense VJP (matches the forward's
        # own fallback)
        count_backward("dense")
        _, vjp = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, causal, scale,
                                             lengths), q, k, v)
        return vjp(g) + (dlen,)
    return _flash_backward(q, k, v, out, lse, g, causal, scale,
                           block_q, block_k, interpret,
                           lengths=lengths) + (dlen,)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_selected(q, k, v, select, causal, scale, block_q, block_k,
                    interpret):
    """(out, lse) of the streaming kernels with a per-query key selection
    ``select`` [B, S, S] int8 applied beside the causal mask. Every causal
    block is visited and masked; no block is skipped for the selection."""
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, select=select)


def _flash_selected_fwd(q, k, v, select, causal, scale, block_q, block_k,
                        interpret):
    out, lse = _flash_selected(q, k, v, select, causal, scale, block_q,
                               block_k, interpret)
    return (out, lse), (q, k, v, select, out, lse)


def _flash_selected_bwd(causal, scale, block_q, block_k, interpret, res,
                        cts):
    q, k, v, select, out, lse = res
    return _flash_backward(q, k, v, out, lse, cts[0], causal, scale,
                           block_q, block_k, interpret,
                           select=select) + (_no_tangent(select),)


_flash_selected.defvjp(_flash_selected_fwd, _flash_selected_bwd)


def _fit_block(S, block):
    """Largest divisor of ``S`` that is <= ``block`` and lane-aligned
    (a multiple of 128, or ``S`` itself when S < block). Returns 0 when
    no aligned divisor exists (caller falls back to dense)."""
    b = min(block, S)
    if S % b == 0:
        return b
    align = 128 if b >= 128 else 8  # lane / sublane tile alignment
    for cand in range((b // align) * align, align - 1, -align):
        if S % cand == 0:
            return cand
    return 0


def _plan_selected(q, k, block_q, block_k):
    """(block_q, block_k) of the streaming kernels for a call with a
    selection: square head-major operands in whole aligned blocks (the
    selection's tiles are cut the same way), or an error."""
    S = q.shape[-2]
    bq, bk = _fit_block(S, block_q), _fit_block(S, block_k)
    if q.ndim != 4 or S != k.shape[2] or not (bq and bk):
        raise ValueError(
            "flash_attention: a selection needs head-major q %s, k %s of one "
            "length that splits into aligned blocks" % (q.shape, k.shape))
    return bq, bk


def _value_dim(v, num_heads=0):
    """The head dim of ``v`` (and of the context), in either layout."""
    return v.shape[2] // num_heads if v.ndim == 3 else v.shape[3]


def _plan(q, k, block_q, block_k, num_heads=0, value_dim=0):
    """Which kernels a call takes, from what it can see: the operands'
    layout (rank 4 is [B, H, S, D]; rank 3 is token-major [B, T, H*hd]
    with ``num_heads``), sequence lengths, head dim (``value_dim``: v's,
    where it is not q's and k's; such a call streams, the short kernels
    take one head dim), dtype and the VMEM
    the short path would need. Returns (short, block_q, block_k):
    ``short`` is the token-major short kernels' blocks ``(rows,
    lanes)``, or the heads a grid step of the head-major short kernels
    takes, or 0 for the streaming kernels. Token-major operands whose
    plan is no tuple are split into heads by the caller."""
    tokens = q.ndim == 3
    if tokens:
        (B, S, E), H = q.shape, num_heads
        D, H_kv, S_kv = E // H, H, k.shape[1]
    else:
        (B, H, S, D), H_kv, S_kv = q.shape, k.shape[1], k.shape[2]
    if S != S_kv:
        return 0, block_q, block_k   # rectangular: the dense fallback
    if S % 128 == 0 and S <= block_k and H == H_kv \
            and value_dim in (0, D):
        # the caller's K block holds the whole sequence (the short kernels
        # take one head count: shared K/V heads stream)
        itemsize = q.dtype.itemsize
        short = _tokens_blocks(B, S, H, D, itemsize) if tokens else None
        short = short or _short_heads(B * H, S, D, itemsize)
        if short:
            return short, block_q, block_k
    # S not a multiple of the tuned blocks (e.g. 2560 % 1024): shrink to
    # the largest aligned divisor rather than silently dropping to the
    # dense O(S^2) path
    bq, bk = _fit_block(S, block_q), _fit_block(S, block_k)
    if bq and bk:
        return 0, bq, bk
    warnings.warn(
        "flash_attention: seq_len %d has no 128-aligned block "
        "divisor; using dense O(S^2) attention" % S)
    return 0, block_q, block_k


def attention_path(q, k, block_q: int = 512, block_k: int = 1024,
                   force_pallas: bool = False, num_heads: int = 0,
                   select=None, v=None) -> str:
    """"short" | "stream" | "dense": what ``flash_attention`` runs for
    these arguments where the computation is placed now (a selection
    always streams, and so does a ``v`` whose head dim is not q's)."""
    if not (force_pallas or compute_platform() == "tpu"):
        return "dense"
    if select is not None:
        return "stream"
    value_dim = 0 if v is None else _value_dim(v, num_heads)
    return ("short" if _plan(q, k, block_q, block_k, num_heads,
                             value_dim)[0] else "stream")


def _check_layout(q, k, num_heads):
    """Token-major operands name their head count, head-major ones carry
    it; returns the head dim."""
    if q.ndim == 3:
        if num_heads <= 0 or q.shape[2] % num_heads \
                or k.shape[2] != q.shape[2]:
            raise ValueError(
                "flash_attention: token-major q %s, k %s need num_heads "
                "dividing their last axis (got %d)"
                % (q.shape, k.shape, num_heads))
        return q.shape[2] // num_heads
    if num_heads and num_heads != q.shape[1]:
        raise ValueError("flash_attention: num_heads %d for q %s"
                         % (num_heads, q.shape))
    return q.shape[3]


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: int = 512, block_k: int = 1024,
                             force_pallas: bool = False, lengths=None,
                             num_heads: int = 0, select=None):
    """``flash_attention`` and the residual its backward needs: (out,
    lse), differentiable (the LSE takes no cotangent). ``lse`` is None
    where the dense math ran (off the TPU) without a selection; with
    ``select`` it is [B*H, S, 1] wherever the call runs."""
    head_dim = _check_layout(q, k, num_heads)
    if scale is None:
        scale = float(head_dim) ** -0.5
    on_tpu = compute_platform() == "tpu"
    if select is not None:
        if lengths is not None:
            raise ValueError("flash_attention: select and lengths together")
        if not (on_tpu or force_pallas):
            out, lse = _dense_attention(q, k, v, causal, scale,
                                        select=select, with_lse=True)
            return out, jax.lax.stop_gradient(lse)
        block_q, block_k = _plan_selected(q, k, block_q, block_k)
        return _flash_selected(q, k, v, select, causal, scale, block_q,
                               block_k, not on_tpu)
    short = None   # no kernels: the dense math (0 is the streaming kernels)
    if on_tpu or force_pallas:
        short, block_q, block_k = _plan(q, k, block_q, block_k, num_heads,
                                        _value_dim(v, num_heads))
    if isinstance(short, tuple):
        return _flash_tokens(q, k, v, lengths, causal, scale, num_heads,
                             short, not on_tpu)
    tokens = q.ndim == 3
    if tokens:   # the other kernels, and the dense math, are head-major
        q, k, v = (split_heads(x, num_heads) for x in (q, k, v))
    if short is None:
        out, lse = _dense_attention(q, k, v, causal, scale, lengths), None
    else:
        out, lse = _flash(q, k, v, lengths, causal, scale, block_q,
                          block_k, short, not on_tpu)
    return (merge_heads(out) if tokens else out), lse


def flash_attention_bwd(q, k, v, lengths, out, lse, g, causal: bool,
                        scale: Optional[float] = None, block_q: int = 512,
                        block_k: int = 1024, num_heads: int = 0,
                        select=None):
    """(dq, dk, dv) from the forward's own ``out`` and ``lse`` (as
    ``flash_attention_with_lse`` returned them for the same arguments):
    the backward kernels alone, no second forward."""
    head_dim = _check_layout(q, k, num_heads)
    if scale is None:
        scale = float(head_dim) ** -0.5
    if select is not None:
        block_q, block_k = _plan_selected(q, k, block_q, block_k)
        return _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                               block_k, compute_platform() != "tpu",
                               select=select)
    short, block_q, block_k = _plan(q, k, block_q, block_k, num_heads,
                                    _value_dim(v, num_heads))
    interpret = compute_platform() != "tpu"
    if isinstance(short, tuple):
        return _flash_tokens_bwd(causal, scale, num_heads, short, interpret,
                                 (q, k, v, lengths, out, lse), (g, None))[:3]
    tokens = q.ndim == 3
    if tokens:
        q, k, v, out, g = (split_heads(x, num_heads)
                           for x in (q, k, v, out, g))
    grads = _flash_bwd(causal, scale, block_q, block_k, short, interpret,
                       (q, k, v, lengths, out, lse), (g, None))[:3]
    return tuple(merge_heads(x) for x in grads) if tokens else grads


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 1024, force_pallas: bool = False,
                    lengths=None, num_heads: int = 0, select=None):
    """Flash attention over ``[B, H, S, D]`` tensors, or token-major
    ``[B, T, H*hd]`` ones with ``num_heads`` (the layout is read from the
    operands' rank; the context comes back in it) — differentiable,
    and no S x S matrix in HBM in either direction: the backward
    recomputes the probabilities from the saved logsumexp. Head-major
    ``k`` and ``v`` may be ``[B, H_kv, S, D]`` with ``H_kv`` dividing
    ``H`` (shared K/V heads; always the streaming kernels on the TPU).
    ``v`` may have a head dim of its own, ``[B, H_kv, S, Dv]``: the context
    is then ``[B, H, S, Dv]`` (latent attention's 192 beside 128; the
    streaming kernels at any length, the short ones take one head dim).

    Which kernels run is decided here, from the shapes (``_plan``):

    - **short** — S a multiple of 128 that fits the K block (S <= 1024
      with the defaults) and whose float32 [S, S] tiles fit
      ``SHORT_VMEM_BUDGET``: a head's whole score tile lives in VMEM,
      so the softmax is plain (no running max), several heads share a
      grid step, MXU operands stay in the input dtype, and ONE backward
      kernel yields dQ, dK and dV from one S/P/dP. Token-major operands
      whose ``H*hd`` axis splits into 128-lane blocks of whole heads are
      read, and the context written, as they are: no head split or
      merge in HBM (``_tokens_blocks``).
    - **stream** — longer S: K blocks stream past each Q block with a
      running softmax; with shared K/V heads the backward is one kernel
      that makes each score tile once for dQ, dK and dV (a K/V head's
      float32 dK and dV stay in VMEM, up to ``STREAM_VMEM_BUDGET``);
      otherwise a dQ and a dK+dV kernel.
      ``block_q`` x ``block_k`` = 512 x 1024 by default; blocks shrink
      to an aligned divisor of S. Token-major operands are split into
      heads, and the context merged, around these kernels.
    - **dense** — where the computation does not run on a TPU (and
      ``force_pallas``, interpret mode, was not asked for): the same
      math in plain XLA.

    ``lengths`` ([B] int) is the padding mask: row b attends only to
    its first ``lengths[b]`` keys — the kernel-side equivalent of the
    reference's additive src_slf_attn_bias over padded positions,
    composable with ``causal``; both masks run on either kernel path
    (the streaming kernels also skip key blocks past the tail). Padded
    QUERY rows produce zeros/garbage exactly like the additive-mask
    formulation; mask the loss, as seq2seq training already does.

    ``select`` ([B, S, S] int8, non-zero where query row r may see key
    column c; one selection for all the heads of a batch row) is a
    per-query key selection applied inside the streaming kernels beside
    the causal mask (head-major operands, shared K/V heads or not; the
    dense math elsewhere). Every causal block is still visited: a
    selection hides keys, it skips no block yet. A selection of every
    causal key gives the unselected result bit for bit.

    Timings on the v5e: PERF.md section 6, "PR 25" and "PR 29"
    (``tools/attn_bench.py`` repeats them).
    """
    return flash_attention_with_lse(q, k, v, causal, scale, block_q,
                                    block_k, force_pallas, lengths,
                                    num_heads, select)[0]
