"""Flash attention as Pallas TPU kernels — forward AND backward.

Parity intent: the reference hand-fuses attention for inference in CUDA
(operators/fused/multihead_matmul_op.cu, math/bert_encoder_functor.cu);
this is the TPU-native equivalent, done the flash way so the S x S
score matrix never materializes in HBM:

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
  Two kernels: dQ iterates K blocks per Q block; dK/dV iterates Q
  blocks per K block; both consume the dense precomputed
  delta = rowsum(dO ∘ O) (an elementwise pass XLA fuses).

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
    forward and both backward kernels — one definition to extend for
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


def _dense_attention(q, k, v, causal, scale, lengths=None):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s,
                      NEG_INF)
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
    return out.astype(q.dtype)


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


def _flash_kernel(*refs, scale, causal, block_q, block_k, nk, has_len):
    from jax.experimental import pallas as pl

    if has_len:
        (q_ref, k_ref, v_ref, len_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
        len_ref = None
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


def _len_bh(lengths, B, H):
    """[B] lengths -> [B*H, 1] int32 (one row per grid batch step)."""
    return jnp.repeat(lengths.astype(jnp.int32), H).reshape(B * H, 1)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   lengths=None):
    """Returns (out [B,H,S,D], lse [B*H, S] float32)."""
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    S_kv = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S != S_kv or S % bq or S % bk:
        # ragged tail, or rectangular cross-attention Q/K — the kernel
        # grid assumes square S; dense math handles both exactly
        q3 = q.reshape(B * H, S, D)
        k3 = k.reshape(B * H, S_kv, D)
        lbh = (None if lengths is None
               else jnp.repeat(lengths.astype(jnp.int32), H))
        return (_dense_attention(q, k, v, causal, scale, lengths),
                _dense_lse(q3, k3, causal, scale, lbh))
    nq, nk = S // bq, S // bk
    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H, S, D)
    v3 = v.reshape(B * H, S, D)

    has_len = lengths is not None
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=bq, block_k=bk, nk=nk,
                               has_len=has_len)
    in_specs = [
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ]
    args = [q3, k3, v3]
    if has_len:
        # whole [BH,1] array in SMEM (scalar per batch row — a
        # (1,1) VMEM block would violate the TPU (8,128) tile rule)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(_len_bh(lengths, B, H))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            # [BH, S, 1]: last block dim = full array dim (exempt from
            # the /128 lane rule), penultimate bq satisfies the /8 rule
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, S, D), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(*refs, scale, causal, block_q, block_k, nk,
                         has_len):
    from jax.experimental import pallas as pl

    if has_len:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, len_ref,
         dq_ref, dq_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_acc) = refs
        len_ref = None
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
                          has_len):
    from jax.experimental import pallas as pl

    if has_len:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, len_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
        len_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(1)
    bi = pl.program_id(0)

    @pl.when(qi == 0)
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

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                    block_k, interpret, lengths=None):
    from jax.experimental import pallas as pl

    B, H, S, D = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    nq, nk = S // bq, S // bk
    q3 = q.reshape(B * H, S, D)
    k3 = k.reshape(B * H, S, D)
    v3 = v.reshape(B * H, S, D)
    do3 = g.reshape(B * H, S, D)
    o3 = out.reshape(B * H, S, D)
    # delta = rowsum(dO ∘ O): one fused elementwise pass, O(S·D)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)            # [BH, S, 1]

    has_len = lengths is not None
    extra_args = []
    dq_len_specs = []
    dkv_len_specs = []
    if has_len:
        extra_args.append(_len_bh(lengths, B, H))
        dq_len_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        dkv_len_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, nk=nk, has_len=has_len)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)),
        ] + dq_len_specs,
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta, *extra_args)

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal, block_q=bq,
        block_k=bk, nq=nq, has_len=has_len)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, j, i: (b, i, 0)),
        ] + dkv_len_specs,
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta, *extra_args)

    shape = (B, H, S, D)
    return (dq.reshape(shape), dk.reshape(shape), dv.reshape(shape))


# ---------------------------------------------------------------------------
# custom VJP plumbing
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    S = q.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    if S != k.shape[2] or S % bq or S % bk:
        # ragged tail / rectangular: dense VJP (matches the forward's
        # own fallback)
        _, vjp = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, causal, scale),
            q, k, v)
        return vjp(g)
    return _flash_backward(q, k, v, out, lse, g, causal, scale,
                           block_q, block_k, interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_masked(q, k, v, lengths, causal, scale, block_q, block_k,
                  interpret):
    out, _lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                               interpret, lengths=lengths)
    return out


def _flash_masked_fwd(q, k, v, lengths, causal, scale, block_q, block_k,
                      interpret):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret, lengths=lengths)
    return out, (q, k, v, lengths, out, lse)


def _flash_masked_bwd(causal, scale, block_q, block_k, interpret, res, g):
    from jax.dtypes import float0

    q, k, v, lengths, out, lse = res
    S = q.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, S)
    dlen = np.zeros(lengths.shape, dtype=float0)  # int arg: no tangent
    if S != k.shape[2] or S % bq or S % bk:
        _, vjp = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, causal, scale,
                                             lengths), q, k, v)
        return vjp(g) + (dlen,)
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal, scale,
                                 block_q, block_k, interpret,
                                 lengths=lengths)
    return (dq, dk, dv, dlen)


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


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


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 512,
                    block_k: int = 1024, force_pallas: bool = False,
                    lengths=None):
    """Flash attention over ``[B, H, S, D]`` tensors — differentiable:
    the backward runs the pallas dQ / dK+dV kernels with blockwise
    probability recomputation from the saved logsumexp (O(S·D) training
    memory; no S×S matrix in HBM in either direction).

    ``lengths`` ([B] int) is the padding mask: row b attends only to
    its first ``lengths[b]`` keys (key blocks past the tail are skipped
    entirely) — the kernel-side equivalent of the reference's additive
    src_slf_attn_bias over padded positions, composable with
    ``causal``. Padded QUERY rows produce zeros/garbage exactly like
    the additive-mask formulation; mask the loss, as seq2seq training
    already does.

    Uses the pallas kernels where the computation runs on a TPU (or
    when ``force_pallas`` — interpret mode off-TPU — is requested, e.g.
    in tests); dense math elsewhere.

    Block defaults are tuned on v5e (b4 h16 d64, causal, fwd+bwd):
    512x1024 blocks turn the 128x128 default's 0.6-0.9x vs XLA dense
    into 1.0-2.3x FASTER (S=512..4096), and at S=8192/16384 flash
    trains in 68/190 ms/step where the dense lowering does not compile
    at all. Blocks auto-cap to S for short sequences.
    """
    if scale is None:
        scale = float(q.shape[-1]) ** -0.5
    S, S_kv = q.shape[2], k.shape[2]
    if S == S_kv:
        # S not a multiple of the tuned blocks (e.g. 2560 % 1024):
        # shrink to the largest aligned divisor rather than silently
        # dropping to the dense O(S^2) path
        bq, bk = _fit_block(S, block_q), _fit_block(S, block_k)
        if bq and bk:
            block_q, block_k = bq, bk
        else:
            warnings.warn(
                "flash_attention: seq_len %d has no 128-aligned block "
                "divisor; using dense O(S^2) attention" % S)
    on_tpu = compute_platform() == "tpu"
    interpret = not on_tpu
    if on_tpu or force_pallas:
        if lengths is not None:
            return _flash_masked(q, k, v, lengths, causal, scale,
                                 block_q, block_k, interpret)
        return _flash(q, k, v, causal, scale, block_q, block_k,
                      interpret)
    return _dense_attention(q, k, v, causal, scale, lengths)
