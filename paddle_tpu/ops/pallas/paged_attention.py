"""Paged decode-step attention: one query token per sequence, keys and
values read straight out of the serving tier's paged KV-cache arena.

The continuous-batching decode engine (``serving/decode``) stores every
sequence's KV history in fixed-size blocks scattered over ONE
preallocated arena; a per-sequence block table maps logical token
positions to arena blocks. A decode step then needs attention of shape
``q:[B, H, D] x cache:[ragged lengths]`` — the classic "paged
attention" kernel. Materializing each sequence's cache densely per step
(gather + concatenate) is exactly the copy this layout exists to avoid,
so the kernel reads the arena THROUGH the block table:

- **pallas TPU path** — grid ``(B, max_blocks)``: the block table rides
  in as a scalar-prefetch operand (``PrefetchScalarGridSpec``), so each
  grid step's index map picks the NEXT arena block for this sequence
  and pallas streams exactly that ``[block_tokens, H, D]`` tile
  HBM->VMEM; a running-softmax scratch (m, l, acc — the flash
  accumulation, float32 regardless of storage dtype) persists across
  the sequentially-iterated block axis. Padded table entries re-fetch
  block 0 and are masked by the per-sequence length, so the ragged
  batch pads to a rectangle without touching ragged memory.
- **dense reference** (CPU hosts, quantized arenas): identical math in
  numpy over the same arena + block table. The serving smoke runs on
  CPU hosts, so this path IS the production path there; on a TPU
  backend the pallas path is the default for f32 arenas and a kernel
  that fails to build raises — nothing routes back here silently.

Quantized arenas (the EQuARX-shaped KV trick: shared-scale int8 codes,
``serving/decode/kvcache.py``) pass their per-(block, head) scales;
dequantization happens tile-local in the kernel — codes travel
HBM->VMEM at 1/4 the f32 width, which is the whole point of quantizing
the cache. bf16 arenas arrive as uint16 bit patterns and are widened
the same way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...core.place import compute_platform

__all__ = ["paged_decode_attention", "paged_attention_reference"]

NEG_INF = -1e30


def _widen(arr, scales, block_ids):
    """Dequantize one gathered [T, H, D] slab to float32.

    ``scales`` is None for f32 arenas, the per-(block, head) scale
    array for int8 codes, or the string ``"bf16"`` for uint16 bit
    patterns (value = bits << 16 reinterpreted as float32)."""
    if scales is None:
        return arr.astype(np.float32)
    if isinstance(scales, str) and scales == "bf16":
        return (arr.astype(np.uint32) << 16).view(np.float32)
    # int8 codes: scale indexed per source block, broadcast over the
    # block's tokens and the head dim
    s = scales[block_ids]                       # [T, H]
    return arr.astype(np.float32) * s[:, :, None]


def paged_attention_reference(q, k_arena, v_arena, block_tables,
                              seq_lens, *, block_tokens: int,
                              scale: Optional[float] = None,
                              k_scales=None, v_scales=None):
    """Dense reference: gather each sequence's blocks, run softmax
    attention, return ``[B, H, D]`` float32. Zero-length rows (padded
    batch slots) return zeros.

    ``k_scales``/``v_scales``: per-(block, head) float32 scales for
    int8 arenas, or the string ``"bf16"`` for uint16 bf16 arenas, or
    None for float32 storage. Shapes: q ``[B, H, D]``, arenas
    ``[num_blocks, block_tokens, H, D]``, block_tables
    ``[B, max_blocks]`` int (-1 padded), seq_lens ``[B]`` int.
    """
    q = np.asarray(q, np.float32)
    B, H, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    out = np.zeros((B, H, D), np.float32)
    block_tables = np.asarray(block_tables)
    seq_lens = np.asarray(seq_lens)
    for b in range(B):
        n = int(seq_lens[b])
        if n <= 0:
            continue
        nblk = -(-n // block_tokens)
        ids = block_tables[b, :nblk]
        # token t lives at (ids[t // bt], t % bt)
        tok_blocks = np.repeat(ids, block_tokens)[:n]
        k = _widen(k_arena[ids].reshape(-1, H, D)[:n], k_scales,
                   tok_blocks)
        v = _widen(v_arena[ids].reshape(-1, H, D)[:n], v_scales,
                   tok_blocks)
        s = np.einsum("hd,thd->ht", q[b], k) * scale      # [H, T]
        s -= s.max(axis=1, keepdims=True)
        p = np.exp(s)
        p /= p.sum(axis=1, keepdims=True)
        out[b] = np.einsum("ht,thd->hd", p, v)
    return out


# ---------------------------------------------------------------------------
# pallas TPU kernel
# ---------------------------------------------------------------------------


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_tokens, scale,
                  n_blocks):
    """One (sequence, cache-block) grid step: flash accumulation over
    this block's keys/values. The index maps already routed the RIGHT
    arena block into ``k_ref``/``v_ref`` via the prefetched table."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[pl.program_id(0)]
    base = j * block_tokens
    valid = base < seq_len

    @pl.when(valid)
    def _accumulate():
        # one query row per sequence: the step is bandwidth-bound, so
        # the scores are a broadcast-multiply-reduce on the VPU rather
        # than an MXU contraction (Mosaic refuses the 3-D "hd,thd->ht"
        # dot). Every intermediate keeps K/V's own [T, H, .] layout:
        # T is the leading (untiled) dim, H sublanes, D lanes — only
        # lane reductions with keepdims and leading-dim reductions.
        q = q_ref[0].astype(jnp.float32)                 # [H, D]
        k = k_ref[0].astype(jnp.float32)                 # [T, H, D]
        v = v_ref[0].astype(jnp.float32)
        s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(pos < seq_len, s, NEG_INF)         # [T, H, 1]
        m_prev = m_ref[...]                              # [H, 1]
        m_cur = jnp.maximum(m_prev, s.max(axis=0))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[None])                     # [T, H, 1]
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=0)
        acc_ref[...] = acc_ref[...] * alpha + jnp.sum(p * v, axis=0)
        m_ref[...] = m_cur

    @pl.when(j == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_tokens", "scale",
                                             "interpret"))
def _paged_pallas(q, k_arena, v_arena, block_tables, seq_lens, *,
                  block_tokens, scale, interpret):
    """Jitted so the decode loop compiles once per (batch bucket, table
    width), not once per call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    max_blocks = block_tables.shape[1]
    # padded (-1) table entries re-fetch block 0; the length mask in
    # the kernel hides their tokens
    tables = jnp.maximum(jnp.asarray(block_tables, jnp.int32), 0)
    lens = jnp.asarray(seq_lens, jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_blocks),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, t, sl: (b, 0, 0)),
            pl.BlockSpec((1, block_tokens, H, D),
                         lambda b, j, t, sl: (t[b, j], 0, 0, 0)),
            pl.BlockSpec((1, block_tokens, H, D),
                         lambda b, j, t, sl: (t[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, t, sl: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, block_tokens=block_tokens,
                               scale=scale, n_blocks=max_blocks)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), jnp.float32),
        interpret=interpret,
    )(tables, lens, q, k_arena, v_arena)


def paged_decode_attention(q, k_arena, v_arena, block_tables, seq_lens,
                           *, block_tokens: int,
                           scale: Optional[float] = None,
                           k_scales=None, v_scales=None,
                           backend: Optional[str] = None):
    """Decode-step attention over a paged KV cache.

    ``backend``: ``None`` picks pallas on TPU and the dense path
    elsewhere; ``"dense"`` forces the reference; ``"pallas"`` /
    ``"pallas_interpret"`` force the kernel (tests run interpret-mode
    parity on CPU). Quantized arenas (int8 codes / bf16 bit patterns)
    always take the dense path off-TPU — on-TPU they are widened
    tile-local, off-TPU there is no bandwidth to save.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(np.asarray(q).shape[-1]))
    quantized = k_scales is not None or v_scales is not None
    if backend is None:
        backend = ("pallas" if not quantized
                   and compute_platform() == "tpu" else "dense")
    if backend == "dense":
        return paged_attention_reference(
            q, k_arena, v_arena, block_tables, seq_lens,
            block_tokens=block_tokens, scale=scale,
            k_scales=k_scales, v_scales=v_scales)
    if backend not in ("pallas", "pallas_interpret"):
        raise ValueError("paged attention backend %r" % (backend,))
    if quantized:
        raise ValueError("pallas paged attention path takes f32 arenas; "
                         "dequantize via backend='dense' off-TPU")
    return np.asarray(_paged_pallas(
        np.asarray(q, np.float32), np.asarray(k_arena, np.float32),
        np.asarray(v_arena, np.float32), np.asarray(block_tables),
        np.asarray(seq_lens), block_tokens=int(block_tokens),
        scale=float(scale), interpret=(backend == "pallas_interpret")))
