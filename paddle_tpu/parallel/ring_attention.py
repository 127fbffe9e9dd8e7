"""Ring attention + Ulysses sequence/context parallelism.

Long-context attention over a TPU mesh axis. The reference snapshot has
no sequence parallelism (SURVEY.md §5 "Long-context" — absent), but its
collective layer was surveyed so ring attention over ICI neighbors could
be "a later drop-in"; this module is that drop-in, built TPU-first:

- ``ring_attention``: blockwise-streaming softmax attention where every
  device holds a sequence shard of Q and rotates its K/V shard around the
  mesh-axis ring with ``lax.ppermute`` (one ICI hop per step). Peak
  memory is O(S_local^2) per device instead of O(S^2); the flash-style
  log-sum-exp accumulator keeps the math exact, not approximate
  (Liu et al., "Ring Attention with Blockwise Transformers").
- ``ulysses_attention``: DeepSpeed-Ulysses-style all-to-all — reshard
  from sequence-sharded to head-sharded with ``lax.all_to_all``, run
  plain full-sequence attention per local head group, reshard back. One
  pair of all-to-alls instead of n ppermute rounds; needs heads % n == 0.

Both are collective-level functions: call them inside ``shard_map`` /
``pjit`` with a live mesh axis. ``sequence_parallel_attention`` is the
host-level convenience that wraps the shard_map for full arrays.

Accumulation is float32 regardless of input dtype (bf16 Q/K/V in, bf16
out, f32 running max/denominator) — the same precision discipline the
TPU flash kernels use.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

NEG_INF = -1e30


def _axis_size(axis_name: str, axis_size: Optional[int]):
    if axis_size is not None:
        return int(axis_size)
    return jax.lax.axis_size(axis_name)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   scale: Optional[float] = None,
                   axis_size: Optional[int] = None, lengths=None):
    """Exact attention over sequence shards rotated around a ring.

    Args:
      q, k, v: local shards ``[B, H, S_local, D]`` — the sequence axis is
        sharded over ``axis_name``; batch/heads are not.
      axis_name: mesh axis carrying the sequence shards (the "ring").
      causal: apply a causal mask in *global* sequence coordinates
        (device i's queries occupy positions ``[i*S_local, (i+1)*S_local)``).
      scale: attention scale; default ``D ** -0.5``.
      axis_size: ring size if known statically (skips lax.axis_size).
      lengths: optional ``[B]`` GLOBAL per-example KV lengths
        (replicated across the ring): key positions >= lengths[b] are
        masked — the padding mask of the masked flash kernels, in ring
        form. KV shards entirely past every example's length are
        skipped (no einsum, the rotation still happens).

    Returns ``[B, H, S_local, D]`` in q.dtype.
    """
    import jax.numpy as jnp
    from jax import lax

    n = _axis_size(axis_name, axis_size)
    idx = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    if scale is None:
        scale = float(D) ** -0.5

    qf = q.astype(jnp.float32) * scale
    perm = [(j, (j + 1) % n) for j in range(n)]
    pos = jnp.arange(S, dtype=jnp.int32)

    lens = (None if lengths is None
            else lengths.reshape(-1).astype(jnp.int32))

    def attend(o, m, l, kb, vb, src):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb.astype(jnp.float32))
        if causal:
            q_pos = idx * S + pos
            k_pos = src * S + pos
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        if lens is not None:
            k_pos = src * S + pos                       # [S_k] global
            vis = k_pos[None, :] < lens[:, None]        # [B, S_k]
            s = jnp.where(vis[:, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vb.astype(jnp.float32))
        return o_new, m_new, l_new

    def accumulate(carry, kb, vb, t):
        o, m, l = carry
        # after t rotations this device holds the shard that started on
        # device (idx - t) mod n
        src = (idx - t) % n
        need = None
        if causal:
            # blocks entirely in the masked future (src > idx)
            # contribute nothing — skip their einsums entirely
            need = src <= idx
        if lens is not None:
            # KV shard entirely past every example's padded tail
            in_len = src * S < jnp.max(lens)
            need = in_len if need is None else jnp.logical_and(need,
                                                              in_len)
        if need is not None:
            return lax.cond(
                need,
                lambda args: attend(*args, src),
                lambda args: args[:3],
                (o, m, l, kb, vb))
        return attend(o, m, l, kb, vb, src)

    def step(t, carry):
        o, m, l, kb, vb = carry
        o, m, l = accumulate((o, m, l), kb, vb, t)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return o, m, l, kb, vb

    o0 = jnp.zeros((B, H, S, D), jnp.float32)
    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    # n-1 attend+rotate rounds, then a final attend with no trailing
    # rotation (the rotated shards would be discarded — one full K/V ICI
    # hop saved per call)
    o, m, l, kb, vb = lax.fori_loop(0, n - 1, step, (o0, m0, l0, k, v))
    o, m, l = accumulate((o, m, l), kb, vb, n - 1)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    if lens is not None:
        # zero-length (all-padding) examples output ZEROS — the same
        # contract as the masked flash kernels, and the only value
        # that's consistent across ring/dense/ulysses
        out = jnp.where((lens > 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      scale: Optional[float] = None,
                      axis_size: Optional[int] = None, lengths=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Local shards ``[B, H, S_local, D]`` sequence-sharded over
    ``axis_name`` with ``H % axis_size == 0``. Reshards to
    ``[B, H/n, S, D]`` (head-sharded, full sequence), runs one dense
    attention, reshards back. Two all-to-alls total — cheaper than a
    full ring when S_local is small relative to ICI latency.
    """
    import jax.numpy as jnp
    from jax import lax

    n = _axis_size(axis_name, axis_size)
    B, H, S, D = q.shape
    if H % n != 0:
        raise ValueError("ulysses needs heads (%d) %% axis size (%d) == 0"
                         % (H, n))
    if scale is None:
        scale = float(D) ** -0.5

    def to_heads(x):  # [B,H,S_loc,D] -> [B,H/n,S,D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", qh.astype(jnp.float32),
                   kh.astype(jnp.float32)) * scale
    Sg = S * n
    if causal:
        posq = jnp.arange(Sg, dtype=jnp.int32)
        mask = posq[:, None] >= posq[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if lengths is not None:
        vis = (jnp.arange(Sg, dtype=jnp.int32)[None, :]
               < lengths.reshape(-1).astype(jnp.int32)[:, None])
        s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    oh = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    if lengths is not None:
        oh = jnp.where(
            (lengths.reshape(-1) > 0)[:, None, None, None], oh, 0.0)
    # back to sequence-sharded layout
    out = lax.all_to_all(oh.astype(q.dtype), axis_name, split_axis=2,
                         concat_axis=1, tiled=True)
    return out


def sequence_parallel_attention(q, k, v, mesh, sp_axis: str = "sp",
                                mode: str = "ring", causal: bool = False,
                                scale: Optional[float] = None,
                                lengths=None):
    """Host-level wrapper: full ``[B, H, S, D]`` arrays in, attention
    computed with the sequence dimension sharded over ``mesh[sp_axis]``.

    ``mode``: "ring" (ppermute streaming) or "ulysses" (all-to-all).
    """
    from jax.sharding import PartitionSpec as P

    n = int(mesh.shape[sp_axis])
    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[mode]
    local = functools.partial(fn, axis_name=sp_axis, causal=causal,
                              scale=scale, axis_size=n)

    spec = P(None, None, sp_axis, None)
    if lengths is None:
        smap = jax.shard_map(local, mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)
        return smap(q, k, v)
    smap = jax.shard_map(
        lambda q, k, v, ln: local(q, k, v, lengths=ln), mesh=mesh,
        in_specs=(spec, spec, spec, P()), out_specs=spec,
        check_vma=False)
    return smap(q, k, v, lengths)


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, lengths=None):
    """Dense single-device attention — the numeric oracle for tests."""
    import jax.numpy as jnp

    D = q.shape[-1]
    if scale is None:
        scale = float(D) ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        S = q.shape[2]
        pos = jnp.arange(S)
        s = jnp.where((pos[:, None] >= pos[None, :])[None, None], s, NEG_INF)
    if lengths is not None:
        S_kv = k.shape[2]
        vis = (jnp.arange(S_kv)[None, :]
               < lengths.reshape(-1).astype(jnp.int32)[:, None])
        s = jnp.where(vis[:, None, None, :], s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    p = p / p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    if lengths is not None:
        out = jnp.where(
            (lengths.reshape(-1) > 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)
