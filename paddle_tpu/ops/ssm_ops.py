"""State-space ops: the causal depthwise convolution and the chunked
selective scan of Mamba-2 (SSD, arXiv:2405.21060), with its gradient op.

The scan is the chunked algorithm in plain XLA einsums, the first form a
later kernel is measured against: inside a chunk the masked ``C B^T``
product applied to ``dt x``; the state at each chunk's end by one product;
the recurrence only across the chunk ends; the carried state's part of the
output by one more product. Decays (``dt A``, their cumulative sums and
exponentials) and the state are float32 whatever type x, B and C arrive in
(bf16 under AMP: they are the MXU's operands, accumulation is float32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op

_HI = jax.lax.Precision.HIGHEST


@register_op(
    "causal_conv1d",
    inputs=[In("X"), In("W"), In("Bias", dispensable=True)],
    outputs=[Out("Out")],
    attrs={"activation": ""},
)
def _causal_conv1d(ins, attrs):
    """Depthwise causal convolution along time: X [B, T, C], W [C, K]
    (``W[:, K-1]`` weighs the current position), Bias [C];
    ``out[t] = sum_k W[:, k] x[t - (K-1) + k] + Bias``, positions before
    the sequence read as zero. ``activation`` "" or "silu". The K taps
    accumulate in float32; Out has X's type."""
    x, w = ins["X"], ins["W"].astype(jnp.float32)
    T, K = x.shape[1], w.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, k:k + T, :] * w[:, k] for k in range(K))
    if ins.get("Bias") is not None:
        out = out + ins["Bias"].astype(jnp.float32)
    act = attrs.get("activation", "")
    if act == "silu":
        out = jax.nn.silu(out)
    elif act:
        raise NotImplementedError("causal_conv1d activation %r" % act)
    return {"Out": out.astype(x.dtype)}


def ssd_chunk_scan(x, dt, A, B, C, D=None, dt_bias=None, chunk=128):
    """y [B, T, H, P] of the selective scan
    ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D_h x_t`` from a zero state, by chunks of ``chunk``
    positions. x [B, T, H, P]; A, D, dt_bias [H]; B, C [B, T, G, N] with
    head h in group ``h // (H / G)``; the step sizes arrive raw, dt [B, T, H],
    and are ``softplus(dt + dt_bias)`` in float32 from here on. A length that is no
    multiple of the chunk is padded with positions of dt = 0 (decay 1, no
    input), which change nothing before them."""
    f32 = jnp.float32
    Bsz, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    mxu = x.dtype
    dt = dt.astype(f32)
    if dt_bias is not None:
        dt = dt + dt_bias.astype(f32)
    dt = jax.nn.softplus(dt)
    Q = min(int(chunk), T)
    pad = -T % Q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (T + pad) // Q
    xc = x.reshape(Bsz, nc, Q, G, H // G, P)
    dtc = dt.reshape(Bsz, nc, Q, G, H // G)
    Bc = B.reshape(Bsz, nc, Q, G, N).astype(mxu)
    Cc = C.reshape(Bsz, nc, Q, G, N).astype(mxu)
    a = dtc * A.astype(f32).reshape(G, H // G)            # <= 0
    cs = jnp.cumsum(a, axis=2)                             # [b,c,Q,g,r]
    xdt32 = xc.astype(f32) * dtc[..., None]
    xdt = xdt32.astype(mxu)

    # inside a chunk: (C B^T * decay, lower triangle) applied to dt x
    cb = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc,
                    preferred_element_type=f32)
    seg = cs[:, :, :, None] - cs[:, :, None, :]            # [b,c,i,j,g,r]
    tri = jnp.tril(jnp.ones((Q, Q), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    m = (cb.transpose(0, 1, 3, 4, 2)[..., None] * decay).astype(mxu)
    y = jnp.einsum("bcijgr,bcjgrp->bcigrp", m, xdt,
                   preferred_element_type=f32)

    # the state each chunk adds by its end, then the recurrence over the
    # chunk ends alone: entering[c] = sum_{c' < c} exp(total of the chunks
    # between) local[c'], float32 at full precision (nc^2 small products)
    to_end = jnp.exp(cs[:, :, -1:] - cs)                   # [b,c,Q,g,r]
    local = jnp.einsum("bcjgn,bcjgrp->bcgrpn", Bc,
                       (xdt32 * to_end[..., None]).astype(mxu),
                       preferred_element_type=f32)
    total = cs[:, :, -1]                                   # [b,c,g,r]
    upto = jnp.cumsum(total, axis=1)
    # exp(sum of totals of chunks c'+1 .. c-1) for c' < c
    between = (upto - total)[:, :, None] - upto[:, None, :]
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[:, :, None, None]
    carry = jnp.exp(jnp.where(earlier, between, -jnp.inf))  # [b,c,c',g,r]
    entering = jnp.einsum("bcdgr,bdgrpn->bcgrpn", carry, local,
                          precision=_HI)
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", Cc, entering.astype(mxu),
                       preferred_element_type=f32) \
        * jnp.exp(cs)[..., None]
    if D is not None:
        y = y + xc.astype(f32) * D.astype(f32).reshape(G, H // G, 1)
    return y.reshape(Bsz, nc * Q, H, P)[:, :T].astype(x.dtype)


def _scan(v, attrs):
    """``ssd_chunk_scan`` over an op's input slots ``v``."""
    return ssd_chunk_scan(v["X"], v["Dt"], v["A"], v["B"], v["C"],
                          D=v.get("D"), dt_bias=v.get("DtBias"),
                          chunk=int(attrs.get("chunk", 128)))


def _ssd_chunk_scan_grad(ins, attrs):
    """The scan's gradients from its inputs alone: the forward is run again
    inside (behind an optimization barrier, so that XLA cannot fold the
    copy into the forward op's and keep its per-position intermediates
    alive until here), and nothing but x, dt, A, B, C, D lives from the
    forward to the backward."""
    names = [n for n in ("X", "Dt", "A", "B", "C", "D", "DtBias")
             if ins.get(n) is not None]
    vals = jax.lax.optimization_barrier(tuple(ins[n] for n in names))
    out, vjp = jax.vjp(lambda *vals: _scan(dict(zip(names, vals)), attrs),
                       *vals)
    grads = vjp(ins["Out@GRAD"].astype(out.dtype))
    return {n + "@GRAD": g for n, g in zip(names, grads)}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "ssd_chunk_scan_grad",
    inputs=[In("X"), In("Dt"), In("A"), In("B"), In("C"),
            In("D", dispensable=True), In("DtBias", dispensable=True),
            In("Out@GRAD")],
    outputs=[Out(n + "@GRAD", dispensable=True)
             for n in ("X", "Dt", "A", "B", "C", "D", "DtBias")],
    attrs={"chunk": 128},
    grad=None,
)(_ssd_chunk_scan_grad)


@register_op(
    "ssd_chunk_scan",
    inputs=[In("X"), In("Dt"), In("A"), In("B"), In("C"),
            In("D", dispensable=True), In("DtBias", dispensable=True)],
    outputs=[Out("Out")],
    attrs={"chunk": 128},
)
def _ssd_chunk_scan(ins, attrs):
    """Mamba-2's selective scan over [B, T, H, P] (see ``ssd_chunk_scan``
    above for the equations and shapes). ``DtBias`` is added to ``Dt`` and
    the softplus applied inside, in float32, so that the step sizes and
    decays never pass through the AMP type. Each trace of the op counts
    ``kernels.ssd_chunk_scan{path=xla_chunked}``."""
    from .. import observability as _obs

    if _obs.enabled():
        _obs.inc("kernels.ssd_chunk_scan", path="xla_chunked")
    return {"Out": _scan(ins, attrs)}
