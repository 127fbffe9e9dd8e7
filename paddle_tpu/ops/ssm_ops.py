"""State-space ops: the causal depthwise convolution (``causal_conv1d``, with
its gradient op), the doubly gated short convolution (``short_conv_gate``,
with its gradient op) and the chunked selective scan of Mamba-2 (SSD,
arXiv:2405.21060), with its gradient op. Each gradient op reads its
forward's inputs and the cotangent, nothing else of the forward.

The scan is the chunked algorithm: inside a chunk the masked ``C B^T``
product applied to ``dt x``; the state at each chunk's end by one product;
the recurrence only across the chunk ends; the carried state's part of the
output by one more product. Decays (``dt A``, their cumulative sums and
exponentials) and the state are float32 whatever type x, B and C arrive in
(bf16 under AMP: they are the MXU's operands, accumulation is float32).

It has two forms behind one float32 prologue (``_prologue``: the softplus,
``dt A`` and its in-chunk cumulative sums) and ``scan_path``, which reads
the choice from the operands. Where the computation runs on a TPU and the
shapes fill whole tiles, the Pallas kernels of ``ops/pallas/ssd_scan.py``
keep a chunk's tiles and the carried state in VMEM (forward; in the gradient
op a state pass and a backward kernel). Everywhere else, the CPU and every
shape the kernels' blocks cannot take, the same algorithm runs as plain XLA
einsums (``_xla_chunked``): the kernels are measured and tested against it.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op
from .pallas import ssd_scan as _kernels

# the module: the package's attribute of that name is the function
_fa = importlib.import_module(".pallas.flash_attention", __package__)
_HI = jax.lax.Precision.HIGHEST


def _tap(x, s):
    """x [B, T, C] read ``s`` positions earlier (later where s < 0), zero
    outside the sequence: ``out[:, t] = x[:, t - s]``, in x's type. One
    ``pad`` that XLA fuses into its reader: no padded copy is written."""
    return jax.lax.pad(x, jnp.zeros((), x.dtype),
                       ((0, 0, 0), (s, -s, 0), (0, 0, 0)))


def _conv_taps(x, w, bias):
    """float32 ``sum_k w[:, k] x[t - (K-1) + k] + bias``: the convolution
    before its activation; w [C, K] float32, bias [C] or None."""
    K = w.shape[1]
    out = sum(_tap(x, K - 1 - k).astype(jnp.float32) * w[:, k]
              for k in range(K))
    return out if bias is None else out + bias.astype(jnp.float32)


# ``causal_conv1d`` and its gradient op (registered first, so that no
# automatic one is made) are at the end of the file: a Mosaic payload holds
# its call stack's source lines, so lines added above the scan's call sites
# would change its kernels' payloads and miss the compilation cache in
# every cell that runs them.


def _taps_sum(xp, w, T):
    """``sum_j w[:, j] xp[:, j : j + T]`` over the padded ``xp``."""
    return sum(xp[:, j:j + T, :] * w[:, j] for j in range(w.shape[1]))


def _gated(x, w):
    """(B, C, z, padded B * z, convolved B * z) in float32: the three
    streams of x [B, T, 3C] read by offset out of the projection's result,
    ``B * z`` with the K - 1 zeros before the sequence, and the taps' sum
    over it; w [C, K] float32."""
    C, K = w.shape
    b, c, z = (x[..., i * C:(i + 1) * C].astype(jnp.float32)
               for i in range(3))
    bz = jnp.pad(b * z, ((0, 0), (K - 1, 0), (0, 0)))
    return b, c, z, bz, _taps_sum(bz, w, x.shape[1])


def short_conv_gate(x, w):
    """[B, T, C] from the projection's result x [B, T, 3C] = ``B | C | z``
    and the taps w [C, K] (``w[:, K-1]`` weighs the current position):
    ``C * conv(B * z)``, the convolution causal and depthwise, positions
    before the sequence read as zero, no bias, no activation. The two gates
    and the K taps' sum are float32; the result has x's type. Each trace
    counts ``kernels.short_conv_gate``."""
    from .. import observability as _obs

    if _obs.enabled():
        _obs.inc("kernels.short_conv_gate")
    _, c, _, _, y = _gated(x, w.astype(jnp.float32))
    return (c * y).astype(x.dtype)


def _short_conv_gate_grad(ins, attrs):
    """The gradients from the op's inputs alone (nothing else lives from the
    forward to the backward): the convolved stream is made again, the
    cotangent runs back through the taps (an anti-causal convolution), and
    the three streams' gradients go out side by side as X's."""
    x, w = ins["X"], ins["W"].astype(jnp.float32)
    g = ins["Out@GRAD"].astype(jnp.float32)
    T, K = x.shape[1], w.shape[1]
    b, c, z, bz, y = _gated(x, w)
    dy = jnp.pad(g * c, ((0, 0), (0, K - 1), (0, 0)))
    # y[t] reads (B z)[t - (K-1) + j] through tap j: (B z)[s] is read by
    # y[s + (K-1) - j]
    dbz = _taps_sum(dy, w[:, ::-1], T)
    dw = jnp.stack([jnp.sum(dy[:, :T] * bz[:, j:j + T], axis=(0, 1))
                    for j in range(K)], axis=-1)
    dx = jnp.concatenate([dbz * z, g * y, dbz * b], -1)
    return {"X@GRAD": dx.astype(x.dtype),
            "W@GRAD": dw.astype(ins["W"].dtype)}


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "short_conv_gate_grad",
    inputs=[In("X"), In("W"), In("Out@GRAD")],
    outputs=[Out("X@GRAD", dispensable=True),
             Out("W@GRAD", dispensable=True)],
    grad=None,
)(_short_conv_gate_grad)


@register_op(
    "short_conv_gate",
    inputs=[In("X"), In("W")],
    outputs=[Out("Out")],
)
def _short_conv_gate(ins, attrs):
    """X [B, T, 3C] the three streams ``B | C | z`` of one projection, W
    [C, K] the taps; Out [B, T, C] = ``C * causal_conv(B * z)``
    (``short_conv_gate`` above)."""
    return {"Out": short_conv_gate(ins["X"], ins["W"])}


def _chunks(T, chunk):
    """(Q, pad): the chunk's length and the positions that fill the last."""
    Q = min(int(chunk), T)
    return Q, -T % Q


def scan_path(x, b, chunk=128):
    """"pallas" | "xla_chunked": which form of the scan these operands
    take. The kernels where the computation runs on a TPU (asked through
    ``ops.pallas.flash_attention``, as ``benchmarks/aot_sizing.py`` answers
    there) and the padded length, the chunk, the heads of a group and the
    state fill the kernels' blocks (``ops.pallas.ssd_scan.fits``)."""
    Q, pad = _chunks(x.shape[1], chunk)
    padded = jax.ShapeDtypeStruct(
        (x.shape[0], x.shape[1] + pad) + x.shape[2:], x.dtype)
    on_tpu = _fa.compute_platform() == "tpu"
    return "pallas" if on_tpu and _kernels.fits(padded, b, Q) \
        else "xla_chunked"


def _prologue(dt, A, dt_bias, Q, pad):
    """Both forms' float32 head: (``softplus(dt + dt_bias)`` [B, T + pad,
    H], the cumulative sums of ``dt A`` inside each chunk of Q positions,
    same shape). The padding is positions of dt = 0: decay 1, no input."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    if dt_bias is not None:
        dt = dt + dt_bias.astype(f32)
    dt = jnp.pad(jax.nn.softplus(dt), ((0, 0), (0, pad), (0, 0)))
    a = (dt * A.astype(f32)).reshape(dt.shape[0], -1, Q, dt.shape[2])
    return dt, jnp.cumsum(a, axis=2).reshape(dt.shape)     # a <= 0


def _xla_chunked(x, dt, cs, B, C, D, Q):
    """The scan over whole chunks in XLA einsums: y [B, T, H, P]."""
    f32 = jnp.float32
    Bsz, T, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    mxu = x.dtype
    nc = T // Q
    xc = x.reshape(Bsz, nc, Q, G, H // G, P)
    dtc = dt.reshape(Bsz, nc, Q, G, H // G)
    cs = cs.reshape(Bsz, nc, Q, G, H // G)                 # [b,c,Q,g,r]
    Bc = B.reshape(Bsz, nc, Q, G, N).astype(mxu)
    Cc = C.reshape(Bsz, nc, Q, G, N).astype(mxu)
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
    return y.reshape(Bsz, T, H, P).astype(x.dtype)


def _pad_time(a, pad):
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def ssd_chunk_scan(x, dt, A, B, C, D=None, dt_bias=None, chunk=128):
    """y [B, T, H, P] of the selective scan
    ``S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t + D_h x_t`` from a zero state, by chunks of ``chunk``
    positions. x [B, T, H, P]; A, D, dt_bias [H]; B, C [B, T, G, N] with
    head h in group ``h // (H / G)``; the step sizes arrive raw, dt [B, T, H],
    and are ``softplus(dt + dt_bias)`` in float32 from here on. A length that is no
    multiple of the chunk is padded with positions of dt = 0 (decay 1, no
    input), which change nothing before them. Differentiable in either
    form (``scan_path``); each trace counts
    ``kernels.ssd_chunk_scan{path=pallas|xla_chunked}``."""
    from .. import observability as _obs

    T = x.shape[1]
    Q, pad = _chunks(T, chunk)
    path = scan_path(x, B, chunk)
    if _obs.enabled():
        _obs.inc("kernels.ssd_chunk_scan", path=path)
    dt, cs = _prologue(dt, A, dt_bias, Q, pad)
    if pad:
        x, B, C = (_pad_time(a, pad) for a in (x, B, C))
    form = _kernels.scan if path == "pallas" else _xla_chunked
    return form(x, dt, cs, B, C, D, Q)[:, :T]


def _scan(v, attrs):
    """``ssd_chunk_scan`` over an op's input slots ``v``."""
    return ssd_chunk_scan(v["X"], v["Dt"], v["A"], v["B"], v["C"],
                          D=v.get("D"), dt_bias=v.get("DtBias"),
                          chunk=int(attrs.get("chunk", 128)))


def _ssd_chunk_scan_grad(ins, attrs):
    """The scan's gradients from its inputs alone: nothing but x, dt, A, B,
    C, D, dt_bias lives from the forward to the backward. ``jax.vjp`` of the
    function in either form. The kernels' form is then the state pass and
    the backward kernel between the prologue and its gradient (its forward
    kernel's result is not used, and XLA drops the call). The XLA form runs
    the forward again, behind an optimization barrier, so that XLA cannot
    fold the copy into the forward op's and keep its per-position
    intermediates alive until here."""
    names = [n for n in ("X", "Dt", "A", "B", "C", "D", "DtBias")
             if ins.get(n) is not None]
    vals = tuple(ins[n] for n in names)
    if scan_path(ins["X"], ins["B"], int(attrs.get("chunk", 128))) \
            == "xla_chunked":
        vals = jax.lax.optimization_barrier(vals)
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
    decays never pass through the AMP type."""
    return {"Out": _scan(ins, attrs)}


def _causal_conv1d(ins, attrs):
    """Depthwise causal convolution along time: X [B, T, C], W [C, K]
    (``W[:, K-1]`` weighs the current position), Bias [C];
    ``out[t] = sum_k W[:, k] x[t - (K-1) + k] + Bias``, positions before
    the sequence read as zero. ``activation`` "" or "silu". The K taps
    accumulate in float32; Out has X's type."""
    x = ins["X"]
    out = _conv_taps(x, ins["W"].astype(jnp.float32), ins.get("Bias"))
    act = attrs.get("activation", "")
    if act == "silu":
        out = jax.nn.silu(out)
    elif act:
        raise NotImplementedError("causal_conv1d activation %r" % act)
    return {"Out": out.astype(x.dtype)}


def _causal_conv1d_grad(ins, attrs):
    """The gradients from the op's inputs alone: under ``silu`` the
    pre-activation is made again and the cotangent taken through it; then
    it runs back through the taps (the anti-causal convolution with the
    taps reversed, positions past the end read as zero), and each tap's
    gradient is the cotangent against the stream that tap read. All in
    float32; each gradient goes out in its operand's type. Each trace
    counts ``kernels.causal_conv1d_grad``."""
    from .. import observability as _obs

    if _obs.enabled():
        _obs.inc("kernels.causal_conv1d_grad")
    x, w = ins["X"], ins["W"].astype(jnp.float32)
    bias = ins.get("Bias")
    K = w.shape[1]
    dy = ins["Out@GRAD"].astype(jnp.float32)
    act = attrs.get("activation", "")
    if act == "silu":
        pre = _conv_taps(x, w, bias)
        sig = jax.nn.sigmoid(pre)
        dy = dy * (sig * (1 + pre * (1 - sig)))
    elif act:
        raise NotImplementedError("causal_conv1d activation %r" % act)
    # out[t] reads x[t - (K-1) + k] through tap k: x[s] is read by
    # out[s + (K-1) - k]
    dx = sum(_tap(dy, k - (K - 1)) * w[:, k] for k in range(K))
    dw = jnp.stack([jnp.sum(dy * _tap(x, K - 1 - k).astype(jnp.float32),
                            axis=(0, 1)) for k in range(K)], axis=-1)
    grads = {"X@GRAD": dx.astype(x.dtype),
             "W@GRAD": dw.astype(ins["W"].dtype)}
    if bias is not None:
        grads["Bias@GRAD"] = jnp.sum(dy, axis=(0, 1)).astype(bias.dtype)
    return grads


# registered before its forward op, so that no auto-VJP grad op is made
register_op(
    "causal_conv1d_grad",
    inputs=[In("X"), In("W"), In("Bias", dispensable=True),
            In("Out@GRAD")],
    outputs=[Out(n + "@GRAD", dispensable=True)
             for n in ("X", "W", "Bias")],
    attrs={"activation": ""},
    grad=None,
)(_causal_conv1d_grad)

register_op(
    "causal_conv1d",
    inputs=[In("X"), In("W"), In("Bias", dispensable=True)],
    outputs=[Out("Out")],
    attrs={"activation": ""},
)(_causal_conv1d)
