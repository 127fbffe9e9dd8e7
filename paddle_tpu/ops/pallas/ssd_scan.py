"""Mamba-2's chunked selective scan (SSD, arXiv:2405.21060) as Pallas TPU
kernels: a chunk's ``[Q, Q]`` tiles and the carried ``[P, N]`` states live in
VMEM, and HBM sees the operands and the result.

``ops/ssm_ops.py`` holds the op, the float32 prologue both of its forms
share (``softplus``, ``dt A``, the in-chunk cumulative sums ``cs``) and the
XLA form of the same algorithm; it calls here where ``scan_path`` says so.
Every function below takes the prologue's results: x ``[B, T, H, P]``,
dt and cs ``[B, T, H]`` float32, B and C ``[B, T, G, N]``, D ``[H]`` or
None, T a whole number of chunks.

**Time is the kernels' minor axis.** They read x as ``[B, H*P, T]`` and B, C
as ``[B, G*N, T]`` and write y and the gradients likewise (``_time_minor``).
That is the layout XLA gives the activations around the op where T is long
(a ``[T, features]`` activation that feeds a weight-gradient matmul is kept
with T minor), so the transposes on both sides of a call cost nothing there;
with row-major operands pinned on the calls, XLA kept the whole layer
row-major and its projections' gradient matmuls lost 12 ms a step in the
hybrid cell (PERF.md section 6, PR 31). It also makes every per-position
vector a lane-dense row ``[1, Q]``: its exponentials are taken once a
position and spread over a head's sublanes by the products that use them.

Three kernels, each over the grid ``(batch, group, chunk)`` with the chunk
axis sequential, each walking the ``r = H / G`` heads of a group, a head the
P sublanes of its own in the group's blocks:

- ``ssd_scan_fwd``: ``cb = C B^T`` once for the group (kept as ``[j, i]``);
  for each head the tile ``exp(cs_i - cs_j)``, i >= j, ``(dt x) @ (cb *
  tile)``, plus ``(S C^T) * exp(cs)``, plus ``D x``; then ``S <- exp(total)
  S + ((dt x) * exp(total - cs)) B`` in a float32 scratch ``[r*P, N]``.
- ``ssd_scan_state``: that recurrence alone, writing the state that enters
  each chunk (``[B, G, nc, r*P, N]`` float32): what the backward needs and
  the forward never stores.
- ``ssd_scan_bwd``: the chunks in reverse with ``dS`` carried in the
  scratch; rebuilds the tiles and gives dx, dB, dC (summed over the group's
  heads inside the step) and, for the XLA epilogue (the prologue's
  ``jax.vjp``), the gradients with respect to dt and cs.

MXU operands have x's type (bf16 under AMP), sums are float32; dt, cs, their
exponentials and the state are float32 throughout, as in the XLA form. dt
and cs are handed in as rows ``[B, G, nc, r, Q]``, and cs once more as
columns ``[B, G, T, r]`` for the tiles' other index (2 MB each at T = 8192),
so that no kernel transposes a vector; the gradient with respect to cs comes
back in both forms and is summed outside.

Each kernel sits behind one ``jax.jit``: the layers of a program, and the
forward ops a recomputing optimizer emits again, share one lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
# contracting dimensions of a @ b, a @ b^T and a^T @ b
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def fits(x, b, chunk):
    """Whether the kernels' blocks take these operands: a chunk of whole
    128-lane tiles that divides T, states of whole lane tiles, heads of
    whole 32-sublane slices in whole groups, bf16 or float32."""
    _, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    return bool(chunk % 128 == 0 and T % chunk == 0 and N % 128 == 0
                and P % 32 == 0 and H % G == 0
                and x.dtype in (jnp.bfloat16, jnp.float32))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _total(v):
    """The sum of all of ``v``: [1, 1]."""
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


def _decay(cs_row, cs_col, upper):
    """A head's tile, transposed: ``exp(cs_i - cs_j)`` at [j, i], i >= j."""
    return jnp.exp(jnp.where(upper, cs_row - cs_col, -jnp.inf))


def _upper(Q):
    return (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            <= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))


def _last(cs, width):
    """The chunk's total, its last cs, as a row [1, width] (Mosaic spreads
    one number over lanes and over sublanes in two steps, not in one)."""
    return jnp.broadcast_to(cs[:, cs.shape[1] - 1:], (1, width))


def _advance(st, s_ref, sl, b, xdt32, cs):
    """``S <- exp(total) S + ((dt x) * exp(total - cs)) B`` on the head
    ``sl`` of the state [r*P, N]; ``st`` is what stood there."""
    u = (xdt32 * jnp.exp(_last(cs, cs.shape[1]) - cs)).astype(b.dtype)
    s_ref[sl, :] = jnp.exp(_last(cs, st.shape[1])) * st + _dot(u, b, _NT)


def _fwd_kernel(x_ref, b_ref, c_ref, dtr_ref, csr_ref, csc_ref, d_ref,
                y_ref, s_ref, *, r, P):
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    b, c = b_ref[...], c_ref[...]                      # [N, Q]: B^T, C^T
    mxu = b.dtype
    cb = _dot(b, c, _TN)                               # [j, i]: C_i . B_j
    upper = _upper(Q)
    for h in range(r):                                 # a head: P sublanes
        sl = slice(h * P, (h + 1) * P)
        cs = csr_ref[h:h + 1, :]                       # [1, Q]
        x32 = x_ref[sl, :].astype(_F32)
        xdt32 = x32 * dtr_ref[h:h + 1, :]
        st = s_ref[sl, :]
        m = (cb * _decay(cs, csc_ref[:, h:h + 1], upper)).astype(mxu)
        y = (_dot(xdt32.astype(mxu), m, _NN)
             + jnp.exp(cs) * _dot(st.astype(mxu), c, _NN)
             + d_ref[sl, :] * x32)
        y_ref[sl, :] = y.astype(y_ref.dtype)
        _advance(st, s_ref, sl, b, xdt32, cs)


def _state_kernel(x_ref, b_ref, dtr_ref, csr_ref, st_ref, s_ref, *, r, P):
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    b = b_ref[...]
    for h in range(r):
        sl = slice(h * P, (h + 1) * P)
        cs = csr_ref[h:h + 1, :]
        st = s_ref[sl, :]
        st_ref[sl, :] = st
        xdt32 = x_ref[sl, :].astype(_F32) * dtr_ref[h:h + 1, :]
        _advance(st, s_ref, sl, b, xdt32, cs)


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, dtr_ref, csr_ref, csc_ref,
                d_ref, st_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcsr_ref, dcsc_ref,
                dtot_ref, dd_ref, ds_ref, *, r, P):
    """One chunk of one group, the chunks arriving last first. ``ds_ref``
    holds the gradient with respect to the state that leaves the chunk.

    With ``u = (dt x) * exp(total - cs)``, ``z = S C^T`` and ``m = cb *
    tile``: ``y = (dt x) m + exp(cs) z + D x`` and ``S' = exp(total) S + u
    B``. The gradient with respect to cs is, at position i, the sum over j
    of ``dm * m`` less that over i at position j (a row, and a column that
    the caller transposes), plus ``dy . (exp(cs) z) - du . u``; what reaches
    ``total`` (the chunk's last cs) leaves as one number a head, ``dtot``,
    and dD likewise as ``dd``."""
    Q = x_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    b, c = b_ref[...], c_ref[...]
    mxu = b.dtype
    cb = _dot(b, c, _TN)
    upper = _upper(Q)
    dcb = jnp.zeros((Q, Q), _F32)
    db = jnp.zeros(b.shape, _F32)
    dc = jnp.zeros(c.shape, _F32)
    for h in range(r):
        sl, one = slice(h * P, (h + 1) * P), slice(h, h + 1)
        x32 = x_ref[sl, :].astype(_F32)
        dy = dy_ref[sl, :]
        dy32 = dy.astype(_F32)
        dt, cs = dtr_ref[one, :], csr_ref[one, :]      # [1, Q]
        e, w = jnp.exp(cs), jnp.exp(_last(cs, Q) - cs)
        xdt32 = x32 * dt
        u32 = xdt32 * w
        st, ds = st_ref[sl, :], ds_ref[sl, :]
        st_m, ds_m = st.astype(mxu), ds.astype(mxu)
        grown = jnp.exp(_last(cs, ds.shape[1])) * ds
        # through the entering state's part of y and the state's update
        y_in = e * _dot(st_m, c, _NN)
        dz = (e * dy32).astype(mxu)
        du = _dot(ds_m, b, _NN)
        dc = dc + _dot(st_m, dz, _TN)
        db = db + _dot(ds_m, u32.astype(mxu), _TN)
        ds_ref[sl, :] = grown + _dot(dz, c, _NT)
        # through the head's tile
        tile = _decay(cs, csc_ref[:, one], upper)
        m32 = cb * tile
        dm = _dot(xdt32.astype(mxu), dy, _TN)          # [j, i]
        dcb = dcb + dm * tile
        wk = dm * m32
        dxdt = w * du + _dot(dy, m32.astype(mxu), _NT)
        dx_ref[sl, :] = (dt * dxdt + d_ref[sl, :] * dy32).astype(
            dx_ref.dtype)
        ddt_ref[one, :] = jnp.sum(dxdt * x32, axis=0, keepdims=True)
        dcsr_ref[one, :] = jnp.sum(wk, axis=0, keepdims=True) + jnp.sum(
            dy32 * y_in - du * u32, axis=0, keepdims=True)
        dcsc_ref[:, one] = -jnp.sum(wk, axis=1, keepdims=True)
        dtot_ref[one, :] = jnp.broadcast_to(
            _total(grown * st) + _total(du * u32), (1, 128))
        dd_ref[one, :] = jnp.broadcast_to(_total(dy32 * x32), (1, 128))
    dcb = dcb.astype(mxu)
    db_ref[...] = (db + _dot(c, dcb, _NT)).astype(db_ref.dtype)
    dc_ref[...] = (dc + _dot(b, dcb, _NN)).astype(dc_ref.dtype)


def _time_minor(a):
    """[B, T, ...] -> [B, prod(...), T]."""
    return jnp.swapaxes(a.reshape(a.shape[0], a.shape[1], -1), 1, 2)


def _time_major(a, shape):
    """``_time_minor``'s inverse, to ``shape`` [B, T, ...]."""
    return jnp.swapaxes(a, 1, 2).reshape(shape)


def _views(x, dt, cs, b, c, d, chunk):
    """The kernels' views of the operands and their dimensions."""
    Bsz, T, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    r, nc = H // G, T // chunk

    def rows(a):                        # [B, T, H] -> [B, G, nc, r, Q]
        return a.reshape(Bsz, nc, chunk, G, r).transpose(0, 3, 1, 4, 2)

    d = jnp.zeros((H,), _F32) if d is None else d.astype(_F32)
    return dict(
        x=_time_minor(x), b=_time_minor(b).astype(x.dtype),
        c=_time_minor(c).astype(x.dtype), dt_rows=rows(dt), cs_rows=rows(cs),
        # [B, T, H] -> [B, G, T, r]
        cs_cols=cs.reshape(Bsz, T, G, r).transpose(0, 2, 1, 3),
        d=jnp.repeat(d, P)[:, None], dims=(Bsz, T, H, P, G, N, r, nc))


def _specs(dims, chunk, reverse=False):
    """Block specs by operand kind, for the grid ``(batch, group, chunk)``;
    ``reverse`` visits the chunks last first."""
    Bsz, T, H, P, G, N, r, nc = dims
    Q = chunk

    def at(ci):
        return (nc - 1 - ci) if reverse else ci

    def per_chunk(*block):
        return pl.BlockSpec((None, None, None) + block,
                            lambda bi, gi, ci: (bi, gi, at(ci), 0, 0))

    return dict(
        x=pl.BlockSpec((None, r * P, Q), lambda bi, gi, ci: (bi, gi, at(ci))),
        bc=pl.BlockSpec((None, N, Q), lambda bi, gi, ci: (bi, gi, at(ci))),
        rows=per_chunk(r, Q), head=per_chunk(r, 128),
        state=per_chunk(r * P, N),
        cols=pl.BlockSpec((None, None, Q, r),
                          lambda bi, gi, ci: (bi, gi, at(ci), 0)),
        d=pl.BlockSpec((r * P, 1), lambda bi, gi, ci: (gi, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def forward(x, dt, cs, b, c, d, *, chunk, interpret=False):
    """y [B, T, H, P] in x's type."""
    v = _views(x, dt, cs, b, c, d, chunk)
    Bsz, T, H, P, G, N, r, nc = dims = v["dims"]
    s = _specs(dims, chunk)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, P=P),
        grid=(Bsz, G, nc),
        in_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"], s["cols"],
                  s["d"]],
        out_specs=s["x"],
        out_shape=jax.ShapeDtypeStruct((Bsz, H * P, T), x.dtype),
        scratch_shapes=[pltpu.VMEM((r * P, N), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_scan_fwd",
    )(v["x"], v["b"], v["c"], v["dt_rows"], v["cs_rows"], v["cs_cols"],
      v["d"])
    return _time_major(y, x.shape)


def _states(v, chunk, interpret):
    """The state entering each chunk: [B, G, nc, r*P, N] float32."""
    Bsz, T, H, P, G, N, r, nc = dims = v["dims"]
    s = _specs(dims, chunk)
    return pl.pallas_call(
        functools.partial(_state_kernel, r=r, P=P),
        grid=(Bsz, G, nc),
        in_specs=[s["x"], s["bc"], s["rows"], s["rows"]],
        out_specs=s["state"],
        out_shape=jax.ShapeDtypeStruct((Bsz, G, nc, r * P, N), _F32),
        scratch_shapes=[pltpu.VMEM((r * P, N), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_scan_state",
    )(v["x"], v["b"], v["dt_rows"], v["cs_rows"])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def backward(x, dt, cs, b, c, d, dy, *, chunk, interpret=False):
    """(dx, ddt, dcs, dB, dC, dD) from the operands and dy [B, T, H, P]:
    the state pass, then the backward kernel. dD is None where D is."""
    v = _views(x, dt, cs, b, c, d, chunk)
    Bsz, T, H, P, G, N, r, nc = dims = v["dims"]
    s = _specs(dims, chunk, reverse=True)
    entering = _states(v, chunk, interpret)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=_F32)
    dx, db, dc, ddt, dcs_r, dcs_c, dtot, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, r=r, P=P),
        grid=(Bsz, G, nc),
        in_specs=[s["x"], s["x"], s["bc"], s["bc"], s["rows"], s["rows"],
                  s["cols"], s["d"], s["state"]],
        out_specs=[s["x"], s["bc"], s["bc"], s["rows"], s["rows"],
                   s["cols"], s["head"], s["head"]],
        out_shape=[jax.ShapeDtypeStruct((Bsz, H * P, T), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, G * N, T), b.dtype),
                   jax.ShapeDtypeStruct((Bsz, G * N, T), c.dtype),
                   f32((Bsz, G, nc, r, chunk)), f32((Bsz, G, nc, r, chunk)),
                   f32((Bsz, G, T, r)),
                   f32((Bsz, G, nc, r, 128)), f32((Bsz, G, nc, r, 128))],
        scratch_shapes=[pltpu.VMEM((r * P, N), _F32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="ssd_scan_bwd",
    )(v["x"], _time_minor(dy).astype(x.dtype), v["b"], v["c"], v["dt_rows"],
      v["cs_rows"], v["cs_cols"], v["d"], entering)

    def positions(rows):                # [B, G, nc, r, Q] -> [B, T, H]
        return rows.transpose(0, 2, 4, 1, 3).reshape(Bsz, T, H)

    def heads(one):                     # [B, G, nc, r, 128] -> [B, nc, H]
        return one[..., 0].transpose(0, 2, 1, 3).reshape(Bsz, nc, H)

    dcs = positions(dcs_r) + dcs_c.transpose(0, 2, 1, 3).reshape(Bsz, T, H)
    # what reached each chunk's total belongs to its last position
    dcs = dcs.reshape(Bsz, nc, chunk, H).at[:, :, -1].add(
        heads(dtot)).reshape(Bsz, T, H)
    return (_time_major(dx, x.shape), positions(ddt), dcs,
            _time_major(db, b.shape), _time_major(dc, c.shape),
            None if d is None else jnp.sum(heads(dd), (0, 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def scan(x, dt, cs, b, c, d, chunk, interpret=False):
    """``forward``, differentiable through ``backward``."""
    return forward(x, dt, cs, b, c, d, chunk=chunk, interpret=interpret)


def _scan_fwd(x, dt, cs, b, c, d, chunk, interpret):
    return (forward(x, dt, cs, b, c, d, chunk=chunk, interpret=interpret),
            (x, dt, cs, b, c, d))


def _scan_bwd(chunk, interpret, res, dy):
    return backward(*res, dy, chunk=chunk, interpret=interpret)


scan.defvjp(_scan_fwd, _scan_bwd)
