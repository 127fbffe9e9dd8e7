"""Top-k-of-many expert routing without drops, over the experts held here.

``moe_topk`` routes every token over ALL the router's experts (``scoring``:
``sigmoid`` scores or a ``softmax`` over all of them, float32; a correction
bias that enters the choice only; the k largest; weights the chosen scores,
over their sum with ``norm_topk``), and computes the part of the result that
the ``held = [first, count]`` experts of this chip give: what expert
parallelism asks of a rank. An expert is ``relu(u W1)^2 W2`` or, where a
third matrix ``W3`` is bound, gated: ``(silu(u W1) * (u W3)) W2``; both go
through the same sorted slots, row buffer, grouped
products and overflow branch. Nothing is
dropped: there is no capacity factor. Static shapes are met by a row buffer:
the routed slots are sorted by expert, the first ``rows`` of them
(``ROWS_MULTIPLE`` times the expected load ``T * k * count / E``) are
gathered, multiplied expert by expert with a grouped product (on the TPU
the grouped-matmul kernels that ship with JAX, ``megablox``, which visit
only the row tiles the held slots fill; elsewhere ``jax.lax.ragged_dot``)
and scattered back. Whether the held slots fit the buffer is only known on
the device, so a ``lax.cond`` takes an exact slower branch when they do not:
every held expert over all the tokens, masked. Both give the reference's
result under any skew.
"""
from __future__ import annotations

import importlib
import math

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op

# the module: the package's attribute of that name is the function
_fa = importlib.import_module(".pallas.flash_attention", __package__)
_HI = jax.lax.Precision.HIGHEST
# rows of the slot buffer over the held experts' expected load
ROWS_MULTIPLE = 2.0
# (rows, contraction, columns) of a tile of the TPU's grouped-product kernels:
# the fastest of five read on a v5e at 8 experts of 2688 x 1856
# (tools/moe_bench.py; 512 rows and narrower tiles were 6-9 % slower)
TILING = (256, 1024, 1024)


def _act(h):
    return jnp.square(jax.nn.relu(h))


def _hidden(dot, w1, w3=None):
    """An expert's hidden rows in float32: ``relu(u W1)^2``, or gated with a
    third matrix, ``silu(u W1) * (u W3)``; ``dot(w)`` is ``u w``."""
    if w3 is None:
        return _act(dot(w1))
    return jax.nn.silu(dot(w1)) * dot(w3)


ROUTE_EPS = 1e-20     # beside the chosen scores' sum, unless the op says


def route(x, router_w, bias, k, scaling, norm_topk, scoring="sigmoid",
          route_eps=ROUTE_EPS):
    """(idx [T, k] int32, weight [T, k] float32): the router's product,
    scores (``sigmoid`` of each logit, or a ``softmax`` over all the
    experts) and weights (over ``sum of the chosen + route_eps`` with
    ``norm_topk``) in float32 at full precision."""
    f32 = jnp.float32
    logits = jnp.dot(x.astype(f32), router_w.astype(f32), precision=_HI)
    if scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        s = jax.nn.softmax(logits, -1)
    else:
        raise ValueError("moe_topk: no scoring %r" % scoring)
    choice = s if bias is None else s + jax.lax.stop_gradient(
        bias.astype(f32))
    _, idx = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, -1, keepdims=True) + route_eps)
    return idx.astype(jnp.int32), scaling * w


def buffer_rows(tokens, k, experts, count):
    """Rows of the slot buffer: ``ROWS_MULTIPLE`` times the expected load of
    the held experts, up to a multiple of 128, at most every slot."""
    want = ROWS_MULTIPLE * tokens * k * count / experts
    return min(tokens * k, max(128, 128 * math.ceil(want / 128)))


def grouped_path(rows):
    """Which grouped product the sorted slots take: ``megablox`` where the
    computation runs on a TPU (asked through ``ops.pallas.flash_attention``,
    as ``benchmarks/aot_sizing.py`` answers there) and the buffer is whole
    row tiles, else ``ragged_dot``."""
    on_tpu = _fa.compute_platform() == "tpu"
    return "megablox" if on_tpu and rows % 128 == 0 else "ragged_dot"


def _grouped_dot(xs, w, sizes):
    """Rows of ``xs`` [rows, K], sorted by expert, times their expert's
    matrix ``w[e]`` [K, N], in float32; ``sizes`` [count] rows an expert.
    Rows past the held slots belong to no expert and come back undefined
    (``ragged_dot``) or zero (``megablox``)."""
    if grouped_path(xs.shape[0]) == "ragged_dot":
        return jax.lax.ragged_dot(xs, w, sizes,
                                  preferred_element_type=jnp.float32)
    return _megablox_dot(xs, w, sizes)


def _megablox():
    """The kernels' module (the package's ``gmm`` attribute is its
    differentiable wrapper, which hands the cotangent on as it comes)."""
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _tiling(rows):
    """``TILING``, with 128 rows where the buffer is no whole number of its
    row tiles (a buffer is always a multiple of 128 here)."""
    return (TILING[0] if rows % TILING[0] == 0 else 128,) + TILING[1:]


def _all_groups(sizes, rows):
    """One more group owns the rows past the held slots; the kernels visit
    the groups that ``w`` holds only."""
    rest = (rows - jnp.sum(sizes)).astype(jnp.int32)[None]
    return jnp.concatenate([sizes.astype(jnp.int32), rest])


@jax.custom_vjp
def _megablox_dot(xs, w, sizes):
    gmm = _megablox()
    rows = xs.shape[0]
    return gmm.gmm(xs, w, _all_groups(sizes, rows), jnp.float32,
                   _tiling(rows))


def _megablox_fwd(xs, w, sizes):
    return _megablox_dot(xs, w, sizes), (xs, w, sizes)


def _megablox_bwd(res, g):
    """The cotangent goes to the MXU in the operands' type, as XLA's default
    precision sends a float32 operand there; sums stay float32."""
    gmm = _megablox()
    xs, w, sizes = res
    rows, tiling = xs.shape[0], _tiling(xs.shape[0])
    groups = _all_groups(sizes, rows)
    g = g.astype(xs.dtype)
    dxs = gmm.gmm(g, w, groups, xs.dtype, tiling, transpose_rhs=True)
    dw = gmm.tgmm(xs.swapaxes(0, 1), g, groups, w.dtype, tiling,
                  num_actual_groups=w.shape[0])
    return dxs, dw, None


_megablox_dot.defvjp(_megablox_fwd, _megablox_bwd)


def _grouped(x, w1, w2, tok, valid, weight, sizes, w3=None):
    """The sorted slots' rows through their experts: gather, two grouped
    products (three for a gated expert), weigh, scatter-add back to the
    tokens. Rows past the held slots are cut off on both sides (``where``,
    not a product: what a grouped product leaves in rows it does not own is
    not defined)."""
    f32 = jnp.float32
    xs = jnp.where(valid[:, None], x[tok].astype(w1.dtype), 0)
    h = _hidden(lambda w: _grouped_dot(xs, w, sizes), w1, w3)
    if w3 is not None:   # silu of an undefined row times another: cut here too
        h = jnp.where(valid[:, None], h, 0.0)
    y = _grouped_dot(h.astype(w1.dtype), w2, sizes)
    y = jnp.where(valid[:, None], y * weight[:, None], 0.0)
    return jnp.zeros(x.shape, f32).at[tok].add(y)


def _every_token(x, w1, w2, local, weight, w3=None):
    """The exact branch for any skew: each held expert over all the tokens,
    weighed by what the router gave it there (0 where it was not chosen)."""
    f32 = jnp.float32
    xc = x.astype(w1.dtype)

    @jax.checkpoint
    def one(out, ew):
        e, a, b, *gate = ew
        m = jnp.sum(jnp.where(local == e, weight, 0.0), -1)     # [T]
        h = _hidden(lambda w: jnp.dot(xc, w, preferred_element_type=f32),
                    a, *gate)
        y = jnp.dot(h.astype(a.dtype), b, preferred_element_type=f32)
        return out + y * m[:, None], None

    count = w1.shape[0]
    experts = (jnp.arange(count, dtype=jnp.int32), w1, w2)
    out, _ = jax.lax.scan(one, jnp.zeros(x.shape, f32),
                          experts + (() if w3 is None else (w3,)))
    return out


def moe_topk(x, router_w, bias, w1, w2, k, held, scaling=1.0,
             norm_topk=True, scoring="sigmoid", w3=None, route_eps=ROUTE_EPS):
    """(out [T, D] float32, load [count + 1] int32): the held experts'
    part of the layer for tokens x [T, D], expert ``relu(u W1)^2 W2`` or,
    with ``w3``, ``(silu(u W1) * (u W3)) W2``;
    ``load[:count]`` the slots each held expert received, ``load[count]``
    1 where the exact slower branch ran."""
    T, experts = x.shape[0], router_w.shape[1]
    first, count = int(held[0]), int(held[1])
    assert w1.shape[0] == count and w2.shape[0] == count
    if count < experts:
        # Only the held experts' outputs are here to pull the router: that
        # part of its gradient alone sends every token their way (their load
        # tripled in 48 steps on the chip). A deployment sums the ranks'
        # parts before it applies them; alone, the router's weight takes a
        # zero gradient, under either scoring. The tokens still take theirs
        # through the scores.
        router_w = jax.lax.stop_gradient(router_w)
    # inner scopes, so that a trace tells the routing from the products
    with jax.named_scope("route"):
        idx, weight = route(x, router_w, bias, k, scaling, norm_topk,
                            scoring, route_eps)
    with jax.named_scope("experts"):
        return _held_part(x, w1, w2, idx, weight, k, experts, first, count,
                          w3)


def _held_part(x, w1, w2, idx, weight, k, experts, first, count, w3=None):
    T = x.shape[0]
    local = idx - first
    is_held = (local >= 0) & (local < count)
    local = jnp.where(is_held, local, count)     # `count`: held elsewhere
    flat = local.reshape(-1)
    sizes = jnp.sum(flat[:, None] == jnp.arange(count)[None, :], 0,
                    dtype=jnp.int32)
    n_held = jnp.sum(sizes)
    rows = buffer_rows(T, k, experts, count)

    # the gated expert's third matrix rides along as one more operand
    more = () if w3 is None else (w3,)

    def fast(x, w1, w2, weight, *w3):
        order = jnp.argsort(flat, stable=True)[:rows]
        valid = jnp.arange(rows) < n_held
        return _grouped(x, w1, w2, order // k, valid,
                        weight.reshape(-1)[order], sizes, *w3)

    def slow(x, w1, w2, weight, *w3):
        return _every_token(x, w1, w2, local, weight, *w3)

    fits = n_held <= rows
    if rows >= T * k:
        out = fast(x, w1, w2, weight, *more)
    else:
        out = jax.lax.cond(fits, fast, slow, x, w1, w2, weight, *more)
    load = jnp.concatenate([sizes, (~fits).astype(jnp.int32)[None]])
    return out, load


@register_op(
    "moe_topk",
    inputs=[In("X"), In("RouterW"), In("Bias", dispensable=True,
                                       no_grad=True),
            In("W1"), In("W2"), In("W3", dispensable=True)],
    outputs=[Out("Out"), Out("Load", dispensable=True, no_grad=True)],
    attrs={"k": 1, "held": [0, 1], "scaling": 1.0, "norm_topk": True,
           "scoring": "sigmoid", "route_eps": ROUTE_EPS},
)
def _moe_topk(ins, attrs):
    """X [T, D] tokens; RouterW [D, E] over all E experts; Bias [E] the
    selection-only correction (a buffer: no gradient); W1 [count, D, F],
    W2 [count, F, D] the held experts ``held[0] .. held[0] + count - 1``.
    ``scoring`` ``sigmoid`` or ``softmax`` (over all E experts);
    ``route_eps`` stands beside the chosen scores' sum. The expert
    is ``relu(u W1)^2 W2``, or gated where W3 [count, D, F] is bound:
    ``(silu(u W1) * (u W3)) W2``.
    RouterW takes a gradient only where every expert is held
    (``moe_topk`` above says why).
    ``Out`` is in W1's type (bf16 under AMP, where X, RouterW and Bias stay
    float32: ``fp16_lists.fp32_slots``); ``Load`` [count + 1] int32 can be
    fetched. Each trace counts ``kernels.moe_grouped{path=megablox}`` or
    ``{path=ragged_dot}``, by the grouped product its slots take."""
    from .. import observability as _obs

    if _obs.enabled():
        k, count = int(attrs.get("k", 1)), int(attrs.get("held", [0, 1])[1])
        rows = buffer_rows(ins["X"].shape[0], k, ins["RouterW"].shape[1],
                           count)
        _obs.inc("kernels.moe_grouped", path=grouped_path(rows))
    out, load = moe_topk(
        ins["X"], ins["RouterW"], ins.get("Bias"), ins["W1"], ins["W2"],
        k=int(attrs.get("k", 1)), held=attrs.get("held", [0, 1]),
        scaling=float(attrs.get("scaling", 1.0)),
        norm_topk=bool(attrs.get("norm_topk", True)),
        scoring=attrs.get("scoring", "sigmoid"), w3=ins.get("W3"),
        route_eps=float(attrs.get("route_eps", ROUTE_EPS)))
    return {"Out": out.astype(ins["W1"].dtype), "Load": load}
