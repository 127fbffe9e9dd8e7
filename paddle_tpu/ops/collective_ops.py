"""Collective communication ops (`c_*`).

Parity: /root/reference/paddle/fluid/operators/collective/ (c_allreduce_
{sum,max,min,prod}, c_broadcast, c_allgather, c_reducescatter,
c_gen_nccl_id, c_comm_init, c_sync_calc_stream, c_sync_comm_stream) —
lowered TPU-natively:

- Inside a mesh-mapped trace (pjit/shard_map data parallelism, see
  paddle_tpu/parallel/), ``ring_id`` resolves to a *named mesh axis* and
  the op emits the XLA collective (lax.psum / all_gather / psum_scatter)
  that rides ICI — replacing the reference's ncclAllReduce kernels keyed
  by NCCLCommContext ring_id.
- Outside any mapped context (single process, world=1) they are identity,
  matching reference behavior with nranks=1.
- Bootstrap ops (gen_nccl_id/comm_init) are no-op hosts: rendezvous is
  jax.distributed's coordination service over DCN, set up at launch
  (dygraph/parallel.py prepare_context), not graph ops. Stream-sync ops are no-ops: XLA
  program order subsumes them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_host_op, register_op

# ring_id -> mesh axis name, set while tracing under shard_map
_ACTIVE_RING_AXES: Dict[int, str] = {}


class ring_axis_guard:
    """Context manager used by the parallel compiler: maps ring ids to the
    mesh axis names live in the current mapped trace."""

    def __init__(self, mapping: Dict[int, str]):
        self.mapping = dict(mapping)

    def __enter__(self):
        self._saved = dict(_ACTIVE_RING_AXES)
        _ACTIVE_RING_AXES.update(self.mapping)
        return self

    def __exit__(self, *exc):
        _ACTIVE_RING_AXES.clear()
        _ACTIVE_RING_AXES.update(self._saved)
        return False


def axis_for_ring(ring_id: int) -> Optional[str]:
    return _ACTIVE_RING_AXES.get(ring_id, _ACTIVE_RING_AXES.get(-1))


# mesh axis names live in the current mapped trace — lets hybrid-parallel
# ops (sharded lookup / ring attention / MoE) pick their parallel path
# inside the mesh engine and their exact dense fallback everywhere else
_ACTIVE_MESH_AXES: set = set()


class mesh_axes_guard:
    """Context manager set by the mesh engine while tracing under
    shard_map: declares which named axes are live."""

    def __init__(self, axes):
        self.axes = set(axes or ())

    def __enter__(self):
        self._saved = set(_ACTIVE_MESH_AXES)
        _ACTIVE_MESH_AXES.update(self.axes)
        return self

    def __exit__(self, *exc):
        _ACTIVE_MESH_AXES.clear()
        _ACTIVE_MESH_AXES.update(self._saved)
        return False


def mesh_axis_active(name: Optional[str]) -> bool:
    return bool(name) and name in _ACTIVE_MESH_AXES


def _allreduce(name, reducer):
    @register_op(
        name,
        inputs=[In("X")],
        outputs=[Out("Out")],
        attrs={"ring_id": 0, "use_calc_stream": False, "use_model_parallel": False},
        grad=None,
    )
    def _op(ins, attrs, _red=reducer):
        axis = axis_for_ring(attrs.get("ring_id", 0))
        x = ins["X"]
        return {"Out": x if axis is None else _red(x, axis)}

    return _op


_allreduce("c_allreduce_sum", lambda x, ax: jax.lax.psum(x, ax))
_allreduce("c_allreduce_max", lambda x, ax: jax.lax.pmax(x, ax))
_allreduce("c_allreduce_min", lambda x, ax: jax.lax.pmin(x, ax))
_allreduce("c_allreduce_prod", lambda x, ax: jnp.exp(jax.lax.psum(jnp.log(x), ax)))


@register_op(
    "c_broadcast",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"ring_id": 0, "root": 0, "use_calc_stream": False},
    grad=None,
)
def _c_broadcast(ins, attrs):
    axis = axis_for_ring(attrs.get("ring_id", 0))
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    # select root's value on every member of the axis
    root = attrs.get("root", 0)
    full = jax.lax.all_gather(x, axis)
    return {"Out": full[root]}


@register_op(
    "c_allgather",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"ring_id": 0, "nranks": 1, "use_calc_stream": False},
    grad=None,
)
def _c_allgather(ins, attrs):
    axis = axis_for_ring(attrs.get("ring_id", 0))
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    g = jax.lax.all_gather(x, axis)  # [nranks, ...]
    return {"Out": g.reshape((-1,) + x.shape[1:])}


@register_op(
    "c_reducescatter",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"ring_id": 0, "nranks": 1, "use_calc_stream": False},
    grad=None,
)
def _c_reducescatter(ins, attrs):
    axis = axis_for_ring(attrs.get("ring_id", 0))
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    return {"Out": jax.lax.psum_scatter(x, axis, tiled=True)}


@register_op(
    "c_concat",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"ring_id": 0, "nranks": 1, "rank": 0},
    grad=None,
)
def _c_concat(ins, attrs):
    axis = axis_for_ring(attrs.get("ring_id", 0))
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    g = jax.lax.all_gather(x, axis)
    return {"Out": jnp.concatenate([g[i] for i in range(g.shape[0])], axis=-1)}


@register_op(
    "alltoall",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"ring_id": 0},
    grad=None,
)
def _alltoall(ins, attrs):
    axis = axis_for_ring(attrs.get("ring_id", 0))
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    n = jax.lax.axis_size(axis)
    xs = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    out = jax.lax.all_to_all(xs, axis, split_axis=0, concat_axis=0, tiled=False)
    return {"Out": out.reshape(x.shape)}


# -- bootstrap / sync: no-ops under the XLA model ---------------------------


@register_host_op("c_gen_nccl_id", inputs=[], outputs=[Out("Out", dispensable=True)],
                  attrs={"rank": 0, "endpoint": "", "other_endpoints": [],
                         "ring_id": 0})
def _c_gen_nccl_id(executor, op, scope):
    # Rendezvous is handled by jax.distributed (coordination service over
    # DCN) at process launch; nothing to do per-ring.
    pass


@register_host_op("c_comm_init", inputs=[In("X", dispensable=True)], outputs=[],
                  attrs={"nranks": 1, "rank": 0, "device_id": 0, "ring_id": 0})
def _c_comm_init(executor, op, scope):
    pass


@register_host_op("c_sync_calc_stream", inputs=[In("X")], outputs=[Out("Out")],
                  attrs={})
def _c_sync_calc_stream(executor, op, scope):
    # XLA program order subsumes stream sync; keep data flowing through.
    executor._write_var(scope, op.output("Out")[0],
                        executor._read_var(scope, op.input("X")[0]))


@register_host_op("c_sync_comm_stream", inputs=[In("X")], outputs=[Out("Out")],
                  attrs={"ring_id": 0})
def _c_sync_comm_stream(executor, op, scope):
    executor._write_var(scope, op.output("Out")[0],
                        executor._read_var(scope, op.input("X")[0]))


@register_host_op("barrier", inputs=[In("X", dispensable=True)],
                  outputs=[Out("Out", dispensable=True)], attrs={"ring_id": 0})
def _barrier(executor, op, scope):
    pass


@register_op(
    "allreduce",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"reduce_type": 0, "sync_mode": False},
    grad=None,
)
def _allreduce_legacy(ins, attrs):
    """Legacy dygraph-DP allreduce (reference
    distributed_ops/allreduce_op.cc; reduce_type 0..3 =
    sum/prod/max/min over the default ring). Same lowering as
    c_allreduce_* — a psum-family collective over the ring-0 axis."""
    axis = axis_for_ring(0)
    x = ins["X"]
    if axis is None:
        return {"Out": x}
    rt = int(attrs.get("reduce_type", 0))
    fns = {0: jax.lax.psum, 1: _pprod, 2: jax.lax.pmax, 3: jax.lax.pmin}
    if rt not in fns:
        raise ValueError("allreduce: bad reduce_type %d" % rt)
    return {"Out": fns[rt](x, axis)}


def _pprod(x, ax):
    return jnp.exp(jax.lax.psum(jnp.log(jnp.abs(x) + 1e-38), ax)) * \
        jnp.where(jax.lax.psum((x < 0).astype(jnp.int32), ax) % 2 == 1,
                  -1.0, 1.0)


@register_op(
    "broadcast",
    inputs=[In("X")],
    outputs=[Out("Out")],
    attrs={"sync_mode": False, "root": 0},
    grad=None,
)
def _broadcast_legacy(ins, attrs):
    """Legacy dygraph-DP broadcast (reference
    distributed_ops/broadcast_op.cc) — same lowering as c_broadcast on
    ring 0."""
    return _c_broadcast(ins, {**attrs, "ring_id": 0})


# -- bucketed / quantized collectives (parallel/collectives.py rewrites) ----

# wire width per element a NATIVE quantized collective would move
# (the EQuARX projection); None means "the tensor's own itemsize"
QUANT_WIRE_ITEMSIZE = {"none": None, "bf16": 2, "int8": 1}

# payload width per element the EMULATED lowering actually psums:
# bf16 crosses as bf16, but int8 codes are summed in an int32
# accumulator (quantized_psum) — 4 bytes/element on today's wire. The
# executed-traffic counters charge these; QUANT_WIRE_ITEMSIZE only
# backs the projected-native-savings estimate.
QUANT_PSUM_ITEMSIZE = {"none": None, "bf16": 2, "int8": 4}

# reduction-strategy spellings of the same psum (the placement search's
# swap dimension — "Synthesizing Optimal Parallelism Placement and
# Reduction Strategies", PAPERS.md):
#   ring       one fused XLA collective (the default lowering)
#   tree       reduce_scatter + all_gather decomposition — exposes
#              the two phases to the scheduler as separate ops
#   two_stage  hierarchical: one psum per mesh axis in sequence (on a
#              dp x sp / 3D mesh, reduce inside the fast axis first);
#              degenerates to ring on a 1-axis mesh
REDUCTION_STRATEGIES = ("ring", "tree", "two_stage")


def _axes_tuple(axis):
    return tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)


def strategy_psum(x, axis, strategy="ring"):
    """The same mathematical psum spelled per ``strategy`` (see
    ``REDUCTION_STRATEGIES``). Integer payloads are exact under every
    spelling; float payloads may differ in summation ORDER (tree /
    two_stage re-associate), which is the documented bounded-difference
    contract of the reduction-swap pass."""
    if strategy in (None, "", "auto", "ring"):
        return jax.lax.psum(x, axis)
    axes = _axes_tuple(axis)
    if strategy == "two_stage":
        out = x
        for a in axes:
            out = jax.lax.psum(out, a)
        return out
    if strategy == "tree":
        a0 = axes[0]
        n = jax.lax.axis_size(a0)
        flat = x.reshape(-1)
        pad = (-flat.size) % n
        if pad:
            flat = jnp.concatenate(
                [flat, jnp.zeros((pad,), flat.dtype)])
        shard = jax.lax.psum_scatter(flat, a0, tiled=True)
        red = jax.lax.all_gather(shard, a0, tiled=True)
        if pad:
            red = red[:x.size]
        red = red.reshape(x.shape)
        for a in axes[1:]:
            red = jax.lax.psum(red, a)
        return red
    raise ValueError("unknown reduction strategy %r (want one of %s)"
                     % (strategy, ", ".join(REDUCTION_STRATEGIES)))


def quantized_psum(x, axis, quant="none", strategy="ring",
                   residual=None):
    """psum with an optional EQuARX-style compressed payload.

    - ``bf16``: the payload crosses the wire as bfloat16 (half the f32
      bytes), summed in bf16, widened back.
    - ``int8``: per-bucket uniform quantization — every replica scales
      by the SAME per-bucket step (pmax of local absmax / 127), rounds
      to [-127, 127], and the integer codes are summed exactly (int32
      accumulator — the emulation of an int8 wire payload with a
      wider-than-wire accumulation, which is how EQuARX avoids
      saturation). Worst-case absolute error per element is
      n * scale / 2 (each replica contributes at most half a step of
      rounding error) — the bound tests/test_collectives.py gates on.

    ``strategy`` picks the reduction spelling (``strategy_psum``) for
    the wire-crossing sum. ``residual`` arms EQuARX ERROR FEEDBACK:
    the caller passes this replica's accumulated rounding error from
    the previous step; it is folded into the payload BEFORE
    quantization and the call returns ``(reduced, new_residual)`` —
    the fresh local rounding error to carry forward. Over steps the
    quantization bias cancels instead of compounding, which is what
    makes int8 legal for the placement search to pick.
    """
    if quant in (None, "", "none"):
        out = strategy_psum(x, axis, strategy)
        return out if residual is None else (out, residual)
    if quant == "bf16":
        src = x if residual is None else x + residual
        q = src.astype(jnp.bfloat16)
        out = strategy_psum(q, axis, strategy).astype(x.dtype)
        if residual is None:
            return out
        return out, src - q.astype(x.dtype)
    if quant == "int8":
        src = x if residual is None else x + residual
        absmax = jax.lax.pmax(jnp.max(jnp.abs(src)), axis)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(x.dtype)
        q = jnp.clip(jnp.round(src / scale), -127, 127).astype(jnp.int32)
        out = strategy_psum(q, axis, strategy).astype(x.dtype) * scale
        if residual is None:
            return out
        return out, src - q.astype(x.dtype) * scale
    raise ValueError("unknown quantized-allreduce mode %r" % (quant,))


def _flat_concat(xs):
    if len(xs) == 1:
        return xs[0].reshape(-1)
    return jnp.concatenate([x.reshape(-1) for x in xs])


def _slice_back(red, xs):
    outs, off = [], 0
    for x in xs:
        k = int(x.size)
        outs.append(red[off:off + k].reshape(x.shape))
        off += k
    return outs


@register_op(
    "c_bucket_allreduce",
    inputs=[In("X", duplicable=True), In("Residual", dispensable=True)],
    outputs=[Out("Out", duplicable=True, is_ref=True),
             Out("ResidualOut", is_ref=True, dispensable=True)],
    attrs={"ring_id": 0, "quant": "none", "strategy": "ring",
           "use_calc_stream": True},
    grad=None,
)
def _c_bucket_allreduce(ins, attrs):
    """N same-dtype grads coalesced into ONE flat psum (the bucketed
    replacement for N per-grad c_allreduce_sum ops — see
    parallel/collectives.py for the scheduling rewrite). psum is
    elementwise over replicas, so concat-then-psum is bit-for-bit
    identical to psum-then-concat; quant != "none" opts into the
    compressed payload; ``strategy`` picks the reduction spelling
    (parallel/scheduling.py swaps it); a bound Residual arms EQuARX
    error feedback — the slot holds THIS replica's shard of a
    dp-sharded rounding-error var, folded into the payload before
    quantization and rewritten after."""
    xs = ins["X"]
    axis = axis_for_ring(attrs.get("ring_id", 0))
    quant = attrs.get("quant", "none")
    strategy = attrs.get("strategy", "ring")
    residual = ins.get("Residual")
    if axis is None:
        # dense fallback (nranks=1): identity, residual untouched
        out = {"Out": list(xs)}
        if residual is not None:
            out["ResidualOut"] = residual
        return out
    flat = _flat_concat(xs)
    if residual is not None:
        red, new_res = quantized_psum(flat, axis, quant, strategy,
                                      residual)
        return {"Out": _slice_back(red, xs), "ResidualOut": new_res}
    red = quantized_psum(flat, axis, quant, strategy)
    return {"Out": _slice_back(red, xs)}


@register_op(
    "c_bucket_allreduce_start",
    inputs=[In("X", duplicable=True), In("Residual", dispensable=True)],
    outputs=[Out("Pending"),
             Out("ResidualOut", is_ref=True, dispensable=True)],
    attrs={"ring_id": 0, "quant": "none", "strategy": "ring",
           "use_calc_stream": True},
    grad=None,
)
def _c_bucket_allreduce_start(ins, attrs):
    """First half of an ASYNC bucket reduction (parallel/scheduling.py
    ``schedule_async_collectives``): issues the flat (possibly
    quantized / strategy-re-spelled) psum into a ``Pending`` flat
    buffer at the bucket's availability point; the matching
    ``c_bucket_allreduce_await`` op slices it back into the grads just
    before their first consumer. Every op between the pair is
    data-independent of the collective, so XLA's scheduler is FREE to
    overlap them — the latency hiding is scheduled by us, in the IR,
    not hoped for."""
    xs = ins["X"]
    axis = axis_for_ring(attrs.get("ring_id", 0))
    quant = attrs.get("quant", "none")
    strategy = attrs.get("strategy", "ring")
    residual = ins.get("Residual")
    flat = _flat_concat(xs)
    if axis is None:
        # dense fallback: pending carries the unreduced concat — the
        # await slices it back, preserving the identity semantics
        out = {"Pending": flat}
        if residual is not None:
            out["ResidualOut"] = residual
        return out
    if residual is not None:
        red, new_res = quantized_psum(flat, axis, quant, strategy,
                                      residual)
        return {"Pending": red, "ResidualOut": new_res}
    return {"Pending": quantized_psum(flat, axis, quant, strategy)}


@register_op(
    "c_bucket_allreduce_await",
    inputs=[In("Pending"), In("X", duplicable=True)],
    outputs=[Out("Out", duplicable=True, is_ref=True)],
    attrs={"ring_id": 0, "use_calc_stream": True},
    grad=None,
)
def _c_bucket_allreduce_await(ins, attrs):
    """Second half of the async pair: slices the Pending flat reduction
    back into the member grads (in place). Carries NO wire payload of
    its own — the collective-schedule checker excludes it (the start op
    is the schedule entry); X is read only for member shapes."""
    return {"Out": _slice_back(ins["Pending"], ins["X"])}


# state slots each sharded-update optimizer carries, in (StateA, StateB)
# order; scalar Beta*Pow accumulators ride separately (per-param, tiny)
SHARDED_UPDATE_SLOTS = {
    "sgd": (),
    "momentum": ("Velocity",),
    "adam": ("Moment1", "Moment2"),
    "adamw": ("Moment1", "Moment2"),
}


@register_op(
    "c_sharded_update",
    inputs=[In("Param", duplicable=True), In("Grad", duplicable=True),
            In("LearningRate"),
            In("StateA", dispensable=True), In("StateB", dispensable=True),
            In("Beta1Pow", duplicable=True, dispensable=True),
            In("Beta2Pow", duplicable=True, dispensable=True)],
    outputs=[Out("ParamOut", duplicable=True, is_ref=True),
             Out("StateAOut", is_ref=True, dispensable=True),
             Out("StateBOut", is_ref=True, dispensable=True),
             Out("Beta1PowOut", duplicable=True, is_ref=True,
                 dispensable=True),
             Out("Beta2PowOut", duplicable=True, is_ref=True,
                 dispensable=True)],
    attrs={"op_type": "sgd", "shard_axis": "", "nranks": 1,
           "padded_size": 0, "quant": "none"},
    grad=None,
)
def _c_sharded_update(ins, attrs):
    """Cross-replica sharded weight update (PAPERS.md "Automatic
    Cross-Replica Sharding of Weight Update in Data-Parallel
    Training"): ONE op replaces a whole optimizer instance's per-param
    (allreduce, update) pairs. Inside the mesh each replica

      1. psums the flat concat of ALL the group's grads (one collective,
         optionally quantized) — elementwise identical to the per-grad
         psums it replaces;
      2. slices ITS 1/n shard of the flat grads/params; optimizer state
         arrives already sharded (StateA/StateB are flat vars the
         rewrite marked with a dp shard spec, so each replica only ever
         holds — and updates — its shard);
      3. applies the (elementwise) optimizer math on the shard;
      4. all_gathers just the updated param shards back to full
         replicated params.

    n redundant full updates become 1/n of one update per replica.
    Outside a mesh (dense run of the transpiled program) the same math
    runs on the full flat arrays — elementwise, so bit-for-bit with the
    sharded path AND with the replicated per-param path.
    """
    from . import optimizer_ops as _oo

    fns = {"sgd": _oo._sgd, "momentum": _oo._momentum,
           "adam": _oo._adam, "adamw": _oo._adamw}
    op_type = attrs["op_type"]
    fn = fns[op_type]
    slots = SHARDED_UPDATE_SLOTS[op_type]
    axis = attrs.get("shard_axis") or None
    quant = attrs.get("quant", "none")
    params, grads = ins["Param"], ins["Grad"]
    sizes = [int(p.size) for p in params]
    total = sum(sizes)
    padded = int(attrs.get("padded_size") or total)
    live = mesh_axis_active(axis)

    def _pad(flat):
        if padded > flat.size:
            return jnp.concatenate(
                [flat, jnp.zeros((padded - flat.size,), flat.dtype)])
        return flat

    g_flat = _pad(_flat_concat(grads))
    p_flat = _pad(_flat_concat(params))
    sub = {"LearningRate": ins["LearningRate"]}
    for scalar in ("Beta1Pow", "Beta2Pow"):
        if ins.get(scalar):
            # per-param accumulators are bitwise-identical (same init,
            # same update); the shard math uses the first
            sub[scalar] = ins[scalar][0]
    if live:
        n = int(attrs.get("nranks", 1))  # static (lax.axis_size is
        shard = padded // n              # missing on older jax)
        g_sum = quantized_psum(g_flat, axis, quant)
        idx = jax.lax.axis_index(axis)
        start = idx * shard
        sub["Grad"] = jax.lax.dynamic_slice(g_sum, (start,), (shard,))
        sub["Param"] = jax.lax.dynamic_slice(p_flat, (start,), (shard,))
        for key, slot in zip(("StateA", "StateB"), slots):
            sub[slot] = ins[key]  # already the local [padded/n] shard
        outs = fn(sub, attrs)
        p_new = jax.lax.all_gather(outs["ParamOut"], axis)
        p_new = p_new.reshape(-1)[:total]
    else:
        sub["Grad"] = g_flat
        sub["Param"] = p_flat
        for key, slot in zip(("StateA", "StateB"), slots):
            sub[slot] = ins[key]  # the full flat state
        outs = fn(sub, attrs)
        p_new = outs["ParamOut"][:total]

    result = {"ParamOut": [], "StateAOut": outs.get(slots[0] + "Out")
              if slots else None}
    if len(slots) > 1:
        result["StateBOut"] = outs.get(slots[1] + "Out")
    off = 0
    for p, k in zip(params, sizes):
        result["ParamOut"].append(p_new[off:off + k].reshape(p.shape))
        off += k
    if ins.get("Beta1Pow"):
        b1 = attrs.get("beta1", 0.9)
        result["Beta1PowOut"] = [b * b1 for b in ins["Beta1Pow"]]
    if ins.get("Beta2Pow"):
        b2 = attrs.get("beta2", 0.999)
        result["Beta2PowOut"] = [b * b2 for b in ins["Beta2Pow"]]
    return result
