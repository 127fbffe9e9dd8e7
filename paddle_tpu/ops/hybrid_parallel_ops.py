"""Hybrid-parallelism ops: the Program-path surface for tensor (sharded
embedding), sequence (ring attention), and expert (MoE) parallelism.

The reference reaches model parallelism by *rewriting user programs*
(transpiler/collective.py:92-131 inserts collective ops;
fleet_base.py:38 drives it). These ops are the rewrite TARGETS for the
analogous TPU passes in ``parallel/transpiler.py``: each op carries a
``shard_axis`` attr; when the mesh engine traces the program under
``shard_map`` with that axis live (collective_ops.mesh_axes_guard), the
op emits the collective formulation over ICI; everywhere else (single
device, interpreter, inference) it computes the exact dense semantics —
so one Program serves both executions, which is what lets the driver
check mesh-vs-single-device loss parity through `exe.run`.

All three are pure JAX fns with grad="auto": backward.py's generated
grad ops differentiate THROUGH the collectives (psum/all_to_all
transpose), which is the TPU-native answer to the reference's
hand-written grad kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import In, Out, register_op
from .collective_ops import mesh_axis_active


@register_op(
    "c_sharded_lookup",
    inputs=[In("W"), In("Ids", no_grad=True)],
    outputs=[Out("Out")],
    attrs={"shard_axis": "mp", "padding_idx": -1, "vocab_size": 0,
           "squeeze_last": True},
)
def _c_sharded_lookup(ins, attrs):
    """Row-sharded embedding lookup (rewrite target of lookup_table,
    parallel/transpiler.apply_sharded_embedding). Under the mesh, W is
    this shard's row block and ids are global: each shard contributes
    its local hits, one psum assembles (sharded_embedding lookup — the
    pslib PullSparse replacement, fleet_wrapper.h:84). Dense fallback
    matches lookup_table exactly."""
    w, ids = ins["W"], ins["Ids"]
    # lookup_table squeezes a trailing [.., 1] ids dim; lookup_table_v2
    # keeps it (out = ids.shape + [D]) — the transpiler records which
    if attrs.get("squeeze_last", True) and ids.ndim >= 2 \
            and ids.shape[-1] == 1:
        ids = ids.squeeze(-1)
    pad = int(attrs.get("padding_idx", -1))
    axis = attrs.get("shard_axis")
    if mesh_axis_active(axis):
        out = _sharded_lookup_grad_exact(w, ids, axis)
    else:
        out = jnp.take(w, jnp.clip(ids, 0, w.shape[0] - 1), axis=0)
    if pad >= 0:
        out = jnp.where((ids == pad)[..., None], 0.0, out)
    return {"Out": out}


def _sharded_lookup_grad_exact(w, ids, axis):
    """sharded_embedding_lookup with a custom VJP.

    The per-op backward (the Program path: append_backward generates
    c_sharded_lookup_grad, which vjp's THIS fn in isolation) would hit
    the psum-transpose pitfall: the cotangent arriving at Out is
    replicated across ``axis`` (it represents d(one loss)/d(out), and
    every axis member computes that loss redundantly), but jax
    transposes psum to psum, summing the replicas — an axis_size-times
    overcount. The mathematically correct pullback of
    out = psum(contrib) for a replicated cotangent is the identity, so:
    scatter ct's hit rows straight into this shard's block."""
    import jax
    from jax.dtypes import float0

    from ..parallel.sharded_embedding import sharded_embedding_lookup

    rows_per, d = w.shape

    # ids ride as a PRIMAL + residual — a bwd closure over the forward
    # trace's ids tracer leaks it into any later staging context
    # (lax.switch/scan transpose under the pipeline engine raises
    # "No constant handler for DynamicJaxprTracer")
    @jax.custom_vjp
    def lookup(w_, ids_):
        return sharded_embedding_lookup(w_, ids_, axis)

    def fwd(w_, ids_):
        return lookup(w_, ids_), ids_

    def bwd(ids_, ct):
        ids_flat = ids_.reshape(-1)
        idx = jax.lax.axis_index(axis)
        local = ids_flat - idx * rows_per
        hit = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        ct2 = jnp.where(hit[:, None], ct.reshape(-1, d), 0.0)
        gw = jnp.zeros((rows_per, d), ct.dtype).at[safe].add(ct2)
        return (gw, np.zeros(ids_.shape, dtype=float0))

    lookup.defvjp(fwd, bwd)
    return lookup(w, ids)


@register_op(
    "c_ring_attention",
    inputs=[In("Q"), In("K"), In("V"),
            In("Lengths", dispensable=True, no_grad=True)],
    outputs=[Out("Out")],
    attrs={"shard_axis": "sp", "causal": False, "scale": 0.0,
           "num_heads": 0},
)
def _c_ring_attention(ins, attrs):
    """Sequence-parallel attention over [B, H, S_local, D], or over
    token-major [B, S_local, H*hd] with ``num_heads`` (split into heads
    here, the context merged back), as the ``flash_attention`` op it
    replaces took them (apply_sequence_parallel): K/V shards
    rotate around the ``shard_axis`` ring via ppermute with an exact
    streaming-softmax accumulator (parallel/ring_attention.py).
    ``Lengths`` [B] carries the GLOBAL per-example padding mask
    (replicated across the ring). Dense fallback is exact
    full-sequence attention."""
    from .pallas.flash_attention import merge_heads, split_heads

    q, k, v = ins["Q"], ins["K"], ins["V"]
    tokens = q.ndim == 3
    if tokens:
        q, k, v = (split_heads(x, int(attrs["num_heads"]))
                   for x in (q, k, v))
    lengths = ins.get("Lengths")
    causal = bool(attrs.get("causal"))
    scale = attrs.get("scale", 0.0) or None
    axis = attrs.get("shard_axis")
    if mesh_axis_active(axis):
        from ..parallel.ring_attention import ring_attention

        out = ring_attention(q, k, v, axis, causal=causal, scale=scale,
                             lengths=lengths)
    else:
        from ..parallel.ring_attention import reference_attention

        out = reference_attention(q, k, v, causal=causal, scale=scale,
                                  lengths=lengths)
    return {"Out": merge_heads(out) if tokens else out}


@register_op(
    "moe",
    inputs=[In("X"), In("GateW"), In("WIn"), In("WOut")],
    outputs=[Out("Out")],
    attrs={"shard_axis": "", "num_groups": 1, "capacity_factor": 1.0},
)
def _moe(ins, attrs):
    """Switch-routed MoE FFN over [T, D] tokens (layers.switch_moe).
    With ``shard_axis`` live, experts are device-local shards and two
    all_to_alls route token slots (parallel/moe.py — GShard-style EP);
    dense fallback runs the identical top-1 + capacity routing in
    ``num_groups`` chunks so both paths drop the same tokens."""
    x, gate_w = ins["X"], ins["GateW"]
    w_in, w_out = ins["WIn"], ins["WOut"]
    cf = float(attrs.get("capacity_factor", 1.0))
    groups = int(attrs.get("num_groups", 1) or 1)
    axis = attrs.get("shard_axis")
    if mesh_axis_active(axis):
        from ..parallel.moe import expert_parallel_moe

        out = expert_parallel_moe(x, gate_w, w_in, w_out, axis, cf)
    else:
        from ..parallel.moe import moe_reference

        out = moe_reference(x, gate_w, w_in, w_out, cf, groups)
    return {"Out": out}
