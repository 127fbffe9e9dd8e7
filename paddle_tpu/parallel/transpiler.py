"""Collective program rewrites.

Parity: /root/reference/python/paddle/fluid/transpiler/collective.py
(GradAllReduce: loss-grad scale 1/nranks :190-213 + per-grad
c_allreduce_sum :215-250; LocalSGD :270) — the same pass over the
Python-native IR. ring_id stays in the op attrs; at execution the mesh
engine maps it to a named axis.
"""
from __future__ import annotations

from typing import Optional, Set

from ..analysis.contracts import checked_rewrite
from ..core.registry import GRAD_SUFFIX, OpInfoMap

OPTIMIZER_OP_TYPES = {
    "sgd", "momentum", "lars_momentum", "adam", "adamw", "adamax", "adagrad",
    "decayed_adagrad", "adadelta", "rmsprop", "ftrl", "lamb", "dpsgd",
    "proximal_gd",
}


def _is_loss_grad_seed(op):
    return (op.type == "fill_constant"
            and op.output("Out")
            and op.output("Out")[0].endswith(GRAD_SUFFIX)
            and float(op.attrs.get("value", 0.0)) == 1.0)


@checked_rewrite("insert_allreduce")
def insert_allreduce_ops(program, nranks: int, ring_id: int = 0,
                         scale_loss: bool = True, skip_grads=None):
    """Rewrite a training program for data parallelism: scale the loss
    grad by 1/nranks and allreduce every grad consumed by an optimizer op.
    Returns the set of grad var names allreduced. Idempotent: a program
    is rewritten at most once (fleet may transpile before the mesh
    engine sees the program). ``skip_grads``: grads of mesh-SHARDED
    params (sharded embedding rows, local experts) — their collective
    transposes already accumulate every shard's contribution, and an
    extra allreduce over the data axes would corrupt them."""
    if getattr(program, "_grads_allreduced", False):
        return set()
    program._grads_allreduced = True
    skip = set(skip_grads or ())
    block = program.global_block()
    if scale_loss:
        for op in block.ops:
            if _is_loss_grad_seed(op):
                op.attrs["value"] = 1.0 / nranks
    grad_names: Set[str] = set()
    for op in block.ops:
        if op.type in OPTIMIZER_OP_TYPES:
            for g in op.input("Grad"):
                if g not in skip:
                    grad_names.add(g)

    new_ops = []
    inserted: Set[str] = set()
    for op in block.ops:
        if op.type in OPTIMIZER_OP_TYPES:
            for g in op.input("Grad"):
                if g not in inserted and g not in skip:
                    from .. import framework

                    ar = framework.Operator(
                        block, "c_allreduce_sum",
                        {"X": [g]}, {"Out": [g]},
                        {"ring_id": ring_id, "use_calc_stream": True})
                    ar._id = program._next_op_id()
                    new_ops.append(ar)
                    inserted.add(g)
        new_ops.append(op)
    block.ops = new_ops
    return grad_names


def insert_local_sgd_ops(program, nranks: int, k_steps: int = 1,
                         ring_id: int = 0):
    """LocalSGD-style periodic parameter averaging (collective.py:270):
    every step here (k-step gating arrives with the step-counter wave),
    params are psum-averaged after the optimizer ops."""
    from .. import framework

    block = program.global_block()
    params = [p.name for p in program.all_parameters()]
    for name in params:
        ar = framework.Operator(block, "c_allreduce_sum", {"X": [name]},
                                {"Out": [name]}, {"ring_id": ring_id})
        ar._id = program._next_op_id()
        block.ops.append(ar)
        sc = framework.Operator(block, "scale", {"X": [name]},
                                {"Out": [name]}, {"scale": 1.0 / nranks,
                                                  "bias": 0.0})
        sc._id = program._next_op_id()
        block.ops.append(sc)
    return params


# -- hybrid parallelism passes (tensor / sequence / expert) -----------------
# The reference reaches distribution by program rewrite
# (transpiler/collective.py:92-131); these passes are the same pattern
# for the axes the reference lacks: ops are swapped for their
# collective-aware twins (ops/hybrid_parallel_ops.py) BEFORE backward
# generation, so append_backward differentiates through the collectives
# via auto-VJP. Each pass records mesh metadata on the program:
#   _var_shard_specs:  var name -> per-dim mesh-axis tuple
#   _feed_shard_specs: feed name -> per-dim mesh-axis tuple
#   _data_axes:        axes the batch is sharded over (loss/grad scale)
#   _allreduce_skip_grads: grads of SHARDED params (their collective
#       transposes already total every shard's contribution)


def _mark_shard(program, name: str, spec):
    specs = getattr(program, "_var_shard_specs", None)
    if specs is None:
        specs = {}
        program._var_shard_specs = specs
    specs[name] = tuple(spec)


def _skip_grad(program, grad_name: str, axes):
    """Record that ``grad_name`` belongs to a param sharded over
    ``axes``. The engine skips its data-axis allreduce ONLY when the
    shard axis IS a data axis (expert parallel: the all_to_all transpose
    already totals every shard's contribution); a grad sharded over an
    orthogonal model axis (mp table blocks under dp x mp) still needs
    the psum over dp."""
    skips = getattr(program, "_allreduce_skip_grads", None)
    if skips is None:
        skips = {}
        program._allreduce_skip_grads = skips
    skips[grad_name] = tuple(a for a in axes if a)


def _bump_version(program):
    # attr-only rewrites must still invalidate the engine's
    # program-version-keyed trace caches
    program._next_op_id()


def _merge_data_axes(program, axes):
    """Union (order-preserving) with axes recorded by earlier passes —
    a later pass must not clobber another's data axes (an MoE
    transformer with long context runs sp AND ep passes)."""
    cur = list(getattr(program, "_data_axes", None) or ())
    for a in axes:
        if a not in cur:
            cur.append(a)
    program._data_axes = tuple(cur)


@checked_rewrite("sharded_embedding")
def apply_sharded_embedding(program, axis: str = "mp", degree: int = 0,
                            startup_program=None):
    """Tensor parallelism for embedding tables: every lookup_table[_v2]
    op becomes c_sharded_lookup with its table row-sharded over ``axis``
    (the pslib sparse-PS replacement, fleet_wrapper.h:84 — here one
    gather+psum pair on ICI). Call BEFORE minimize(). Returns the
    sharded table names.

    Uneven vocab (V % degree != 0): the table var is PADDED to the next
    multiple of ``degree`` — lookups never touch pad rows (ids < V), so
    their grads are zero and the optimizer leaves them at init. The
    startup program's init op is re-shaped to match, which is why it
    must be passed when vocab is uneven."""
    block = program.global_block()
    tables = []
    for op in block.ops:
        if op.type not in ("lookup_table", "lookup_table_v2"):
            continue
        w = op.input("W")[0]
        v = block._find_var_recursive(w)
        vocab = int(v.shape[0]) if v is not None and v.shape else 0
        if degree and vocab and vocab % degree:
            v_pad = -(-vocab // degree) * degree
            if startup_program is None:
                raise ValueError(
                    "sharded embedding %r: vocab %d not divisible by "
                    "mp degree %d — pass startup_program so the table "
                    "can be padded to %d rows"
                    % (w, v.shape[0], degree, v_pad))
            _pad_table_rows(program, startup_program, w, v, v_pad)
        if op.attrs.get("is_sparse"):
            # mesh sharding REPLACES the SelectedRows sparse-grad path:
            # the local block grad is dense [V/mp, D] (the design — one
            # gather/psum pair instead of sparse push RPC), which is a
            # deliberate, visible semantics change for is_sparse tables
            import warnings

            warnings.warn(
                "sharded embedding %r: is_sparse=True becomes a dense "
                "row-sharded gradient under tensor parallelism" % w)
        squeeze = op.type == "lookup_table"  # v2 keeps the trailing dim
        op.type = "c_sharded_lookup"
        op.attrs = {"shard_axis": axis,
                    "padding_idx": int(op.attrs.get("padding_idx", -1)),
                    "squeeze_last": squeeze,
                    # the TRUE vocab (captured before pad-row growth)
                    "vocab_size": vocab}
        _mark_shard(program, w, (axis,))
        _skip_grad(program, w + GRAD_SUFFIX, (axis,))
        tables.append(w)
    _merge_data_axes(program, ("dp",))
    _bump_version(program)
    return tables


def _pad_table_rows(program, startup_program, name, var, v_pad):
    """Grow an embedding var to ``v_pad`` rows in BOTH programs (main
    var shape + every startup init op writing it); pad rows are inert:
    never looked up, zero grad."""
    new_shape = (v_pad,) + tuple(var.shape[1:])
    var.shape = new_shape
    for blk in ([startup_program.global_block()]
                + [program.global_block()]):
        for op in blk.ops:
            if name in op.output_arg_names and "shape" in op.attrs:
                op.attrs["shape"] = list(new_shape)
    sv = startup_program.global_block()._find_var_recursive(name)
    if sv is not None:
        sv.shape = new_shape


@checked_rewrite("sequence_parallel")
def apply_sequence_parallel(program, axis: str = "sp", degree: int = 0,
                            feed_specs=None):
    """Sequence/context parallelism: flash_attention ops become
    c_ring_attention over ``axis`` (K/V shards rotate the ring via
    ppermute — long-context training). ``feed_specs`` declares how data
    feeds are laid out over the mesh, e.g. {"x": ("dp", None, "sp")} for
    [B, H, S, D] with batch over dp and sequence over sp (token-major
    ops, [B, T, H*hd] with ``num_heads``, keep their layout: ("dp",
    "sp")). ``degree``
    (when given) validates the attention sequence length divides evenly
    — a clear error here beats a cryptic shard_map one at run time.
    Call BEFORE minimize()."""
    block = program.global_block()
    n = 0
    for op in block.ops:
        if op.type != "flash_attention":
            continue
        # a Lengths (padding) input carries straight through: ring
        # attention masks GLOBAL key positions >= lengths[b], the same
        # contract as the masked flash kernels. The [B] lengths var is
        # BATCH-aligned: pin it to the 'dp' axis so the engine's
        # default data-axis sharding can never split it over the ring
        # (an sp-only mesh would otherwise shard [B] over sp and mask
        # with the wrong example's length — with that pin, an sp-only
        # mesh fails loudly on the missing 'dp' axis instead)
        for ln in op.input("Lengths"):
            fs = getattr(program, "_feed_shard_specs", None)
            if fs is None:
                fs = {}
                program._feed_shard_specs = fs
            fs.setdefault(ln, ("dp",))
        if degree:
            q = block._find_var_recursive(op.input("Q")[0])
            if q is not None and q.shape is not None and len(q.shape) >= 3:
                # [B, T, H*hd] or [B, H, S, D]
                seq = q.shape[1 if len(q.shape) == 3 else 2]
                if seq and seq % degree:
                    raise ValueError(
                        "sequence parallel: attention seq len %d not "
                        "divisible by sp degree %d (Q=%r)"
                        % (seq, degree, op.input("Q")[0]))
        op.type = "c_ring_attention"
        op.outputs.pop("LSE", None)   # the flash kernels' residual
        op.attrs = {"shard_axis": axis,
                    "causal": bool(op.attrs.get("causal")),
                    "scale": float(op.attrs.get("scale", 0.0)),
                    "num_heads": int(op.attrs.get("num_heads", 0))}
        n += 1
    if feed_specs:
        fs = getattr(program, "_feed_shard_specs", None)
        if fs is None:
            fs = {}
            program._feed_shard_specs = fs
        fs.update({k: tuple(v) for k, v in feed_specs.items()})
    _merge_data_axes(program, ("dp", axis))
    _bump_version(program)
    return n


@checked_rewrite("expert_parallel")
def apply_expert_parallel(program, axis: str = "ep", degree: int = 1):
    """Expert parallelism: moe ops route tokens to device-local expert
    shards via two all_to_alls over ``axis``; tokens (the batch) are
    sharded over the same axis. Dense runs of the transpiled program
    chunk routing into ``degree`` groups so both paths drop identical
    tokens. Call BEFORE minimize()."""
    block = program.global_block()
    experts = []
    for op in block.ops:
        if op.type != "moe":
            continue
        w_in, w_out = op.input("WIn")[0], op.input("WOut")[0]
        for w in (w_in, w_out):
            v = block._find_var_recursive(w)
            if v is not None and v.shape and v.shape[0] % degree:
                raise ValueError(
                    "expert parallel %r: %d experts not divisible by "
                    "ep degree %d" % (w, v.shape[0], degree))
            _mark_shard(program, w, (axis,))
            _skip_grad(program, w + GRAD_SUFFIX, (axis,))
        op.attrs = dict(op.attrs)
        op.attrs["shard_axis"] = axis
        op.attrs["num_groups"] = int(degree)
        experts.append((w_in, w_out))
    _merge_data_axes(program, (axis,))
    _bump_version(program)
    return experts


def shard_optimizer_state(program):
    """After minimize(): optimizer accumulators of a sharded param
    (momentum velocity, adam moments) are elementwise-paired with it and
    must shard identically. Matches by optimizer-op Param input + shape."""
    specs = getattr(program, "_var_shard_specs", None)
    if not specs:
        return
    block = program.global_block()
    for op in block.ops:
        if op.type not in OPTIMIZER_OP_TYPES:
            continue
        params = op.input("Param")
        if not params or params[0] not in specs:
            continue
        spec = specs[params[0]]
        pvar = block._find_var_recursive(params[0])
        pshape = tuple(pvar.shape) if pvar is not None else None
        grads = set(op.input("Grad"))
        for name in op.input_arg_names:
            if name in specs or name == params[0] or name in grads:
                continue
            v = block._find_var_recursive(name)
            if (v is not None and v.shape is not None
                    and tuple(v.shape) == pshape):
                specs[name] = spec


def mark_sync_batch_norm(program, enable=True):
    """BuildStrategy.sync_batch_norm: tag batch_norm ops so their batch
    statistics pmean across the mesh axis (reference
    ir/sync_batch_norm_pass.cc rewriting batch_norm -> sync_batch_norm).
    Applies the CURRENT strategy value each call (the engine keys its
    compile cache on it, so flipping the knob between runs retraces)."""
    if getattr(program, "_sync_bn_marked", None) == enable:
        return
    program._sync_bn_marked = enable
    for block in program.blocks:
        for op in block.ops:
            if op.type == "batch_norm":
                op.attrs["_sync_stats"] = bool(enable)
