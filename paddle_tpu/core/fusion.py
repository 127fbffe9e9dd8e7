"""Single-chip fusion rewrites: fused optimizer update + fused epilogues.

The multi-chip fast path (parallel/collectives.py) made the optimizer
boundary a single flat-buffer op per optimizer instance; this module is
the SINGLE-CHIP mirror, driven by the step profiler's finding that the
optimizer and elementwise-epilogue phases are memory-bound op chains:

- ``apply_fused_optimizer``: each sgd / momentum / adam / adamw
  instance's per-param update ops collapse into ONE ``fused_optimizer``
  op over flattened params/grads, with optimizer state re-laid-out
  into flat vars (the exact mechanism — and restart resync — the
  sharded-update rewrite already proved). One kernel launch per step
  (ops/pallas/fused_optimizer.py) instead of a per-param op chain.
- ``apply_fused_epilogues``: adjacent forward chains
  ``elementwise_add -> {relu,gelu,tanh,sigmoid} [-> dropout]`` and
  ``elementwise_add -> layer_norm`` collapse into the fused epilogue
  ops (ops/fused_ops.py), which re-emit every intermediate the
  pre-built backward still reads — bit-for-bit, fewer traced ops.

Both are ``@checked_rewrite`` passes: under ``PADDLE_TPU_VERIFY_IR``
their declared contracts (analysis/contracts.py — every (param, grad)
pair updated exactly once; no written var lost) run around the pass
and the whole program re-verifies.

Knobs (default OFF; read per call — one env read each, so the
disabled executor hot path stays under the gate-4 overhead budget):

==============================  ===========================================
``PADDLE_TPU_FUSED_OPTIMIZER``  ``1`` fuses optimizer instances on the
                                single-chip executor path
``PADDLE_TPU_FUSED_EPILOGUE``   ``1`` fuses add->act[->dropout] and
                                add->layer_norm epilogues
==============================  ===========================================

``bench.py`` flips both ON for its single-chip configs (the bit-parity
suite in tests/test_single_chip_fusion.py is the license to); the dp
engine refuses a fused-optimizer program (its grads would dodge the
allreduce transpiler) — the mesh-side equivalent is the sharded update.
"""
from __future__ import annotations

import hashlib
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analysis.contracts import checked_rewrite

__all__ = ["fused_optimizer_enabled", "fused_epilogue_enabled",
           "maybe_rewrite_single_chip", "apply_fused_optimizer",
           "apply_fused_epilogues", "FUSED_OPTIMIZER_TYPES",
           "EPILOGUE_ACTS"]

# optimizer op types the fused update supports — elementwise update
# math only (same precondition as the cross-replica sharded update;
# lars/lamb carry param-norm terms and stay per-param), with the state
# slots each folds into the flat StateA/StateB vars
FUSED_OPTIMIZER_TYPES: Dict[str, Tuple[str, ...]] = {
    "sgd": (),
    "momentum": ("Velocity",),
    "adam": ("Moment1", "Moment2"),
    "adamw": ("Moment1", "Moment2"),
}

EPILOGUE_ACTS = ("relu", "gelu", "tanh", "sigmoid")


_TRUTHY = ("1", "true", "yes", "on")


def _env_on(raw) -> bool:
    return bool(raw) and raw.strip().lower() in _TRUTHY


def fused_optimizer_mode() -> Optional[str]:
    """``PADDLE_TPU_FUSED_OPTIMIZER``: unset/off -> None; truthy ->
    ``"auto"`` (flat layout on TPU backends where the pallas kernel
    runs, chain layout elsewhere); ``flat`` / ``chain`` force a
    layout."""
    raw = os.environ.get("PADDLE_TPU_FUSED_OPTIMIZER")
    if not raw:
        return None
    raw = raw.strip().lower()
    if raw in ("flat", "chain"):
        return raw
    return "auto" if raw in _TRUTHY else None


def fused_optimizer_enabled() -> bool:
    return fused_optimizer_mode() is not None


def fused_epilogue_enabled() -> bool:
    return _env_on(os.environ.get("PADDLE_TPU_FUSED_EPILOGUE"))


def maybe_rewrite_single_chip(program, scope, place=None) -> None:
    """Executor entry point, called on every run. The knobs are read
    at a program's FIRST run and baked in (the same contract the
    collective-path knobs keep), so the steady-state cost is ONE
    attribute read + a branch — the gate-4 per-run budget. Applies
    the epilogue pass, then the optimizer pass; a program the
    parallel transpiler already rewrote keeps its collective path.
    ``place`` is the executor's: the optimizer pass runs under its
    device, so the ``auto`` layout follows where the program will run
    (``compute_platform()``), not the default backend."""
    state = getattr(program, "_sc_fusion", None)
    if state is not None:
        if state and scope is not None:
            # restart semantics: a startup re-run re-initializes the
            # retired per-param state vars — rebuild the flat state
            # exactly like the sharded update does (shared layout)
            from ..parallel.collectives import resync_sharded_state

            resync_sharded_state(program, scope)
        return
    mode = fused_optimizer_mode()
    fuse_epi = fused_epilogue_enabled()
    n_opt = 0
    if fuse_epi:
        apply_fused_epilogues(program)
    if mode is not None:
        import contextlib

        import jax

        with (jax.default_device(place.jax_device()) if place is not None
              else contextlib.nullcontext()):
            n_opt = apply_fused_optimizer(program, scope, layout=mode)
    try:
        # flat layout re-laid state into flat vars -> later runs must
        # resync them after a startup re-run; chain layout kept the
        # per-param vars, nothing to resync
        program._sc_fusion = bool(
            n_opt and getattr(program, "_sharded_flat_layout", None))
    except AttributeError:
        pass


def _attrs_sig(attrs) -> Tuple:
    return tuple(sorted((k, repr(v)) for k, v in attrs.items()
                        if not k.startswith("_")))


# ---------------------------------------------------------------------------
# fused optimizer update
# ---------------------------------------------------------------------------


@checked_rewrite("fused_optimizer")
def apply_fused_optimizer(program, scope, use_pallas: bool = True,
                          layout: str = "auto") -> int:
    """Rewrite each supported optimizer instance's per-param update ops
    into ONE ``fused_optimizer`` op. Returns the number of instances
    fused.

    ``layout="chain"`` keeps the per-param state vars and the op
    applies the shared update math pair by pair — the zero-overhead
    layout for backends where XLA fuses the chain anyway (re-laying
    state flat was measured ~40% slower per step on CPU from the
    per-step concats). ``layout="flat"`` re-lays optimizer state into
    flat zero-padded vars (padding to the pallas lane tile) so ONE
    pallas streaming kernel updates the whole buffer — the TPU
    layout. ``"auto"`` picks flat exactly when the pallas kernel
    would actually run (``compute_platform()`` is a TPU).

    Grouping key: (op type, hyperparam attrs, LearningRate var, param
    dtype) — one group per optimizer instance per dtype, mirroring the
    sharded-update pass. Spared (kept per-param): params with sparse /
    dynamic-shaped grads, grad dtype != param dtype (the flat concat
    would change promotion semantics), mesh-sharded params,
    single-member groups (nothing to fuse), and groups whose member
    vars are touched by unrelated ops between the group's first and
    last update (the fused op hoists every update to the first
    position — any interleaved reader would see post-update values).
    """
    if getattr(program, "_fused_optimizer_applied", False):
        return 0
    program._fused_optimizer_applied = True
    if getattr(program, "_grads_allreduced", False) or \
            getattr(program, "_sharded_update_n", None) is not None:
        return 0  # dp-transpiled: the collective path owns the update
    if layout == "auto":
        from .place import compute_platform

        layout = "flat" if compute_platform() == "tpu" else "chain"
    if layout not in ("flat", "chain"):
        raise ValueError("fused optimizer layout %r" % (layout,))
    from .. import framework
    from ..parallel.collectives import _splice_flat_state, _src_token
    from ..ops.pallas.fused_optimizer import LANE_PAD

    block = program.global_block()
    ops = block.ops
    shard_specs = getattr(program, "_var_shard_specs", None) or {}

    groups: Dict[Tuple, List[int]] = {}
    for i, op in enumerate(ops):
        if op.type not in FUSED_OPTIMIZER_TYPES:
            continue
        if not op.input("Param") or not op.input("Grad"):
            continue
        p = op.input("Param")[0]
        pv = block._find_var_recursive(p)
        if (p in shard_specs or pv is None or not pv.shape
                or not all(isinstance(s, int) and s > 0
                           for s in pv.shape)
                or getattr(pv, "type", "lod_tensor") != "lod_tensor"):
            continue
        g = op.input("Grad")[0]
        gv = block._find_var_recursive(g)
        if gv is not None and getattr(gv, "type", "") == "selected_rows":
            continue  # sparse grads keep the row-wise per-param kernel
        if gv is not None and getattr(gv, "dtype", None) and \
                str(gv.dtype) != str(pv.dtype):
            continue  # mixed-dtype pair: concat would change promotion
        key = (op.type, _attrs_sig(op.attrs),
               op.input("LearningRate")[0], str(pv.dtype))
        groups.setdefault(key, []).append(i)

    n_groups = 0
    removed = set()
    replace_at: Dict[int, object] = {}
    for key, idxs in sorted(groups.items(), key=lambda kv: kv[1][0]):
        if len(idxs) < 2:
            continue  # a single update op is already one launch
        op_type, _, lr_name, dtype = key
        member_ops = [ops[i] for i in idxs]
        # the fused op lands at the FIRST member's position, so every
        # member's update happens there; an unrelated op interleaved
        # between the members that touches a member's param/state (or
        # rewrites the LR) would observe different values — spare the
        # whole group
        member_set = set(idxs)
        grads_set = {mop.input("Grad")[0] for mop in member_ops}
        guarded = {lr_name}
        for mop in member_ops:
            guarded.update(n for n in mop.input_arg_names if n)
            guarded.update(n for n in mop.output_arg_names if n)
        # reading a member's GRAD between the members is harmless (the
        # update never rewrites it); reading param/state is not, and
        # WRITING anything a member touches (grads included) is not
        read_guard = guarded - grads_set

        def _clashes(j):
            if j in member_set:
                return False
            op_j = ops[j]
            return any(n in read_guard for n in op_j.input_arg_names) \
                or any(n in guarded for n in op_j.output_arg_names)

        if any(_clashes(j) for j in range(idxs[0] + 1, idxs[-1])):
            continue

        params = [op.input("Param")[0] for op in member_ops]
        grads = [op.input("Grad")[0] for op in member_ops]
        sizes = [int(np.prod(block.var(p).shape)) for p in params]
        total = sum(sizes)
        padded = -(-total // LANE_PAD) * LANE_PAD
        n_groups += 1
        sig = hashlib.sha1(("%s|%s" % (op_type, ",".join(
            "%s:%d" % t for t in zip(params, sizes)))).encode())
        gtag = sig.hexdigest()[:8]

        inputs = {"Param": params, "Grad": grads,
                  "LearningRate": [lr_name]}
        outputs = {"ParamOut": params}
        for slot_key, slot in zip(("StateA", "StateB"),
                                  FUSED_OPTIMIZER_TYPES[op_type]):
            state_names = [op.input(slot)[0] for op in member_ops]
            if layout == "chain":
                # per-param accumulators stay exactly where they are
                inputs[slot_key] = state_names
                outputs[slot_key + "Out"] = state_names
                continue
            flat_name = "fused_opt_%s.%s" % (gtag, slot.lower())
            fv = block.create_var(name=flat_name, shape=(padded,),
                                  dtype=dtype, persistable=True)
            fv.stop_gradient = True
            flat = _splice_flat_state(block, scope, state_names,
                                      total, padded, dtype, slot)
            for sn in state_names:
                block.var(sn).persistable = False
            scope.var(flat_name).get_tensor()._array = flat
            # the sharded update's restart-resync machinery is layout-
            # agnostic — register the flat var under the same program
            # attrs so resync_sharded_state rebuilds it after a
            # startup re-run
            for attr in ("_sharded_flat_layout", "_sharded_src_tokens"):
                if getattr(program, attr, None) is None:
                    setattr(program, attr, {})
            program._sharded_flat_layout[flat_name] = (
                tuple(state_names), total, padded, dtype, slot)
            program._sharded_src_tokens[flat_name] = tuple(
                _src_token(scope, sn) for sn in state_names)
            inputs[slot_key] = [flat_name]
            outputs[slot_key + "Out"] = [flat_name]
        for scalar in ("Beta1Pow", "Beta2Pow"):
            names = [op.input(scalar) for op in member_ops]
            if all(n for n in names):
                inputs[scalar] = [n[0] for n in names]
                outputs[scalar + "Out"] = [n[0] for n in names]

        attrs = dict(member_ops[0].attrs)
        attrs.update({"op_type": op_type, "layout": layout,
                      "padded_size": int(padded),
                      "use_pallas": bool(use_pallas)})
        fo = framework.Operator(block, "fused_optimizer", inputs,
                                outputs, attrs)
        fo._id = program._next_op_id()
        replace_at[idxs[0]] = fo
        removed.update(idxs)

    if not n_groups:
        return 0
    new_ops = []
    for i, op in enumerate(ops):
        if i in replace_at:
            new_ops.append(replace_at[i])
        if i not in removed:
            new_ops.append(op)
    block.ops = new_ops
    program._fused_optimizer_groups = n_groups
    from ..parallel.transpiler import _bump_version

    _bump_version(program)
    from .. import observability as _obs

    _obs.inc("fusion.optimizer_groups", n_groups)
    return n_groups


# ---------------------------------------------------------------------------
# fused epilogues
# ---------------------------------------------------------------------------


def _single_writer_names(ops) -> set:
    counts: Dict[str, int] = {}
    for op in ops:
        for n in op.output_arg_names:
            if n:
                counts[n] = counts.get(n, 0) + 1
    return {n for n, c in counts.items() if c == 1}


def _first_backward_index(ops) -> int:
    from .registry import GRAD_SUFFIX

    for i, op in enumerate(ops):
        if "_fwd_op_id" in op.attrs or any(
                GRAD_SUFFIX in n for n in op.output_arg_names if n):
            return i
    return len(ops)


@checked_rewrite("fused_epilogue")
def apply_fused_epilogues(program) -> int:
    """Collapse adjacent forward epilogue chains into the fused ops:

    - ``elementwise_add -> act`` (act in EPILOGUE_ACTS), optionally
      ``-> dropout``  =>  ``fused_bias_act``
    - ``elementwise_add -> layer_norm``  =>  ``fused_residual_layer_norm``

    Only SINGLE-WRITER intermediates fuse (a rebound name means the
    chain is not a private dataflow edge), only in the forward region
    (backward ops recompute through their own wiring), and every
    intermediate name is re-emitted by the fused op — pre-built grad
    ops keep reading the values they were built against. Returns the
    number of chains fused."""
    if getattr(program, "_fused_epilogue_applied", False):
        return 0
    program._fused_epilogue_applied = True
    from .. import framework

    block = program.global_block()
    ops = block.ops
    single = _single_writer_names(ops)
    bwd_start = _first_backward_index(ops)

    fused: List[Tuple[int, int, object]] = []  # (start, end_excl, op)
    i = 0
    while i < bwd_start - 1:
        opA = ops[i]
        if opA.type != "elementwise_add" or len(opA.output("Out")) != 1:
            i += 1
            continue
        a_out = opA.output("Out")[0]
        if a_out not in single:
            i += 1
            continue
        opB = ops[i + 1]
        end = None
        new_op = None
        if opB.type in EPILOGUE_ACTS and opB.input("X") == [a_out] \
                and len(opB.output("Out")) == 1:
            b_out = opB.output("Out")[0]
            if b_out not in single:
                i += 1
                continue
            attrs = {"act": opB.type,
                     "axis": opA.attrs.get("axis", -1),
                     "approximate": bool(opB.attrs.get("approximate",
                                                       False)),
                     "alpha": opB.attrs.get("alpha", 0.02),
                     "dropout_prob": -1.0}
            outputs = {"Out": [b_out], "AddOut": [a_out]}
            end = i + 2
            opC = ops[i + 2] if i + 2 < bwd_start else None
            if (opC is not None and opC.type == "dropout"
                    and opC.input("X") == [b_out]
                    and not opC.input("Seed")
                    and len(opC.output("Out")) == 1
                    and opC.output("Out")[0] in single):
                attrs.update({
                    "dropout_prob": float(
                        opC.attrs.get("dropout_prob", 0.5)),
                    "is_test": bool(opC.attrs.get("is_test", False)),
                    "fix_seed": bool(opC.attrs.get("fix_seed", False)),
                    "seed": int(opC.attrs.get("seed", 0) or 0),
                    "dropout_implementation": opC.attrs.get(
                        "dropout_implementation",
                        "downgrade_in_infer"),
                    # the fused op draws from the ORIGINAL dropout
                    # op's RNG stream, so masks match the pre-built
                    # dropout_grad ops bit-for-bit. NOT spelled
                    # _fwd_op_id: that attr marks BACKWARD ops
                    # (classify_ops keys the phase boundary on it —
                    # carrying it here would flip the rest of the
                    # forward region to "backward" in every profile)
                    "_rng_op_id": opC._id or 0,
                })
                outputs = {"Out": opC.output("Out"),
                           "AddOut": [a_out], "ActOut": [b_out]}
                if opC.output("Mask"):
                    outputs["Mask"] = opC.output("Mask")
                end = i + 3
            new_op = framework.Operator(
                block, "fused_bias_act",
                {"X": opA.input("X"), "Y": opA.input("Y")},
                outputs, attrs)
        elif opB.type == "layer_norm" and opB.input("X") == [a_out] \
                and len(opB.output("Y")) == 1 \
                and opB.output("Y")[0] in single:
            outputs = {"Out": opB.output("Y"), "AddOut": [a_out],
                       "Mean": opB.output("Mean"),
                       "Variance": opB.output("Variance")}
            new_op = framework.Operator(
                block, "fused_residual_layer_norm",
                {"X": opA.input("X"), "Y": opA.input("Y"),
                 "Scale": opB.input("Scale"),
                 "Bias": opB.input("Bias")},
                outputs,
                {"axis": opA.attrs.get("axis", -1),
                 "epsilon": opB.attrs.get("epsilon", 1e-5),
                 "begin_norm_axis": opB.attrs.get("begin_norm_axis",
                                                  1)})
            end = i + 2
        if new_op is None:
            i += 1
            continue
        new_op._id = program._next_op_id()
        fused.append((i, end, new_op))
        i = end

    if not fused:
        return 0
    new_ops: List = []
    k = 0
    for start, end, op in fused:
        new_ops.extend(ops[k:start])
        new_ops.append(op)
        k = end
    new_ops.extend(ops[k:])
    block.ops = new_ops
    from ..parallel.transpiler import _bump_version

    _bump_version(program)
    from .. import observability as _obs

    _obs.inc("fusion.epilogue_chains", len(fused))
    return len(fused)
