"""Collective-schedule rewrite passes (placement synthesis, ISSUE 15).

Three ``@checked_rewrite`` passes over an already-bucketed program —
the rewrite vocabulary the placement search (paddle_tpu/placement/)
enumerates over, each usable standalone via an env knob:

- **Async start/await scheduling** (``schedule_async_collectives``,
  ``PADDLE_TPU_ASYNC_COLLECTIVES=1``): each ``c_bucket_allreduce``
  splits into a ``c_bucket_allreduce_start`` op at the bucket's
  availability anchor (issuing the flat psum into a Pending buffer)
  and a ``c_bucket_allreduce_await`` op placed just before the
  earliest consumer of any member grad. Everything between the pair is
  data-independent of the collective, so overlap is SCHEDULED in the
  IR rather than left to XLA's hoisting heuristics. With a profile
  report the split is gated by measured slack: a bucket with no
  backward compute left after its anchor (a tail bucket) stays fused —
  splitting it buys nothing and costs an op.

- **Reduction-strategy swap** (``swap_reduction_strategy``,
  ``PADDLE_TPU_REDUCE_STRATEGY=ring|tree|two_stage``): re-spells every
  bucket reduction per ``ops.collective_ops.strategy_psum`` without
  moving an op. Integer (int8-code) payloads are exact under every
  spelling; float payloads may re-associate — the documented
  bit-for-bit-or-bounded contract.

- **Per-bucket quantization + EQuARX error feedback**
  (``configure_bucket_quant``, ``PADDLE_TPU_QUANT_ERROR_FEEDBACK=1``):
  overrides the ``quant`` attr per bucket op (the search decides
  int8/bf16 per bucket where wire bytes dominate) and, for quantized
  buckets under error feedback, wires a per-replica Residual var —
  dp-sharded, one rounding-error shard per replica — so the
  quantization bias cancels across steps instead of compounding.

All three register contracts in ``analysis/contracts.py``, so the
PR-12 invariant net (and ``tools/ir_mutate.py``) extends to them.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

from ..analysis.contracts import checked_rewrite
from ..ops.collective_ops import REDUCTION_STRATEGIES
from .transpiler import _bump_version

__all__ = [
    "reduce_strategy_mode", "async_collectives_enabled",
    "quant_error_feedback", "swap_reduction_strategy",
    "configure_bucket_quant", "schedule_async_collectives",
    "BUCKET_OP_TYPES",
]

# ops a strategy/quant reconfiguration may touch; the await carries no
# payload and no strategy (it only slices the Pending buffer back)
BUCKET_OP_TYPES = ("c_bucket_allreduce", "c_bucket_allreduce_start")

_TRUTHY = ("1", "true", "yes", "on")


def reduce_strategy_mode() -> str:
    """``PADDLE_TPU_REDUCE_STRATEGY``: ring (default) | tree |
    two_stage."""
    raw = os.environ.get("PADDLE_TPU_REDUCE_STRATEGY", "").strip().lower()
    if raw in ("", "auto", "ring"):
        return "ring"
    if raw in REDUCTION_STRATEGIES:
        return raw
    raise ValueError("PADDLE_TPU_REDUCE_STRATEGY=%r (want one of %s)"
                     % (raw, ", ".join(REDUCTION_STRATEGIES)))


def async_collectives_enabled() -> bool:
    """``PADDLE_TPU_ASYNC_COLLECTIVES=1``: split bucket reductions into
    start/await pairs at first mesh run."""
    raw = os.environ.get("PADDLE_TPU_ASYNC_COLLECTIVES", "").strip()
    return raw.lower() in _TRUTHY


def quant_error_feedback() -> bool:
    """``PADDLE_TPU_QUANT_ERROR_FEEDBACK=1``: arm the EQuARX residual
    on quantized bucket reductions."""
    raw = os.environ.get("PADDLE_TPU_QUANT_ERROR_FEEDBACK", "").strip()
    return raw.lower() in _TRUTHY


# ---------------------------------------------------------------------------
# reduction-strategy swap
# ---------------------------------------------------------------------------


@checked_rewrite("reduction_swap")
def swap_reduction_strategy(program, strategy: str) -> int:
    """Re-spell every bucket reduction with ``strategy`` (attr-only —
    no op is added, removed, or moved; the contract pins exactly
    that). Returns the number of ops re-spelled. Idempotent in effect:
    re-applying the same strategy is a no-op version bump."""
    if strategy not in REDUCTION_STRATEGIES:
        raise ValueError("unknown reduction strategy %r (want one of %s)"
                         % (strategy, ", ".join(REDUCTION_STRATEGIES)))
    block = program.global_block()
    n = 0
    changed = False
    for op in block.ops:
        if op.type not in BUCKET_OP_TYPES:
            continue
        if op.attrs.get("strategy", "ring") != strategy:
            op.attrs["strategy"] = strategy
            changed = True
        n += 1
    if changed:
        _bump_version(program)
    return n


# ---------------------------------------------------------------------------
# per-bucket quantization + EQuARX error-feedback residuals
# ---------------------------------------------------------------------------


def _bucket_numel(block, scope, op) -> Optional[int]:
    from .collectives import _numel_and_dtype

    total = 0
    for n in op.input("X"):
        k, _dt = _numel_and_dtype(block, scope, n)
        if k is None:
            return None
        total += k
    return total


@checked_rewrite("bucket_quant")
def configure_bucket_quant(program, scope, nranks: int, axis: str,
                           modes=None, error_feedback: bool = False,
                           materialize: bool = True) -> int:
    """Reconfigure quantization on the program's bucket ops.

    ``modes``: None keeps each op's baked-in quant; a string applies
    uniformly; a sequence applies per bucket op in program order
    (shorter sequences leave the tail untouched — the search emits one
    entry per bucket). With ``error_feedback`` every bucket left
    quantized gets a Residual/ResidualOut pair bound to a fresh
    persistable var of ``nranks * bucket_numel`` zeros, sharded over
    ``axis`` — each replica owns its rounding-error shard.
    ``materialize=False`` skips writing the zero arrays into the scope
    (the placement search rewrites candidates SYMBOLICALLY — a
    resnet-scale residual per candidate would allocate hundreds of MB
    nobody ever runs; the engine's first-run path materializes).
    Returns the number of ops reconfigured or wired."""
    from ..ops.collective_ops import QUANT_WIRE_ITEMSIZE

    block = program.global_block()
    bucket_ops = [op for op in block.ops if op.type in BUCKET_OP_TYPES]
    if not bucket_ops:
        return 0
    if isinstance(modes, str):
        modes = [modes] * len(bucket_ops)
    touched = 0
    for i, op in enumerate(bucket_ops):
        if modes is not None and i < len(modes) and modes[i] is not None:
            mode = modes[i]
            if mode not in QUANT_WIRE_ITEMSIZE:
                raise ValueError("bucket %d: unknown quant mode %r"
                                 % (i, mode))
            if op.attrs.get("quant", "none") != mode:
                op.attrs["quant"] = mode
                touched += 1
        quant = op.attrs.get("quant", "none")
        has_res = bool(op.input("Residual"))
        if error_feedback and quant != "none" and not has_res:
            total = _bucket_numel(block, scope, op)
            if total is None:
                continue  # unknown payload: leave unwired, stay exact
            dtype = "float32"
            v = block._find_var_recursive(op.input("X")[0])
            if v is not None and v.dtype:
                dtype = str(v.dtype)
            rname = "bucket_ar_residual_%d" % op._id
            rv = block.create_var(name=rname,
                                  shape=(int(nranks) * int(total),),
                                  dtype=dtype, persistable=True)
            rv.stop_gradient = True
            if materialize and scope is not None:
                scope.var(rname).get_tensor()._array = np.zeros(
                    int(nranks) * int(total), dtype=np.dtype(dtype))
            specs = getattr(program, "_var_shard_specs", None)
            if specs is None:
                specs = {}
                program._var_shard_specs = specs
            specs[rname] = (axis,)
            op.inputs["Residual"] = [rname]
            op.outputs["ResidualOut"] = [rname]
            touched += 1
    if touched:
        _bump_version(program)
    return touched


# ---------------------------------------------------------------------------
# async start/await scheduling
# ---------------------------------------------------------------------------


def _measured_slack_ok(report, compute_pos, anchor_idx) -> bool:
    """With a report: does measured backward compute remain after this
    bucket's availability point? A tail bucket (budget 0) stays fused."""
    if report is None:
        return True
    segs = [s for s in (report.get("backward_segments") or [])
            if isinstance(s, (list, tuple)) and len(s) == 3]
    if not segs:
        return True
    pos = compute_pos[anchor_idx]
    return any(float(ms) > 0 and end > pos for _s, end, ms in segs)


@checked_rewrite("async_collective")
def schedule_async_collectives(program, report=None, scope=None) -> int:
    """Split each ``c_bucket_allreduce`` into a start/await pair: the
    start stays at the bucket's availability anchor, the await lands
    just before the earliest consumer of any member grad — maximal
    scheduled overlap under the consumer barrier. Buckets with no room
    (first consumer immediately follows, or the report says zero
    hideable budget at the anchor) stay fused. Returns the number of
    buckets split; the decision record lands on
    ``program._async_schedule``."""
    if getattr(program, "_async_scheduled", False):
        return 0
    program._async_scheduled = True
    from .. import framework
    from .collectives import _numel_and_dtype

    block = program.global_block()
    ops = block.ops
    cand = [i for i, op in enumerate(ops)
            if op.type == "c_bucket_allreduce"]
    if not cand:
        program._async_schedule = {"split": 0, "kept": 0}
        return 0

    # every later TOUCH bounds the await: a reader before the await
    # would see the unreduced value, and an op that WRITES a member
    # between the pair would be clobbered by the await's write-back of
    # the (stale-input) reduction
    consumed_at: Dict[str, List[int]] = {}
    for j, op in enumerate(ops):
        for nm in set(op.input_arg_names) | set(op.output_arg_names):
            consumed_at.setdefault(nm, []).append(j)
    # compute-sequence positions (the report's coordinate system)
    compute_pos = []
    k = 0
    for op in ops:
        compute_pos.append(k)
        if not op.type.startswith("c_"):
            k += 1
    if report is not None and int(report.get("n_compute") or -1) != k:
        report = None  # stale report: split on structure alone

    import bisect

    split = 0
    kept = 0
    replace_at: Dict[int, object] = {}   # bucket idx -> start op
    before: Dict[int, List] = {}         # op idx -> [await ops]
    tail: List = []                      # awaits with no consumer
    for i in cand:
        op = ops[i]
        members = op.input("X")
        first_use = len(ops)
        for g in members:
            c = consumed_at.get(g, ())
            kk = bisect.bisect_right(c, i)
            if kk < len(c):
                first_use = min(first_use, c[kk])
        total = 0
        dtype = None
        unknown = False
        for g in members:
            n_el, dt = _numel_and_dtype(block, scope, g)
            if n_el is None:
                unknown = True
                break
            total += n_el
            dtype = dtype or dt
        if (unknown or first_use <= i + 1
                or not _measured_slack_ok(report, compute_pos, i)):
            kept += 1
            continue
        pname = "bucket_ar_pending_%d" % op._id
        pv = block.create_var(name=pname, shape=(int(total),),
                              dtype=dtype or "float32")
        pv.stop_gradient = True
        attrs = {"ring_id": op.attrs.get("ring_id", 0),
                 "quant": op.attrs.get("quant", "none"),
                 "strategy": op.attrs.get("strategy", "ring"),
                 "use_calc_stream": True}
        s_in = {"X": list(members)}
        s_out = {"Pending": [pname]}
        if op.input("Residual"):
            s_in["Residual"] = list(op.input("Residual"))
            s_out["ResidualOut"] = list(op.output("ResidualOut"))
        start = framework.Operator(block, "c_bucket_allreduce_start",
                                   s_in, s_out, attrs)
        start._id = program._next_op_id()
        await_op = framework.Operator(
            block, "c_bucket_allreduce_await",
            {"Pending": [pname], "X": list(members)},
            {"Out": list(members)},
            {"ring_id": op.attrs.get("ring_id", 0),
             "use_calc_stream": True})
        await_op._id = program._next_op_id()
        replace_at[i] = start
        if first_use < len(ops):
            before.setdefault(first_use, []).append(await_op)
        else:
            tail.append(await_op)
        split += 1

    if split:
        new_ops = []
        for i, op in enumerate(ops):
            new_ops.extend(before.get(i, ()))
            new_ops.append(replace_at.get(i, op))
        new_ops.extend(tail)
        block.ops = new_ops
        _bump_version(program)
    program._async_schedule = {"split": split, "kept": kept}
    return split
