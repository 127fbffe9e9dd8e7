"""Fast collective path: bucketed / quantized allreduce + cross-replica
sharded weight update (program rewrites over the transpiled IR).

Two PAPERS.md blueprints, applied as passes after
``transpiler.insert_allreduce_ops``:

- **Bucketed gradient allreduce** (``bucket_allreduce_ops``): N per-grad
  ``c_allreduce_sum`` ops coalesce into few ``c_bucket_allreduce`` ops
  (one flat psum each). Buckets are assembled in grad *availability*
  order — the order backward produces them — and each bucket op is
  hoisted to just after the last op that touches any of its grads, so
  early buckets reduce while later backward compute still runs (XLA
  overlaps the independent collective), and a size cap
  (``PADDLE_TPU_BUCKET_MB``) keeps buckets pipelined instead of one
  giant end-of-step psum. Bit-for-bit: psum is elementwise over
  replicas, so concat-then-psum == psum-then-concat.

- **Quantized allreduce** (EQuARX): opt-in via
  ``PADDLE_TPU_QUANT_ALLREDUCE=bf16|int8`` — the bucket payload crosses
  the wire compressed (per-bucket scale for int8; see
  ``ops.collective_ops.quantized_psum``). Off by default; gated by the
  measured-error + mlp-convergence tests in tests/test_collectives.py.

- **Cross-replica sharded weight update**
  (``apply_sharded_weight_update``): each optimizer instance's per-param
  (allreduce, update) pairs collapse into ONE ``c_sharded_update`` op —
  one flat grad psum, each replica updates its 1/n shard of the flat
  param/optimizer state, one allgather of updated param shards.
  Optimizer state lives in flat vars sharded over the data axis (a
  shard spec the engine's shard_map honors), so each replica holds 1/n
  of the moments — the paper's memory/compute win. Opt-in via
  ``PADDLE_TPU_SHARDED_UPDATE=1`` or
  ``BuildStrategy.fuse_all_optimizer_ops``.

- **Profile-guided bucket planning** (``plan_buckets_profile``,
  ``PADDLE_TPU_BUCKET_PLAN=profile``): bucket boundaries chosen from a
  saved step-profile report (``PADDLE_TPU_BUCKET_PROFILE`` names the
  json — a bench record, its ``profile`` block, or a raw
  ``profiler.profile_step`` dict) instead of the byte cap: a cost
  model fitted to the measured per-bucket costs prices every candidate
  bucket against the measured backward compute remaining after its
  availability point, so buckets close exactly where the measurement
  says further coalescing would expose wire time (DynaFlow-style
  scheduling from measured operator timing, PAPERS.md). Bit-for-bit
  like any bucketing; a missing/stale report falls back to the size
  plan (``parallel.bucket_plan{mode=}`` records which ran).

Knob summary (read once per program, at first mesh run):

==============================  ============================================
``PADDLE_TPU_BUCKET_MB``        bucket cap in MB (default 4; ``0`` disables
                                bucketing). ``BuildStrategy.
                                fuse_all_reduce_ops=False`` also disables.
``PADDLE_TPU_QUANT_ALLREDUCE``  ``bf16`` | ``int8`` (default off/exact)
``PADDLE_TPU_SHARDED_UPDATE``   ``1`` enables, ``0`` forces off (overrides
                                the BuildStrategy knob either way)
``PADDLE_TPU_BUCKET_PLAN``      ``size`` (default) | ``profile``
``PADDLE_TPU_BUCKET_PROFILE``   path to the saved profile report the
                                ``profile`` plan consumes
==============================  ============================================
"""
from __future__ import annotations

import hashlib
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..analysis.contracts import checked_rewrite
from ..ops.collective_ops import QUANT_WIRE_ITEMSIZE, SHARDED_UPDATE_SLOTS
from .transpiler import _bump_version, _merge_data_axes

DEFAULT_BUCKET_MB = 4.0

# profile-guided planner: stay safely under the measured hide budget —
# a bucket predicted to cost more than this fraction of the backward
# compute remaining after its anchor is closed early instead
PROFILE_PLAN_BUDGET_FRAC = 0.5

# optimizer ops whose update math is elementwise in (param, grad, state)
# — the precondition for flat-shard updates being bit-for-bit with the
# per-param path. lars/lamb (param-norm terms) and friends stay on the
# per-param path. SHARDED_UPDATE_SLOTS also names each op's accumulator
# input slots, folded into the flat sharded state vars.
_SHARDABLE_OPTIMIZERS = frozenset(SHARDED_UPDATE_SLOTS)


def bucket_mb(build_strategy=None) -> float:
    if build_strategy is not None and not getattr(
            build_strategy, "fuse_all_reduce_ops", True):
        return 0.0
    raw = os.environ.get("PADDLE_TPU_BUCKET_MB", "").strip()
    if not raw:
        return DEFAULT_BUCKET_MB
    try:
        return max(0.0, float(raw))
    except ValueError:
        return DEFAULT_BUCKET_MB


def quant_mode() -> str:
    raw = os.environ.get("PADDLE_TPU_QUANT_ALLREDUCE", "").strip().lower()
    if raw in ("", "0", "none", "off", "false"):
        return "none"
    if raw not in QUANT_WIRE_ITEMSIZE:
        raise ValueError(
            "PADDLE_TPU_QUANT_ALLREDUCE=%r (want bf16 or int8)" % raw)
    return raw


def bucket_plan_mode() -> str:
    """``PADDLE_TPU_BUCKET_PLAN``: ``size`` (default — the static
    byte-cap greedy plan) or ``profile`` (measurement-driven: bucket
    boundaries chosen against a saved ``profile_step`` report named by
    ``PADDLE_TPU_BUCKET_PROFILE``)."""
    raw = os.environ.get("PADDLE_TPU_BUCKET_PLAN", "").strip().lower()
    if raw in ("", "size", "static"):
        return "size"
    if raw == "profile":
        return "profile"
    raise ValueError(
        "PADDLE_TPU_BUCKET_PLAN=%r (want size or profile)" % raw)


def load_profile_report(path: Optional[str] = None) -> Optional[Dict]:
    """The saved step-profile report a profile-guided plan consumes:
    a ``profiler.profile_step`` dict (or a bench record / ``profile``
    block wrapping one) with ``per_bucket`` (measured per-bucket cost
    vs bytes) and ``backward_segments`` (measured backward time per
    compute-position range). None when the path is unset/unreadable or
    the document lacks the required fields — callers fall back to the
    size plan, never crash the step. (Thin wrapper over the shared
    ``observability.steering.load_report`` loader every report
    consumer now goes through.)"""
    from ..observability import steering

    return steering.load_report(path)


def sharded_update_enabled(build_strategy=None) -> bool:
    raw = os.environ.get("PADDLE_TPU_SHARDED_UPDATE", "").strip()
    if raw:
        return raw.lower() in ("1", "true", "yes", "on")
    return bool(build_strategy is not None and getattr(
        build_strategy, "fuse_all_optimizer_ops", False))


def _lookup_value(store, name):
    """Live value of ``name`` from either a Scope or a plain state
    mapping (engine's scope-state dict); None when absent."""
    if store is None or not name:
        return None
    find = getattr(store, "find_var", None)
    if find is None:
        return store.get(name)
    var = find(name)
    if var is not None and var.is_initialized():
        return var.raw().array
    return None


def _numel_and_dtype(block, store, name) -> Tuple[Optional[int], str]:
    """Element count + dtype of a var, best effort: block var shape,
    else its live value (Scope or state mapping), else the replicated
    param a grad mirrors. The ONE size resolver behind both the bucket
    planner's byte accounting and engine._var_nbytes — the two must
    agree for the bucketing/quantization counters to be coherent."""
    from ..core.lod_lowering import _grad_base

    v = block._find_var_recursive(name)
    shape = getattr(v, "shape", None) if v is not None else None
    dtype = str(getattr(v, "dtype", None) or "float32")
    if shape and all(isinstance(s, int) and s > 0 for s in shape):
        return int(np.prod(shape)), dtype
    arr = _lookup_value(store, name)
    if arr is not None:
        return int(getattr(arr, "size", 0)), str(arr.dtype)
    base = _grad_base(name)
    if base:
        bv = block._find_var_recursive(base)
        bshape = getattr(bv, "shape", None) if bv is not None else None
        if bshape and all(isinstance(s, int) and s > 0 for s in bshape):
            return (int(np.prod(bshape)),
                    str(getattr(bv, "dtype", None) or "float32"))
        arr = _lookup_value(store, base)
        if arr is not None:
            return int(getattr(arr, "size", 0)), str(arr.dtype)
    return None, dtype


def maybe_rewrite_collectives(program, scope, nranks: int, data_axes,
                              build_strategy=None, multiproc=False) -> None:
    """Engine entry point: apply the sharded-update pass (opt-in), then
    bucket whatever per-grad allreduces remain, then the placement-era
    schedule shaping (reduction-strategy spelling, per-bucket quant +
    error feedback, async start/await — parallel/scheduling.py). All
    passes are idempotent per program (same contract as
    insert_allreduce_ops); the knobs are read at the program's FIRST
    mesh run and baked in. With ``PADDLE_TPU_PLACEMENT_PLAN`` set, a
    searched placement artifact (paddle_tpu/placement) OVERRIDES the
    hand knobs wholesale — the plan names the same decisions the env
    vars do, chosen by the verifier-gated search instead of an
    operator."""
    if nranks <= 1 or not data_axes:
        return
    from ..placement.plan import active_plan

    pplan = active_plan()
    if pplan is not None and not pplan.matches(nranks, data_axes):
        from .. import observability as _obs

        _obs.inc("placement.plan_skipped", reason="mesh_mismatch")
        pplan = None
    if pplan is not None and pplan.sharded_update \
            and (len(data_axes) != 1 or multiproc):
        # the plan's fused sharded update cannot run on this topology
        # — skip the plan WHOLESALE (never apply its bucket/strategy
        # half while silently dropping the update it was priced with)
        from .. import observability as _obs

        _obs.inc("placement.plan_skipped", reason="unsupported_topology")
        pplan = None
    quant = pplan.quant_mode if pplan is not None else quant_mode()
    use_sharded = (pplan.sharded_update if pplan is not None
                   else sharded_update_enabled(build_strategy))
    if use_sharded and len(data_axes) == 1 and not multiproc:
        apply_sharded_weight_update(program, scope, nranks,
                                    axis=data_axes[0], quant=quant)
    resync_sharded_state(program, scope)
    if pplan is not None:
        mb, plan, report = (pplan.bucket_mb, pplan.bucket_plan_mode,
                            pplan.report)
    else:
        mb = bucket_mb(build_strategy)
        plan = bucket_plan_mode()
        report = load_profile_report() if plan == "profile" else None
    if mb > 0:
        bucket_allreduce_ops(program, bucket_bytes=int(mb * (1 << 20)),
                             quant=quant, scope=scope, plan=plan,
                             report=report)
    elif quant != "none":
        # quantization without bucketing: rewrite per-grad allreduces
        # into single-member bucket ops so the payload still compresses
        bucket_allreduce_ops(program, bucket_bytes=0, quant=quant,
                             scope=scope)
    if getattr(program, "_placement_shaped", False):
        return  # shaping already baked in (steady-state: one getattr)
    program._placement_shaped = True
    from .scheduling import (async_collectives_enabled,
                             configure_bucket_quant,
                             quant_error_feedback, reduce_strategy_mode,
                             schedule_async_collectives,
                             swap_reduction_strategy)

    strategy = pplan.strategy if pplan is not None \
        else reduce_strategy_mode()
    if strategy != "ring":
        swap_reduction_strategy(program, strategy)
    ef = pplan.error_feedback if pplan is not None \
        else quant_error_feedback()
    qmodes = pplan.quant_buckets if pplan is not None else None
    if ef or qmodes:
        configure_bucket_quant(program, scope, nranks, data_axes[0],
                               modes=qmodes, error_feedback=ef)
    do_async = pplan.async_collectives if pplan is not None \
        else async_collectives_enabled()
    if do_async:
        schedule_async_collectives(program, report=report, scope=scope)
    if pplan is not None:
        program._placement_plan = pplan.summary()


# -- bucketed allreduce -----------------------------------------------------


def _pergrad_allreduce_indices(ops) -> List[int]:
    out = []
    for i, op in enumerate(ops):
        if op.type != "c_allreduce_sum":
            continue
        x, o = op.input("X"), op.output("Out")
        if len(x) == 1 and x == o:
            out.append(i)
    return out


def plan_buckets(items, bucket_bytes: int):
    """Greedy size-capped bucketing in availability order.

    ``items``: [(anchor, first_consumer, key, nbytes, idx)] sorted by
    anchor (the last op index that touches the grad before its
    allreduce — i.e. when the grad becomes available). A bucket closes
    when adding a member would blow the byte cap, change the (ring,
    dtype) key, or push the bucket's insertion point (max anchor + 1)
    past any member's first consumer. Returns a list of buckets, each
    {"members": [idx...], "anchor": int, "key": key}."""
    buckets: List[Dict] = []
    open_by_key: Dict = {}
    for anchor, first_use, key, nbytes, idx in sorted(items):
        b = open_by_key.get(key)
        if b is not None:
            new_anchor = max(b["anchor"], anchor)
            fits = (bucket_bytes > 0
                    and b["bytes"] + nbytes <= bucket_bytes)
            ordered = (new_anchor + 1 <= min(b["min_use"], first_use))
            if not (fits and ordered):
                b = None
        if b is None:
            b = {"members": [], "bytes": 0, "anchor": -1,
                 "min_use": first_use, "key": key}
            buckets.append(b)
            open_by_key[key] = b
        b["members"].append(idx)
        b["bytes"] += nbytes
        b["anchor"] = max(b["anchor"], anchor)
        b["min_use"] = min(b["min_use"], first_use)
    return buckets


def _fit_cost_model(report) -> Optional[Tuple[float, float]]:
    """(intercept_ms, ms_per_byte) fitted to the report's measured
    per-bucket collective costs — the cost model the profile-guided
    planner prices candidate buckets with. With one measured point the
    per-op latency and the bandwidth term cannot be separated; a small
    fixed floor (10% of the measured cost) stands in for the latency so
    the planner never treats splitting as free and shatters the plan
    back to per-grad."""
    pts = [(float(b.get("bytes") or 0), float(b.get("collective_ms") or 0))
           for b in report.get("per_bucket") or []
           if (b.get("collective_ms") or 0) > 0
           and (b.get("bytes") or 0) > 0]
    if not pts:
        return None
    xs = [p[0] for p in pts]
    ys = [p[1] for p in pts]
    if len(set(xs)) >= 2:
        n = float(len(pts))
        mx = sum(xs) / n
        my = sum(ys) / n
        var = sum((x - mx) ** 2 for x in xs)
        slope = sum((x - mx) * (y - my) for x, y in pts) / var
        icept = my - slope * mx
        if slope <= 0:   # degenerate fit (noise-dominated): fall back
            slope = my / mx if mx else 0.0
            icept = 0.0
        return max(0.0, icept), max(0.0, slope)
    icept = 0.1 * ys[0]
    slope = max(0.0, ys[0] - icept) / xs[0] if xs[0] else 0.0
    return icept, slope   # model reproduces the measured point


def plan_buckets_profile(items, report, bucket_bytes: int,
                         compute_pos) -> Optional[List[Dict]]:
    """Measurement-driven bucketing (DynaFlow-style: scheduling from
    measured operator timing, PAPERS.md).

    ``items`` is the same ``(anchor, first_use, key, nbytes, idx)``
    list ``plan_buckets`` takes; ``report`` a saved ``profile_step``
    report; ``compute_pos(op_index)`` maps an anchor to its position in
    the collective-free op sequence (the coordinate system the
    report's ``backward_segments`` measure — identical under any
    bucket plan, since only collective ops move).

    The rule the measurement drives: a bucket's predicted serial cost
    (fitted ``a + b*bytes`` model) must stay under
    ``PROFILE_PLAN_BUDGET_FRAC`` of the measured backward compute
    remaining after its availability point — the report's
    ``max_hideable_frac`` budget. Growing a bucket both raises its
    cost and (by dragging the anchor later) shrinks its budget, so
    buckets close exactly where the measurement says further
    coalescing would expose wire time; grads whose own budget is
    already ~zero (produced at the very end of backward — nothing left
    to hide behind) merge into one tail bucket per key, minimizing op
    count where overlap is impossible. The byte cap and the
    first-consumer ordering constraint still bind. Returns None when
    the report carries no usable cost model (caller falls back to the
    size plan)."""
    model = _fit_cost_model(report)
    segs = [s for s in (report.get("backward_segments") or [])
            if isinstance(s, (list, tuple)) and len(s) == 3]
    if model is None or not segs:
        return None
    icept, slope = model

    def cost(nbytes):
        return icept + slope * nbytes

    def hide(pos):
        return sum(float(ms) for _s, e, ms in segs if e > pos)

    frac = PROFILE_PLAN_BUDGET_FRAC
    buckets: List[Dict] = []
    open_by_key: Dict = {}
    tail_by_key: Dict = {}
    for anchor, first_use, key, nbytes, idx in sorted(items):
        pos = compute_pos(anchor)
        budget = hide(pos)
        hideable = budget > 0.0 and cost(nbytes) - icept < budget
        store = open_by_key if hideable else tail_by_key
        b = store.get(key)
        if b is not None:
            new_anchor = max(b["anchor"], anchor)
            # same cap contract as plan_buckets: bucket_bytes <= 0
            # means one bucket per grad (nothing ever coalesces)
            fits_cap = (bucket_bytes > 0
                        and b["bytes"] + nbytes <= bucket_bytes)
            ordered = (new_anchor + 1 <= min(b["min_use"], first_use))
            fits_budget = (not hideable) or (
                cost(b["bytes"] + nbytes)
                <= frac * hide(compute_pos(new_anchor)))
            if not (fits_cap and ordered and fits_budget):
                b = None
        if b is None:
            b = {"members": [], "bytes": 0, "anchor": -1,
                 "min_use": first_use, "key": key}
            buckets.append(b)
            store[key] = b
        b["members"].append(idx)
        b["bytes"] += nbytes
        b["anchor"] = max(b["anchor"], anchor)
        b["min_use"] = min(b["min_use"], first_use)
    return buckets


@checked_rewrite("bucket_allreduce")
def bucket_allreduce_ops(program, bucket_bytes: int = 4 << 20,
                         quant: str = "none", scope=None,
                         plan: str = "size", report=None) -> int:
    """Coalesce per-grad ``c_allreduce_sum`` ops into
    ``c_bucket_allreduce`` ops (one flat psum per bucket), hoisted to
    each bucket's availability point. Returns the number of bucket ops
    emitted (0 = nothing to do). ``bucket_bytes <= 0`` means "one
    bucket per grad" — used to apply quantization without coalescing.
    ``plan="profile"`` with a loaded ``report`` switches the boundary
    choice to ``plan_buckets_profile`` (falling back to the size plan
    when the report doesn't fit this program)."""
    if getattr(program, "_allreduce_bucketed", False):
        return 0
    program._allreduce_bucketed = True
    from .. import framework

    block = program.global_block()
    ops = block.ops
    cand = _pergrad_allreduce_indices(ops)
    if not cand or (len(cand) <= 1 and quant == "none"):
        return 0

    # one pass over the program: per-var sorted op-index lists, so each
    # candidate's anchor (last non-candidate toucher before it) and
    # first consumer resolve by bisection instead of an O(ops) rescan
    # per grad
    import bisect

    cand_set = set(cand)
    touched_at: Dict[str, List[int]] = {}
    consumed_at: Dict[str, List[int]] = {}
    for j, op in enumerate(ops):
        ins = op.input_arg_names
        for nm in ins:
            consumed_at.setdefault(nm, []).append(j)
        if j not in cand_set:
            for nm in set(ins) | set(op.output_arg_names):
                touched_at.setdefault(nm, []).append(j)

    items = []
    for i in cand:
        g = ops[i].input("X")[0]
        t = touched_at.get(g, ())
        k = bisect.bisect_left(t, i)
        last = t[k - 1] if k else -1
        c = consumed_at.get(g, ())
        k = bisect.bisect_right(c, i)
        use = c[k] if k < len(c) else len(ops)
        n, dtype = _numel_and_dtype(block, scope, g)
        if n is None:
            continue  # unknown payload: leave its per-grad op alone
        try:
            itemsize = np.dtype(dtype).itemsize if dtype else 4
        except TypeError:  # same tolerance as engine._var_nbytes
            itemsize = 4
        items.append((last, use, (ops[i].attrs.get("ring_id", 0), dtype),
                      n * itemsize, i))
    if not items:
        return 0

    mode_used = "size"
    buckets = None
    if plan == "profile" and report is not None:
        # positions in the collective-free op sequence — the report's
        # coordinate system; a report from a different program shape
        # (stale file, wrong model) is detected and ignored
        cpos = []
        k = 0
        for op in ops:
            cpos.append(k)
            if not op.type.startswith("c_"):
                k += 1
        if int(report.get("n_compute") or -1) == k:
            def compute_pos(anchor):
                if anchor < 0:
                    return 0
                p = cpos[anchor]
                return p + (0 if ops[anchor].type.startswith("c_") else 1)

            buckets = plan_buckets_profile(items, report, bucket_bytes,
                                           compute_pos)
            if buckets is not None:
                mode_used = "profile"
    if buckets is None:
        buckets = plan_buckets(items, bucket_bytes)
    from .. import observability as _obs

    _obs.inc("parallel.bucket_plan", mode=mode_used)
    program._bucket_plan = {
        "requested": plan, "mode": mode_used,
        "n_buckets": len(buckets),
        "bucket_bytes": [b["bytes"] for b in buckets],
        "anchors": [b["anchor"] for b in buckets],
    }
    removed = set()
    # bucket ops to splice in right AFTER the op at index `anchor`
    # (anchor -1 = before everything)
    after: Dict[int, List] = {}
    for b in buckets:
        names = [ops[i].input("X")[0] for i in b["members"]]
        rid = b["key"][0]
        ar = framework.Operator(
            block, "c_bucket_allreduce", {"X": names}, {"Out": names},
            {"ring_id": rid, "quant": quant, "use_calc_stream": True})
        ar._id = program._next_op_id()
        removed.update(b["members"])
        after.setdefault(b["anchor"], []).append(ar)

    new_ops = list(after.get(-1, []))
    for i, op in enumerate(ops):
        if i not in removed:
            new_ops.append(op)
        new_ops.extend(after.get(i, ()))
    block.ops = new_ops
    _bump_version(program)
    return len(buckets)


# -- cross-replica sharded weight update ------------------------------------


def _attrs_sig(attrs) -> Tuple:
    return tuple(sorted((k, repr(v)) for k, v in attrs.items()
                        if not k.startswith("_")))


def _splice_flat_state(block, scope, state_names, total, padded, dtype,
                       slot):
    """Concatenate the per-param accumulators named in ``state_names``
    (zeros where uninitialized) into one zero-padded flat array."""
    parts = []
    for sn in state_names:
        var = scope.find_var(sn)
        if var is not None and var.is_initialized():
            parts.append(np.asarray(var.raw().array).ravel())
        else:
            sv = block.var(sn)
            parts.append(np.zeros(int(np.prod(sv.shape)),
                                  dtype=np.dtype(dtype)))
    flat = np.concatenate(parts) if parts else np.zeros(0, np.dtype(dtype))
    if flat.size != total:
        raise ValueError(
            "sharded update: state %r totals %d elements, "
            "params total %d" % (slot, flat.size, total))
    return np.concatenate([flat, np.zeros(padded - total, flat.dtype)])


def _src_token(scope, name):
    """The var's current scope value OBJECT (None when absent or
    uninitialized): training never touches the retired per-param
    state vars, so a different object means something outside the
    mesh step — a startup re-run — re-initialized the var. The token
    holds the array itself (not its id), keeping it alive so a later
    allocation can never alias a freed array's address."""
    var = scope.find_var(name)
    if var is None or not var.is_initialized():
        return None
    return var.raw().array


def resync_sharded_state(program, scope) -> int:
    """Re-running the STARTUP program resets the retired per-param
    optimizer state vars but cannot see the flat ``sharded_update_*``
    vars it never knew about — a restarted job would silently keep its
    trained moments. Detect the restart (EVERY source var's array
    object replaced since the splice; a partial change is left alone —
    per-param values are stale by design after training) and rebuild
    the flat state from the freshly-initialized per-param values.
    Returns the number of flat vars rebuilt."""
    layout = getattr(program, "_sharded_flat_layout", None)
    if not layout:
        return 0
    tokens = program._sharded_src_tokens
    block = program.global_block()
    n = 0
    for flat_name, (srcs, total, padded, dtype, slot) in layout.items():
        cur = tuple(_src_token(scope, sn) for sn in srcs)
        old = tokens[flat_name]
        # vars uninitialized both then and now carry no signal either
        # way; every var WITH a signal must have been replaced
        signal = [(o, c) for o, c in zip(old, cur)
                  if o is not None or c is not None]
        if not signal or any(o is c for o, c in signal):
            continue
        scope.var(flat_name).get_tensor()._array = _splice_flat_state(
            block, scope, srcs, total, padded, dtype, slot)
        tokens[flat_name] = cur
        n += 1
    return n


@checked_rewrite("sharded_update")
def apply_sharded_weight_update(program, scope, nranks: int,
                                axis: str = "dp",
                                quant: str = "none") -> int:
    """Rewrite each (supported) optimizer instance's per-param
    (c_allreduce_sum, update-op) pairs into ONE ``c_sharded_update``
    op, and re-layout its optimizer state into flat vars sharded over
    ``axis`` (spec recorded in ``program._var_shard_specs``; existing
    scope values are spliced in flattened + zero-padded to a multiple
    of ``nranks``). Returns the number of groups rewritten.

    Grouping key: (op type, hyperparam attrs, LearningRate var, param
    dtype) — i.e. one group per optimizer instance per dtype. Params
    that are mesh-sharded (``_var_shard_specs``), use non-elementwise
    optimizers, or whose reduced grad has readers besides the update
    op keep their per-param path untouched.
    """
    prev = getattr(program, "_sharded_update_n", None)
    if prev is not None:
        if prev != nranks:
            raise ValueError(
                "program already sharded-update-rewritten for %d ranks, "
                "mesh now has %d" % (prev, nranks))
        return 0
    program._sharded_update_n = nranks
    from .. import framework

    block = program.global_block()
    ops = block.ops
    shard_specs = getattr(program, "_var_shard_specs", None) or {}
    cand = set(_pergrad_allreduce_indices(ops))
    grad_ar: Dict[str, int] = {ops[i].input("X")[0]: i for i in cand}
    consumed_at: Dict[str, List[int]] = {}
    for j, op in enumerate(ops):
        for nm in op.input_arg_names:
            consumed_at.setdefault(nm, []).append(j)
    groups: Dict[Tuple, List[int]] = {}
    for i, op in enumerate(ops):
        if op.type not in _SHARDABLE_OPTIMIZERS:
            continue
        p = op.input("Param")[0]
        pv = block._find_var_recursive(p)
        if (p in shard_specs or pv is None or not pv.shape
                or not all(isinstance(s, int) and s > 0 for s in pv.shape)
                or getattr(pv, "type", "lod_tensor") != "lod_tensor"):
            continue
        g = op.input("Grad")[0]
        gv = block._find_var_recursive(g)
        if gv is not None and getattr(gv, "type", "") == "selected_rows":
            continue  # sparse grads keep the row-wise per-param kernel
        ai = grad_ar.get(g)
        if ai is not None and any(j > ai and j != i
                                  for j in consumed_at.get(g, ())):
            # some other op reads the REDUCED grad after its allreduce
            # (grad clipping, a fetch op, ...); collapsing this pair
            # would delete the in-place reduction that reader relies
            # on — keep the param on the per-grad path
            continue
        key = (op.type, _attrs_sig(op.attrs),
               op.input("LearningRate")[0], str(pv.dtype))
        groups.setdefault(key, []).append(i)

    if not groups:
        return 0
    removed = set()
    # new group op spliced in at the position of the group's FIRST
    # optimizer op
    replace_at: Dict[int, object] = {}
    n_groups = 0
    for key, idxs in sorted(groups.items(), key=lambda kv: kv[1][0]):
        op_type, _, lr_name, dtype = key
        member_ops = [ops[i] for i in idxs]
        params = [op.input("Param")[0] for op in member_ops]
        grads = [op.input("Grad")[0] for op in member_ops]
        sizes = [int(np.prod(block.var(p).shape)) for p in params]
        total = sum(sizes)
        shard = -(-total // nranks)
        padded = shard * nranks
        n_groups += 1
        # content-derived name: scope vars are process-global, and a
        # per-program group counter would collide when two programs
        # with sharded updates share one Scope (e.g. a GAN's two
        # optimizers) — the digest of (op type, member params) keeps
        # distinct groups distinct and is stable across rebuilds
        sig = hashlib.sha1(("%s|%s" % (op_type, ",".join(
            "%s:%d" % t for t in zip(params, sizes)))).encode())
        gtag = sig.hexdigest()[:8]

        inputs = {"Param": params, "Grad": grads, "LearningRate": [lr_name]}
        outputs = {"ParamOut": params}
        for slot_key, slot in zip(("StateA", "StateB"),
                                  SHARDED_UPDATE_SLOTS[op_type]):
            state_names = [op.input(slot)[0] for op in member_ops]
            flat_name = "sharded_update_%s.%s" % (gtag, slot.lower())
            fv = block.create_var(name=flat_name, shape=(padded,),
                                  dtype=dtype, persistable=True)
            fv.stop_gradient = True
            # splice current accumulator values into the flat var,
            # zero-padded; retire the per-param vars (stale from here,
            # but remembered so resync_sharded_state can rebuild the
            # flat state when a startup re-run re-initializes them)
            flat = _splice_flat_state(block, scope, state_names,
                                      total, padded, dtype, slot)
            for sn in state_names:
                block.var(sn).persistable = False
            scope.var(flat_name).get_tensor()._array = flat
            for attr in ("_sharded_flat_layout", "_sharded_src_tokens"):
                if getattr(program, attr, None) is None:
                    setattr(program, attr, {})
            program._sharded_flat_layout[flat_name] = (
                tuple(state_names), total, padded, dtype, slot)
            program._sharded_src_tokens[flat_name] = tuple(
                _src_token(scope, sn) for sn in state_names)
            inputs[slot_key] = [flat_name]
            outputs[slot_key + "Out"] = [flat_name]
            specs = getattr(program, "_var_shard_specs", None)
            if specs is None:
                specs = {}
                program._var_shard_specs = specs
            specs[flat_name] = (axis,)
        for scalar in ("Beta1Pow", "Beta2Pow"):
            names = [op.input(scalar) for op in member_ops]
            if all(n for n in names):
                inputs[scalar] = [n[0] for n in names]
                outputs[scalar + "Out"] = [n[0] for n in names]

        attrs = dict(member_ops[0].attrs)
        attrs.update({"op_type": op_type, "shard_axis": axis,
                      "nranks": int(nranks), "padded_size": int(padded),
                      "quant": quant})
        su = framework.Operator(block, "c_sharded_update", inputs,
                                outputs, attrs)
        su._id = program._next_op_id()
        replace_at[idxs[0]] = su
        removed.update(idxs)
        removed.update(grad_ar[g] for g in grads if g in grad_ar)

    new_ops = []
    for i, op in enumerate(ops):
        if i in replace_at:
            new_ops.append(replace_at[i])
        if i not in removed:
            new_ops.append(op)
    block.ops = new_ops
    _merge_data_axes(program, (axis,))
    _bump_version(program)
    return n_groups


# -- steering registration ---------------------------------------------------
# The PR-10 profile-guided bucket planner, exposed through the shared
# `profile report → plan` registry (observability.steering) so every
# report consumer — this planner, the placement search, future serving
# / lazy-dygraph replanners — dispatches through ONE interface instead
# of growing private report plumbing.


def _steer_bucket_layout(report, items=None, bucket_bytes=4 << 20,
                         compute_pos=None, **_ctx):
    """``steer("bucket_layout", report, items=..., compute_pos=...)``
    → the measured bucket layout (``plan_buckets_profile``), or None
    when the report/context cannot drive a plan (callers fall back to
    the size plan)."""
    if report is None or items is None or compute_pos is None:
        return None
    return plan_buckets_profile(items, report, bucket_bytes, compute_pos)


from ..observability import steering as _steering  # noqa: E402

_steering.register_steerer(
    "bucket_layout", _steer_bucket_layout,
    "profile-guided gradient-bucket boundaries (PR 10)")
