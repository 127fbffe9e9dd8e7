"""Step profiler: phase annotation, overlap/critical-path analysis,
analytic FLOP accounting — plus the legacy ``fluid.profiler`` session
API this module absorbed from the old ``paddle_tpu/profiler.py`` shim.

The ROADMAP's top open question after PR 6 — "verify with a profile
that the bucketed collectives actually overlap backward compute" — is
unanswerable from a fused XLA step: one dispatch, one number. This
module makes step time attributable:

**Phase classification** (``classify_ops``). Every op of a transpiled
program lands in one of four phases — ``forward`` (before the first
grad-producing op), ``backward`` (``_fwd_op_id``-stamped grad ops and
everything up to the optimizer), ``collective`` (the ``c_*`` family,
each ``c_bucket_allreduce`` numbered as a bucket), ``optimizer`` (the
update ops + anything after them). The classification is positional
and name-based (``@GRAD`` outputs), mirroring the reference's op-role
attr without carrying one.

**Phase annotation**. Every trace entry point
(``core.compiler_engine._trace_ops`` — shared by the executor, the
mesh engine and the pipeline stage slices) wraps each op in
``jax.named_scope("<phase>/<op_type>")`` by ``classify_ops``, always,
so an XPlane / Perfetto device trace of any compiled step shows
phase-labeled regions. named_scope adds no equation to the jaxpr: it
costs a little at trace time and nothing per step.

**Measured phase breakdown** (``profile_step``). Host-side timing of
a compiled program by *phase-sliced re-execution*: the op list minus
its (in-place) collectives is re-jitted at cumulative cut points
(end-of-forward, each bucket's availability point — the anchors the
bucket pass already computed — end-of-backward, end-of-program), each
prefix hard-synced on a scalar folded from the segment's outputs plus
the cut's live set (so XLA cannot dead-code the work being timed).
Segment time = adjacent-prefix difference. Collective cost is
measured separately: the full program vs the collective-free program
gives the *exposed* (serialized-into-the-step) collective time, and a
per-bucket psum/allgather microbench at the bucket's exact payload
gives the *serial* collective time. From these:

    overlap_frac      = 1 - exposed / serial       (achieved overlap)
    critical_path_ms  = compute_total + exposed    (≈ fused step time)
    per bucket        : serial cost, remaining backward compute after
                        its availability point, max hideable fraction

The numbers are emitted as ``profile.phase_ms{phase=}`` histograms,
``profile.overlap_frac`` / ``profile.critical_path_ms`` gauges, and
chrome-trace rows (cat="phase") that ride the normal span pipeline
into the merged job ``trace.json``.

**Timeline analyzer** (``analyze_timeline``). The pure half: given
any span timeline (synthetic, or cut from a merged trace.json), it
reports per-bucket achieved overlap and the busy-time critical path —
the function the tests drive with constructed overlapped/serialized
cases.

**FLOP accounting** (``program_flops`` + the ``flops_*`` formulas).
Analytic per-op FLOPs from static block shapes (matmul/conv/attention
formulas; ``*_grad`` ops cost 2x their forward op — the standard
"training step = 3x forward" accounting), so ``bench.py`` computes
``mfu_est`` from the op registry for every workload instead of a
hardcoded per-model estimate. ``DEVICE_PEAKS`` is the one table of
published chip peaks the estimates are normalized against, keyed by
``device_kind``; a device that is not in it (the CPU included) gets no
``mfu_est`` rather than a number against some other chip's peak.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    # phase classification
    "classify_ops",
    # measured profiling + analysis
    "build_phase_plan", "profile_step", "analyze_timeline",
    # FLOP accounting
    "program_flops", "flops_mlp", "flops_transformer_lm",
    "DEVICE_PEAKS", "peak_flops", "mfu_est",
    # legacy fluid.profiler session API (absorbed shim)
    "RecordEvent", "record_event", "is_profiler_enabled",
    "get_trace_events", "reset_profiler", "start_profiler",
    "stop_profiler", "profiler", "cuda_profiler",
]

# optimizer update op types (ops/optimizer_ops.py registrations) — the
# boundary between the backward and optimizer phases.
OPTIMIZER_OPS = frozenset({
    "sgd", "momentum", "lars_momentum", "adam", "adamw", "adamax",
    "adagrad", "decayed_adagrad", "adadelta", "rmsprop", "ftrl", "lamb",
    "dpsgd", "dgc", "dgc_momentum", "dgc_clip_by_norm", "proximal_gd",
    "proximal_adagrad", "lookahead_update", "ema_accumulate",
    "ema_adaptive_decay", "model_average_accumulate",
})

# collectives that are safe to SKIP for the collective-free timing run:
# in-place (outputs rebind the input names) and shape-preserving, so
# the remaining program still traces — only the values differ, and a
# timing run never reads them
_SKIP_SAFE_COLLECTIVES = ("c_allreduce", "c_bucket_allreduce",
                          "c_sharded_update", "c_broadcast")


# -- phase classification ---------------------------------------------------


def classify_ops(block, ops=None) -> List[str]:
    """Phase label per op: forward | backward | collective | optimizer.

    Positional: forward until the first grad op (``_fwd_op_id`` attr or
    an ``@GRAD`` output), backward until the first optimizer op,
    optimizer after. ``c_*`` collectives are always ``collective``.
    """
    from ..core.registry import GRAD_SUFFIX

    ops = list(block.ops) if ops is None else list(ops)
    phases: List[str] = []
    seen_bwd = False
    seen_opt = False
    for op in ops:
        t = op.type
        if t.startswith("c_"):
            phases.append("collective")
            continue
        if t in OPTIMIZER_OPS:
            seen_opt = True
            phases.append("optimizer")
            continue
        if not seen_opt and ("_fwd_op_id" in op.attrs or any(
                GRAD_SUFFIX in n for n in op.output_arg_names if n)):
            seen_bwd = True
            phases.append("backward")
            continue
        phases.append("optimizer" if seen_opt
                      else ("backward" if seen_bwd else "forward"))
    return phases


# -- timeline analyzer (pure) -----------------------------------------------


def _union_length(intervals: List[Tuple[float, float]]) -> float:
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def _intersect_length(a0: float, a1: float,
                      merged: List[Tuple[float, float]]) -> float:
    got = 0.0
    for b0, b1 in merged:
        lo, hi = max(a0, b0), min(a1, b1)
        if hi > lo:
            got += hi - lo
    return got


def _merge(intervals: List[Tuple[float, float]]):
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def analyze_timeline(spans) -> Dict:
    """Overlap / critical-path analysis over a span timeline.

    ``spans``: iterable of dicts (``{"phase", "ts", "dur"[, "bucket"]}``)
    or tuples ``(phase, ts, dur[, bucket])``; times in any consistent
    unit (reported as ms). Phases ``forward|backward|optimizer`` (or
    anything else non-collective) count as compute; ``collective``
    spans are the ones whose hidden fraction is measured against the
    compute union. Returns::

        {compute_ms, collective_ms, overlapped_collective_ms,
         exposed_collective_ms, overlap_frac, critical_path_ms,
         serialized_ms, per_bucket: [{bucket, collective_ms,
                                      overlapped_ms, overlap_frac}]}

    ``critical_path_ms`` is the busy time (union of all spans) — on a
    serialized timeline it equals ``serialized_ms``; every unit of
    collective time hidden under compute shortens it by one unit.
    """
    comp: List[Tuple[float, float]] = []
    coll: List[Tuple[float, float, object]] = []
    for sp in spans:
        if isinstance(sp, dict):
            phase = sp.get("phase") or sp.get("cat") or "compute"
            ts, dur = float(sp["ts"]), float(sp["dur"])
            bucket = sp.get("bucket")
        else:
            phase, ts, dur = sp[0], float(sp[1]), float(sp[2])
            bucket = sp[3] if len(sp) > 3 else None
        if dur < 0:
            raise ValueError("span with negative duration: %r" % (sp,))
        if phase == "collective":
            coll.append((ts, ts + dur, bucket))
        else:
            comp.append((ts, ts + dur))
    merged_comp = _merge(comp)
    compute_ms = _union_length(comp)
    per_bucket = []
    coll_total = 0.0
    overlapped = 0.0
    for i, (a, b, bucket) in enumerate(coll):
        dur = b - a
        got = _intersect_length(a, b, merged_comp)
        coll_total += dur
        overlapped += got
        per_bucket.append({
            "bucket": bucket if bucket is not None else i,
            "collective_ms": dur, "overlapped_ms": got,
            "overlap_frac": (got / dur) if dur else 0.0,
        })
    busy = _union_length(comp + [(a, b) for a, b, _ in coll])
    return {
        "compute_ms": compute_ms,
        "collective_ms": coll_total,
        "overlapped_collective_ms": overlapped,
        "exposed_collective_ms": coll_total - overlapped,
        "overlap_frac": (overlapped / coll_total) if coll_total else None,
        "critical_path_ms": busy,
        "serialized_ms": compute_ms + coll_total,
        "per_bucket": per_bucket,
    }


# -- measured phase profiling ----------------------------------------------


def build_phase_plan(program, max_bucket_cuts: int = 12,
                     state=None) -> Dict:
    """Static plan for phase-sliced timing of ``program``:

    - ``phases``: per-op labels (classify_ops);
    - ``collectives``: [{index, type, bucket, bytes, numel, dtype,
      kind}] for every collective op, payloads resolved through the
      same size resolver the bucket planner uses;
    - ``cuts``: [(label, n_compute_ops)] cumulative cut points over
      the collective-free op sequence — end-of-forward, one per bucket
      availability point (capped at ``max_bucket_cuts``), end-of-
      backward, end-of-program;
    - ``skippable``: True when every collective is in-place (the
      collective-free timing run is exact).
    """
    from ..ops.collective_ops import QUANT_PSUM_ITEMSIZE
    from ..parallel.collectives import _numel_and_dtype as numel_and_dtype

    block = program.global_block()
    ops = list(block.ops)
    phases = classify_ops(block, ops)

    collectives = []
    skippable = True
    bucket_no = 0
    for i, (op, ph) in enumerate(zip(ops, phases)):
        if ph != "collective":
            continue
        if op.type == "c_bucket_allreduce_await":
            # the await half of an async pair carries no wire payload
            # (its start op is the bucket entry) and is skip-safe by
            # construction — removing the pair removes both halves
            continue
        if not any(op.type.startswith(p) for p in _SKIP_SAFE_COLLECTIVES):
            skippable = False
        if op.type == "c_sharded_update":
            padded = int(op.attrs.get("padded_size", 0))
            pname = op.input("Param")[0] if op.input("Param") else None
            _, dtype = numel_and_dtype(block, state, pname) \
                if pname else (None, "float32")
            try:
                item = np.dtype(dtype).itemsize
            except TypeError:
                item = 4
            q = QUANT_PSUM_ITEMSIZE.get(op.attrs.get("quant", "none"))
            collectives.append({
                "index": i, "type": op.type, "bucket": bucket_no,
                "numel": padded, "dtype": dtype, "kind": "sharded_update",
                # one psum (at the executed quant width) + one allgather
                "bytes": padded * (q or item) + padded * item,
                # psum-equivalent elements at the native dtype (the
                # psum dominates; int32-emulated int8 = native width)
                "bench_numel": max(1, int(padded * (q or item) / item)),
                "avail_pos": None,  # filled below
            })
            bucket_no += 1
            continue
        numel = 0
        dtype = "float32"
        is_bucket = op.type.startswith("c_bucket_allreduce")
        # bucket payload = the X members only (an error-feedback
        # Residual input is device-local state, not wire traffic)
        payload_names = op.input("X") if is_bucket \
            else op.input_arg_names
        for n in payload_names:
            if not n:
                continue
            k, dtype = numel_and_dtype(block, state, n)
            numel += k or 0
        try:
            item = np.dtype(dtype).itemsize
        except TypeError:
            item = 4
        base_item = item
        if is_bucket:
            q = QUANT_PSUM_ITEMSIZE.get(op.attrs.get("quant", "none"))
            item = q or item
        collectives.append({
            "index": i, "type": op.type, "bucket": bucket_no,
            "numel": numel, "dtype": dtype,
            # what the serial microbench should move: the EXECUTED
            # wire width (bf16 psums half the f32 bytes; int8 codes
            # psum in int32 = no change) expressed as an equivalent
            # element count at the native dtype
            "bench_numel": max(1, int(numel * item / base_item)),
            "kind": ("allreduce" if "allreduce" in op.type
                     else op.type[2:]),
            "bytes": numel * item,
            # placement-search fitter fields: which spelling and wire
            # mode this measured point belongs to
            "strategy": op.attrs.get("strategy", "ring")
            if is_bucket else "ring",
            "quant": op.attrs.get("quant", "none"),
            "avail_pos": None,  # filled below
        })
        bucket_no += 1

    # compute-only sequence + cumulative cut points
    compute_pos = []           # original index -> compute-seq index
    n_compute = 0
    for ph in phases:
        compute_pos.append(n_compute)
        if ph != "collective":
            n_compute += 1
    fwd_end = sum(1 for ph in phases if ph == "forward")
    bwd_end = sum(1 for ph in phases if ph in ("forward", "backward"))
    for c in collectives:
        # availability point: the compute prefix that must have run
        # for this bucket's payload to exist (the bucket op sits right
        # after its anchor — collectives.plan_buckets hoisted it
        # there). EVERY collective gets one, whether or not it also
        # becomes a timing cut below — the overlap report keys on the
        # position, never on cut labels
        c["avail_pos"] = min(bwd_end, compute_pos[c["index"]])
    cuts: List[Tuple[str, int]] = [("forward", fwd_end)]
    for c in collectives[:max_bucket_cuts]:
        cuts.append(("backward@bucket%d" % c["bucket"], c["avail_pos"]))
    cuts.append(("backward", bwd_end))
    cuts.append(("optimizer", n_compute))
    # dedupe while keeping order + monotonicity
    seen: Dict[int, str] = {}
    ordered = []
    for label, pos in sorted(cuts, key=lambda kv: kv[1]):
        if pos in seen or pos == 0:
            continue
        seen[pos] = label
        ordered.append((label, pos))
    return {"phases": phases, "collectives": collectives,
            "cuts": ordered, "n_compute": n_compute,
            "skippable": skippable}


def _sync_vars(prefix_ops, rest_ops, seg_ops) -> List[str]:
    """Vars a prefix timing run must fold into its sync scalar: the
    cut's live set (written by the prefix, read after it — what a real
    scheduler must have materialized by the cut) plus the outputs of
    the segment being timed (so its tail is never dead-coded)."""
    written = {n for op in prefix_ops for n in op.output_arg_names if n}
    live = set()
    for op in rest_ops:
        for n in op.input_arg_names:
            if n in written:
                live.add(n)
    seg_out = {n for op in seg_ops for n in op.output_arg_names if n}
    return sorted(live | (seg_out & written))


def _whole_sync(run_ops, persist_written) -> List[str]:
    """Sync set for a WHOLE-program timing run: every written
    persistable (param/optimizer-state updates, which the grads and
    their collectives feed) plus the tail ops' outputs — so XLA cannot
    dead-code the update chains being timed."""
    written = {n for op in run_ops for n in op.output_arg_names if n}
    return sorted((persist_written & written)
                  | set(_sync_vars(run_ops, (), run_ops[-4:])))


def _exec_inputs(program, scope, feed: Dict, mesh=None,
                 axis_name: str = "dp") -> Dict:
    """Everything a measurement runner needs to execute ``program`` the
    way its engine does: staged feed/state arrays, the mesh data axes +
    shard specs, and a ``make_fn(op_subset, sync_names)`` factory
    (``_mesh_runner_factory``). Shared by ``profile_step`` and
    ``device_trace.device_profile_step`` so the two measurements run
    the SAME execution, host-timed vs device-traced."""
    import jax.numpy as jnp

    from ..core.compiler_engine import _analyze
    from ..core.tensor import LoDTensor

    block = program.global_block()
    ops = list(block.ops)
    feed_vals = {}
    for name, value in (feed or {}).items():
        arr = value.array if isinstance(value, LoDTensor) else \
            jnp.asarray(np.asarray(value))
        feed_vals[name] = arr
    feed_names = tuple(sorted(feed_vals))

    read_first, _written, persist_written = _analyze(program)
    state = {}
    for n in sorted(read_first - set(feed_names)):
        var = scope.find_var(n)
        if var is None or not var.is_initialized():
            raise RuntimeError("var %r must be fed or initialized "
                               "before profiling" % n)
        state[n] = var.raw().array
    state_names = tuple(sorted(state))

    data_axes: Tuple[str, ...] = ()
    shard_specs: Dict = {}
    feed_specs: Dict = {}
    if mesh is not None:
        mesh_axes = set(mesh.axis_names)
        data_axes = tuple(a for a in (getattr(program, "_data_axes", None)
                                      or (axis_name,)) if a in mesh_axes)
        if not data_axes:
            data_axes = (mesh.axis_names[0],)
        shard_specs = dict(getattr(program, "_var_shard_specs", None)
                           or {})
        feed_specs = dict(getattr(program, "_feed_shard_specs", None)
                          or {})
    make_fn = _mesh_runner_factory(block, mesh, data_axes, shard_specs,
                                   feed_specs, state_names, feed_names)
    return {"block": block, "ops": ops, "state": state,
            "feed_vals": feed_vals, "feed_names": feed_names,
            "state_names": state_names, "data_axes": data_axes,
            "persist_written": persist_written, "make_fn": make_fn}


def _time_call(fn, args, repeats: int) -> float:
    import jax

    out = fn(*args)
    jax.block_until_ready(out)   # compile + first run
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _mesh_runner_factory(block, mesh, data_axes, shard_specs, feed_specs,
                         state_names, feed_names):
    """Returns make_fn(op_subset, sync_names) -> jitted callable
    (state, feeds, seed) -> scalar, executed like the dp engine
    executes the real step (same guards, same specs)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core.compiler_engine import _trace_ops
    from ..ops.collective_ops import mesh_axes_guard, ring_axis_guard
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    ring_val = (tuple(data_axes) if len(data_axes) > 1
                else (data_axes[0] if data_axes else None))
    default_feed_spec = (data_axes[0],) if data_axes else ()

    def make_fn(op_subset, sync_names):
        def step(state_d, feeds_d, seed):
            env = dict(state_d)
            env.update(feeds_d)
            with ring_axis_guard({0: ring_val, -1: ring_val}), \
                    mesh_axes_guard(mesh_axes):
                _trace_ops(block, op_subset, env, seed)
                s = jnp.float32(0.0)
                for n in sync_names:
                    v = env.get(n)
                    if v is None:
                        continue
                    try:
                        s = s + jnp.sum(jnp.asarray(v)).astype(jnp.float32)
                    except TypeError:
                        pass
                if data_axes:
                    s = jax.lax.psum(s, tuple(data_axes))
            return s

        if mesh is None:
            return jax.jit(step)
        mapped = jax.shard_map(
            step, mesh=mesh,
            in_specs=({n: P(*shard_specs.get(n, ()))
                       for n in state_names},
                      {n: P(*feed_specs.get(n, default_feed_spec))
                       for n in feed_names}, P()),
            out_specs=P(), check_vma=False)
        return jax.jit(mapped)

    return make_fn


# microbench payload cap: above this, collective time is linear in
# bytes (bandwidth-bound), so bench the cap and scale — a bert-scale
# c_sharded_update (~110M elements x 8 replicas) would otherwise
# materialize a multi-GB argument just to time one psum
_MICROBENCH_MAX_ELEMS = 4 << 20


def _bench_collective(mesh, data_axes, numel: int, dtype: str,
                      kind: str, repeats: int) -> float:
    """Serial cost of one collective at its payload: a psum (and, for
    sharded updates, an allgather of the updated shards) over the data
    axes, fed a genuinely sharded argument so XLA cannot fold the
    reduction away. Payloads above ``_MICROBENCH_MAX_ELEMS`` are timed
    at the cap and scaled linearly (bandwidth-bound regime)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    if mesh is None or not data_axes or numel <= 0:
        return 0.0
    scale = 1.0
    if numel > _MICROBENCH_MAX_ELEMS:
        scale = numel / float(_MICROBENCH_MAX_ELEMS)
        numel = _MICROBENCH_MAX_ELEMS
    axis = data_axes[0]
    n = int(np.prod([mesh.shape[a] for a in data_axes]))
    try:
        dt = jnp.dtype(dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            dt = jnp.float32
    except TypeError:
        dt = jnp.float32

    def body(x):
        r = jax.lax.psum(x, tuple(data_axes))
        if kind == "sharded_update":
            shard = r[: max(1, x.shape[0] // n)]
            # the real op updates its 1/n shard between the psum and
            # the allgather (a few elementwise passes — momentum-ish);
            # include that so the "serial" cost covers the SAME work
            # the fused op performs, and exposed-vs-serial compare
            # like with like
            shard = shard * jnp.asarray(0.999, shard.dtype) \
                + shard * shard * jnp.asarray(1e-6, shard.dtype)
            r = jax.lax.all_gather(shard, axis, tiled=True)
        return jnp.sum(r)

    # shard dim 0 over EVERY data axis: per-shard payload must equal
    # the op's numel even on a multi-data-axis (dp x sp) mesh
    mapped = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(tuple(data_axes)), out_specs=P(),
        check_vma=False))
    # per-shard payload = the op's numel (replicas each hold the full
    # flat grad); a global array sharded over the axis keeps shard
    # values distinct so the psum cannot be folded away
    arg = jnp.arange(numel * n, dtype=jnp.float32).astype(dt)
    return _time_call(mapped, (arg,), repeats) * scale


def profile_step(program, scope, feed: Dict, mesh=None,
                 axis_name: str = "dp", repeats: int = 2,
                 budget_s: Optional[float] = None,
                 max_bucket_cuts: int = 12, seed: int = 0) -> Dict:
    """Measured per-step phase breakdown + overlap report for a static
    program (single-chip when ``mesh`` is None, dp mesh otherwise).

    The program must be runnable as-is (startup executed, transpiler /
    collective rewrites already applied — i.e. profile AFTER the step
    has run once through its engine). Profiling re-executes phase
    slices; it never donates or writes back state, so the training
    state is untouched. See the module docstring for the method and
    the shape of the returned report.
    """
    import jax.numpy as jnp

    if budget_s is None:
        budget_s = float(os.environ.get("PADDLE_TPU_PROFILE_BUDGET_S",
                                        "120") or 120)
    deadline = time.monotonic() + budget_s

    ctx = _exec_inputs(program, scope, feed, mesh=mesh,
                       axis_name=axis_name)
    ops = ctx["ops"]
    state = ctx["state"]
    data_axes = ctx["data_axes"]
    make_fn = ctx["make_fn"]
    persist_written = ctx["persist_written"]

    plan = build_phase_plan(program, max_bucket_cuts=max_bucket_cuts,
                            state=state)
    seed_v = jnp.uint32(seed)
    args = (state, ctx["feed_vals"], seed_v)

    # full fused step + collective-free step (exposed-collective time),
    # both synced on the step's REAL output set (_whole_sync)
    t_full = _time_call(make_fn(ops, _whole_sync(ops, persist_written)),
                        args, repeats)
    compute_ops = [op for op, ph in zip(ops, plan["phases"])
                   if ph != "collective"]
    exposed_measurable = bool(plan["collectives"]) and plan["skippable"]
    if exposed_measurable:
        t_nocoll = _time_call(
            make_fn(compute_ops, _whole_sync(compute_ops,
                                             persist_written)),
            args, repeats)
    else:
        t_nocoll = t_full
    exposed_ms = max(0.0, (t_full - t_nocoll)) * 1e3

    # cumulative prefix timing over the collective-free sequence
    phase_ms: Dict[str, float] = {}
    seg_times: List[Tuple[str, float]] = []
    seg_spans: List[Tuple[str, float, int, int]] = []  # + (start, end)
    prev_pos, prev_t = 0, 0.0
    truncated = False
    for label, pos in plan["cuts"]:
        if time.monotonic() > deadline:
            truncated = True
            break
        prefix = compute_ops[:pos]
        rest = compute_ops[pos:]
        sync = _sync_vars(prefix, rest, compute_ops[prev_pos:pos])
        t = _time_call(make_fn(prefix, sync), args, repeats)
        seg = max(0.0, t - prev_t) * 1e3
        seg_times.append((label, seg))
        seg_spans.append((label.split("@", 1)[0], seg, prev_pos, pos))
        phase_ms[seg_spans[-1][0]] = \
            phase_ms.get(seg_spans[-1][0], 0.0) + seg
        prev_pos, prev_t = pos, max(t, prev_t)
    compute_ms = sum(phase_ms.values())

    # serial collective cost per bucket (microbench at exact payload)
    per_bucket = []
    coll_serial_ms = 0.0
    bwd_segs = [(ms, start, end) for base, ms, start, end in seg_spans
                if base == "backward"]
    for c in plan["collectives"]:
        if time.monotonic() > deadline:
            truncated = True
            break
        try:
            c_ms = _bench_collective(mesh, data_axes,
                                     c.get("bench_numel", c["numel"]),
                                     c["dtype"], c["kind"],
                                     repeats) * 1e3
        except Exception:
            c_ms = 0.0
        coll_serial_ms += c_ms
        # backward compute remaining after this bucket's availability
        # POSITION (not its cut label — cuts are deduped/capped, every
        # collective still has an exact position): segments that start
        # at/after the availability point are hideable budget; a
        # segment straddling it counts fully (a small overestimate for
        # collectives beyond the max_bucket_cuts cap, whose position
        # fell inside a kept segment)
        pos_c = c["avail_pos"]
        after = sum(ms for ms, _start, end in bwd_segs if end > pos_c)
        per_bucket.append({
            "bucket": c["bucket"], "op": c["type"], "kind": c["kind"],
            "bytes": c["bytes"], "collective_ms": c_ms,
            # which reduction spelling / wire mode this measured point
            # belongs to — the placement cost-model fit keys on these
            "strategy": c.get("strategy", "ring"),
            "quant": c.get("quant", "none"),
            # availability position in the compute-only op sequence —
            # stable across bucket plans (compute ops never move), so a
            # profile-guided replan can key its budgets on it
            "avail_pos": c["avail_pos"],
            "backward_after_ms": after,
            "max_hideable_frac": (min(1.0, after / c_ms)
                                  if c_ms > 0 else 0.0),
        })
    if not plan["collectives"]:
        overlap_frac = None          # no collectives: nothing to hide
        exposed_ms = 0.0
    elif not exposed_measurable or coll_serial_ms <= 0:
        # a non-skippable collective (shape-changing allgather etc.)
        # means no collective-free run exists — report "unmeasured",
        # never a fabricated perfect overlap
        overlap_frac = None
        exposed_ms = None
    else:
        overlap_frac = max(0.0, min(1.0, 1.0 - exposed_ms
                                    / coll_serial_ms))
    phase_ms_out = dict(phase_ms)
    if plan["collectives"]:
        phase_ms_out["collective"] = coll_serial_ms

    # feed staging (ISSUE 14): the H2D cost of this step's feed dict
    # from HOST memory, hard-synced — what a naive per-step input
    # pipeline pays on the critical path every step. Reported beside
    # the compute phases (not inside phase_ms: the phase identities
    # are device-compute conservation checks), as the before-number
    # the async feeder (core/native_feed.AsyncDeviceFeeder) hides.
    feed_ms = 0.0
    if ctx["feed_vals"] and time.monotonic() <= deadline:
        import jax

        host_feed = [np.asarray(v) for v in ctx["feed_vals"].values()]

        def _stage_feed():
            return [jax.device_put(v) for v in host_feed]

        try:
            feed_ms = _time_call(lambda: _stage_feed(), (),
                                 repeats) * 1e3
        except Exception:
            feed_ms = 0.0

    prof = {
        "method": "phase-sliced reexecution + collective microbench",
        "step_ms": t_full * 1e3,
        "phase_ms": phase_ms_out,
        # flat copies bench records / tools/bench_diff.py watch
        # directly (descending into a dict-valued metric is not in the
        # diff schema)
        "feed_ms": feed_ms,
        "optimizer_ms": phase_ms.get("optimizer", 0.0),
        "segments_ms": seg_times,
        "compute_ms": compute_ms,
        "collective_ms": coll_serial_ms,
        "exposed_collective_ms": exposed_ms,
        "overlap_frac": overlap_frac,
        "critical_path_ms": (compute_ms + exposed_ms
                             if exposed_ms is not None else None),
        "serialized_ms": compute_ms + coll_serial_ms,
        "per_bucket": per_bucket,
        # what a profile-guided bucket replan consumes
        # (parallel.collectives.plan_buckets_profile): measured
        # backward time per compute-position range — positions index
        # the collective-free op sequence, identical under ANY bucket
        # plan — plus the sequence length as a compatibility check
        "backward_segments": [[start, end, ms]
                              for ms, start, end in bwd_segs],
        "n_compute": plan["n_compute"],
        # mesh context for the placement cost-model fitter: the data
        # fan-in the measured collective costs were taken at (strategy
        # transfer factors scale with it)
        "nranks": (int(np.prod([mesh.shape[a] for a in data_axes]))
                   if mesh is not None and data_axes else 1),
        # a c_sharded_update fuses the optimizer math INTO the
        # collective op: both the exposed measurement (full minus
        # collective-free) and the serial microbench (which emulates
        # the per-shard update) then cover comm + fused update
        # together — flagged so readers don't compare against a
        # pure-communication model
        "exposed_includes_fused_update": any(
            c["kind"] == "sharded_update"
            for c in plan["collectives"]),
        "n_ops": len(ops),
        "truncated": truncated,
    }
    _emit_profile(prof)
    return prof


def _emit_profile(prof: Dict) -> None:
    """Registry + span emission: ``profile.phase_ms{phase=}``
    histograms, overlap/critical-path gauges, and one chrome-trace row
    per measured segment (cat="phase" — merged into the job trace.json
    through the normal span/spool pipeline)."""
    from .. import observability as _obs
    from . import tracing

    if not _obs.enabled():
        return
    for phase, ms in prof["phase_ms"].items():
        _obs.observe("profile.phase_ms", ms, phase=phase)
    if prof["overlap_frac"] is not None:
        _obs.set_gauge("profile.overlap_frac", prof["overlap_frac"])
    if prof["critical_path_ms"] is not None:
        _obs.set_gauge("profile.critical_path_ms",
                       prof["critical_path_ms"])
    if prof.get("feed_ms") is not None:
        _obs.set_gauge("profile.feed_ms", prof["feed_ms"])
    if tracing.active():
        t0 = time.perf_counter() * 1e6
        off = 0.0
        for label, ms in prof["segments_ms"]:
            tracing._record("profile/" + label, t0 + off, ms * 1e3,
                            "phase", {"phase": label.split("@", 1)[0]})
            off += ms * 1e3
        for b in prof["per_bucket"]:
            tracing._record("profile/collective%s" % b["bucket"],
                            t0 + off, b["collective_ms"] * 1e3, "phase",
                            {"phase": "collective",
                             "bucket": b["bucket"],
                             "bytes": b["bytes"]})
            off += b["collective_ms"] * 1e3


# -- analytic FLOP accounting ----------------------------------------------

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports —
# THE one place a peak lives. MFU is model FLOPs over the chip's bf16
# MXU peak whatever dtype the program computes in.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s per chip",
    },
}


def peak_flops(device_kind: str, n_devices: int = 1) -> Optional[float]:
    """bf16 peak FLOP/s of ``n_devices`` chips of this kind, or None
    for a kind the table does not list."""
    peaks = DEVICE_PEAKS.get(device_kind)
    if peaks is None:
        return None
    return peaks["bf16_flops"] * max(1, int(n_devices))


def mfu_est(flops_per_step: float, step_s: float, device_kind: str,
            n_devices: int = 1) -> Optional[float]:
    peak = peak_flops(device_kind, n_devices)
    if not step_s or not flops_per_step or peak is None:
        return None
    return flops_per_step / step_s / peak


def _shape_of(block, state, name) -> Optional[Tuple[int, ...]]:
    if not name:
        return None
    v = block._find_var_recursive(name)
    shape = getattr(v, "shape", None) if v is not None else None
    if shape and all(isinstance(s, int) and s > 0 for s in shape):
        return tuple(shape)
    if state is not None:
        arr = state.get(name) if isinstance(state, dict) else None
        if arr is None and not isinstance(state, dict):
            find = getattr(state, "find_var", None)
            if find is not None:
                var = find(name)
                if var is not None and var.is_initialized():
                    arr = var.raw().array
        if arr is not None and getattr(arr, "shape", None) is not None:
            return tuple(int(s) for s in arr.shape)
    # a grad var mirrors the shape of the var it differentiates; grad
    # vars often carry no static shape of their own
    from ..core.lod_lowering import _grad_base

    base = _grad_base(name)
    if base:
        return _shape_of(block, state, base)
    return None


def _prod(shape) -> int:
    return int(np.prod(shape)) if shape else 0


def _fl_mul(op, shp):
    x, y = shp(op.input("X")[0]), shp(op.input("Y")[0])
    if not x or not y:
        return 0
    xnc = int(op.attrs.get("x_num_col_dims", 1))
    ync = int(op.attrs.get("y_num_col_dims", 1))
    m = _prod(x[:xnc])
    k = _prod(x[xnc:])
    n = _prod(y[ync:])
    return 2 * m * k * n


def _fl_matmul(op, shp):
    x = shp(op.input("X")[0])
    outs = op.output("Out")
    out = shp(outs[0]) if outs else None
    if not x or not out:
        return 0
    k = x[-2] if op.attrs.get("transpose_X") or \
        op.attrs.get("transpose_x") else x[-1]
    return 2 * _prod(out) * int(k)


def _fl_conv2d(op, shp):
    outs = op.output("Output") or op.output("Out")
    out = shp(outs[0]) if outs else None
    f = shp(op.input("Filter")[0])
    if not out or not f:
        return 0
    return 2 * _prod(out) * int(f[1]) * int(f[2]) * int(f[3])


def _fl_flash(op, shp):
    q = shp(op.input("Q")[0])
    if not q or len(q) < 4:
        return 0
    b, h, s, d = q[-4], q[-3], q[-2], q[-1]
    f = 4 * b * h * s * s * d
    return f // 2 if op.attrs.get("causal") else f


def _fl_first_input(mult):
    def fn(op, shp):
        for n in op.input_arg_names:
            s = shp(n)
            if s:
                return mult * _prod(s)
        return 0
    return fn


def _fl_outputs(mult=1):
    def fn(op, shp):
        tot = 0
        for n in op.output_arg_names:
            s = shp(n)
            if s:
                tot += _prod(s)
        return mult * tot
    return fn


# (category, estimator). *_grad ops resolve through their base type at
# 2x (dgrad + wgrad — the standard training-step accounting); unknown
# ops fall back to one flop per output element under "other".
_FLOPS_TABLE = {
    "mul": ("matmul", _fl_mul),
    "matmul": ("matmul", _fl_matmul),
    "conv2d": ("conv", _fl_conv2d),
    "depthwise_conv2d": ("conv", _fl_conv2d),
    "flash_attention": ("attention", _fl_flash),
    "batch_norm": ("norm", _fl_first_input(8)),
    "layer_norm": ("norm", _fl_first_input(8)),
    "softmax": ("elementwise", _fl_first_input(5)),
    "softmax_with_cross_entropy": ("loss", _fl_first_input(6)),
    "cross_entropy": ("loss", _fl_first_input(3)),
    "lookup_table": ("embedding", lambda op, shp: 0),
    "lookup_table_v2": ("embedding", lambda op, shp: 0),
}

_ZERO_FLOP_OPS = frozenset({
    "fill_constant", "reshape", "reshape2", "transpose", "transpose2",
    "feed", "fetch", "shape", "squeeze", "squeeze2", "unsqueeze",
    "unsqueeze2", "assign", "share_data", "static_axis_size",
})


class _GradOpView:
    """Presents a ``*_grad`` op to a FORWARD estimator: grad ops carry
    the forward op's inputs verbatim plus ``<slot>@GRAD`` inputs for
    each forward output, so a forward formula asking for the output
    slot ("Out"/"Output") resolves through the output-grad input —
    same shape, which is all the estimators read."""

    __slots__ = ("_op",)

    def __init__(self, op):
        self._op = op

    def input(self, slot):
        return self._op.input(slot)

    def output(self, slot):
        got = self._op.output(slot)
        if got:
            return got
        from ..core.registry import GRAD_SUFFIX

        return self._op.input(slot + GRAD_SUFFIX)

    @property
    def attrs(self):
        return self._op.attrs

    @property
    def input_arg_names(self):
        return self._op.input_arg_names

    @property
    def output_arg_names(self):
        return self._op.output_arg_names


def op_flops(op, block, state=None) -> Tuple[int, str]:
    """(flops, category) for one op — analytic, from static shapes."""
    def shp(name):
        return _shape_of(block, state, name)

    t = op.type
    if t.startswith("c_"):
        return 0, "collective"
    if t in _ZERO_FLOP_OPS:
        return 0, "other"
    grad = t.endswith("_grad")
    base = t[:-5] if grad else t
    if base in OPTIMIZER_OPS:
        # a handful of elementwise passes over every param element
        tot = sum(_prod(shp(n)) or 0
                  for n in (op.input("Param") or [])[:1])
        return 4 * tot, "optimizer"
    cat, fn = _FLOPS_TABLE.get(base, (None, None))
    if fn is None:
        return _fl_outputs(1)(op, shp), "other"
    f = fn(_GradOpView(op) if grad else op, shp)
    if grad:
        f *= 2
    return f, cat


def program_flops(program, state=None) -> Dict:
    """Analytic FLOPs of one execution of ``program``:
    ``{"total": F, "by_category": {...}}`` — per-step when the program
    is a training step. Shapes come from the block (falling back to
    live scope/state values); ops without resolvable shapes count 0.
    """
    block = program.global_block()
    by_cat: Dict[str, int] = {}
    total = 0
    for op in block.ops:
        f, cat = op_flops(op, block, state)
        if f:
            by_cat[cat] = by_cat.get(cat, 0) + f
            total += f
    return {"total": total, "by_category": by_cat}


def flops_mlp(batch: int, dims: Sequence[int], train: bool = True) -> int:
    """Analytic per-step FLOPs of a dense MLP (the dygraph_mlp bench
    shape): 2*b*sum(d_i*d_{i+1}) forward, x3 for a training step."""
    fwd = 2 * batch * sum(int(a) * int(b)
                          for a, b in zip(dims, dims[1:]))
    return 3 * fwd if train else fwd


def flops_transformer_lm(batch: int, seq_len: int, d_model: int,
                         n_layers: int, vocab: int,
                         train: bool = True) -> int:
    """Analytic per-step FLOPs of a standard transformer LM block stack
    (qkvo + 4x FFN + attention scores/context) plus the logit matmul —
    the dygraph_bert bench shape."""
    per_layer = 24 * batch * seq_len * d_model * d_model \
        + 4 * batch * seq_len * seq_len * d_model
    fwd = n_layers * per_layer + 2 * batch * seq_len * d_model * vocab
    return 3 * fwd if train else fwd


# -- legacy fluid.profiler session API (absorbed from the old shim) --------
#
# Parity: /root/reference/python/paddle/fluid/profiler.py (:253 profiler
# context manager, :129 start_profiler, :196 stop_profiler) + the C++
# RecordEvent/DeviceTracer pair. The host-event machinery lives in
# ``observability/tracing.py``; this surface keeps the fluid API:
# RecordEvent spans feed the same buffer as all other runtime spans,
# start/stop bracket a *session* drained into a snapshot on stop, and
# ``profiler(...)`` prints the per-op host summary table. Device-side
# tracing delegates to jax.profiler (XPlane -> TensorBoard/Perfetto).

from . import tracing as _tracing  # noqa: E402

_last_trace: List[Tuple] = []   # (name, ts_us, dur_us) finished session
_trace_dir = None


class RecordEvent:
    """RAII op-phase annotation (reference platform/profiler.cc:66) —
    an observability span with cat='op'."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self._span = _tracing.span(self.name, cat="op")
        self._span.__enter__()
        return self

    def __exit__(self, *exc):
        return self._span.__exit__(*exc)


def record_event(name):
    return RecordEvent(name)


def is_profiler_enabled():
    return _tracing.profiler_session_active()


def get_trace_events():
    """(name, ts_us, dur_us) host events for timeline export: the live
    session while profiling, else the last finished session's
    snapshot."""
    if _tracing.profiler_session_active():
        return [(n, ts, dur)
                for (n, ts, dur, _tid, _cat, _a)
                in _tracing.profiler_session_events()]
    return list(_last_trace)


def reset_profiler():
    # session-scoped: metrics-mode spans recorded by other subsystems
    # are not this API's to destroy
    _tracing.profiler_session_reset()


def start_profiler(state="All", tracer_option=None, trace_dir=None):
    global _trace_dir
    _trace_dir = trace_dir
    _tracing.profiler_session_start()
    if trace_dir:
        import jax

        jax.profiler.start_trace(trace_dir)


def stop_profiler(sorted_key="total", profile_path="/tmp/profile"):
    if _trace_dir:
        import jax

        jax.profiler.stop_trace()
    session, agg = _tracing.profiler_session_stop()
    # the aggregate side stays exact even when buffer pressure dropped
    # old spans mid-session; the timeline snapshot below is best-effort
    rows = sorted(((name, (count, total_us / 1e6))
                   for name, (count, total_us) in agg.items()),
                  key=lambda kv: -kv[1][1])
    if rows:
        print("%-40s %10s %14s %14s"
              % ("Event", "Calls", "Total(ms)", "Avg(ms)"))
        for name, (count, total) in rows[:50]:
            print("%-40s %10d %14.3f %14.3f"
                  % (name, count, total * 1e3, total * 1e3 / max(count, 1)))
    del _last_trace[:]
    _last_trace.extend((n, ts, dur) for (n, ts, dur, _t, _c, _a)
                       in session)


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path="/tmp/profile",
             tracer_option=None):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    # name kept for API compatibility; delegates to the XLA trace
    with profiler():
        yield
