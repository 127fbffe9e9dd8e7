"""GPipe-style pipeline parallelism over a 'pp' mesh axis.

TPU-native counterpart of the reference's pipeline trainer
(/root/reference/paddle/fluid/framework/pipeline_trainer.cc:253 and
section_worker.cc:142-258 — SectionWorker threads per stage passing
Scopes through blocking queues, with cross-section device copies; the
program is split at ``cut_list`` by python optimizer.py:3422).

Here the same semantics compile into ONE SPMD program over a 'pp' mesh
axis:

- ``split_forward_at_cuts`` partitions the forward op list into stages
  at the ops producing each cut var (the reference's program split);
- every device runs the same traced program and selects its stage via
  ``lax.switch`` on ``lax.axis_index('pp')``;
- stage boundary activations are packed into one fixed-size f32 buffer
  and rotated to the next stage with ``lax.ppermute`` each tick — the
  compiled-collective replacement for section scope queues + memcpy;
- the microbatch schedule is a ``lax.scan`` over n_micro + n_stages - 1
  ticks (the GPipe fill/drain schedule); ``jax.grad`` through the scan
  IS the backward pipeline — the transpose of ppermute sends grads the
  reverse direction, and per-stage grad accumulation falls out of the
  scan transpose;
- the wrapped optimizer's update ops (recorded by PipelineOptimizer in
  ``program._pipeline_meta``) are then traced once with the pipeline's
  mean grads bound to the accumulator vars, so update semantics are
  byte-identical to the single-device microbatch-accumulation path.

Params are replicated across the pp axis (each stage only *reads* its
own subset inside its switch branch; XLA's liveness keeps the unused
replicas out of the stage's working set). Forward-side persistable
writes (BN running stats) are not propagated back — batch norm under
pipelining wants sync-BN or frozen stats anyway.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.compiler_engine import _program_version, _trace_ops
from ..core.scope import Scope
from ..core.tensor import LoDTensor
from .mesh_utils import make_mesh

_pp_cache: Dict = {}


def _cut_names(cut_list) -> List[str]:
    """Reference cut_list is a list of lists of Variables
    (optimizer.py:3422); accept that, flat lists, and names."""
    names = []
    for entry in cut_list or []:
        group = entry if isinstance(entry, (list, tuple)) else [entry]
        for v in group:
            names.append(v if isinstance(v, str) else v.name)
    return names


def split_forward_at_cuts(program, cut_list, n_fwd_ops: int):
    """Partition ops[0:n_fwd_ops] into len(cuts)+1 contiguous stages;
    stage i ends with the op producing the i-th cut var (the same
    split-point contract as the reference's optimizer.py:3422)."""
    block = program.global_block()
    ops = list(block.ops[:n_fwd_ops])
    idxs = []
    for name in _cut_names(cut_list):
        prods = [i for i, op in enumerate(ops)
                 if name in op.output_arg_names]
        if not prods:
            raise ValueError("cut var %r is not produced by any forward "
                             "op" % name)
        idxs.append(max(prods))
    if idxs != sorted(idxs):
        raise ValueError("cut_list vars must appear in program order; "
                         "producer indices %r" % idxs)
    bounds = [0] + [i + 1 for i in idxs] + [len(ops)]
    stages = [ops[bounds[i]:bounds[i + 1]]
              for i in range(len(bounds) - 1)]
    if any(not s for s in stages):
        raise ValueError("empty pipeline stage (consecutive cuts at the "
                         "same op?)")
    return stages


def _stage_rw(ops) -> Tuple[set, set]:
    written, read_first = set(), set()
    for op in ops:
        for n in op.input_arg_names:
            if n and n not in written:
                read_first.add(n)
        for n in op.output_arg_names:
            if n:
                written.add(n)
    return written, read_first


def _boundary_live_sets(stages, external: set) -> List[List[str]]:
    """For each stage boundary i (between stage i and i+1): vars written
    by stages <= i and read-before-written by stages > i, excluding
    external vars (feeds/params/state, which are routed directly).
    Carrying the full live set lets skip connections cross several
    boundaries untouched."""
    rw = [_stage_rw(s) for s in stages]
    live = []
    for i in range(len(stages) - 1):
        produced = set()
        for w, _ in rw[:i + 1]:
            produced |= w
        needed = set()
        shadow = set()
        for w, r in rw[i + 1:]:
            needed |= (r - shadow)
            shadow |= w
        live.append(sorted((produced & needed) - external))
    return live


def run_pipeline_parallel(core, program, scope: Scope, feed: Dict,
                          fetch_list: Sequence, mesh=None,
                          axis_name: str = "pp", return_numpy: bool = True):
    """One full-batch training step, pipelined over the mesh's pp axis.

    ``feed`` carries the FULL batch; it is split into
    ``num_microbatches`` along dim 0 (the reference feeds one microbatch
    per run into the section queues). Fetch support: the loss var
    (returned as the mean over microbatches, matching the accumulated
    1/k-scaled loss of the single-device path).
    """
    import jax
    import jax.numpy as jnp

    from .. import observability as _obs

    meta = getattr(program, "_pipeline_meta", None)
    if meta is None:
        raise ValueError(
            "program has no pipeline metadata — minimize() it with "
            "PipelineOptimizer(cut_list=...) first")
    stages = split_forward_at_cuts(program, meta["cut_list"],
                                   meta["n_fwd_ops"])
    n_stages = len(stages)
    n_micro = int(meta["num_microbatches"])
    loss_name = meta["loss"]
    if _obs.enabled():
        # the GPipe fill/drain bubble: (S-1) of (M+S-1) ticks are idle
        # per device — THE pipeline-efficiency number follow-up perf
        # PRs must watch (more microbatches -> smaller fraction)
        _obs.set_gauge("pipeline.stages", n_stages)
        _obs.set_gauge("pipeline.microbatches", n_micro)
        _obs.set_gauge("pipeline.bubble_fraction",
                       (n_stages - 1.0) / (n_micro + n_stages - 1.0))
        for i, s in enumerate(stages):
            _obs.set_gauge("pipeline.stage_ops", len(s), stage=i)

    if mesh is None:
        mesh = make_mesh([n_stages], [axis_name])
    if mesh.shape[axis_name] != n_stages:
        raise ValueError("mesh axis %r has %d devices but cut_list "
                         "defines %d stages"
                         % (axis_name, mesh.shape[axis_name], n_stages))

    # -- hybrid composition: dp replicas of the pipeline, model axes
    # inside the stages (dp x pp x mp in ONE program) ---------------------
    # MODEL axes are the ones transpiled ops actually use: var shard
    # specs (mp tables) plus any op-level shard_axis attr (sp ring
    # attention, ep MoE). Only a remaining axis DECLARED as a data
    # axis may shard the batch — silently promoting an op axis to a
    # batch axis runs to completion with wrong gradients (the hazard
    # engine.py guards the same way).
    shard_specs = dict(getattr(program, "_var_shard_specs", None) or {})
    if getattr(program, "_feed_shard_specs", None):
        raise NotImplementedError(
            "pipeline + per-feed shard specs (sequence parallelism) "
            "is not supported — drop strategy.pipeline or the sp pass")
    model_axes = {a for spec in shard_specs.values() for a in spec if a}
    model_axes |= {op.attrs.get("shard_axis")
                   for op in program.global_block().ops
                   if op.attrs.get("shard_axis")}
    declared_data = set(getattr(program, "_data_axes", None) or ("dp",))
    dp_axes = tuple(a for a in mesh.axis_names
                    if a != axis_name and a not in model_axes)
    bad = [a for a in dp_axes if a not in declared_data]
    if bad:
        raise ValueError(
            "mesh axes %r are neither the pp axis, a model shard axis, "
            "nor declared data axes %r — refusing to guess"
            % (bad, sorted(declared_data)))
    if len(dp_axes) > 1:
        raise NotImplementedError(
            "at most one data axis composes with pp (got %r)"
            % (dp_axes,))
    dp_axis = dp_axes[0] if dp_axes else None
    dp = mesh.shape[dp_axis] if dp_axis else 1
    for n, spec in shard_specs.items():
        for a in spec:
            if a is not None and a not in mesh.axis_names:
                raise ValueError(
                    "var %r sharded over axis %r absent from mesh %s"
                    % (n, a, list(mesh.axis_names)))

    block = program.global_block()
    feed_vals = {}
    for name, value in (feed or {}).items():
        arr = value.array if isinstance(value, LoDTensor) \
            else jnp.asarray(np.asarray(value))
        if arr.shape[0] % (n_micro * dp):
            raise ValueError(
                "feed %r batch %d not divisible by num_microbatches %d "
                "x dp %d" % (name, arr.shape[0], n_micro, dp))
        feed_vals[name] = arr.reshape((n_micro, arr.shape[0] // n_micro)
                                      + arr.shape[1:])
    feed_names = tuple(sorted(feed_vals))

    # forward external state: params + anything else read-before-write
    fwd_read = set()
    shadow = set()
    for s in stages:
        w, r = _stage_rw(s)
        fwd_read |= (r - shadow)
        shadow |= w
    state = {}
    for n in sorted(fwd_read - set(feed_names)):
        var = scope.find_var(n)
        if var is None or not var.is_initialized():
            raise RuntimeError("var %r must be fed or initialized" % n)
        state[n] = var.raw().array
    param_names = tuple(n for n in meta["params"] if n in state)
    other_state = {n: v for n, v in state.items() if n not in param_names}
    params = {n: state[n] for n in param_names}

    live = _boundary_live_sets(stages, set(feed_names) | set(state))

    from .mesh_utils import mesh_key

    key = (_program_version(program), feed_names,
           tuple((n, tuple(v.shape)) for n, v in sorted(feed_vals.items())),
           tuple(param_names), tuple(sorted(other_state)), mesh_key(mesh),
           axis_name, n_micro, dp_axis,
           tuple(sorted((k, v) for k, v in shard_specs.items())))
    compiled = _pp_cache.get(key)
    if compiled is None:
        from ..analysis import maybe_verify_program, verify_enabled

        if verify_enabled():
            # stage-partition contract + full well-formedness check on
            # the first compile of this (program, mesh) pairing
            from ..analysis.contracts import check_pipeline_split

            check_pipeline_split(program, stages, meta["n_fwd_ops"])
            maybe_verify_program(program, where="parallel.pipeline",
                                 scope=scope)
        _obs.inc("pipeline.compiles")
        with _obs.tracing.span("pipeline/build", cat="compile",
                               stages=n_stages, microbatches=n_micro):
            compiled = _build_pipeline_fn(
                block, stages, live, meta, mesh, axis_name, n_stages,
                n_micro, feed_names, param_names,
                tuple(sorted(other_state)), loss_name,
                {n: (v.shape, v.dtype) for n, v in feed_vals.items()},
                {n: (v.shape, v.dtype) for n, v in params.items()},
                {n: (v.shape, v.dtype) for n, v in other_state.items()},
                dp_axis=dp_axis, shard_specs=shard_specs)
        # bounded LRU, same rationale as executor_core._gc_plan_cache:
        # program mutation bumps the version and would leak executables
        if len(_pp_cache) >= 16:
            _pp_cache.pop(next(iter(_pp_cache)))
        _pp_cache[key] = compiled
    else:
        _pp_cache[key] = _pp_cache.pop(key)
    jitted, upd_external, persist_out, (boundary_bytes, buffer_bytes) = \
        compiled
    if _obs.enabled():
        for i, b in enumerate(boundary_bytes):
            _obs.set_gauge("pipeline.boundary_bytes", b, boundary=i)
        # actual per-tick ppermute transfer: every boundary moves the
        # max-padded rotating buffer, not its logical payload
        _obs.set_gauge("pipeline.buffer_bytes", buffer_bytes)

    # optimizer state is read FRESH each call — moments/lr change every
    # step and must not be baked into the compiled closure
    upd_state = {}
    for n in upd_external:
        var = scope.find_var(n)
        if var is None or not var.is_initialized():
            raise RuntimeError("optimizer state %r not initialized" % n)
        upd_state[n] = var.raw().array

    seed = jnp.uint32(core.rng.next_seed(0)
                      ^ ((core.rng.step * 2654435761) & 0xFFFFFFFF))
    core.rng.advance()
    import time as _time

    from ..observability import distributed as _dtrace
    from . import engine as _dp_engine

    # pipeline steps share the dp engine's sync-round counter: a
    # hybrid job's pp and dp step spans join the same job-trace round
    round_no = _dp_engine._sync_round
    _dp_engine._sync_round += 1
    t_step = _time.perf_counter() if _obs.enabled() else None
    with _obs.tracing.span("pipeline/step", cat="step",
                           stages=n_stages, microbatches=n_micro,
                           round=round_no,
                           **_dtrace.fleet_round_args(round_no)):
        loss_mean, new_persist = jitted(params, other_state, upd_state,
                                        feed_vals, seed)
    if t_step is not None:
        _obs.inc("pipeline.steps")
        _obs.observe("pipeline.step_ms",
                     (_time.perf_counter() - t_step) * 1e3)
        # collective-traffic estimate, same counter family as the dp
        # engine (engine._estimate_collective_bytes): per step the
        # pipeline psums the loss + every param grad over pp (x dp),
        # and each of the 2*(M+S-1) fwd/bwd ticks rotates the
        # max-padded boundary buffer via ppermute
        grad_bytes = sum(
            int(np.prod(v.shape)) * np.dtype(v.dtype).itemsize
            for v in params.values())
        ticks = 2 * (n_micro + n_stages - 1)
        _obs.inc("parallel.collective_ops", len(params) + 1 + ticks)
        _obs.inc("parallel.collective_ops", len(params) + 1,
                 kind="allreduce")
        _obs.inc("parallel.collective_ops", ticks, kind="ppermute")
        _obs.inc("parallel.collective_bytes",
                 grad_bytes + ticks * buffer_bytes)
        _obs.inc("parallel.collective_bytes", grad_bytes,
                 kind="allreduce")
        _obs.inc("parallel.collective_bytes", ticks * buffer_bytes,
                 kind="ppermute")

    for n, v in new_persist.items():
        scope.var(n).get_tensor()._array = v

    results = []
    for f in fetch_list or []:
        name = f if isinstance(f, str) else f.name
        if name != loss_name:
            raise NotImplementedError(
                "pipeline fetch supports the loss var only, got %r" % name)
        results.append(np.asarray(loss_mean) if return_numpy else loss_mean)
    return results


def _build_pipeline_fn(block, stages, live, meta, mesh, axis_name,
                       n_stages, n_micro, feed_names, param_names,
                       other_names, loss_name, feed_meta, param_meta,
                       other_meta, dp_axis=None, shard_specs=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .. import observability as _obs
    from ..ops.collective_ops import mesh_axes_guard

    shard_specs = shard_specs or {}
    dp = mesh.shape[dp_axis] if dp_axis else 1
    mesh_axes = set(mesh.axis_names)

    def _local_shape(name, shape):
        """Per-shard shape of a var under its shard spec."""
        spec = shard_specs.get(name)
        if not spec:
            return tuple(shape)
        out = list(shape)
        for d, a in enumerate(spec):
            if a:
                out[d] = out[d] // mesh.shape[a]
        return tuple(out)

    # -- dry pass: boundary layouts via eval_shape ------------------------
    # One microbatch flows through all stages abstractly (at the LOCAL
    # per-dp-shard batch size and LOCAL param shard shapes — that is
    # what the kernels inside shard_map see); each boundary's live set
    # fixes the packing layout for the rotating activation buffer.
    # NOTE: no mesh_axes_guard here — this pass runs OUTSIDE shard_map
    # (axis collectives would be unbound); hybrid ops take their dense
    # fallback, which is shape-identical on local shard shapes, and
    # only shapes matter to eval_shape.
    def _dry(params_a, other_a, mb_feeds_a):
        env = dict(params_a)
        env.update(other_a)
        outs = []
        for i, ops in enumerate(stages):
            env.update(mb_feeds_a)
            # per-stage host span: stage tracing cost is the only
            # per-stage work visible host-side (inside the compiled
            # step the stages are one fused XLA program; device-level
            # per-stage timing lives in the XPlane trace)
            with _obs.tracing.span("pipeline/stage", cat="step",
                                   stage=i, ops=len(ops)):
                _trace_ops(block, ops, env, jnp.uint32(0))
            if i < n_stages - 1:
                outs.append([env[n] for n in live[i]])
        return outs

    params_s = {n: jax.ShapeDtypeStruct(_local_shape(n, s), d)
                for n, (s, d) in param_meta.items()}
    other_s = {n: jax.ShapeDtypeStruct(_local_shape(n, s), d)
               for n, (s, d) in other_meta.items()}
    mb_feeds_s = {n: jax.ShapeDtypeStruct((s[1] // dp,) + tuple(s[2:]), d)
                  for n, (s, d) in feed_meta.items()}
    shapes = jax.eval_shape(_dry, params_s, other_s, mb_feeds_s)
    layouts = [
        [(n, tuple(sd.shape), sd.dtype) for n, sd in zip(live[i], stage)]
        for i, stage in enumerate(shapes)
    ]

    for lay in layouts:
        for n, shape, dtype in lay:
            if not jnp.issubdtype(dtype, jnp.floating):
                raise NotImplementedError(
                    "non-float var %r (%s) crosses a pipeline stage "
                    "boundary" % (n, dtype))
    sizes = [sum(int(np.prod(s)) for _, s, _ in lay) for lay in layouts]
    buf_size = max(sizes) if sizes else 1

    def _pack(env, lay):
        if not lay:
            return jnp.zeros((buf_size,), jnp.float32)
        flat = jnp.concatenate(
            [env[n].astype(jnp.float32).reshape(-1) for n, _, _ in lay])
        return jnp.pad(flat, (0, buf_size - flat.shape[0]))

    def _unpack(buf, lay):
        out, off = {}, 0
        for n, shape, dtype in lay:
            k = int(np.prod(shape))
            out[n] = buf[off:off + k].reshape(shape).astype(dtype)
            off += k
        return out

    def _branch(i):
        def run(buf, feeds_t, seed_t, params, other):
            env = dict(params)
            env.update(other)
            if i > 0:
                env.update(_unpack(buf, layouts[i - 1]))
            env.update(feeds_t)
            _trace_ops(block, stages[i], env, seed_t)
            if i < n_stages - 1:
                return _pack(env, layouts[i]), jnp.float32(0.0)
            return (jnp.zeros((buf_size,), jnp.float32),
                    env[loss_name].reshape(()).astype(jnp.float32))
        return run

    branches = [_branch(i) for i in range(n_stages)]

    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def shard_step(params, other, feeds, seed):
        """Per-shard pipeline forward + LOCAL backward, then explicit
        grad collectives. The gradient is taken INSIDE the shard (of
        the pre-psum local loss) rather than through the shard_map
        boundary: differentiating through a replicated (P()) out-spec
        divides the cotangent by the replicating axes' sizes, which
        silently under-scales sharded-param grads (measured exactly
        1/mp on the embedding table). With the local grad, the
        cotangent entering each stage op is the true replicated one,
        and the cross-device reduction is the explicit psum(pp) +
        pmean(dp) below — the hand-placed collectives of the standard
        SPMD recipe."""
        sid = jax.lax.axis_index(axis_name)

        def local_loss(params_d):
            def tick(carry, t):
                buf, loss_sum = carry
                mbr = t - sid
                mb = jnp.clip(mbr, 0, n_micro - 1)
                feeds_t = {
                    n: jax.lax.dynamic_index_in_dim(v, mb, 0,
                                                    keepdims=False)
                    for n, v in feeds.items()
                }
                seed_t = seed + jnp.uint32(0x9E3779B9) * \
                    mb.astype(jnp.uint32)
                # fill/drain ticks see a garbage (zero) rotating
                # buffer; the loss is masked below, but grad through a
                # masked tick still NaNs when an op has an unbounded
                # derivative at 0 (log, sqrt, 1/x): zero cotangent x
                # inf Jacobian. A ONES sentinel keeps those Jacobians
                # finite, so masked cotangents stay 0.
                is_real_in = (mbr >= 0) & (mbr < n_micro)
                safe_buf = jnp.where(is_real_in, buf,
                                     jnp.ones_like(buf))
                with mesh_axes_guard(mesh_axes):
                    newbuf, loss = jax.lax.switch(
                        sid, branches, safe_buf, feeds_t, seed_t,
                        params_d, other)
                is_real = ((t - (n_stages - 1) >= 0)
                           & (t - (n_stages - 1) < n_micro))
                loss_sum = loss_sum + jnp.where(is_real, loss, 0.0)
                sent = jax.lax.ppermute(newbuf, axis_name, perm)
                return (sent, loss_sum), None

            init = (jnp.zeros((buf_size,), jnp.float32),
                    jnp.float32(0.0))
            (_, loss_sum), _ = jax.lax.scan(tick, init,
                                            jnp.arange(n_ticks))
            # mean over this shard's microbatches; nonzero only on the
            # last pp stage (the psum below broadcasts it)
            return loss_sum / n_micro

        loss_local, g = jax.value_and_grad(local_loss)(params)
        loss = jax.lax.psum(loss_local, axis_name)
        g = {n: jax.lax.psum(v, axis_name) for n, v in g.items()}
        if dp_axis:
            # dp replicas each pipelined their own batch shard
            loss = jax.lax.pmean(loss, dp_axis)
            g = {n: jax.lax.pmean(v, dp_axis) for n, v in g.items()}
        return loss, g

    feed_spec = P(None, dp_axis) if dp_axis else P()
    param_specs = {n: P(*shard_specs.get(n, ())) for n in param_names}
    smap = jax.shard_map(
        shard_step, mesh=mesh,
        in_specs=(param_specs,
                  {n: P(*shard_specs.get(n, ())) for n in other_names},
                  {n: feed_spec for n in feed_names},
                  P()),
        out_specs=(P(), param_specs), check_vma=False)

    # -- optimizer update: trace the program's own update block ----------
    update_ops = meta["update_ops"]
    acc_map = meta["acc_map"]  # param name -> accumulator (grad) var name
    upd_w, upd_r = _stage_rw(update_ops)
    upd_external = tuple(sorted(
        n for n in upd_r
        if n not in acc_map.values() and n not in param_names))
    persist_out = tuple(sorted(
        n for n in upd_w
        if (v := block._find_var_recursive(n)) is not None
        and getattr(v, "persistable", False)
        and not n.endswith(".pipe_acc")))

    def full_step(params, other, upd_st, feeds, seed):
        loss, grads = smap(params, other, feeds, seed)
        env = dict(params)
        env.update(upd_st)
        # the single-device path accumulates k grads of the 1/k-scaled
        # loss into the acc vars = the mean grad the pipeline computed
        for p, acc in acc_map.items():
            if p in grads:
                env[acc] = grads[p]
        _trace_ops(block, update_ops, env, seed)
        new_persist = {n: env[n] for n in persist_out if n in env}
        return loss, new_persist

    # gauge payloads, returned so the caller can refresh them every
    # step (metrics armed AFTER the compile must still see them):
    # boundary_bytes is each boundary's LOGICAL f32 payload; the wire
    # cost per ppermute tick is the max-padded rotating buffer
    # (buffer_bytes) regardless of boundary — both are exported so a
    # schedule PR can't claim a win by shrinking a non-max boundary
    boundary_bytes = tuple(s * 4 for s in sizes)
    return (jax.jit(full_step), upd_external, persist_out,
            (boundary_bytes, buf_size * 4))
