"""Whole-program compilation: trace a Block into ONE jitted XLA function.

This is the TPU answer to the reference's op-by-op C++ executor hot loop
(/root/reference/paddle/fluid/framework/executor.cc:449): instead of
dispatching ~hundreds of kernels per step through an interpreter, the
whole (feed → fetch) block is traced once into a single XLA program —
fused, laid out for the MXU, with parameter/optimizer-state buffers
DONATED so updates are in-place in HBM. Repeat steps are one dispatch.

Semantics preserved vs the interpreter:
- program order == trace order; same-name rebinding == SSA env update,
  so in-place contracts (ParamOut==Param) hold via donation;
- stateful RNG ops get a per-op stream folded from a step seed that the
  host advances each run (no recompilation, masks vary per step);
- persistable vars (params, optimizer state, BN running stats) round-trip
  scope -> device args -> scope.

Programs containing host ops / LoD-dependent ops fall back to the
interpreter (executor_core.py) — the same duality the build plan calls
for (SURVEY.md §7 step 3).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Sequence, Set, Tuple

import numpy as np

from .enforce import UnimplementedError
from .registry import BOUND_OUTPUTS_ATTR, RNG_SEED_ATTR, OpInfoMap
from .scope import Scope
from .tensor import LoDTensor


class UntraceableProgramError(UnimplementedError):
    """The program is valid but cannot be traced into ONE XLA function:
    a while carry whose shape/dtype varies across trips, a host op
    that is not const-foldable here, a LoD feed. These are the cases
    the op-by-op interpreter exists for, and the only ones the executor
    answers by falling back to it. Anything raised AFTER the trace — a
    Pallas kernel Mosaic refuses, an XLA compile error, running out of
    device memory — is a failure of a traceable program and
    propagates."""


# compiled step functions (XLA executables — the heaviest objects in
# the process): LRU-bounded so program-churning workloads (e.g. a
# @declarative fn fed fresh signatures forever) can't grow without
# limit; an evicted program just recompiles on next run
_cache: "OrderedDict" = OrderedDict()
_CACHE_CAP = 128


def _lru_get(cache, key):
    hit = cache.get(key)
    if hit is not None:
        cache.move_to_end(key)
    return hit


def _lru_put(cache, key, value, cap):
    cache[key] = value
    while len(cache) > cap:
        cache.popitem(last=False)


def _program_version(program) -> Tuple:
    return (program._uid, program._op_id,
            tuple(len(b.ops) for b in program.blocks))


_analysis_cache: "OrderedDict" = OrderedDict()
_ANALYSIS_CAP = 1024


_block_rw_cache: "weakref.WeakKeyDictionary" = None  # set below


def _block_rw(block) -> Tuple[Set[str], Set[str]]:
    """(written, read-before-written) over a block, recursing through
    while/conditional sub-blocks (their external reads are this block's
    reads; their writes land in parent vars by name). Memoized per
    block (invalidated by op count): the while op re-derives its
    snapshot set every execution and backward calls this per while op."""
    global _block_rw_cache
    if _block_rw_cache is None:
        import weakref as _weakref

        _block_rw_cache = _weakref.WeakKeyDictionary()
    hit = _block_rw_cache.get(block)
    if hit is not None and hit[0] == len(block.ops):
        return hit[1]
    result = _block_rw_impl(block)
    try:
        _block_rw_cache[block] = (len(block.ops), result)
    except TypeError:
        pass
    return result


def _block_rw_impl(block) -> Tuple[Set[str], Set[str]]:
    written: Set[str] = set()
    read_first: Set[str] = set()
    for op in block.ops:
        sb = op.attrs.get("sub_block")
        if op.type in ("while", "conditional_block") and sb is not None:
            sw, sr = _block_rw(sb)
            for n in sr | set(op.input_arg_names):
                if n and n not in written:
                    read_first.add(n)
            for n in sw | set(op.output_arg_names):
                if n:
                    written.add(n)
            continue
        for n in op.input_arg_names:
            if n and n not in written:
                read_first.add(n)
        for n in op.output_arg_names:
            if n:
                written.add(n)
    return written, read_first


def _analyze(program):
    """Read-before-write set R (external inputs) and written set W.
    Cached per program version — a full-program scan per step is real
    overhead on 1000-op programs."""
    key = _program_version(program)
    hit = _lru_get(_analysis_cache, key)
    if hit is not None:
        return hit
    written, read_first = _block_rw(program.global_block())
    # persistable outputs that must land back in the scope (params,
    # optimizer state, BN stats) — also shape-stable per version
    block = program.global_block()
    persist_written = frozenset(
        n for n in written
        if (v := block._find_var_recursive(n)) is not None and v.persistable)
    result = (read_first, written, persist_written)
    _lru_put(_analysis_cache, key, result, _ANALYSIS_CAP)
    return result


def _op_seed(step_seed, op_id: int):
    import jax.numpy as jnp

    return (step_seed * jnp.uint32(1000003)
            + jnp.uint32((op_id * 131) & 0xFFFFFFFF))


def _fold_plan(block):
    """Constant-folding analysis over the global block.

    A host op (value-dependent output shape, e.g. ``range`` — reference
    operators/range_op.cc runs it CPU-side too) would force the whole
    program onto the op-by-op interpreter. When such an op is marked
    ``const_foldable`` and its inputs derive transitively from
    deterministic constant producers (fill_constant chains — not feeds,
    not scope state, not RNG), the compiler evaluates it ONCE at compile
    time and embeds the result as an XLA literal, keeping the program on
    the whole-compile path (partial evaluation, the XLA-idiomatic answer
    to the reference's host-kernel ops).

    Returns (fold_idxs, needed_idxs, fold_out_names): host-op indices to
    pre-evaluate + skip in the trace, the pure producer indices their
    evaluation needs, and the folded output var names.
    """
    infos = OpInfoMap.instance()
    writer_count: Dict[str, int] = {}
    for op in block.ops:
        for n in op.output_arg_names:
            if n:
                writer_count[n] = writer_count.get(n, 0) + 1
        # while/conditional ops are appended with outputs={} but their
        # sub-blocks write parent vars by name — count those writes, or
        # a loop-mutated var would classify as a single-writer constant
        # and a downstream fold would bake in the stale pre-loop value
        sb = op.attrs.get("sub_block")
        if op.type in ("while", "conditional_block") and sb is not None:
            for n in _block_rw(sb)[0]:
                writer_count[n] = writer_count.get(n, 0) + 1
    static: Dict[str, int] = {}  # var -> producing op index
    fold_idxs = set()
    for i, op in enumerate(block.ops):
        if op.type in ("while", "conditional_block"):
            continue
        try:
            info = infos.get(op.type)
        except KeyError:
            continue
        const_ok = info.const_foldable and info.host_fn is not None
        pure = (info.host_fn is None and not info.needs_rng
                and not info.needs_lod and not info.side_effect)
        if not (pure or const_ok):
            continue
        ins = [n for n in op.input_arg_names if n]
        outs = [n for n in op.output_arg_names if n]
        if not outs or any(n not in static for n in ins):
            continue
        ok = True
        for n in outs:
            v = block._find_var_recursive(n)
            if writer_count.get(n, 0) != 1 or (
                    v is not None and getattr(v, "persistable", False)):
                ok = False
                break
        if not ok:
            continue
        for n in outs:
            static[n] = i
        if const_ok:
            fold_idxs.add(i)
    if not fold_idxs:
        return frozenset(), frozenset(), frozenset()
    needed = set()
    stack = [n for i in fold_idxs
             for n in block.ops[i].input_arg_names if n]
    while stack:
        n = stack.pop()
        i = static.get(n)
        if i is None or i in needed or i in fold_idxs:
            continue
        needed.add(i)
        stack.extend(m for m in block.ops[i].input_arg_names if m)
    fold_outs = frozenset(n for i in fold_idxs
                          for n in block.ops[i].output_arg_names if n)
    return frozenset(fold_idxs), frozenset(needed), fold_outs


def block_is_traceable(block) -> bool:
    """True if every op lowers to pure XLA (recursively through
    while/conditional_block sub-blocks). Const-foldable host ops with
    static inputs don't count against a block (_fold_plan)."""
    return not untraceable_reasons(block)


def untraceable_reasons(block) -> List[str]:
    """Blocking op types (with reason tags) that keep this block off the
    whole-compile path — surfaced by the executor's fallback warning so a
    30x interpreter cliff is never silent."""
    infos = OpInfoMap.instance()
    fold_idxs = _fold_plan(block)[0]
    reasons: List[str] = []
    for i, op in enumerate(block.ops):
        sb = op.attrs.get("sub_block")
        if op.type in ("while", "conditional_block"):
            if sb is None:
                reasons.append("%s (no sub_block)" % op.type)
            else:
                reasons.extend("%s>%s" % (op.type, r)
                               for r in untraceable_reasons(sb))
            continue
        try:
            info = infos.get(op.type)
        except KeyError:
            reasons.append("%s (unregistered)" % op.type)
            continue
        if i in fold_idxs:
            continue
        if info.host_fn is not None:
            reasons.append("%s (host)" % op.type)
        elif info.needs_lod:
            reasons.append("%s (lod)" % op.type)
    return sorted(set(reasons))


def _trace_while(block, op, env: Dict, step_seed) -> None:
    """Lower the while op to lax.while_loop.

    Reference semantics (operators/controlflow/while_op.cc): the body
    writes parent-scope vars by name each trip. In SSA terms the loop
    carry is {Condition} ∪ {parent vars the body writes}; vars the body
    only reads are closed over; body temporaries stay inside the trace.
    An iteration counter rides in the carry so stateful ops (dropout)
    get a fresh RNG stream per trip.
    """
    import jax
    import jax.numpy as jnp

    sub_block = op.attrs["sub_block"]
    cond_name = op.input("Condition")[0]
    writes = _block_rw(sub_block)[0]
    carry_names = sorted({cond_name} | {n for n in writes if n in env})
    if cond_name not in env:
        raise NotImplementedError("while Condition %r not traced" % cond_name)

    def cond_fn(state):
        carry, _i = state
        return carry[cond_name].reshape(()).astype(bool)

    def body_fn(state):
        carry, i = state
        benv = dict(env)
        benv.update(carry)
        _trace_block(sub_block, benv,
                     step_seed + jnp.uint32(0x9E3779B9) * i.astype(jnp.uint32))
        return {n: benv[n] for n in carry_names}, i + 1

    init = ({n: env[n] for n in carry_names}, jnp.uint32(1))
    final_carry, _ = jax.lax.while_loop(cond_fn, body_fn, init)
    env.update(final_carry)


def _trace_conditional_block(block, op, env: Dict, step_seed) -> None:
    """Lower conditional_block to lax.cond: true branch traces the sub
    block, false branch keeps the carried vars unchanged."""
    import jax

    sub_block = op.attrs["sub_block"]
    cond_name = op.input("Cond")[0]
    if not op.attrs.get("is_scalar_condition", True):
        raise NotImplementedError("non-scalar conditional_block")
    writes = _block_rw(sub_block)[0]
    carry_names = sorted(n for n in writes if n in env)

    def true_fn(carry):
        benv = dict(env)
        benv.update(carry)
        _trace_block(sub_block, benv, step_seed)
        return {n: benv[n] for n in carry_names}

    def false_fn(carry):
        return carry

    pred = env[cond_name].reshape(()).astype(bool)
    out = jax.lax.cond(pred, true_fn, false_fn,
                       {n: env[n] for n in carry_names})
    env.update(out)


def _trace_block(block, env: Dict, step_seed) -> None:
    _trace_ops(block, block.ops, env, step_seed)


def _trace_ops(block, ops, env: Dict, step_seed) -> None:
    """Trace a specific op sequence (a whole block, or one pipeline
    stage's slice of it) into the running jax trace.

    Const-foldable host ops (range with constant bounds) are
    pre-evaluated on the host and embedded as XLA literals — applied
    here, not in a wrapper, so every trace entry point (whole program,
    data-parallel shard, pipeline stage slice) gets the same treatment,
    and the same op-role scopes.
    """
    infos = OpInfoMap.instance()
    fold_vals = [None]

    def trace_one(op):
        if op.type == "while":
            _trace_while(block, op, env, step_seed)
            return
        if op.type == "conditional_block":
            _trace_conditional_block(block, op, env, step_seed)
            return
        info = infos.get(op.type)
        if info.host_fn is not None:
            if fold_vals[0] is None:
                import jax.numpy as jnp

                fold_vals[0] = {
                    n: jnp.asarray(v)
                    for n, v in _fold_block_values(block).items()}
            out_names = [n for n in op.output_arg_names if n]
            if out_names and all(n in fold_vals[0] for n in out_names):
                for n in out_names:
                    env[n] = fold_vals[0][n]
                return
            raise NotImplementedError(
                "host op %r cannot be traced (not const-foldable here)"
                % op.type)
        ins = {}
        for slot in info.inputs:
            names = op.input(slot.name)
            if not names:
                ins[slot.name] = None
                continue
            vals = [env.get(n) for n in names]
            ins[slot.name] = vals if slot.duplicable else vals[0]
        attrs = dict(op.attrs)
        attrs[BOUND_OUTPUTS_ATTR] = tuple(
            s.name for s in info.outputs if op.output(s.name)
        )
        if info.needs_rng:
            if int(attrs.get("seed", 0) or 0) > 0:
                import jax.numpy as jnp

                ins[RNG_SEED_ATTR] = jnp.uint32(attrs["seed"])
            else:
                # _fwd_op_id: a grad op reuses its forward op's stream
                sid = attrs.get("_fwd_op_id", op._id or 0)
                ins[RNG_SEED_ATTR] = _op_seed(step_seed, sid)
        try:
            outs = info.fn(ins, attrs)
        except Exception as e:
            from .enforce import annotate_op_error

            annotate_op_error(e, op, "compiled trace")
            raise
        for slot in info.outputs:
            names = op.output(slot.name)
            if not names:
                continue
            o = outs.get(slot.name)
            if o is None:
                continue
            vals = o if slot.duplicable else [o]
            for n, v in zip(names, vals):
                if n and v is not None:
                    env[n] = v

    import jax

    from ..observability.profiler import classify_ops

    # every op is traced inside jax.named_scope("<role>/<op_type>"),
    # role = forward | backward | collective | optimizer, so a device
    # trace of any compiled step says which part of the step an
    # operation belongs to. named_scope adds NO equations, only
    # name-stack metadata: trace-time cost, nothing per step. An op built
    # inside ``fluid.name_scope`` has that scope as a third component.
    for op, role in zip(ops, classify_ops(block, ops)):
        inner = (op.attrs.get("op_namescope") or "").strip("/")
        with jax.named_scope("/".join(filter(None, (role, op.type, inner)))):
            trace_one(op)


import weakref

_fold_values_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _fold_block_values(block) -> Dict[str, np.ndarray]:
    """Evaluate the const-foldable subgraph once (host interpreter over a
    scratch scope) and cache the concrete outputs per block, invalidated
    by the owning program's version (same fingerprint compile_program
    keys on — op count alone misses same-count in-place edits)."""
    prog = getattr(block, "program", None)
    stamp = (_program_version(prog) if prog is not None
             else (len(block.ops),))
    hit = _fold_values_cache.get(block)
    if hit is not None and hit[0] == stamp:
        return hit[1]
    fold_idxs, needed, fold_outs = _fold_plan(block)
    values: Dict[str, np.ndarray] = {}
    if fold_idxs:
        from .executor_core import CoreExecutor
        from .place import CPUPlace

        # run each op eagerly (info.fn / host_fn directly, no jax.jit),
        # under ensure_compile_time_eval: _trace_block is usually already
        # inside an outer jit trace, where any jnp bind would otherwise
        # produce tracers — np.asarray on those raises.
        import jax

        scratch_exe = CoreExecutor(CPUPlace())
        scratch = Scope()
        infos = OpInfoMap.instance()
        with jax.ensure_compile_time_eval():
            for i in sorted(needed | fold_idxs):
                op = block.ops[i]
                info = infos.get(op.type)
                if info.host_fn is not None:
                    info.host_fn(scratch_exe, op, scratch)
                    continue
                ins = {}
                for slot in info.inputs:
                    names = op.input(slot.name)
                    if not names:
                        ins[slot.name] = None
                        continue
                    vals = [scratch_exe._read_var(scratch, n)
                            for n in names]
                    ins[slot.name] = vals if slot.duplicable else vals[0]
                attrs = dict(op.attrs)
                attrs[BOUND_OUTPUTS_ATTR] = tuple(
                    s.name for s in info.outputs if op.output(s.name))
                outs = info.fn(ins, attrs)
                for slot in info.outputs:
                    names = op.output(slot.name)
                    o = outs.get(slot.name) if names else None
                    if o is None:
                        continue
                    for n, v in zip(names,
                                    o if slot.duplicable else [o]):
                        if n and v is not None:
                            scratch_exe._write_var(scratch, n, v)
            for n in fold_outs:
                var = scratch.find_var(n)
                if var is not None and var.is_initialized():
                    values[n] = np.asarray(var.raw().array)
    try:
        _fold_values_cache[block] = (stamp, values)
    except TypeError:  # non-weakrefable block: skip caching
        pass
    return values


def compile_program(program, feed_names: Tuple[str, ...],
                    fetch_names: Tuple[str, ...], state_names: Tuple[str, ...],
                    out_state_names: Tuple[str, ...], donate: bool = True):
    """Build (and cache) the jitted step function for this program."""
    import time

    import jax

    key = (_program_version(program), feed_names, fetch_names, state_names,
           out_state_names)
    fn = _lru_get(_cache, key)
    if fn is not None:
        return fn

    from .. import observability as _obs

    # a fresh jit closure == a retrace + XLA compile at first call; a
    # steady-state training loop should see exactly one of these, so
    # growth of this counter mid-run IS a recompile storm
    _obs.inc("executor.compiles")

    block = program.global_block()

    def step(state: Dict, feeds: Dict, step_seed):
        # trace-time side effect: jax.jit re-enters this Python body
        # once per novel input-shape signature, so this counts actual
        # XLA (re)traces — `executor.compiles` above counts only fresh
        # jit closures and stays flat while a shape-churning caller
        # (e.g. unbucketed serving batches) compiles over and over.
        # The serving CI smoke asserts this equals the bucket-ladder
        # size, not the number of distinct observed batch sizes.
        _obs.inc("executor.jit_traces")
        env = dict(state)
        env.update(feeds)
        t_trace = time.perf_counter()
        try:
            with _obs.tracing.span("executor/trace", cat="step"):
                _trace_block(block, env, step_seed)
        except (NotImplementedError, TypeError) as e:
            # raised while TRACING the block (lax.while_loop rejecting
            # a varying carry raises TypeError); lowering and compiling
            # happen after this function returns
            raise UntraceableProgramError(
                "program %s cannot be traced whole: %r"
                % (program._uid, e)) from e
        # the Python trace of the block: every process pays it before
        # XLA's persistent cache can answer
        _obs.inc("executor.trace_s", time.perf_counter() - t_trace)
        new_state = {n: env[n] for n in out_state_names if n in env}
        fetches = [env[n] for n in fetch_names]
        return fetches, new_state

    from .compile_cache import scoped_name

    step.__name__ = scoped_name("step")   # the name is in XLA's cache key
    fn = jax.jit(step, donate_argnums=(0,) if donate else ())
    _lru_put(_cache, key, fn, _CACHE_CAP)
    return fn


def _stage_compiled_call(core, device, program, scope: Scope, feed: Dict,
                         fetch_list: Sequence):
    """The jitted step for this (program, feed, fetch) signature and
    the arguments to call it with: ``(fn, (state, feed_vals, seed),
    fetch_names)``. Shared by ``run_compiled_program`` (which calls
    it) and ``lower_compiled_program`` (which only lowers it).
    ``device`` is the executor's place, resolved once by the caller."""
    import jax
    import jax.numpy as jnp

    import time as _time

    from .. import observability as _obs

    fetch_names = tuple(f if isinstance(f, str) else f.name
                        for f in fetch_list)
    # feed staging: LoDTensor / jax.Array feeds are already device
    # values and pass through untouched (the async feed pipeline —
    # core/native_feed.AsyncDeviceFeeder — hands exactly those in, so
    # its H2D work never lands on this step's critical path; the old
    # np.asarray round-trip would have pulled a staged array back to
    # host). Host numpy feeds pay their H2D here, measured as
    # executor.feed_ms so the profiler can attribute it. They are
    # staged on the executor's OWN device: under TPUPlace(1) a feed put
    # on the default device (chip 0) would cross chips every step.
    t_feed = _time.perf_counter() if _obs.enabled() else None
    feed_vals = {}
    with jax.default_device(device):
        for name, value in feed.items():
            if isinstance(value, LoDTensor):
                if value.lod():
                    raise UntraceableProgramError(
                        "LoD feeds use the interpreter")
                feed_vals[name] = value.array
            elif isinstance(value, jax.Array):
                feed_vals[name] = value
            else:
                feed_vals[name] = jnp.asarray(np.asarray(value))
    if t_feed is not None:
        _obs.observe("executor.feed_ms",
                     (_time.perf_counter() - t_feed) * 1e3)
    feed_names = tuple(sorted(feed_vals))

    read_first, written, persist_written = _analyze(program)
    state_names = []
    state = {}
    for n in sorted(read_first - set(feed_names)):
        var = scope.find_var(n)
        if var is None or not var.is_initialized():
            raise RuntimeError(
                "variable %r must be fed or initialized in scope" % n)
        h = var.raw()
        if not isinstance(h, LoDTensor):
            raise UntraceableProgramError("non-dense state %r" % n)
        state[n] = h.array
        state_names.append(n)
    state_names = tuple(state_names)
    # every written persistable (params from startup programs, optimizer
    # state, BN running stats) must land back in the scope
    out_state_names = tuple(sorted(set(state_names) | persist_written))

    fn = compile_program(program, feed_names, fetch_names, state_names,
                         out_state_names)
    seed = jnp.uint32(core.rng.next_seed(0)
                      ^ (core.rng.step * 2654435761 & 0xFFFFFFFF))
    return fn, (state, feed_vals, seed), fetch_names


def lower_compiled_program(core, program, scope: Scope, feed: Dict,
                           fetch_list: Sequence):
    """The ``jax.stages.Lowered`` of the step ``run_compiled_program``
    would run for these inputs — for inspecting what the step contains
    (e.g. that a Pallas kernel went in as a Mosaic custom call and not
    as its XLA reference). Executes nothing and leaves the scope and
    the RNG stream untouched."""
    import jax

    device = core.place.jax_device()
    fn, args, _ = _stage_compiled_call(core, device, program, scope, feed,
                                       fetch_list)
    with jax.default_device(device):
        return fn.lower(*args)


def run_compiled_program(core, program, scope: Scope, feed: Dict,
                         fetch_list: Sequence, return_numpy: bool = True):
    """One compiled step. Each part of the host's work is a span of its
    own (per-op detail lives in the XPlane device trace; the op-by-op
    interpreter records per-op spans): under a live ``jax.profiler``
    trace they say what the host was doing whenever the device idled."""
    import jax

    from .. import observability as _obs

    span = _obs.tracing.span
    device = core.place.jax_device()
    with span("executor/stage", cat="step"):
        fn, args, fetch_names = _stage_compiled_call(
            core, device, program, scope, feed, fetch_list)
    # returns once the step is enqueued (or, at a new shape, traced
    # and compiled): from here to the first device operation
    with jax.default_device(device), span("executor/launch", cat="step"):
        fetches, new_state = fn(*args)
    core.rng.advance()
    _obs.inc("executor.steps", path="compiled")

    with span("executor/writeback", cat="step"):
        for n, v in new_state.items():
            scope.var(n).get_tensor()._array = v
        tensors = []
        for name, v in zip(fetch_names, fetches):
            t = scope.var(name).get_tensor()
            t._array = v
            tensors.append(t)
    if not return_numpy:
        return tensors
    # the wait for the device and the copy to the host
    with span("executor/fetch", cat="step"):
        return [np.asarray(v) for v in fetches]
