"""Op-by-op program interpreter (the fallback executor).

Counterpart of the reference C++ Executor hot loop
(/root/reference/paddle/fluid/framework/executor.cc:195,449: create ops
from descs, ``for op in ops: op->Run(scope, place)``). TPU-native twists:

- Each (op type, attrs) pair is jitted once and cached; jax's own aval
  cache handles shape specialization. Kernels enqueue async on the device
  — the host loop races ahead exactly like the reference's stream model.
- Stateful RNG ops receive a traced uint32 seed derived from a host
  counter, so repeated steps don't recompile and dropout masks vary.
- Ops marked ``host_op`` (control flow, feed/fetch, prints) run on the
  host against the Scope, possibly recursing into sub-blocks — the same
  role the reference's OperatorBase (kernel-less) ops play.

The preferred path for steady-state training is whole-program compilation
(compiler_engine.py); this interpreter exists for arbitrary programs,
debugging, and parity with Executor semantics.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from .registry import (
    BOUND_OUTPUTS_ATTR,
    LOD_ATTR_PREFIX,
    RNG_SEED_ATTR,
    OpInfoMap,
)
from .scope import Scope
from .tensor import LoDTensor, LoDTensorArray, SelectedRows

_jit_cache: Dict = {}


def _canon(v):
    if isinstance(v, (list, tuple)):
        return tuple(_canon(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _canon(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return (v.dtype.str, v.shape, v.tobytes())
    return v


def _get_jitted(op_type: str, attrs: Dict):
    import jax

    key = (op_type, _canon(attrs))
    fn = _jit_cache.get(key)
    if fn is None:
        info = OpInfoMap.instance().get(op_type)

        def call(ins, _info=info, _attrs=dict(attrs)):
            return _info.fn(ins, _attrs)

        fn = jax.jit(call)
        _jit_cache[key] = fn
    return fn


class RNGState:
    """Host-side seed counter; folded per-op-id so every RNG op in a step
    draws a distinct stream, and every step advances."""

    def __init__(self, seed: int = 0):
        self.seed = seed or np.random.randint(1, 2**31 - 1)
        self.step = 0

    def next_seed(self, op_id: int) -> np.uint32:
        s = np.uint32((self.seed * 1000003 + self.step * 8191 + op_id * 131) & 0xFFFFFFFF)
        return s

    def advance(self):
        self.step += 1


_obs_cache = []


def _obs_module():
    """Lazy module ref (a top-level import would be circular; importing
    per run_op call would tax the interpreter hot loop)."""
    if not _obs_cache:
        from .. import observability

        _obs_cache.append(observability)
    return _obs_cache[0]


class CoreExecutor:
    def __init__(self, place):
        self.place = place
        self.rng = RNGState()
        # (program version, protect set) -> eager-GC plan
        self._gc_plan_cache: Dict = {}

    # -- variable IO ------------------------------------------------------

    def _read_var(self, scope: Scope, name: str):
        if name in ("", "@EMPTY@"):
            return None
        var = scope.find_var(name)
        if var is None or not var.is_initialized():
            return None
        h = var.raw()
        if isinstance(h, LoDTensor):
            return h.array
        if isinstance(h, SelectedRows):
            return h  # host ops deal with these directly
        return h

    def _write_var(self, scope: Scope, name: str, value, lod=None):
        if name in ("", "@EMPTY@") or value is None:
            return
        var = scope.var(name)
        if isinstance(value, (LoDTensor, SelectedRows, LoDTensorArray)):
            var.set(value)
            return
        t = var.get_tensor() if isinstance(var.raw(), (LoDTensor, type(None))) else None
        if t is None:
            var.set(LoDTensor())
            t = var.get_tensor()
        t.set(value)
        if lod is not None:
            t._lod = [list(l) for l in lod]

    # -- op execution -----------------------------------------------------

    def run_op(self, op, scope: Scope):
        obs = _obs_module()
        try:
            if obs.tracing.active():
                # per-op host span: feeds both the legacy profiler
                # session table and the unified chrome-trace export
                with obs.tracing.span(op.type, cat="op"):
                    self._run_op_impl(op, scope)
            else:
                self._run_op_impl(op, scope)
            if obs.enabled():
                obs.inc("executor.ops", type=op.type)
            return None
        except Exception as e:
            # EnforceNotMet ergonomics (reference operator.cc catch):
            # every kernel failure carries the op's signature; the
            # original exception type survives for caller handling
            from .enforce import annotate_op_error

            annotate_op_error(e, op, "execution")
            raise

    def _run_op_impl(self, op, scope: Scope):
        info = OpInfoMap.instance().get(op.type)

        if getattr(info, "host_fn", None) is not None:
            info.host_fn(self, op, scope)
            self._maybe_check_nan_inf(op, scope)
            return

        ins = {}
        in_lods = {}
        for slot in info.inputs:
            names = op.input(slot.name)
            if not names:
                ins[slot.name] = None
                continue
            # one scope lookup per name: value AND LoD come off the same
            # handle. LoD is collected for EVERY op, not just needs_lod
            # consumers — infer_lod="propagate" must carry LoD through
            # intermediate ops (embedding between a feed and
            # sequence_pool)
            vals, lods = [], []
            for n in names:
                var = (scope.find_var(n)
                       if n not in ("", "@EMPTY@") else None)
                h = (var.raw()
                     if var is not None and var.is_initialized() else None)
                if isinstance(h, LoDTensor):
                    vals.append(h.array)
                    lods.append(tuple(tuple(l) for l in h.lod())
                                if h.lod() else ())
                else:
                    vals.append(h)
                    lods.append(())
            if any(lods):
                in_lods[slot.name] = tuple(lods)
            ins[slot.name] = vals if slot.duplicable else vals[0]

        attrs = dict(op.attrs)
        attrs[BOUND_OUTPUTS_ATTR] = tuple(
            s.name for s in info.outputs if op.output(s.name)
        )
        if info.needs_lod:
            for k, v in in_lods.items():
                attrs[LOD_ATTR_PREFIX + k] = v

        # SelectedRows operands (sparse embedding grads) can't cross a
        # jit boundary — run the op's python body eagerly; supporting
        # ops (sum, sgd, merge_selected_rows...) isinstance-dispatch on
        # them, mirroring the reference kernels' SelectedRows overloads
        has_sr = any(
            isinstance(v, SelectedRows)
            for vs in ins.values() if vs is not None
            for v in (vs if isinstance(vs, list) else [vs]))
        if has_sr:
            outs = info.fn(ins, attrs)
        else:
            fn = _get_jitted(op.type, attrs)
            if info.needs_rng:
                import jax.numpy as jnp

                if int(attrs.get("seed", 0) or 0) > 0:
                    seed_val = np.uint32(attrs["seed"])
                else:
                    # A grad op reuses its forward op's stream (attr set
                    # by backward.py) so e.g. dropout masks match
                    # fwd/bwd.
                    seed_id = attrs.get("_fwd_op_id", op._id or 0)
                    seed_val = self.rng.next_seed(seed_id)
                ins = dict(ins)
                ins[RNG_SEED_ATTR] = jnp.asarray(seed_val, dtype=jnp.uint32)

            outs = fn(ins)

        out_lods = self._infer_out_lods(info, op, in_lods, attrs)
        for slot in info.outputs:
            names = op.output(slot.name)
            if not names:
                continue
            o = outs.get(slot.name)
            if o is None:
                continue
            vals = o if slot.duplicable else [o]
            for i, (n, v) in enumerate(zip(names, vals)):
                lod = out_lods.get((slot.name, i))
                # consistency guard: a propagated lod only attaches when
                # the output's row count matches it. Without this, a
                # grad op propagates a SEQUENCE lod onto the [V, D]
                # table grad, sgd copies it onto the param, and the next
                # batch's lookup reads the STALE lod off the table slot
                # (the multi-batch ragged-training bug).
                if lod is not None and hasattr(v, "shape"):
                    total = lod[-1][-1] if (lod and len(lod[-1])) else 0
                    if len(v.shape) == 0 or int(v.shape[0]) != int(total):
                        lod = None
                # a PERSISTABLE output (param / optimizer state) never
                # carries a sequence lod: a table grad whose row count
                # HAPPENS to equal a batch's token total would otherwise
                # stamp a sequence lod onto the table, poisoning later
                # batches' propagate (row-count guard can't catch the
                # coincidence)
                if lod is not None:
                    bv = op.block._find_var_recursive(n) \
                        if getattr(op, "block", None) is not None else None
                    if bv is not None and getattr(bv, "persistable",
                                                  False):
                        lod = None
                # no inferred lod -> CLEAR any stale lod on the reused
                # scope tensor rather than silently keeping it
                self._write_var(scope, n, v,
                                lod=lod if lod is not None else ())
        self._maybe_check_nan_inf(op, scope)

    def _maybe_check_nan_inf(self, op, scope):
        """FLAGS_check_nan_inf (reference operator.cc:1032): validate
        every float output of the op just executed."""
        from .flags import flag

        if not flag("check_nan_inf"):
            return
        import jax.numpy as jnp

        from .enforce import EnforceNotMet
        from .tensor import LoDTensor

        for n in op.output_arg_names:
            var = scope.find_var(n)
            if var is None or not var.is_initialized():
                continue
            h = var.raw()
            if isinstance(h, SelectedRows):
                # validate the value tensor of a sparse grad too — the
                # reference's checker walks SelectedRows values as well
                h = h.get_tensor()
            if not isinstance(h, LoDTensor) or h.array is None:
                continue
            arr = h.array
            if hasattr(arr, "dtype") and jnp.issubdtype(arr.dtype,
                                                        jnp.floating):
                if not bool(jnp.all(jnp.isfinite(arr))):
                    raise EnforceNotMet(
                        "Operator %r output %r contains Inf/Nan "
                        "(FLAGS_check_nan_inf)" % (op.type, n))

    def _infer_out_lods(self, info, op, in_lods, attrs):
        out_lods: Dict = {}
        if info.infer_lod is None:
            return out_lods
        if callable(info.infer_lod):
            res = info.infer_lod(in_lods, attrs) or {}
            for (slot, i), lod in res.items():
                out_lods[(slot, i)] = lod
            return out_lods
        # "propagate": first NON-PERSISTABLE input slot's lod flows to
        # every output (a param slot like lookup_table's W must never
        # be the lod source — see the persistable-output guard).
        src = None
        blk = getattr(op, "block", None)
        for slot in info.inputs:
            lods = in_lods.get(slot.name)
            if lods and lods[0]:
                names = op.input(slot.name)
                if blk is not None and names:
                    bv = blk._find_var_recursive(names[0])
                    if bv is not None and getattr(bv, "persistable",
                                                  False):
                        continue
                src = lods[0]
                break
        if src:
            for slot in info.outputs:
                for i in range(len(op.output(slot.name))):
                    out_lods[(slot.name, i)] = src
        return out_lods

    # -- block / program --------------------------------------------------

    def run_block(self, block, scope: Scope, gc_plan=None):
        import jax

        with jax.default_device(self.place.jax_device()):
            for i, op in enumerate(block.ops):
                self.run_op(op, scope)
                if gc_plan is not None:
                    for name in gc_plan.get(i, ()):
                        scope.erase(name)

    @staticmethod
    def _build_gc_plan(program, protect):
        """Eager-deletion plan (reference framework/garbage_collector.cc
        + eager_deletion_pass): op index -> names whose LAST use that op
        is. Protected: feeds/fetches/persistables, and any name touched
        inside a sub-block (while/cond bodies read parent-scope vars the
        top-level scan can't see)."""
        sub_used = set()
        for b in program.blocks[1:]:
            for op in b.ops:
                sub_used.update(op.input_arg_names)
                sub_used.update(op.output_arg_names)
        block = program.global_block()
        last_use: Dict[str, int] = {}
        for i, op in enumerate(block.ops):
            for name in list(op.input_arg_names) + list(
                    op.output_arg_names):
                last_use[name] = i
        plan: Dict[int, list] = {}
        for name, i in last_use.items():
            if name in protect or name in sub_used:
                continue
            v = block._find_var_recursive(name)
            if v is None or getattr(v, "persistable", False):
                continue
            plan.setdefault(i, []).append(name)
        return plan

    def run_program(
        self,
        program,
        scope: Scope,
        feed: Optional[Dict] = None,
        fetch_list: Optional[Sequence] = None,
        return_numpy: bool = True,
    ):
        obs = _obs_module()
        t_step = time.perf_counter() if obs.enabled() else None
        feed = feed or {}
        for name, value in feed.items():
            if isinstance(value, LoDTensor):
                self._write_var(scope, name, value)
            else:
                self._write_var(scope, name, np.asarray(value))

        gc_plan = None
        from .flags import get_flags

        if get_flags("FLAGS_eager_delete_tensor_gb")[
                "FLAGS_eager_delete_tensor_gb"] >= 0:
            protect = frozenset(feed) | frozenset(
                (f if isinstance(f, str) else f.name)
                for f in (fetch_list or []))
            from .compiler_engine import _program_version

            key = (_program_version(program), protect)
            gc_plan = self._gc_plan_cache.get(key)
            if gc_plan is None:
                gc_plan = self._build_gc_plan(program, protect)
                # bounded LRU: old program versions keep dead keys alive
                # in long-lived executors that mutate programs
                if len(self._gc_plan_cache) >= 64:
                    self._gc_plan_cache.pop(
                        next(iter(self._gc_plan_cache)))
                self._gc_plan_cache[key] = gc_plan
            else:
                self._gc_plan_cache[key] = self._gc_plan_cache.pop(key)
        with obs.tracing.span("executor/step", cat="step",
                              path="interpreter"):
            self.run_block(program.global_block(), scope, gc_plan=gc_plan)
        self.rng.advance()
        if t_step is not None:
            obs.inc("executor.steps", path="interpreter")
            obs.observe("executor.step_ms",
                        (time.perf_counter() - t_step) * 1e3,
                        path="interpreter")

        results = []
        for f in fetch_list or []:
            name = f if isinstance(f, str) else f.name
            var = scope.find_var(name)
            if var is None:
                raise RuntimeError("fetch variable %r not produced" % name)
            h = var.raw()
            if isinstance(h, LoDTensor):
                results.append(h.numpy() if return_numpy else h)
            elif isinstance(h, SelectedRows):
                results.append(np.asarray(h.to_dense()))
            else:
                results.append(h)
        return results
