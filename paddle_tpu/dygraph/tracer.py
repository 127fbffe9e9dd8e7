"""Eager Tracer + tape autograd engine.

Parity: /root/reference/paddle/fluid/imperative/tracer.cc:45 (TraceOp:
run the op eagerly, tape a grad node when any input requires grad) and
basic_engine.cc:159 (queue-driven backward with GradientAccumulator).

TPU-native formulation: the "grad node" is the `jax.vjp` pullback of the
op's pure function, captured at forward time (residuals live on device);
backward walks the tape in reverse calling pullbacks and summing
cotangents — BasicEngine + GradientAccumulator without a second set of
grad kernels. ClearBackwardTrace == dropping the tape (frees residuals).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.registry import (
    BOUND_OUTPUTS_ATTR,
    RNG_SEED_ATTR,
    OpInfoMap,
)
from .varbase import ParamBase, VarBase

_active_tracer: Optional["Tracer"] = None

_obs_cache: List = []


def _obs():
    """Cached observability module ref (same idiom as executor_core):
    trace_op is the eager hot path."""
    if not _obs_cache:
        from .. import observability

        _obs_cache.append(observability)
    return _obs_cache[0]


# content digests of ndarray-valued attrs, memoized per array OBJECT
# (weakref-guarded against id reuse): layer attrs are the same arrays
# every step, and re-hashing them on every trace put O(bytes) sha1
# work on the lazy hot path — at dygraph_bert scale, thousands of
# times per step. Contract: an array used as an op attr is immutable
# once traced (the same contract the jit caches keyed on this
# signature already rely on — mutating it in place would stale THEM,
# cached digest or not).
_ndarray_digests: Dict[int, Tuple] = {}
_NDARRAY_DIGEST_CAP = 4096


def _ndarray_digest(v: np.ndarray) -> Tuple:
    key = id(v)
    hit = _ndarray_digests.get(key)
    if hit is not None and hit[0]() is v:
        return hit[1]
    import hashlib
    import weakref

    d = ("ndarray", tuple(v.shape), v.dtype.str,
         hashlib.sha1(np.ascontiguousarray(v).tobytes()).hexdigest())
    try:
        ref = weakref.ref(v)
    except TypeError:
        return d  # non-weakrefable subclass: no safe identity guard
    if len(_ndarray_digests) >= _NDARRAY_DIGEST_CAP:
        # drop dead entries first; if ALL are live, reset (bounded)
        dead = [k for k, (r, _d) in _ndarray_digests.items()
                if r() is None]
        for k in dead:
            del _ndarray_digests[k]
        if len(_ndarray_digests) >= _NDARRAY_DIGEST_CAP:
            _ndarray_digests.clear()
    _ndarray_digests[key] = (ref, d)
    return d


def _canon_attr(v):
    """Hashable, content-faithful canonical form of an attr value for
    cache signatures. Array-valued attrs hash by CONTENT (shape +
    dtype + digest of the bytes): ``repr`` elides interior elements of
    large arrays, which can alias two different ops onto one cached
    compiled graph — a silent wrong-answer bug. The digest is memoized
    per array object (``_ndarray_digest``) so steady-state traces stop
    re-hashing the same attrs every step."""
    if isinstance(v, np.ndarray):
        return _ndarray_digest(v)
    if isinstance(v, (list, tuple)):
        return tuple(_canon_attr(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _canon_attr(x)) for k, x in v.items()))
    return v


def attrs_signature(attrs: Dict) -> str:
    """Stable signature of an op's attr dict, safe for jit-cache keys."""
    return repr(sorted((k, _canon_attr(v)) for k, v in attrs.items()))


def current_tracer() -> Optional["Tracer"]:
    return _active_tracer


def _set_tracer(t):
    global _active_tracer
    _active_tracer = t


class TapeRecord:
    __slots__ = ("op_type", "vjp_fn", "in_vars", "out_vars", "fwd_fn",
                 "lazy_vjp", "__weakref__")

    def __init__(self, op_type, vjp_fn, in_vars, out_vars, fwd_fn=None,
                 lazy_vjp=None):
        self.op_type = op_type
        self.vjp_fn = vjp_fn  # pullback: (cotangents,) -> input grads
        self.in_vars = in_vars  # [VarBase] aligned with pullback results
        self.out_vars = out_vars  # [VarBase] aligned with cotangent order
        # pure forward (primals -> flat outputs); lets higher-order grads
        # re-derive the pullback WITH its primal dependence (the saved
        # vjp_fn treats residuals as constants)
        self.fwd_fn = fwd_fn
        # lazy mode: (cot_handles) -> [grad PendingValues] — queues a
        # vjp node on the LazyEngine instead of computing eagerly
        self.lazy_vjp = lazy_vjp


class BasicEngine:
    """Backward over the tape (reference imperative/basic_engine.cc:159)."""

    def __init__(self, tracer):
        self.tracer = tracer

    def backward(self, loss: VarBase, retain_graph=False):
        import jax.numpy as jnp

        tape = self.tracer.tape
        if loss._array is None:
            raise ValueError("backward() on uninitialized VarBase")
        if self.tracer.lazy_engine is not None:
            return self._backward_lazy(loss, retain_graph)
        grads: Dict[int, object] = {id(loss): jnp.ones_like(loss._array)}
        alive: Dict[int, VarBase] = {id(loss): loss}
        for rec in reversed(tape):
            needed = any(id(ov) in grads for ov in rec.out_vars)
            if not needed:
                continue
            cots = tuple(
                grads.get(id(ov), None) if grads.get(id(ov)) is not None
                else jnp.zeros_like(ov._array)
                for ov in rec.out_vars
            )
            in_grads = rec.vjp_fn(cots)
            for iv, g in zip(rec.in_vars, in_grads):
                prev = grads.get(id(iv))
                grads[id(iv)] = g if prev is None else prev + g
                alive[id(iv)] = iv
        # deposit on leaves (non-stop-gradient vars keep .grad)
        for vid, v in alive.items():
            if not v.stop_gradient and vid in grads:
                g = grads[vid]
                v._grad = g if v._grad is None else v._grad + g
        if not retain_graph:
            self.tracer.tape.clear()

    def _backward_lazy(self, loss: VarBase, retain_graph=False):
        """Same tape walk, but every pullback/accumulation is QUEUED on
        the LazyEngine (lazy.py) — the whole backward becomes part of
        the one compiled step."""
        import jax.numpy as jnp

        eng = self.tracer.lazy_engine
        tape = self.tracer.tape

        grads: Dict[int, object] = {id(loss): eng.ones_like(loss._array)}
        alive: Dict[int, VarBase] = {id(loss): loss}
        for rec in reversed(tape):
            if not any(id(ov) in grads for ov in rec.out_vars):
                continue
            cots = tuple(
                grads[id(ov)] if grads.get(id(ov)) is not None
                else eng.zeros_like(ov._array)
                for ov in rec.out_vars)
            if rec.lazy_vjp is not None:
                in_grads = rec.lazy_vjp(cots)
            else:
                # eager-style record: force cotangents concrete, run
                # its pullback eagerly
                from .lazy import is_pending

                cots = tuple(c.force() if is_pending(c) else c
                             for c in cots)
                in_grads = rec.vjp_fn(cots)
            for iv, g in zip(rec.in_vars, in_grads):
                prev = grads.get(id(iv))
                grads[id(iv)] = g if prev is None else eng.add(prev, g)
                alive[id(iv)] = iv
        for vid, v in alive.items():
            if not v.stop_gradient and vid in grads:
                g = grads[vid]
                if v._grad is None:
                    v._grad = g
                else:
                    v._grad = eng.add(v._grad, g)
        if not retain_graph:
            self.tracer.tape.clear()


class Tracer:
    def __init__(self, lazy=False):
        self.tape: List[TapeRecord] = []
        self.engine = BasicEngine(self)
        self._params: Dict[str, ParamBase] = {}
        self._no_grad = False
        self.train_mode = True
        self._seed_counter = np.random.randint(1, 2**31 - 1)
        # ProgramDesc recording (reference imperative/jit/
        # program_desc_tracer.cc): when set, every traced op is ALSO
        # appended to this Program so jit.save / dygraph_to_static can
        # emit a static graph
        self._recording_program = None
        # lazy (queued) dispatch: ops queue on a LazyEngine and flush
        # as ONE compiled call (lazy.py) — ~40 dispatches/step -> 1
        self.lazy_engine = None
        if lazy:
            from .lazy import LazyEngine

            self.lazy_engine = LazyEngine()
        # (op_type, attrs_sig, in_avals) -> (out_avals, struct)
        self._aval_cache: Dict = {}
        # (aval_cache_key, stop_gradient pattern) -> wrt positions
        self._wrt_cache: Dict = {}

    def flush(self):
        if self.lazy_engine is not None:
            self.lazy_engine.flush()

    # -- ProgramDesc recording --------------------------------------------
    def start_program_recording(self, program):
        self.flush()   # recording runs ops eagerly; settle the queue
        self._recording_program = program

    def stop_program_recording(self):
        prog = self._recording_program
        self._recording_program = None
        return prog

    def _record_var(self, vb: VarBase, block):
        if not block.has_var_local(vb.name):
            shape = tuple(vb._array.shape) if vb._array is not None else None
            dtype = str(vb._array.dtype) if vb._array is not None \
                else "float32"
            if isinstance(vb, ParamBase):
                v = block.create_var(name=vb.name, shape=shape,
                                     dtype=dtype, persistable=True)
                v.stop_gradient = vb.stop_gradient
            else:
                block.create_var(name=vb.name, shape=shape, dtype=dtype)
        return vb.name

    def _record_op(self, op_type, var_map, result, attrs):
        block = self._recording_program.global_block()
        ins = {}
        for slot, vs in var_map.items():
            if vs is None:
                continue
            vlist = vs if isinstance(vs, list) else [vs]
            ins[slot] = [self._record_var(v, block) for v in vlist]
        outs = {slot: [self._record_var(v, block) for v in vs]
                for slot, vs in result.items()}
        clean = {k: v for k, v in (attrs or {}).items()
                 if k != BOUND_OUTPUTS_ATTR}
        block.append_op(op_type, inputs=ins, outputs=outs, attrs=clean,
                        infer_shape=False)

    # -- parameter registry (LayerHelper uses this in dygraph mode) -------
    def register_parameter(self, p: ParamBase):
        self._params[p.name] = p

    def get_parameter(self, name) -> Optional[ParamBase]:
        return self._params.get(name)

    def all_parameters(self):
        return list(self._params.values())

    # -- no-grad switch ---------------------------------------------------
    def no_grad_guard(self):
        import contextlib

        @contextlib.contextmanager
        def _g():
            old = self._no_grad
            self._no_grad = True
            try:
                yield
            finally:
                self._no_grad = old

        return _g()

    # -- core: trace one op ----------------------------------------------
    def trace_op(self, op_type, inputs, outputs=None, attrs=None,
                 stop_gradient=False):
        """Execute op eagerly; returns {slot: [VarBase]}.

        `outputs` may pre-name slots (ignored values) — kept for
        LayerHelper compatibility; fresh VarBases are always returned and
        (when given) copied into provided VarBases.
        """
        import jax
        import jax.numpy as jnp

        info = OpInfoMap.instance().get(op_type)
        if info.host_fn is not None:
            raise RuntimeError("host op %r is not usable in dygraph" % op_type)

        use_lazy = (self.lazy_engine is not None
                    and self._recording_program is None)
        obs = _obs()
        if obs.enabled():
            obs.inc("dygraph.ops",
                    dispatch="lazy" if use_lazy else "eager")
        if use_lazy:
            return self._trace_op_lazy(info, op_type, inputs, outputs,
                                       attrs, stop_gradient)

        def as_var(v):
            return v if isinstance(v, VarBase) else VarBase(v, stop_gradient=True)

        in_map: Dict[str, object] = {}
        var_map: Dict[str, object] = {}
        for slot in info.inputs:
            arg = (inputs or {}).get(slot.name)
            if arg is None or (isinstance(arg, (list, tuple)) and not arg):
                in_map[slot.name] = None
                var_map[slot.name] = None
                continue
            vs = [as_var(a) for a in (arg if isinstance(arg, (list, tuple))
                                      else [arg])]
            var_map[slot.name] = vs if slot.duplicable else vs[0]
            arrs = [v._array for v in vs]
            in_map[slot.name] = arrs if slot.duplicable else arrs[0]

        attrs = dict(attrs or {})
        if outputs:
            attrs[BOUND_OUTPUTS_ATTR] = tuple(
                s.name for s in info.outputs if s.name in outputs)
        else:
            attrs[BOUND_OUTPUTS_ATTR] = tuple(s.name for s in info.outputs)
        if info.needs_rng:
            self._seed_counter += 1
            in_map[RNG_SEED_ATTR] = jnp.uint32(
                max(int(attrs.get("seed", 0) or 0), 0)
                or (self._seed_counter & 0xFFFFFFFF))
            if "is_test" in info.attrs and "is_test" not in attrs:
                attrs["is_test"] = not self.train_mode

        # differentiable leaves
        wrt: List[Tuple[str, int]] = []
        if not self._no_grad and not stop_gradient and info.grad is not None:
            for slot in info.inputs:
                if slot.no_grad:
                    continue
                vs = var_map.get(slot.name)
                if vs is None:
                    continue
                for i, v in enumerate(vs if isinstance(vs, list) else [vs]):
                    if not v.stop_gradient and jnp.issubdtype(
                            np.dtype(v._array.dtype), jnp.floating):
                        wrt.append((slot.name, i))
        requires_grad = bool(wrt)

        struct_holder: List[Tuple[str, int]] = []

        def fwd_flat(*diff_vals):
            rebuilt = {k: (list(v) if isinstance(v, list) else v)
                       for k, v in in_map.items()}
            for (slot, i), val in zip(wrt, diff_vals):
                if isinstance(rebuilt[slot], list):
                    rebuilt[slot][i] = val
                else:
                    rebuilt[slot] = val
            outs = info.fn(rebuilt, attrs)
            flat, struct = [], []
            for s in info.outputs:
                o = outs.get(s.name)
                if o is None:
                    continue
                if s.duplicable:
                    flat.extend(o)
                    struct.append((s.name, len(o)))
                else:
                    flat.append(o)
                    struct.append((s.name, 1))
            struct_holder.clear()
            struct_holder.extend(struct)
            return tuple(flat)

        if requires_grad:
            primals = []
            in_vars = []
            for slot, i in wrt:
                v = var_map[slot]
                vb = v[i] if isinstance(v, list) else v
                primals.append(vb._array)
                in_vars.append(vb)
            flat_out, vjp_fn = jax.vjp(fwd_flat, *primals)
        else:
            flat_out = fwd_flat()
            vjp_fn, in_vars = None, []

        # Reuse caller-provided VarBases as the outputs so downstream code
        # and the tape share object identity (LayerHelper pattern).
        result: Dict[str, List[VarBase]] = {}
        out_vars_flat: List[VarBase] = []
        k = 0
        for slot_name, count in list(struct_holder):
            slot = info.output_slot(slot_name)
            provided = (outputs or {}).get(slot_name)
            plist = (list(provided) if isinstance(provided, (list, tuple))
                     else [provided] if provided is not None else [])
            vs = []
            for j in range(count):
                pv = plist[j] if j < len(plist) else None
                if isinstance(pv, VarBase):
                    ov = pv
                    ov._array = flat_out[k]
                    ov.stop_gradient = (not requires_grad) or slot.no_grad
                else:
                    ov = VarBase(
                        flat_out[k],
                        stop_gradient=(not requires_grad) or slot.no_grad)
                k += 1
                vs.append(ov)
                out_vars_flat.append(ov)
            result[slot_name] = vs
        if requires_grad:
            self.tape.append(
                TapeRecord(op_type, vjp_fn, in_vars, out_vars_flat,
                           fwd_fn=fwd_flat))
        if self._recording_program is not None:
            self._record_op(op_type, var_map, result, attrs)
        return result

    def _trace_op_lazy(self, info, op_type, inputs, outputs, attrs,
                       stop_gradient):
        """Queue the op on the LazyEngine instead of dispatching it:
        out-VarBases carry PendingValues; shapes come from a cached
        jax.eval_shape (host-only, no device round-trip)."""
        import jax
        import jax.numpy as jnp

        eng = self.lazy_engine

        def as_var(v):
            return v if isinstance(v, VarBase) else VarBase(
                v, stop_gradient=True)

        var_map: Dict[str, object] = {}
        handles: List[object] = []
        flat_vars: List[Optional[VarBase]] = []  # aligned with handles
        layout: List[Tuple[str, Optional[int]]] = []  # (slot, n or None)
        for slot in info.inputs:
            arg = (inputs or {}).get(slot.name)
            if arg is None or (isinstance(arg, (list, tuple)) and not arg):
                var_map[slot.name] = None
                continue
            vs = [as_var(a) for a in (arg if isinstance(arg, (list, tuple))
                                      else [arg])]
            var_map[slot.name] = vs if slot.duplicable else vs[0]
            if slot.duplicable:
                layout.append((slot.name, len(vs)))
                handles.extend(v._array for v in vs)
                flat_vars.extend(vs)
            else:
                layout.append((slot.name, None))
                handles.append(vs[0]._array)
                flat_vars.append(vs[0])

        attrs = dict(attrs or {})
        if outputs:
            attrs[BOUND_OUTPUTS_ATTR] = tuple(
                s.name for s in info.outputs if s.name in outputs)
        else:
            attrs[BOUND_OUTPUTS_ATTR] = tuple(s.name for s in info.outputs)
        if info.needs_rng:
            self._seed_counter += 1
            seed_val = jnp.uint32(
                max(int(attrs.get("seed", 0) or 0), 0)
                or (self._seed_counter & 0xFFFFFFFF))
            layout.append((RNG_SEED_ATTR, None))
            handles.append(seed_val)
            flat_vars.append(None)   # not a VarBase: never a wrt leaf
            if "is_test" in info.attrs and "is_test" not in attrs:
                attrs["is_test"] = not self.train_mode

        def rebuild(vals):
            m = {s.name: None for s in info.inputs}
            k = 0
            for name, n in layout:
                if n is None:
                    m[name] = vals[k]
                    k += 1
                else:
                    m[name] = list(vals[k:k + n])
                    k += n
            return m

        from .lazy import aval_of as _aval

        in_avals = [_aval(h) for h in handles]
        attrs_sig = attrs_signature(attrs)
        # the slot LAYOUT is part of the identity: two dispensable-slot
        # patterns (e.g. slice with StartsTensor vs EndsTensor) can
        # have identical avals but bind inputs differently
        layout_t = tuple(layout)
        cache_key = (op_type, attrs_sig, layout_t,
                     tuple((tuple(a.shape), str(a.dtype))
                           for a in in_avals))

        def op_fn(vals):
            outs = info.fn(rebuild(vals), attrs)
            flat = []
            for s in info.outputs:
                o = outs.get(s.name)
                if o is None:
                    continue
                flat.extend(o) if s.duplicable else flat.append(o)
            return tuple(flat)

        cached = self._aval_cache.get(cache_key)
        if cached is None:
            holder: List[Tuple[str, int]] = []

            def _probe(*vals):
                outs = info.fn(rebuild(list(vals)), attrs)
                flat, struct = [], []
                for s in info.outputs:
                    o = outs.get(s.name)
                    if o is None:
                        continue
                    if s.duplicable:
                        flat.extend(o)
                        struct.append((s.name, len(o)))
                    else:
                        flat.append(o)
                        struct.append((s.name, 1))
                holder.clear()
                holder.extend(struct)
                return tuple(flat)

            out_shapes = jax.eval_shape(_probe, *in_avals)
            cached = (list(out_shapes), list(holder))
            self._aval_cache[cache_key] = cached
        out_avals, struct = cached

        # differentiable leaves — same eligibility as the eager path;
        # positions are cached per (op signature, stop-gradient
        # pattern): the float-dtype checks are hot at BERT scale
        wrt_pos: List[int] = []
        in_vars: List[VarBase] = []
        if not self._no_grad and not stop_gradient and \
                info.grad is not None:
            sg = tuple(v is None or v.stop_gradient for v in flat_vars)
            wk = (cache_key, sg)
            wrt_t = self._wrt_cache.get(wk)
            if wrt_t is None:
                flat_idx = 0
                pos = []
                for name, n in layout:
                    if name == RNG_SEED_ATTR:
                        flat_idx += 1
                        continue
                    slot = next(s for s in info.inputs
                                if s.name == name)
                    vs = var_map[name]
                    vlist = vs if isinstance(vs, list) else [vs]
                    for v in vlist:
                        if not slot.no_grad and not v.stop_gradient \
                                and jnp.issubdtype(
                                    np.dtype(_aval(v._array).dtype),
                                    jnp.floating):
                            pos.append(flat_idx)
                        flat_idx += 1
                wrt_t = tuple(pos)
                self._wrt_cache[wk] = wrt_t
            wrt_pos = list(wrt_t)
            in_vars = [flat_vars[p] for p in wrt_t]
        requires_grad = bool(wrt_pos)

        op_sig = ("op", op_type, attrs_sig, layout_t)
        pendings = eng.add_node(op_fn, handles, out_avals, op_sig)

        result: Dict[str, List[VarBase]] = {}
        out_vars_flat: List[VarBase] = []
        k = 0
        for slot_name, count in struct:
            slot = info.output_slot(slot_name)
            provided = (outputs or {}).get(slot_name)
            plist = (list(provided) if isinstance(provided, (list, tuple))
                     else [provided] if provided is not None else [])
            vs = []
            for j in range(count):
                pv = plist[j] if j < len(plist) else None
                if isinstance(pv, VarBase):
                    ov = pv
                    ov._array = pendings[k]
                    ov.stop_gradient = (not requires_grad) or slot.no_grad
                else:
                    ov = VarBase(
                        None,
                        stop_gradient=(not requires_grad) or slot.no_grad)
                    ov._array = pendings[k]
                k += 1
                vs.append(ov)
                out_vars_flat.append(ov)
            result[slot_name] = vs

        if requires_grad:
            n_in = len(handles)
            wrt_t = tuple(wrt_pos)

            def lazy_vjp(cot_handles, _handles=handles, _wrt=wrt_t,
                         _n=n_in):
                def vjp_node_fn(vals):
                    ins, cots = vals[:_n], vals[_n:]

                    def fwd_w(*wvals):
                        vv = list(ins)
                        for p, wv in zip(_wrt, wvals):
                            vv[p] = wv
                        return op_fn(vv)

                    _, pull = jax.vjp(
                        fwd_w, *[ins[p] for p in _wrt])
                    return tuple(pull(tuple(cots)))

                grad_avals = [_aval(_handles[p]) for p in _wrt]
                return eng.add_node(
                    vjp_node_fn, list(_handles) + list(cot_handles),
                    grad_avals,
                    ("vjp", op_type, attrs_sig, layout_t, _wrt))

            rec = TapeRecord(op_type, None, in_vars, out_vars_flat,
                             lazy_vjp=lazy_vjp)
            # pin this record's input pendings: a pre-backward flush
            # must materialize them for the later eager/vjp use
            for h in handles:
                if type(h).__name__ == "PendingValue" and not h._resolved:
                    h.add_owner(rec, None)
            self.tape.append(rec)
        return result

    @staticmethod
    def _static_index(idx) -> bool:
        """True when idx is a plain Python index (hashable/reprable) —
        the kind the lazy queue can carry in a structure signature."""
        if isinstance(idx, (int, slice, type(None), type(Ellipsis))):
            return True
        if isinstance(idx, tuple):
            return all(Tracer._static_index(i) for i in idx)
        return False

    def trace_getitem(self, var: VarBase, idx):
        import jax

        if self._recording_program is not None:
            from ..core.enforce import UnimplementedError

            raise UnimplementedError(
                "tensor slicing (__getitem__) inside a program-recorded "
                "trace is not supported yet — use layers.slice")
        if self.lazy_engine is not None and self._static_index(idx):
            return self._trace_getitem_lazy(var, idx)
        fwd = lambda x: (x[idx],)  # noqa: E731
        out, vjp_fn = jax.vjp(fwd, var._force())
        ov = VarBase(out[0], stop_gradient=False)
        self.tape.append(TapeRecord("getitem", vjp_fn, [var], [ov],
                                    fwd_fn=fwd))
        return ov

    def _trace_getitem_lazy(self, var: VarBase, idx):
        """Queue a subscript as a lazy node (a mid-step flush for x[i]
        would defeat the whole queued-dispatch mode)."""
        import jax

        from .lazy import aval_of

        eng = self.lazy_engine
        h = var._array
        in_aval = aval_of(h)
        out_aval = jax.eval_shape(lambda x: x[idx], in_aval)
        sig_idx = repr(idx)
        (p,) = eng.add_node(lambda vals: (vals[0][idx],), [h],
                            [out_aval], ("getitem", sig_idx))
        ov = VarBase(None, stop_gradient=var.stop_gradient)
        ov._array = p
        if var.stop_gradient:
            return ov

        def lazy_vjp(cot_handles, _h=h, _idx=idx, _aval=in_aval):
            def node_fn(vals):
                x, ct = vals
                _, pull = jax.vjp(lambda a: a[_idx], x)
                return (pull(ct)[0],)

            return eng.add_node(node_fn, [_h, cot_handles[0]], [_aval],
                                ("getitem_vjp", repr(_idx)))

        rec = TapeRecord("getitem", None, [var], [ov], lazy_vjp=lazy_vjp)
        if type(h).__name__ == "PendingValue" and not h._resolved:
            h.add_owner(rec, None)
        self.tape.append(rec)
        return ov


class PartialGradEngine:
    """paddle.grad()-style partial/higher-order gradients (reference
    imperative/partial_grad_engine.cc): walk only the tape segment
    between `outputs` and `inputs`, return grads without touching
    `.grad` accumulators. With create_graph=True the backward ops are
    themselves taped (each pullback call goes through jax.vjp), so
    grad-of-grad works."""

    def __init__(self, tracer):
        self.tracer = tracer

    def run(self, outputs, inputs, grad_outputs=None, retain_graph=None,
            create_graph=False, only_inputs=True, allow_unused=False,
            no_grad_vars=None):
        import jax
        import jax.numpy as jnp

        if not only_inputs:
            raise NotImplementedError("only_inputs=False is not supported")
        outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        no_grad_ids = {id(v) for v in (no_grad_vars or [])}
        if retain_graph is None:
            retain_graph = create_graph
        if self.tracer.lazy_engine is not None:
            if create_graph:
                raise NotImplementedError(
                    "dygraph.grad(create_graph=True) needs the eager "
                    "tracer — use fluid.dygraph.guard(lazy=False) for "
                    "higher-order gradients")
            return self._run_lazy(outputs, inputs, grad_outputs,
                                  retain_graph, allow_unused,
                                  no_grad_ids)

        # grad VarBases keyed by forward var identity
        gvars: Dict[int, VarBase] = {}
        for i, o in enumerate(outputs):
            seed = None
            if grad_outputs is not None and i < len(grad_outputs) \
                    and grad_outputs[i] is not None:
                go = grad_outputs[i]
                seed = go if isinstance(go, VarBase) else VarBase(
                    go, stop_gradient=not create_graph)
            else:
                seed = VarBase(jnp.ones_like(o._array),
                               stop_gradient=not create_graph)
            gvars[id(o)] = seed

        tape = list(self.tracer.tape)
        for rec in reversed(tape):
            if not any(id(ov) in gvars for ov in rec.out_vars):
                continue
            cot_vars = []
            for ov in rec.out_vars:
                gv = gvars.get(id(ov))
                if gv is None:
                    gv = VarBase(jnp.zeros_like(ov._array),
                                 stop_gradient=True)
                cot_vars.append(gv)
            cots = tuple(g._array for g in cot_vars)
            if create_graph and rec.fwd_fn is not None:
                # re-derive the pullback THROUGH the forward so the grads
                # depend on the primals too (d(gx)/dx needs it)
                n_p = len(rec.in_vars)
                primals = tuple(v._array for v in rec.in_vars)

                def grad_call(*args, _rec=rec, _np=n_p):
                    prim, cot = args[:_np], args[_np:]
                    _, pull = jax.vjp(_rec.fwd_fn, *prim)
                    return pull(tuple(cot))

                in_grad_arrays, vjp2 = jax.vjp(grad_call,
                                               *(primals + cots))
                new_gvars = [VarBase(a, stop_gradient=False)
                             for a in in_grad_arrays]
                self.tracer.tape.append(TapeRecord(
                    rec.op_type + "_grad", vjp2,
                    list(rec.in_vars) + cot_vars, new_gvars,
                    fwd_fn=grad_call))
            else:
                in_grad_arrays = rec.vjp_fn(cots)
                new_gvars = [VarBase(a, stop_gradient=True)
                             for a in in_grad_arrays]
            for iv, gv in zip(rec.in_vars, new_gvars):
                if id(iv) in no_grad_ids:
                    continue
                prev = gvars.get(id(iv))
                if prev is None:
                    gvars[id(iv)] = gv
                else:
                    summed = prev._array + gv._array
                    if create_graph:
                        sv = VarBase(summed, stop_gradient=False)
                        self.tracer.tape.append(TapeRecord(
                            "grad_add", lambda c: (c[0], c[0]),
                            [prev, gv], [sv]))
                        gvars[id(iv)] = sv
                    else:
                        gvars[id(iv)] = VarBase(summed, stop_gradient=True)

        results = []
        for v in inputs:
            gv = gvars.get(id(v))
            if gv is None and not allow_unused:
                raise ValueError(
                    "one of the inputs is unreachable from outputs; pass "
                    "allow_unused=True to get None for it")
            results.append(gv)
        if not retain_graph:
            # reference semantics: the graph is freed after grad() unless
            # retained — otherwise every call leaks taped residuals
            self.tracer.tape.clear()
        return results

    def _run_lazy(self, outputs, inputs, grad_outputs, retain_graph,
                  allow_unused, no_grad_ids):
        """grad() under lazy dispatch: the tape walk queues vjp nodes
        (first-order only; results are detached VarBases, matching the
        eager create_graph=False contract)."""
        import jax.numpy as jnp

        from .lazy import aval_of, is_pending

        eng = self.tracer.lazy_engine

        ghandles: Dict[int, object] = {}
        for i, o in enumerate(outputs):
            if grad_outputs is not None and i < len(grad_outputs) \
                    and grad_outputs[i] is not None:
                go = grad_outputs[i]
                ghandles[id(o)] = (go._array if isinstance(go, VarBase)
                                   else go)
            else:
                ghandles[id(o)] = eng.ones_like(o._array)

        for rec in reversed(list(self.tracer.tape)):
            if not any(id(ov) in ghandles for ov in rec.out_vars):
                continue
            cots = []
            for ov in rec.out_vars:
                g = ghandles.get(id(ov))
                if g is None:
                    g = eng.zeros_like(ov._array)
                cots.append(g)
            if rec.lazy_vjp is not None:
                in_grads = rec.lazy_vjp(tuple(cots))
            else:
                cc = tuple(c.force() if is_pending(c) else c
                           for c in cots)
                in_grads = rec.vjp_fn(cc)
            for iv, g in zip(rec.in_vars, in_grads):
                if id(iv) in no_grad_ids:
                    continue
                prev = ghandles.get(id(iv))
                ghandles[id(iv)] = g if prev is None else \
                    eng.add(prev, g)

        results = []
        for v in inputs:
            h = ghandles.get(id(v))
            if h is None:
                if not allow_unused:
                    raise ValueError(
                        "one of the inputs is unreachable from outputs; "
                        "pass allow_unused=True to get None for it")
                results.append(None)
                continue
            gv = VarBase(None, stop_gradient=True)
            gv._array = h
            results.append(gv)
        if not retain_graph:
            self.tracer.tape.clear()
        return results


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """fluid.dygraph.grad (reference dygraph/base.py grad ->
    PartialGradEngine)."""
    t = current_tracer()
    if t is None:
        raise RuntimeError("dygraph.grad() requires dygraph mode "
                           "(fluid.dygraph.guard())")
    return PartialGradEngine(t).run(
        outputs, inputs, grad_outputs, retain_graph, create_graph,
        only_inputs, allow_unused, no_grad_vars)
