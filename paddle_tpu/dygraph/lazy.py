"""Lazy (queued) eager execution — async/batched dygraph dispatch.

Parity intent: the reference attacks per-op eager overhead with
generated C++ fast paths (pybind/op_function_generator.cc); on TPU the
cost is not Python but PER-OP DEVICE DISPATCH — a ~40-op training
step pays ~40 dispatches, each with its own launch latency
(BASELINE.md round-4 dygraph row). The TPU-native fix is
the lazy-tensor pattern (torch/XLA's mark_step): ops queue into a
graph of LazyNodes; VarBase arrays become PendingValues; a FLUSH
compiles the queued graph into ONE jitted XLA call (cached by graph
structure, so steady-state training is one dispatch per step) and
materializes only values still referenced by live VarBases.

Flush triggers: any host read (``numpy()``/``float``/``__array__``),
``optimizer.minimize`` (the natural step boundary — like mark_step),
program recording, or a node-count safety valve.

Enable with ``fluid.dygraph.guard(lazy=True)`` or
``FLAGS_dygraph_lazy=true``.
"""
from __future__ import annotations

import weakref
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

__all__ = ["PendingValue", "LazyEngine", "is_pending", "aval_of",
           "plan_lazy_policy", "apply_lazy_policy", "JIT_CACHE_CAP_MAX"]

_obs_cache: List = []


def _obs():
    """Lazy module ref (importing per flush is cheap, but force() sits
    on value-read paths; mirror executor_core's cached-ref pattern)."""
    if not _obs_cache:
        from .. import observability

        _obs_cache.append(observability)
    return _obs_cache[0]


def is_pending(x) -> bool:
    return isinstance(x, PendingValue)


_sds_memo: Dict = {}


def _sds(shape, dtype):
    """Memoized jax.ShapeDtypeStruct — construction dominates the
    per-op host cost at BERT scale (jax __setattr__ checks x thousands
    of ops/step), and the distinct (shape, dtype) set is tiny."""
    key = (shape, dtype)
    s = _sds_memo.get(key)
    if s is None:
        import jax

        s = jax.ShapeDtypeStruct(shape, dtype)
        if len(_sds_memo) < 4096:
            _sds_memo[key] = s
    return s


def aval_of(h):
    """jax.ShapeDtypeStruct of a handle (concrete array or pending)."""
    if isinstance(h, PendingValue):
        return h.aval
    return _sds(tuple(np.shape(h)), h.dtype)


class PendingValue:
    """Placeholder for a not-yet-computed array. Duck-types the shape/
    dtype surface so shape-reading code works without forcing; any
    value read (``__array__``) forces a flush."""

    __slots__ = ("aval", "value", "_resolved", "engine", "_owners",
                 "_pinned", "__weakref__")

    def __init__(self, aval, engine):
        self.aval = aval          # jax.ShapeDtypeStruct
        self.value = None
        self._resolved = False
        self.engine = engine
        self._owners: List = []   # [(weakref(obj), attr or None)]
        self._pinned = False      # force() in flight: must materialize

    # -- shape surface ----------------------------------------------------
    @property
    def shape(self):
        return tuple(self.aval.shape)

    @property
    def dtype(self):
        return self.aval.dtype

    @property
    def ndim(self):
        return len(self.aval.shape)

    @property
    def size(self):
        n = 1
        for s in self.aval.shape:
            n *= s
        return n

    # -- ownership (decides what a flush must materialize) ----------------
    def add_owner(self, obj, attr: Optional[str]):
        """attr None means "needed while obj is alive" (tape records);
        otherwise needed while ``getattr(obj, attr) is self``."""
        self._owners.append((weakref.ref(obj), attr))

    def is_needed(self) -> bool:
        if self._pinned:
            return True
        for ref, attr in self._owners:
            o = ref()
            if o is None:
                continue
            if attr is None or getattr(o, attr, None) is self:
                return True
        return False

    # -- forcing ----------------------------------------------------------
    def force(self):
        if not self._resolved:
            # pin BEFORE flushing: a value held only by local dicts
            # (mid-backward cotangents on a mixed eager/lazy tape) has
            # no VarBase owner, but the very act of forcing proves it
            # is needed — without the pin the flush would skip its
            # materialization and the read below would hit the
            # "dead at flush time" RuntimeError
            self._pinned = True
            self.engine.flush()
        if not self._resolved:
            raise RuntimeError("pending value did not resolve on flush")
        if self.value is None:
            raise RuntimeError(
                "pending value was dead at flush time (no live owner) "
                "but was read later — please report")
        return self.value

    def __array__(self, dtype=None):
        a = np.asarray(self.force())
        return a.astype(dtype) if dtype is not None else a

    def __repr__(self):
        return "PendingValue(shape=%s, dtype=%s, resolved=%s)" % (
            self.shape, self.dtype, self._resolved)


class _LazyNode:
    __slots__ = ("fn", "ins", "outs", "sig")

    def __init__(self, fn, ins, outs, sig):
        self.fn = fn      # list of arrays -> tuple of arrays
        self.ins = ins    # handles: concrete arrays or PendingValues
        self.outs = outs  # [PendingValue]
        self.sig = sig    # structural signature (hashable)


class LazyEngine:
    """Queue of LazyNodes + structure-keyed jit cache."""

    MAX_NODES = 4000      # safety valve: auto-flush beyond this
    JIT_CACHE_CAP = 64

    def __init__(self):
        self.nodes: List[_LazyNode] = []
        self._jit_cache: "OrderedDict" = OrderedDict()
        self._flushing = False
        # optimizer-op shape cache (backward_utils._lazy_opt_op)
        self._opt_aval_cache: Dict = {}

    # -- graph building ---------------------------------------------------
    def add_node(self, fn, in_handles, out_avals, sig) -> List[PendingValue]:
        outs = [PendingValue(a, self) for a in out_avals]
        self.nodes.append(_LazyNode(fn, list(in_handles), outs, sig))
        if len(self.nodes) >= self.MAX_NODES:
            # safety valve mid-structure: owners are not attached yet
            # (the caller binds outs to VarBases AFTER add_node), and
            # mid-backward cotangent handles live only in local dicts —
            # liveness is unknowable here, so materialize EVERYTHING
            self.flush(conservative=True)
        return outs

    def constant_node(self, make, aval, sig) -> PendingValue:
        """Zero-input node (ones/zeros seeds etc.)."""
        return self.add_node(lambda vals: (make(),), [], [aval], sig)[0]

    def binop_node(self, fn, a, b, sig_kind) -> PendingValue:
        """Elementwise two-arg node (e.g. gradient accumulation) —
        shared by BasicEngine._backward_lazy and
        PartialGradEngine._run_lazy."""
        av = aval_of(a)
        return self.add_node(lambda vals: (fn(vals[0], vals[1]),),
                             [a, b], [av],
                             (sig_kind, tuple(av.shape),
                              str(av.dtype)))[0]

    def add(self, a, b) -> PendingValue:
        return self.binop_node(lambda x, y: x + y, a, b, "grad_add")

    def ones_like(self, h) -> PendingValue:
        import jax.numpy as jnp

        av = aval_of(h)
        return self.constant_node(
            lambda: jnp.ones(av.shape, av.dtype), av,
            ("ones", tuple(av.shape), str(av.dtype)))

    def zeros_like(self, h) -> PendingValue:
        import jax.numpy as jnp

        av = aval_of(h)
        return self.constant_node(
            lambda: jnp.zeros(av.shape, av.dtype), av,
            ("zeros", tuple(av.shape), str(av.dtype)))

    # -- flush ------------------------------------------------------------
    def flush(self, conservative=False):
        if self._flushing or not self.nodes:
            return
        self._flushing = True
        try:
            self._flush_impl(conservative)
        finally:
            self._flushing = False

    def _flush_impl(self, conservative=False):
        import jax

        obs = _obs()
        if obs.enabled():
            obs.inc("lazy.flushes")
            obs.observe("lazy.graph_nodes", len(self.nodes))
        nodes, self.nodes = self.nodes, []
        pos: Dict[int, Tuple[int, int]] = {}
        for ni, nd in enumerate(nodes):
            for oj, p in enumerate(nd.outs):
                pos[id(p)] = (ni, oj)

        ext: List = []
        ext_ids: Dict[int, int] = {}
        wiring: List[Tuple] = []
        sig_parts: List = []
        for nd in nodes:
            w = []
            for h in nd.ins:
                if isinstance(h, PendingValue) and not h._resolved:
                    # unresolved ⇒ produced in THIS batch (every prior
                    # flush resolves all of its pendings)
                    w.append(("n",) + pos[id(h)])
                    continue
                if isinstance(h, PendingValue):
                    h = h.force()   # raises if dead-at-flush
                k = ext_ids.get(id(h))
                if k is None:
                    k = len(ext)
                    ext_ids[id(h)] = k
                    ext.append(h)
                w.append(("e", k))
            wiring.append(tuple(w))
            sig_parts.append((nd.sig, tuple(w)))

        needed = tuple(sorted(
            pos[id(p)]
            for nd in nodes for p in nd.outs
            if conservative or p.is_needed()))
        ext_avals = tuple(
            (tuple(np.shape(a)), str(getattr(a, "dtype", type(a))))
            for a in ext)
        key = (tuple(sig_parts), needed, ext_avals)

        fn = self._jit_cache.get(key)
        if fn is not None:
            self._jit_cache.move_to_end(key)
            obs.inc("lazy.cache_hits")
        else:
            from ..analysis import verify_enabled as _verify_enabled

            if _verify_enabled():
                # flush graphs are the lazy path's "rewritten program":
                # structurally verify the wiring before jitting it
                from ..analysis import verify_lazy_graph

                verify_lazy_graph(wiring,
                                  [len(nd.outs) for nd in nodes],
                                  len(ext), needed)
            # a structural cache miss == a retrace + XLA recompile of
            # the whole queued step: the metric that catches signature
            # churn (varying shapes/attrs) killing steady-state perf
            obs.inc("lazy.recompiles")
            node_fns = tuple(nd.fn for nd in nodes)
            wiring_t = tuple(wiring)
            needed_t = needed

            def replay(ext_vals):
                results: List = []
                for nf, w in zip(node_fns, wiring_t):
                    vals = [ext_vals[e[1]] if e[0] == "e"
                            else results[e[1]][e[2]] for e in w]
                    results.append(nf(vals))
                return tuple(results[ni][oj] for (ni, oj) in needed_t)

            fn = jax.jit(replay)
            self._jit_cache[key] = fn
            while len(self._jit_cache) > self.JIT_CACHE_CAP:
                self._jit_cache.popitem(last=False)

        with obs.tracing.span("lazy/flush", cat="step",
                              nodes=len(nodes)):
            out_vals = fn(ext)
        by_pos = dict(zip(needed, out_vals))
        for ni, nd in enumerate(nodes):
            for oj, p in enumerate(nd.outs):
                p.value = by_pos.get((ni, oj))
                p._resolved = True
                p._owners = []


# -- recompile-vs-reuse policy steering (self-driving runtime) --------------
#
# The structural jit cache trades memory for retraces: a cap smaller
# than the program's working set of flush signatures turns steady
# state into an eviction→recompile treadmill (lazy.recompiles grows,
# lazy.cache_hits stalls). The steering daemon watches that ratio;
# this steerer turns it into a plan {"jit_cache_cap": N} the canary
# can try on one replica before the fleet adopts it.

JIT_CACHE_CAP_MAX = 512


def plan_lazy_policy(recompiles, cache_hits, cache_cap=None):
    """Propose a jit-cache cap from observed recompile/hit counts:
    double the cap (bounded by ``JIT_CACHE_CAP_MAX``) while recompiles
    dominate AND exceed the cap (signature working set larger than the
    cache); keep it otherwise."""
    cap = int(cache_cap if cache_cap is not None
              else LazyEngine.JIT_CACHE_CAP)
    r, h = max(0, int(recompiles)), max(0, int(cache_hits))
    total = r + h
    frac = (r / total) if total else 0.0
    new_cap = cap
    if total and frac > 0.5 and r > cap:
        new_cap = min(JIT_CACHE_CAP_MAX, cap * 2)
    return {"jit_cache_cap": new_cap, "prev_cap": cap,
            "recompile_frac": round(frac, 6),
            "recompiles": r, "cache_hits": h}


def _steer_lazy_policy(report, recompiles=None, cache_hits=None,
                       cache_cap=None, **_ctx):
    """``report → plan`` steerer: counts come from context (the daemon
    reads them off the merged counters); falls back to the live
    process registry so a manual ``steer("lazy_policy", None)`` works
    inside a running job."""
    if recompiles is None or cache_hits is None:
        obs = _obs()
        recompiles = obs.counter_value("lazy.recompiles")
        cache_hits = obs.counter_value("lazy.cache_hits")
    return plan_lazy_policy(recompiles, cache_hits,
                            cache_cap=cache_cap)


def apply_lazy_policy(plan, engine_cls=None):
    """Install a promoted policy plan: sets the (class-level) jit
    cache cap. The canary's apply/rollback hooks call this with the
    proposed and the incumbent plan respectively."""
    cls = engine_cls or LazyEngine
    cap = int(plan["jit_cache_cap"])
    if not 1 <= cap <= JIT_CACHE_CAP_MAX:
        raise ValueError("jit_cache_cap %d outside [1, %d]"
                         % (cap, JIT_CACHE_CAP_MAX))
    cls.JIT_CACHE_CAP = cap
    return cap


from ..observability import steering as _steering  # noqa: E402

_steering.register_steerer(
    "lazy_policy", _steer_lazy_policy,
    "recompile-vs-reuse jit-cache policy from flush counters (ISSUE 16)")
