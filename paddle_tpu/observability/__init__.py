"""Unified runtime observability: metrics registry + span tracing.

Every execution path reports here — static ``Executor`` (compiled and
interpreter), the lazy dygraph engine, the mesh data-parallel engine,
the pipeline engine, the LoD-lowering planner, and the memory facade —
so "why was step N slow", "how often did the lazy engine recompile" and
"did the pipeline bubble grow" are answerable without print-debugging.

Opt-in: set ``PADDLE_TPU_METRICS=1`` (or the ``FLAGS_tpu_metrics``
flag via ``fluid.set_flags``), or call ``observability.enable()``.
When disabled (the default) every instrumentation site is a single
cached-module-attribute load plus a branch — a no-op on the hot path.

Metric families (see README "Runtime observability"):

=====================================  ======================================
``executor.steps{path=...}``           counter: compiled | interpreter steps
``executor.step_ms{path=...}``         histogram: host step latency (compiled:
                                       the whole Executor.run, fetch included)
``executor.feed_ms``                   histogram: host feeds staged in a step
``executor.trace_s``                   counter: seconds of Python tracing of
                                       compiled steps (span executor/trace)
``executor.lower_s`` / ``parallel.lower_s``  counter: seconds of lowering
                                       (jaxpr -> MLIR, Pallas bodies inside)
                                       reported by jax.monitoring under a
                                       span of that family (<family>/lower)
``executor.ops{type=...}``             counter: interpreter per-op executions
``kernels.flash_attention{path=...}``  counter: traces of the flash_attention
                                       op, by kernels: short | stream | dense
``kernels.flash_attention_select{form=...}``  counter: the same traces, by
                                       the form of their key selection:
                                       none | mask (int8 [B, S, S])
``kernels.flash_attention_grad{path=...}``  counter: traces of attention's
                                       backward, counted at the branch that
                                       ran: fused (the streaming path's one
                                       kernel) | split (its dQ and dK+dV
                                       pair) | short | dense
``executor.compiles``                  counter: whole-program (re)compiles
``executor.jit_traces``                counter: per-shape XLA (re)traces
``executor.compile_fallbacks``         counter: compiled -> interpreter drops
``lod_lowering.declines{op_type=...}`` counter: ragged lowering declines
``lazy.flushes``                       counter: lazy-engine flushes
``lazy.cache_hits`` / ``lazy.recompiles``  counter: flush jit cache hit/miss
``lazy.graph_nodes``                   histogram: nodes per flushed graph
``dygraph.ops{dispatch=...}``          counter: traced eager/lazy ops
``parallel.steps`` / ``.compiles``     counter: mesh-engine steps/compiles
``parallel.collective_bytes``          counter: bytes allreduced per step
``parallel.step_ms``                   histogram: host step latency of the
                                       whole run_data_parallel, fetch included
``parallel.trace_s``                   counter: seconds of Python tracing of
                                       mesh steps (span parallel/trace)
``pipeline.steps`` / ``.step_ms``      counter / histogram
``pipeline.bubble_fraction``           gauge: (S-1)/(M+S-1) GPipe bubble
``pipeline.boundary_bytes{boundary=}`` gauge: rotating-buffer payload
``memory.*_bytes``                     gauge: live/peak/limit device bytes
``serving.*``                          serving engine + fleet router
                                       (always-on; incl. ``shed{class=}``,
                                       ``hedges``, ``hedge_wasted``,
                                       ``fleet_retries``, ``dedup_hits``,
                                       ``replica_ejections{cause=}``,
                                       ``replica_rejoins`` — see
                                       ``paddle_tpu/serving/metrics.py``)
``rpc.retries{method=}``               counter: PS client retries per rpc
``rpc.timeouts{method=}``              counter: per-attempt deadline trips
``rpc.latency_ms{method=}``            histogram: per-ATTEMPT reply latency
                                       (retries observe separately)
``ps.evictions`` / ``ps.readmissions`` counter: heartbeat-monitor actions
``ps.failovers{cause=}``               counter: client endpoint advances
                                       (cause: transport | redirect)
``ps.promotions``                      counter: backup -> primary
``ps.catchup_ms``                      histogram: rejoin snapshot catch-up
``ps.replication_lag_rounds{backup=}`` gauge: rounds the backup is behind
                                       (0 after each ack; frozen = dropped)
``ps.replication_bytes{mode=}``        counter: shipped payload, full | delta
``ps.delta_rounds`` / ``ps.anchor_rounds``  counter: delta vs full-anchor ships
``ps.lease_renewals``                  counter: primary lease renewal acks
``ps.lease_expiries{shard=}``          counter: backup lease-view expiries
``fault.injected{side=,kind=}``        counter: injected RPC-frame faults
``checkpoint.save_ms``                 histogram: atomic checkpoint commit
``checkpoint.bytes``                   counter: checkpointed payload bytes
``checkpoint.delta_bytes``             counter: incremental-save fresh bytes
``checkpoint.shards_reused``           counter: shards linked from prev ckpt
``checkpoint.corrupt``                 counter: rotations failing sha256
=====================================  ======================================

The ``rpc.* / ps.* / fault.* / checkpoint.*`` families (like
``serving.*``) record unconditionally — recovery events are rare, and
CI asserts on them without needing ``PADDLE_TPU_METRICS``. The
``method=`` label on ``rpc.retries`` / ``rpc.timeouts`` exists for
retry-policy tuning: a rising retry rate under a clean network on ONE
method (say ``send_barrier``) means that call shape's per-attempt
deadline is mis-set, not the transport.

Export: ``dump()`` -> JSON-able dict, ``dump(fmt="prometheus")`` ->
text exposition format, ``chrome_trace()`` / ``write_chrome_trace()``
-> Perfetto-loadable ``trace_event`` JSON merging all host spans
(including the legacy ``fluid.profiler`` timeline).

Distributed (ISSUE 5, ``observability/distributed`` +
``observability/flight``): setting ``PADDLE_TPU_METRICS_DIR`` arms
this layer plus a periodic/at-exit/on-SIGTERM per-process dumper;
rpc headers carry ``trace_id``/``parent_span`` so one sync round or
serving request is one cross-process trace; every recovery decision
lands in a bounded always-on flight-recorder ring; and the launch
supervisor merges everything into a job-level ``metrics.json`` + one
chrome-trace ``trace.json`` (``tools/ft_timeline.py`` prints the
ordered cross-process postmortem). See README "Distributed
observability".
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Optional

from . import flight  # noqa: F401
from . import tracing  # noqa: F401
from . import distributed  # noqa: F401
from . import profiler  # noqa: F401
from . import spool  # noqa: F401
from .registry import Counter, Gauge, Histogram, MetricsRegistry  # noqa: F401
from .tracing import span  # noqa: F401

__all__ = ["enable", "disable", "enabled", "metrics", "counter", "gauge",
           "histogram", "inc", "set_gauge", "observe", "counter_value",
           "gauge_value", "span", "dump", "dump_prometheus",
           "chrome_trace", "write_chrome_trace", "reset",
           "MetricsRegistry", "Counter", "Gauge", "Histogram",
           "flight", "distributed", "profiler", "spool"]

_registry = MetricsRegistry()
_enabled = False


def _init_from_env() -> None:
    """Arm from the environment before core.flags is even imported —
    observability must not drag the flag module (and transitively jax)
    in at import time. Precedence matches core/flags._init_from_env
    exactly (FLAGS_tpu_metrics primary, PADDLE_TPU_METRICS alias) so
    the flag value and this layer's armed state can never diverge.

    A set ``PADDLE_TPU_METRICS_DIR`` additionally arms the layer AND
    the per-process dumper (``observability.distributed``): asking for
    a job-level dump dir without metrics would produce empty dumps, so
    the dir is the one switch a distributed job needs."""
    raw = os.environ.get("FLAGS_tpu_metrics")
    if raw is None:
        raw = os.environ.get("PADDLE_TPU_METRICS", "")
    if raw.lower() in ("1", "true", "yes", "on"):
        enable()
    if distributed.metrics_dir() is not None:
        enable()
        distributed.arm_from_env()
    # the crash postmortem hook is unconditional (a black box that
    # needs arming is not a black box): it chains the existing
    # excepthook and, with no metrics dir, only prints the flight-ring
    # tail to stderr before the normal traceback
    flight.install_excepthook()


def enabled() -> bool:
    return _enabled


def _sync_flag(on: bool) -> None:
    """Keep FLAGS_tpu_metrics truthful when enable()/disable() is
    called directly (get_flags must report the armed state). Written
    via sys.modules so this never forces core.flags (and its package
    init) to load early — if flags isn't loaded yet, its own env init
    resolves to the same value."""
    fl = sys.modules.get(__package__.rsplit(".", 1)[0] + ".core.flags")
    if fl is not None:
        fl._values["FLAGS_tpu_metrics"] = bool(on)


# what JAX reports once a jaxpr is lowered to an MLIR module, every
# Pallas body's lowering to Mosaic inside it
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_listening = False


def _count_lowering(event: str, seconds: float, **_) -> None:
    """Lowering seconds of the program's own steps: counted only on a
    thread that is inside a program span (``executor.lower_s`` under
    ``executor/launch``, ``parallel.lower_s`` under ``parallel/step``),
    with a span ``<family>/lower`` beside the counter. What a user's
    own ``jax.jit`` lowers under no span counts nothing."""
    if event != _LOWER_EVENT or not _enabled:
        return
    family = tracing.open_family()
    if family is None:
        return
    _registry.counter(family + ".lower_s").inc(seconds)
    tracing.record_ending_now(family + "/lower", seconds, cat="compile")


def _listen_for_lowering() -> None:
    """One ``jax.monitoring`` listener a process, registered by the
    first ``enable()`` that finds JAX loaded (this module never loads
    it)."""
    global _listening
    if _listening or "jax" not in sys.modules:
        return
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_count_lowering)
    _listening = True


def enable() -> None:
    global _enabled
    _enabled = True
    tracing._set_metrics_on(True)
    _sync_flag(True)
    _listen_for_lowering()


def disable() -> None:
    global _enabled
    _enabled = False
    tracing._set_metrics_on(False)
    _sync_flag(False)


def metrics() -> MetricsRegistry:
    return _registry


# -- direct metric handles (create regardless of enabled: tests and
# callers that hold a handle pay the branch themselves) --------------------

def counter(name: str, **labels) -> Counter:
    return _registry.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _registry.gauge(name, **labels)


def histogram(name: str, **labels) -> Histogram:
    return _registry.histogram(name, **labels)


# -- guarded one-shot helpers (the instrumentation-site surface) -----------

def inc(name: str, n: int = 1, **labels) -> None:
    if _enabled:
        _registry.counter(name, **labels).inc(n)


def set_gauge(name: str, v, **labels) -> None:
    if _enabled:
        _registry.gauge(name, **labels).set(v)


def observe(name: str, v, **labels) -> None:
    if _enabled:
        _registry.histogram(name, **labels).observe(v)


def counter_value(name: str, **labels):
    return _registry.counter_value(name, **labels)


def gauge_value(name: str, **labels):
    return _registry.gauge_value(name, **labels)


# -- export ----------------------------------------------------------------

def _refresh_memory_gauges() -> None:
    """Pull-style gauges: live/peak device bytes are sampled at dump
    time (the backend owns the counters; polling every step would be
    overhead for numbers only a dump reader looks at). memory_usage
    itself writes the ``memory.*_bytes`` gauges when the layer is
    enabled; a disabled dump stays a pure observation and creates
    nothing."""
    if not _enabled:
        return
    try:
        from ..core.memory import memory_usage

        memory_usage()
    except Exception:
        pass


def dump(fmt: str = "json") -> object:
    """Snapshot of every metric. ``fmt="json"`` (default) returns a
    JSON-able dict; ``fmt="prometheus"`` returns the text exposition
    format."""
    _refresh_memory_gauges()
    if fmt == "prometheus":
        return _registry.to_prometheus()
    if fmt != "json":
        raise ValueError("unknown dump format %r" % fmt)
    out = _registry.snapshot()
    out["spans"] = tracing.stats()
    out["enabled"] = _enabled
    return out


def dump_prometheus() -> str:
    return dump(fmt="prometheus")


def _legacy_profiler_events():
    """The old ``fluid.profiler`` timeline — live session if one is
    running, else the last finished session's snapshot — so the chrome
    export keeps the ``get_trace_events()`` contract alive."""
    try:
        if tracing.profiler_session_active():
            return []   # live session spans are already in the buffer
        return profiler.get_trace_events()
    except Exception:
        return []


def chrome_trace() -> Dict:
    """Perfetto-loadable ``trace_event`` JSON merging the span buffer
    with the legacy profiler timeline."""
    return tracing.chrome_trace(extra_events=_legacy_profiler_events())


def write_chrome_trace(path: str) -> str:
    return tracing.write_chrome_trace(
        path, extra_events=_legacy_profiler_events())


def reset() -> None:
    """Clear all metrics and buffered spans — including the legacy
    profiler's finished-session snapshot, so a post-reset
    chrome_trace() is actually empty (enabled state is kept)."""
    _registry.reset()
    tracing.clear()
    try:
        del profiler._last_trace[:]
    except Exception:
        pass


_init_from_env()
