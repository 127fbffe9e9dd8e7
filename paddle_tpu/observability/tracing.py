"""Host span/trace layer — the generalization of profiler.RecordEvent.

One bounded in-process buffer of completed spans, fed by every
execution path (interpreter per-op events, compiled-step dispatches,
lazy flushes, parallel/pipeline steps). Two independent switches arm
it:

- the metrics flag (``PADDLE_TPU_METRICS`` / ``FLAGS_tpu_metrics``):
  always-on production telemetry, exported via
  ``observability.chrome_trace()``;
- a legacy profiler *session* (``fluid.profiler.start_profiler`` /
  ``stop_profiler``): bounded in time, drained into the session
  snapshot on stop so back-to-back sessions never bleed — the
  contract the old 115-line host profiler kept.

When neither is armed, ``span()`` returns a shared no-op context
manager: no allocation, no timestamp read — the hot-path cost of the
disabled layer is one module-attribute load and one branch.

Span records are tuples ``(name, ts_us, dur_us, tid, cat, args)``
(args may be None) — directly convertible to chrome ``trace_event``
"X" entries for Perfetto / chrome://tracing. A span's parent is the
span that encloses it on the same thread; a span opened with a
``step=`` argument hands it to every span opened inside it, so the
spans of one ``Executor.run`` share one ``step``; ``nest()`` rebuilds
the tree and gives each span its self time.

Two sinks, one primitive: an armed span is also a
``jax.profiler.TraceAnnotation`` named ``"pt:" + name``. Under a live
``jax.profiler`` trace that puts every program span into the XPlane's
host plane, on the clock of the device operations; with no trace
running the annotation is one check of a flag. JAX is not imported for
it: the class is taken once ``jax`` is in ``sys.modules``.
"""
from __future__ import annotations

import json
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

__all__ = ["span", "active", "open_family", "record_ending_now",
           "trace_events", "nest", "chrome_trace", "write_chrome_trace",
           "clear", "ANNOTATION_PREFIX"]

# what a program span is called in a jax.profiler trace
ANNOTATION_PREFIX = "pt:"

_MAX_EVENTS = 65536

_lock = threading.Lock()
_events: List[Tuple] = []   # (name, ts_us, dur_us, tid, cat, args)
_dropped = 0

# on-disk span spool (observability/spool.py), installed by
# distributed.arm when PADDLE_TPU_METRICS_DIR is set: the bounded ring
# above then becomes a live CACHE while the spool's head segments +
# seeded reservoir are the RECORD a day-long job merges from. None
# (the default) costs one attribute load per recorded span.
_spool = None


def _set_spool(sp) -> None:
    global _spool
    _spool = sp


def spool():
    return _spool

# armed-by: the metrics layer (observability.enable) and/or a legacy
# profiler session (profiler.start_profiler)
_metrics_on = False
_profiler_on = False
_session_start = 0   # index into _events where the live session began
# exact per-name (count, total_us) aggregates for the live profiler
# session: the span BUFFER is bounded (old spans drop under pressure)
# but the session summary table must stay exact for any session length
# — the contract the old profiler's _host_events defaultdict kept
_session_agg: Dict[str, List] = {}


def active() -> bool:
    return _metrics_on or _profiler_on


def _set_metrics_on(on: bool) -> None:
    global _metrics_on
    _metrics_on = bool(on)


class _NullSpan:
    """Shared disabled-path context manager — zero per-use allocation."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


_open = threading.local()   # .span: the innermost open span of the thread
_annotation = None          # jax.profiler.TraceAnnotation, once jax is loaded


def _annotation_class():
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class Span:
    __slots__ = ("name", "cat", "args", "_t0", "_outer", "_note")

    def __init__(self, name: str, cat: str, args: Optional[Dict]):
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self):
        outer = self._outer = getattr(_open, "span", None)
        if outer is not None and outer.args and "step" in outer.args:
            if self.args is None:
                self.args = {"step": outer.args["step"]}
            else:
                self.args.setdefault("step", outer.args["step"])
        _open.span = self
        note = _annotation or _annotation_class()
        if note is not None:
            note = note(ANNOTATION_PREFIX + self.name)
            note.__enter__()
        self._note = note
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._note is not None:
            self._note.__exit__(*exc)
        _open.span = self._outer
        if active():   # session may have stopped mid-span; drop then
            _record(self.name, self._t0 * 1e6, dur * 1e6,
                    self.cat, self.args)
        return False


def span(name: str, cat: str = "op", **args):
    """Context manager timing a host span. No-op unless the layer is
    armed. Nesting works naturally (inner spans simply record shorter,
    later-starting intervals on the same thread id — chrome tracing
    and ``nest()`` reconstruct the stack from containment). ``step=``
    is inherited by the spans opened inside."""
    if not (_metrics_on or _profiler_on):
        return _NULL
    return Span(name, cat, args or None)


def open_family() -> Optional[str]:
    """The part before the ``/`` of this thread's innermost open span
    that has one (``executor`` under ``executor/launch``; the
    interpreter's per-op spans are named by the op type alone and stand
    under ``executor/step``). None where no such span is open."""
    sp = getattr(_open, "span", None)
    while sp is not None:
        family, slash, _ = sp.name.partition("/")
        if slash:
            return family
        sp = sp._outer
    return None


def record_ending_now(name: str, dur_s: float, cat: str = "op") -> None:
    """Record a span of ``dur_s`` seconds that ends now: a duration
    something else measured (``jax.monitoring``), as a child of this
    thread's innermost open span, whose ``step`` it takes. The buffer
    only: an annotation cannot be opened in the past."""
    if not active():
        return
    outer = getattr(_open, "span", None)
    args = None
    if outer is not None and outer.args and "step" in outer.args:
        args = {"step": outer.args["step"]}
    _record(name, (time.perf_counter() - dur_s) * 1e6, dur_s * 1e6,
            cat, args)


def _record(name, ts_us, dur_us, cat, args) -> None:
    global _dropped, _session_start
    ev = (name, ts_us, dur_us, threading.get_ident(), cat, args)
    with _lock:
        if _profiler_on:
            agg = _session_agg.get(name)
            if agg is None:
                agg = _session_agg[name] = [0, 0.0]
            agg[0] += 1
            agg[1] += dur_us
        if len(_events) >= _MAX_EVENTS:
            # drop the oldest half in one move: amortized O(1) per
            # record, and the newest spans (the ones being debugged)
            # survive
            cut = _MAX_EVENTS // 2
            del _events[:cut]
            _dropped += cut
            _session_start = max(0, _session_start - cut)
        _events.append(ev)
    sp = _spool
    if sp is not None:
        sp.offer(ev)


def stats() -> Dict[str, int]:
    with _lock:
        return {"recorded": len(_events), "dropped": _dropped}


def trace_events() -> List[Tuple]:
    """All buffered spans (live metrics spans + any live profiler
    session)."""
    with _lock:
        return list(_events)


def nest(events) -> List[Dict]:
    """The span records of ``events`` as dicts in order of start, each
    with its ``parent`` (index into the result of the enclosing span on
    the same thread, or None), its ``depth`` and its ``self_us``: the
    duration less what its children cover."""
    out: List[Dict] = []
    stacks: Dict[int, List[int]] = {}
    for name, ts, dur, tid, cat, args in sorted(
            events, key=lambda e: (e[1], -e[2])):
        stack = stacks.setdefault(tid, [])
        while stack and ts >= (out[stack[-1]]["ts_us"]
                               + out[stack[-1]]["dur_us"]):
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            out[parent]["self_us"] -= dur
        out.append({"name": name, "ts_us": ts, "dur_us": dur, "tid": tid,
                    "cat": cat, "args": args, "parent": parent,
                    "depth": len(stack), "self_us": dur})
        stack.append(len(out) - 1)
    return out


def clear() -> None:
    global _dropped, _session_start
    with _lock:
        del _events[:]
        _dropped = 0
        _session_start = 0
        _session_agg.clear()


# -- legacy profiler sessions ---------------------------------------------

def profiler_session_active() -> bool:
    return _profiler_on


def profiler_session_start() -> None:
    global _profiler_on, _session_start
    with _lock:
        _session_start = len(_events)
        _session_agg.clear()
    _profiler_on = True


def profiler_session_events() -> List[Tuple]:
    """Spans recorded since the live session started (empty when no
    session is live)."""
    if not _profiler_on:
        return []
    with _lock:
        return list(_events[_session_start:])


def profiler_session_reset() -> None:
    """Discard the live session's spans and aggregates without ending
    it (and without touching metrics-mode spans recorded before the
    session — the legacy reset_profiler only ever owned its own
    events)."""
    global _session_start
    with _lock:
        if _profiler_on:
            del _events[_session_start:]
        else:
            _session_start = len(_events)
        _session_agg.clear()


def profiler_session_stop():
    """End the live session: (spans, exact per-name aggregates). The
    spans are drained OUT of the buffer (the old profiler's
    snapshot-and-clear contract: sessions never bleed into each other,
    and a later metrics-mode chrome export doesn't double-count them);
    the aggregates are exact even if buffer pressure dropped old spans
    mid-session. A stop with no live session is a harmless no-op (the
    legacy profiler tolerated it; without this guard it would drain
    metrics-mode spans that were never the session's)."""
    global _profiler_on
    if not _profiler_on:
        return [], {}
    _profiler_on = False
    with _lock:
        sess = list(_events[_session_start:])
        del _events[_session_start:]
        agg = {k: tuple(v) for k, v in _session_agg.items()}
        _session_agg.clear()
    return sess, agg


# -- chrome trace_event export --------------------------------------------

def chrome_trace(extra_events=None) -> Dict:
    """chrome://tracing / Perfetto ``trace_event`` JSON object.

    Merges the live span buffer with ``extra_events`` — (name, ts_us,
    dur_us) triples or full 6-tuples — which is how the legacy
    ``profiler.get_trace_events()`` timeline survives into the unified
    export (observability.chrome_trace passes it in)."""
    seen = []
    for ev in trace_events():
        seen.append(ev)
    for ev in (extra_events or []):
        if len(ev) == 3:
            name, ts, dur = ev
            seen.append((name, ts, dur, 0, "op", None))
        else:
            seen.append(tuple(ev))
    out = []
    for name, ts, dur, tid, cat, args in seen:
        entry = {"name": name, "ph": "X", "ts": ts, "dur": dur,
                 "pid": 0, "tid": tid, "cat": cat}
        if args:
            entry["args"] = dict(args)
        out.append(entry)
    out.sort(key=lambda e: e["ts"])
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(path: str, extra_events=None) -> str:
    with open(path, "w") as f:
        json.dump(chrome_trace(extra_events), f)
    return path
