"""Profile -> chrome://tracing JSON converter.

Parity: /root/reference/tools/timeline.py (profile proto -> chrome
trace). Host-side events recorded by fluid.profiler convert directly:
per-OP events when the interpreter executes (host/LoD programs,
FLAGS_check_nan_inf), the "executor/run" span and its parts (stage,
launch, writeback, fetch) per step on the whole-compiled path (a
compiled step IS one fused kernel — per-op device detail lives in the
jax.profiler XPlane trace dir for TensorBoard/Perfetto, which
replaces the CUPTI DeviceTracer path; the same spans are in it as
"pt:executor/*").

Usage:
    with fluid.profiler.profiler():
        ... training ...
    from paddle_tpu.tools.timeline import write_chrome_trace
    write_chrome_trace("/tmp/timeline.json")
"""
from __future__ import annotations

import json

__all__ = ["chrome_trace_events", "write_chrome_trace"]


def chrome_trace_events(events=None, pid=0, tid=0):
    """Convert (name, ts_us, dur_us) tuples into chrome trace 'X' events."""
    if events is None:
        from .. import profiler

        events = profiler.get_trace_events()
    return [
        {"name": name, "ph": "X", "ts": ts, "dur": dur,
         "pid": pid, "tid": tid, "cat": "op"}
        for (name, ts, dur) in events
    ]


def write_chrome_trace(path, events=None):
    trace = {"traceEvents": chrome_trace_events(events),
             "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(trace, f)
    return path
