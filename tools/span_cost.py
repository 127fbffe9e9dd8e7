"""What one program span costs the host.

    python3 tools/span_cost.py [--spans 200000]

Times an empty ``with observability.tracing.span(...)`` off (the shared null
object), armed (a record in the buffer and a ``TraceAnnotation`` with no trace
running) and armed under a live ``jax.profiler`` trace (the annotation is
written to the host plane too), on the machine it runs on; prints nanoseconds
a span. A step of the mesh path opens seven armed spans (the root and six
``parallel/*``), one of the one-chip path six. Host clock; touches no device.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def ns_per_span(n):
    from paddle_tpu.observability import tracing

    span = tracing.span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("parallel/stage", cat="step"):
            pass
    return (time.perf_counter() - t0) / n * 1e9


def main(argv):
    p = argparse.ArgumentParser(prog="tools/span_cost.py")
    p.add_argument("--spans", type=int, default=200000)
    args = p.parse_args(argv)

    import jax

    from paddle_tpu import observability as obs

    out = {"spans": args.spans, "off_ns": ns_per_span(args.spans)}
    obs.enable()
    ns_per_span(1000)
    out["armed_ns"] = ns_per_span(args.spans)
    obs.tracing.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=options)
        try:
            out["armed_traced_ns"] = ns_per_span(min(args.spans, 50000))
        finally:
            jax.profiler.stop_trace()
    obs.disable()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
