"""One ``M`` layer's selective scan alone, on the chip: the Pallas kernels of
``ops/pallas/ssd_scan.py`` (forward, state pass, state pass + backward kernel)
and the ops around them, against the XLA form of ``ops/ssm_ops.py`` on the
same inputs, at the hybrid cell's shape unless told otherwise.

    chiprun --chips 1 -- python3 tools/ssm_bench.py [--out <file>]
    JAX_PLATFORMS=cpu python3 tools/ssm_bench.py --compile-only   # v5e compiler, no chip

One JSON line for the forward op and one for the gradient op in either form:
milliseconds on the host's clock (median of ``--iters``; a launch costs the
host 0.3-0.7 ms) and the device's own time a call with its five longest
operations (a profiler trace of ``--iters`` calls; the kernels go by their
names: ``ssd_scan_fwd``, and in the gradient op ``ssd_scan_state`` and
``ssd_scan_bwd``). Then the largest difference of the kernels' result and of
each gradient from the XLA form's, relative to its largest entry.
"""
from __future__ import annotations

import argparse
import collections
import glob
import importlib
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops import ssm_ops

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
SLOTS = ("X", "Dt", "A", "B", "C", "D", "DtBias")


def inputs(key, batch, t, h, p, g, n, dtype):
    """The op's slots as the model feeds them: x, B, C in ``dtype``, the raw
    step sizes in it too (AMP casts the projection), A, D, DtBias float32."""
    k = jax.random.split(key, 8)
    f32 = jnp.float32
    return {
        "X": jax.random.normal(k[0], (batch, t, h, p), f32).astype(dtype),
        "Dt": jax.random.normal(k[1], (batch, t, h), f32).astype(dtype),
        "A": -jnp.exp(0.5 * jax.random.normal(k[2], (h,), f32)),
        "B": jax.random.normal(k[3], (batch, t, g, n), f32).astype(dtype),
        "C": jax.random.normal(k[4], (batch, t, g, n), f32).astype(dtype),
        "D": 1 + 0.1 * jax.random.normal(k[5], (h,), f32),
        "DtBias": jax.random.normal(k[6], (h,), f32) - 3,
        "Out@GRAD": jax.random.normal(k[7], (batch, t, h, p),
                                      f32).astype(dtype)}


def ops(chunk):
    """(forward op, gradient op) over the slots, as tuples of arrays."""
    reg = OpInfoMap.instance()
    fwd, bwd = reg.get("ssd_chunk_scan").fn, reg.get("ssd_chunk_scan_grad").fn
    attrs = {"chunk": chunk}

    def forward(ins):
        return fwd(ins, attrs)["Out"]

    def grad(ins):
        got = bwd(ins, attrs)
        return tuple(got[n + "@GRAD"] for n in SLOTS)
    return forward, grad


def on(platform, fn):
    """``fn`` jitted, traced with the program's question answered
    ``platform``: a function of its own, since a trace is cached by the
    function traced."""
    def traced(*args):
        asked = fa.compute_platform
        fa.compute_platform = lambda: platform
        try:
            return fn(*args)
        finally:
            fa.compute_platform = asked
    return jax.jit(traced)


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def ms_of(fn, args, iters):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return round(1e3 * statistics.median(times), 3)


def device_ms(fn, args, iters):
    """{"ms": the device's time a call, "ops": its five longest operations}
    from a profiler trace of ``iters`` calls of ``fn``, already compiled. The
    trace lives in a directory of this call's own under the temporary
    directory and goes with it."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory(prefix="ssm_bench_") as trace_dir:
        jax.profiler.start_trace(trace_dir)
        for _ in range(iters):
            jax.block_until_ready(fn(*args))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        profile = ProfileData.from_file(path)   # read whole, here
    total = collections.Counter()
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        op = ev.name.split(" = ")[0].lstrip("%")
                        total[op.rstrip("0123456789").rstrip(".")] += \
                            ev.duration_ns
    return {"ms": round(sum(total.values()) / iters / 1e6, 4),
            "ops": {k: round(v / iters / 1e6, 4)
                    for k, v in total.most_common(5)}}


def compile_only(ins, chunk):
    """The three kernels through the TPU's compiler for a described v5e."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    forward, grad = ops(chunk)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), ins)
    for name, fn in (("ssd_chunk_scan", forward),
                     ("ssd_chunk_scan_grad", grad)):
        t0 = time.perf_counter()
        hlo = on("tpu", fn).lower(shapes).compile().as_text()
        print(json.dumps({
            "compiled": name, "s": round(time.perf_counter() - t0, 2),
            "mosaic_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        }), flush=True)
    return 0


def main(argv):
    p = argparse.ArgumentParser(prog="tools/ssm_bench.py")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--heads", type=int, default=64)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--groups", type=int, default=8)
    p.add_argument("--state", type=int, default=128)
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out")
    args = p.parse_args(argv)

    ins = inputs(jax.random.key(7), args.batch, args.tokens, args.heads,
                 args.head_dim, args.groups, args.state,
                 jnp.dtype(args.dtype))
    chunk = args.chunk
    if args.compile_only:
        return compile_only(ins, chunk)
    platform = jax.devices()[0].platform
    forward, grad = ops(chunk)
    lines = [{"platform": platform, "shape": {
        k: getattr(args, k) for k in ("batch", "tokens", "heads", "head_dim",
                                      "groups", "state", "chunk", "dtype")}}]
    print(json.dumps(lines[0]), flush=True)
    results = {}
    for path, asked in (("xla_chunked", "cpu"), (ssm_ops.scan_path(
            ins["X"], ins["B"], chunk), platform)):
        if path in results:     # off the TPU both are the XLA form
            continue
        f, g = on(asked, forward), on(asked, grad)
        results[path] = (f(ins), g(ins))
        for name, fn in (("ssd_chunk_scan", f), ("ssd_chunk_scan_grad", g)):
            line = {"path": path, "op": name,
                    "host_ms": ms_of(fn, (ins,), args.iters)}
            if platform == "tpu":
                line["device"] = device_ms(fn, (ins,), args.iters)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if "pallas" in results:
        (y, grads), (y0, grads0) = results["pallas"], results["xla_chunked"]
        lines.append({"rel_diff_from_xla_form": dict(
            [("Out", rel(y, y0))] + [(n + "@GRAD", rel(a, b)) for n, a, b in
                                     zip(SLOTS, grads, grads0)])})
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(lines, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
