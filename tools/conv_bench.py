"""One causal depthwise convolution alone, on the chip: the ``causal_conv1d``
op and its gradient op ``causal_conv1d_grad`` as the registry holds them, at
the two cells' shapes unless told otherwise (the delta-rule cell's
``[1, 8192, 4096]`` without a bias, the hybrid cell's ``[1, 8192, 6144]``
with one; 4 taps, ``silu``, bf16 operands as AMP hands them over).

    chiprun --chips 1 -- python3 tools/conv_bench.py [--out <file>]
    JAX_PLATFORMS=cpu python3 tools/conv_bench.py --compile-only   # v5e compiler, no chip

One JSON line an op and a shape: milliseconds on the host's clock (median of
``--iters``; a launch costs the host 0.3-0.7 ms), the device's own time a
call with its five longest operations (a profiler trace of ``--iters``
calls), the floor (the op's operands and results moved once: x in and Out
out; x and the cotangent in and X's gradient out) and the GB/s that floor
makes over the device's time. The tool asks the registry alone, so it reads
an older commit's automatic gradient op from that commit's checkout.
``--compile-only`` prints, beside that floor, the bytes the compiled op's
instructions move (the compiler's own count for a described v5e).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpInfoMap
from tools.ssm_bench import device_ms, ms_of

SHAPES = {"kda": (1, 8192, 4096, False), "mamba": (1, 8192, 6144, True)}
TAPS, ACTIVATION, DTYPE = 4, "silu", jnp.bfloat16


def inputs(key, batch, t, c, bias):
    k = jax.random.split(key, 4)
    ins = {"X": jax.random.normal(k[0], (batch, t, c)).astype(DTYPE),
           "W": (0.5 * jax.random.normal(k[1], (c, TAPS))).astype(DTYPE),
           "Out@GRAD": jax.random.normal(k[3], (batch, t, c)).astype(DTYPE)}
    if bias:
        ins["Bias"] = jax.random.normal(k[2], (c,)).astype(DTYPE)
    return ins


def ops():
    """{op type: (jitted op over the slots, its floor in bytes of X's)}."""
    reg = OpInfoMap.instance()
    attrs = {"activation": ACTIVATION}

    def forward(ins):
        ins = {k: v for k, v in ins.items() if k != "Out@GRAD"}
        return reg.get("causal_conv1d").fn(ins, attrs)["Out"]

    def grad(ins):
        got = reg.get("causal_conv1d_grad").fn(ins, attrs)
        return tuple(got[n + "@GRAD"] for n in ("X", "W", "Bias")
                     if n in ins)
    return {"causal_conv1d": (jax.jit(forward), 2),
            "causal_conv1d_grad": (jax.jit(grad), 3)}


def compile_only(cases):
    """Each op at each shape through the TPU's compiler for a described
    v5e: the bytes its instructions move, beside the floor."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    fns = ops()
    for shape, ins in cases:
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), ins)
        for name, (fn, passes) in fns.items():
            t0 = time.perf_counter()
            compiled = fn.lower(shapes).compile()
            cost = compiled.cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            print(json.dumps({
                "compiled": name, "shape": shape,
                "s": round(time.perf_counter() - t0, 2),
                "moved_mb": round(cost["bytes accessed"] / 1e6, 1),
                "floor_mb": round(passes * ins["X"].nbytes / 1e6, 1),
                "temporaries_mb": round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e6, 1),
            }), flush=True)
    return 0


def main(argv):
    p = argparse.ArgumentParser(prog="tools/conv_bench.py")
    p.add_argument("--shapes", default="kda,mamba",
                   help="of %s" % ",".join(sorted(SHAPES)))
    p.add_argument("--tokens", type=int,
                   help="instead of the shapes' own (a rehearsal on the CPU)")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out")
    args = p.parse_args(argv)

    cases = []
    for i, name in enumerate(args.shapes.split(",")):
        b, t, c, bias = SHAPES[name]
        t = args.tokens or t
        cases.append(([b, t, c, bias], inputs(
            jax.random.key(7 + i), b, t, c, bias)))
    if args.compile_only:
        return compile_only(cases)
    platform = jax.devices()[0].platform
    lines = [{"platform": platform, "taps": TAPS, "dtype": DTYPE.__name__,
              "activation": ACTIVATION}]
    print(json.dumps(lines[0]), flush=True)
    fns = ops()
    for shape, ins in cases:
        for name, (fn, passes) in fns.items():
            floor_mb = passes * ins["X"].nbytes / 1e6
            line = {"op": name, "shape": shape,
                    "host_ms": ms_of(fn, (ins,), args.iters),
                    "floor_mb": round(floor_mb, 1)}
            if platform == "tpu":
                line["device"] = device_ms(fn, (ins,), args.iters)
                line["floor_gb_per_s"] = round(
                    floor_mb / line["device"]["ms"], 1)
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(lines, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
