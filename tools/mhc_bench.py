"""One sublayer's hyper-connection ops alone, on the chip: ``mhc_pre``,
``mhc_pre_grad``, ``mhc_post`` and ``mhc_post_grad`` on the Pallas kernels of
``ops/pallas/hyper_connection.py`` against their XLA form on the same inputs,
at the latent-attention cell's shape unless told otherwise, and each kernel
entry alone at several block sizes.

    chiprun --chips 1 -- python3 tools/mhc_bench.py [--out <file>]
    JAX_PLATFORMS=cpu python3 tools/mhc_bench.py --compile-only   # v5e compiler, no chip

One JSON line an op and form: milliseconds on the host's clock (median of
``--iters``) and the device's own time a call with its five longest
operations (a profiler trace of ``--iters`` calls; the kernels go by their
names, ``mhc_pre_fwd``, ``mhc_pre_reads``, ``mhc_pre_writes``,
``mhc_post_fwd``, ``mhc_post_bwd``), with the bytes the op must move at the
least and the share of the chip's 819 GB/s that makes of the device's time.
Then the largest difference of each output of the kernels' form from the XLA
form's, relative to its largest entry; then one line a kernel entry and block
size (``--tiles``). ``--compile-only`` sends every one of them through the
TPU's compiler for a described v5e: a block that overflows VMEM fails there.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

import ssm_bench as sb
from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops.pallas import hyper_connection as hk

HBM_GB_S = 819.0
# blocks tried where an entry holds whole rows of C (tokens), and where it
# tiles C (tokens, lanes; a width that does not divide C is left out)
ROW_TOKENS = (8, 16, 32, 64)
TILES = ((32, 3584), (64, 1792), (128, 896), (128, 512), (256, 256),
         (64, 896), (32, 1792), (256, 512))


def inputs(key, batch, n, t, c):
    """The four ops' slots, float32 as the model feeds them (the sublayer's
    output arrives through AMP's cast)."""
    k = jax.random.split(key, 11)
    f32 = jnp.float32
    kk = 2 * n + n * n
    return {
        "X": jax.random.normal(k[0], (batch, n, t, c), f32),
        "Phi": 0.02 * jax.random.normal(k[1], (n * c, kk), f32),
        "Alpha": jax.random.normal(k[2], (3,), f32),
        "BPre": jax.random.normal(k[3], (n,), f32),
        "BPost": jax.random.normal(k[4], (n,), f32),
        "BRes": jax.random.normal(k[5], (n, n), f32),
        "Y": jax.random.normal(k[6], (batch, t, c), f32),
        "H@GRAD": jax.random.normal(k[7], (batch, t, c), f32),
        "HPost@GRAD": jax.random.normal(k[8], (batch, n, t), f32),
        "HRes@GRAD": jax.random.normal(k[9], (batch, n, n, t), f32),
        "Out@GRAD": jax.random.normal(k[10], (batch, n, t, c), f32)}


def ops():
    """{op type: function of the slots -> tuple of its outputs}; the post
    ops read the maps among their slots (``HRes``, ``HPost``)."""
    reg = OpInfoMap.instance()

    def of(name):
        fn = reg.get(name).fn
        return lambda ins: tuple(v for _, v in sorted(fn(ins, {}).items()))
    return {name: of(name) for name in ("mhc_pre", "mhc_pre_grad", "mhc_post",
                                        "mhc_post_grad")}


def least_bytes(name, batch, n, t, c):
    """What the op must move: the streams (and their cotangent) once each
    way, ``h``, ``y`` and their cotangents once."""
    plane = 4 * batch * t * c
    return {"mhc_pre": (n + 1) * plane, "mhc_post": (2 * n + 1) * plane,
            "mhc_pre_grad": (2 * n + 1) * plane,
            "mhc_post_grad": (3 * n + 2) * plane}[name]


def entries(ins, n):
    """{entry: (function of (block) -> jitted call, its arguments, the blocks
    tried)}: each kernel entry alone, on operands of its shapes."""
    x, y, d = ins["X"], ins["Y"], ins["Out@GRAD"]
    batch, _, t, c = x.shape
    kk = 2 * n + n * n
    phi = jnp.swapaxes(ins["Phi"].reshape(n, c, kk), 1, 2)

    def per_token(width):
        return jnp.ones((batch, t, width), jnp.float32)

    tiles = [tile for tile in TILES if c % tile[1] == 0 and t % tile[0] == 0]
    rows = [r for r in ROW_TOKENS if t % r == 0]
    return {
        "pre_forward": (lambda b: lambda *a: hk.pre_forward(
            *a, eps=1e-6, tokens=b), (x, phi, jnp.ones((2, kk))), rows),
        "pre_grad_reads": (lambda b: lambda *a: hk.pre_grad_reads(
            *a, eps=1e-6, tokens=b), (x, phi, y), rows),
        "pre_grad_writes": (lambda b: lambda *a: hk.pre_grad_writes(
            *a, tokens=b), (x, phi, y, per_token(kk), per_token(n + 1)),
            rows),
        "post_forward": (lambda b: lambda *a: hk.post_forward(*a, tile=b),
                         (x, per_token(n * n + n), y), tiles),
        "post_backward": (lambda b: lambda *a: hk.post_backward(*a, tile=b),
                          (x, per_token(n * n + n), y, d), tiles)}


def compile_only(ins, n):
    """The four ops in the kernels' form and every entry at every block
    through the TPU's compiler for a described v5e."""
    import time

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    failed = 0

    def attempt(what, fn, args):
        nonlocal failed
        t0 = time.perf_counter()
        try:
            hlo = fn.lower(*described(args)).compile().as_text()
            line = {"mosaic_calls": hlo.count(
                'custom_call_target="tpu_custom_call"')}
        except Exception as e:  # noqa: BLE001 - each case is reported
            failed += 1
            line = {"failed": "%s: %s" % (type(e).__name__,
                                          str(e)[:300].replace("\n", " | "))}
        print(json.dumps(dict(what, s=round(time.perf_counter() - t0, 2),
                              **line)), flush=True)

    maps = dict(ins, HRes=ins["HRes@GRAD"], HPost=ins["HPost@GRAD"])
    for name, fn in ops().items():
        attempt({"compiled": name}, sb.on("tpu", fn), (maps,))
    for name, (make, args, blocks) in entries(ins, n).items():
        for block in blocks:
            attempt({"compiled": name, "block": block}, jax.jit(make(block)),
                    args)
    return 1 if failed else 0


def main(argv):
    p = argparse.ArgumentParser(prog="tools/mhc_bench.py")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--streams", type=int, default=4)
    p.add_argument("--tokens", type=int, default=4096)
    p.add_argument("--hidden", type=int, default=3584)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--tiles", action="store_true",
                   help="also each kernel entry at each block size")
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--out")
    args = p.parse_args(argv)

    n = args.streams
    dims = (args.batch, n, args.tokens, args.hidden)
    ins = inputs(jax.random.key(7), *dims)
    if args.compile_only:
        return compile_only(ins, n)
    platform = jax.devices()[0].platform
    lines = [{"platform": platform, "shape": dict(zip(
        ("batch", "streams", "tokens", "hidden"), dims))}]
    print(json.dumps(lines[0]), flush=True)

    def timed(fn, call_args):
        line = {"host_ms": sb.ms_of(fn, call_args, args.iters)}
        if platform == "tpu":
            line["device"] = sb.device_ms(fn, call_args, args.iters)
        return line

    pre = jax.jit(lambda ins: OpInfoMap.instance().get("mhc_pre").fn(
        ins, {}))(ins)
    ins = dict(ins, HRes=pre["HRes"], HPost=pre["HPost"])
    results = {}
    for form, asked in (("xla", "cpu"), ("pallas", platform)):
        if asked != "tpu" and form == "pallas":
            continue            # off the TPU both are the XLA form
        for name, fn in ops().items():
            fn = sb.on(asked, fn)
            results[form, name] = fn(ins)
            line = dict({"form": form, "op": name}, **timed(fn, (ins,)))
            if "device" in line:
                least = least_bytes(name, *dims)
                line["least_gb"] = round(least / 1e9, 3)
                line["hbm_share_pct"] = round(
                    100 * least / 1e9 / HBM_GB_S / line["device"]["ms"] * 1e3,
                    1)
            lines.append(line)
            print(json.dumps(line), flush=True)
    for name in ops() if ("pallas", "mhc_pre") in results else ():
        lines.append({"op": name, "rel_diff_from_xla_form": [
            sb.rel(a, b) for a, b in zip(results["pallas", name],
                                         results["xla", name])]})
        print(json.dumps(lines[-1]), flush=True)
    if args.tiles and platform == "tpu":
        for name, (make, call_args, blocks) in entries(ins, n).items():
            for block in blocks:
                try:
                    line = timed(jax.jit(make(block)), call_args)
                except Exception as e:  # noqa: BLE001 - reported, next block
                    line = {"failed": "%s: %s" % (type(e).__name__,
                                                  str(e)[:200])}
                lines.append(dict({"entry": name, "block": block}, **line))
                print(json.dumps(lines[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(lines, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
