"""One ``K`` layer's delta rule alone, on the chip: the ``kda_chunk`` op and
its gradient op of ``ops/kda_ops.py`` on the Pallas kernels of
``ops/pallas/kda.py``, at the delta-rule cell's shape unless told otherwise.

    chiprun --chips 1 -- python3 tools/kda_bench.py [--variants 4,8 2,4] [--out <file>]

First, at ``--check-tokens`` positions, how near the ops in bf16 come to the
XLA form in float32 at full precision, on the kernels and in XLA einsums
(relative L2 error of the output and of each gradient): the kernels should
read what the einsums read. Then one JSON line for the forward op and one
for the gradient op at each variant ``heads,base`` (the heads of a grid
step, ``kda.HEADS``, and the positions whose pairs are summed channel by
channel, ``kda.BASE``: this tool sets them, the kernels read them):
milliseconds on the host's clock (median of ``--iters``) and the device's
own time a call with its five longest operations (a profiler trace, as
``tools/ssm_bench.py`` reads one). The gradient op is two kernels: the
state pass ``kda_states``, which goes over the chunks in order without q
and without an output and keeps, of every chunk and head, the state that
enters it, the inverse ``T``, the Gram matrix ``A`` and the inverse's
products ``W`` and ``U0``; and ``kda_bwd``, which reads them in reverse. Its
line names each by the device's own time where it is among the five
longest (``state_pass_ms``, ``kda_bwd_ms``: a third Mosaic call, the
``vjp``'s dead forward, would stand beside them) and gives the bytes kept a
layer (``kept_bytes``). Whether the kernels compile for the v5e is
``tests/tpu_kernel_cases.py``'s to say, here, without a chip.

The operands are 4-D parameters of the jitted call, whose layout XLA pins
head-minor, so each call pays ~1 ms of ``reshape`` and ``copy`` that the
model's step, where the operands come from 2-D products, does not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                os.path.dirname(os.path.abspath(__file__))]

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops import kda_ops
from paddle_tpu.ops.pallas import kda
from ssm_bench import device_ms, ms_of


def inputs(key, batch, t, h, d, dtype):
    """The op's slots as the model feeds them: q, k, v after silu, the raw
    gate projections in ``dtype`` (AMP casts the products before them), the
    decay's leaves float32 and seeded as the configuration seeds them."""
    k = jax.random.split(key, 8)
    f32 = jnp.float32

    def act(key, shape):
        return jax.nn.silu(jax.random.normal(key, shape, f32)).astype(dtype)

    dt = jnp.exp(jax.random.uniform(k[6], (h * d,), f32, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return {
        "Q": act(k[0], (batch, t, h, d)), "K": act(k[1], (batch, t, h, d)),
        "V": act(k[2], (batch, t, h, d)),
        "G": (0.5 * jax.random.normal(k[3], (batch, t, h, d), f32)
              ).astype(dtype),
        "Beta": jax.random.normal(k[4], (batch, t, h), f32).astype(dtype),
        "ALog": jnp.log(jax.random.uniform(k[5], (h,), f32, 1.0, 16.0)),
        "DtBias": dt + jnp.log(-jnp.expm1(-dt)),
        "Out@GRAD": jax.random.normal(k[7], (batch, t, h, d),
                                      f32).astype(dtype)}


def ops(chunk, path=None):
    """(forward op, gradient op) jitted afresh; ``path`` answers
    ``kda_path`` while they trace."""
    reg = OpInfoMap.instance()
    fwd, bwd = reg.get("kda_chunk").fn, reg.get("kda_chunk_grad").fn
    attrs = {"chunk": chunk}

    def on_path(fn):
        def traced(ins):
            was = kda_ops.kda_path
            if path:
                kda_ops.kda_path = lambda *a, **k: path
            try:
                return fn(ins)
            finally:
                kda_ops.kda_path = was
        return jax.jit(traced)

    return (on_path(lambda ins: {"Out": fwd(ins, attrs)["Out"]}),
            on_path(lambda ins: bwd(ins, attrs)))


def gradient_kernels(device, ins, chunk):
    """The gradient op's two kernels by the device's own time, from
    ``device_ms``'s five longest operations, and the bytes the state pass
    keeps for the backward."""
    def ms(kernel):
        return next((v for k, v in device["ops"].items()
                     if k.startswith(kernel)), None)

    B, T, H, K = ins["K"].shape
    T += -T % chunk
    f32 = jnp.float32
    kept = jax.eval_shape(
        lambda *a: kda.states(*a, chunk=chunk),
        *(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
            ((B, T, H, K), ins["K"].dtype),
            ((B, T, H, ins["V"].shape[-1]), ins["V"].dtype),
            ((B, T, H, K), f32), ((B, T, H), f32))))
    return {"state_pass_ms": ms("kda_states"), "kda_bwd_ms": ms("kda_bwd"),
            "kept_bytes": sum(a.size * a.dtype.itemsize for a in kept)}


def rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def main(argv):
    p = argparse.ArgumentParser(prog="tools/kda_bench.py")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--check-tokens", type=int, default=2048)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--head-dim", type=int, default=128)
    p.add_argument("--chunk", type=int, default=64)
    p.add_argument("--variants", nargs="+",
                   default=["%d,%d" % (kda.HEADS, kda.BASE)])
    p.add_argument("--dtype", default="bfloat16")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--out")
    args = p.parse_args(argv)
    variants = [tuple(int(x) for x in v.split(",")) for v in args.variants]
    dtype = jnp.dtype(args.dtype)
    platform = jax.devices()[0].platform
    lines = []

    def say(line):
        lines.append(line)
        print(json.dumps(line), flush=True)

    say({"platform": platform, "shape": {
        k: getattr(args, k) for k in ("batch", "tokens", "heads", "head_dim",
                                      "chunk", "dtype")}})
    if platform != "tpu":
        print("kda_bench: the kernels need a TPU", file=sys.stderr)
        return 1

    def both(ins, path=None):
        fwd, bwd = ops(args.chunk, path)
        return dict(fwd(ins), **bwd(ins))

    ins = inputs(jax.random.key(11), args.batch, args.check_tokens,
                 args.heads, args.head_dim, dtype)
    with jax.default_matmul_precision("highest"):
        true = both({k: v.astype(jnp.float32) for k, v in ins.items()},
                    "xla_chunked")
    einsums = both(ins, "xla_chunked")
    for heads, base in variants:
        kda.HEADS, kda.BASE = heads, base
        jax.clear_caches()
        got = both(ins)
        say({"heads": heads, "base": base, "against_float32": {
            n: {"kernels": rel(got[n], true[n]),
                "einsums": rel(einsums[n], true[n])} for n in sorted(got)}})

    ins = inputs(jax.random.key(7), args.batch, args.tokens, args.heads,
                 args.head_dim, dtype)
    for heads, base in variants:
        kda.HEADS, kda.BASE = heads, base
        jax.clear_caches()
        for name, fn in zip(("kda_chunk", "kda_chunk_grad"), ops(args.chunk)):
            line = {"op": name, "heads": heads, "base": base,
                    "host_ms": ms_of(fn, (ins,), args.iters),
                    "device": device_ms(fn, (ins,), args.iters)}
            if name == "kda_chunk_grad":
                line.update(gradient_kernels(line["device"], ins,
                                             args.chunk))
            say(line)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
