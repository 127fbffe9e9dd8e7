"""The held experts' grouped products alone, on the chip: ``moe_topk``'s
sorted-slot branch forward + backward at a cell's shapes, through
``jax.lax.ragged_dot`` and through the megablox kernels at several tilings,
for several loads of the held experts (level and skewed onto one expert).

    chiprun --chips 1 -- python3 tools/moe_bench.py [--out <file>]

Prints one JSON line for each (path, tiling, load, skew): milliseconds of one
forward + backward (median of ``--iters``), the TFLOP/s that is of the slots'
products (forward twice two products, backward four), and the largest
difference from the ``ragged_dot`` result relative to its largest entry.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def slots(key, tokens, k, experts, count, load, skew):
    """Choices idx [tokens, k]: ``load`` slots on the held experts 0..count-1
    (``skew`` of them on expert 0, the rest level), the others elsewhere."""
    import jax
    import jax.numpy as jnp

    k1, k2, k3 = jax.random.split(key, 3)
    held = jax.random.permutation(k1, tokens * k)[:load]
    first = jax.random.uniform(k2, (load,)) < skew
    which = jnp.where(first, 0, jax.random.randint(k3, (load,), 0, count))
    flat = jax.random.randint(k1, (tokens * k,), count, experts)
    return flat.at[held].set(which).reshape(tokens, k).astype(jnp.int32)


def main(argv):
    p = argparse.ArgumentParser(prog="tools/moe_bench.py")
    p.add_argument("--tokens", type=int, default=8192)
    p.add_argument("--hidden", type=int, default=2688)
    p.add_argument("--width", type=int, default=1856)
    p.add_argument("--experts", type=int, default=128)
    p.add_argument("--held", type=int, default=8)
    p.add_argument("--k", type=int, default=6)
    p.add_argument("--loads", type=int, nargs="+",
                   default=[2400, 3072, 3900])
    p.add_argument("--tilings", nargs="+",
                   default=["512,1024,1024", "256,1024,1024",
                            "512,896,1024", "512,1344,512"])
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--out")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import moe_ops
    import importlib
    fa = importlib.import_module('paddle_tpu.ops.pallas.flash_attention')

    t, d, f = args.tokens, args.hidden, args.width
    key = jax.random.key(7)
    ks = jax.random.split(key, 5)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    w1 = (0.02 * jax.random.normal(ks[1], (args.held, d, f))).astype(bf16)
    w2 = (0.02 * jax.random.normal(ks[2], (args.held, f, d))).astype(bf16)
    weight = jax.random.uniform(ks[3], (t, args.k), jnp.float32)
    g = jax.random.normal(ks[4], (t, d), jnp.float32)

    def step(x, w1, w2, weight, idx):
        def f_(x, w1, w2, weight):
            out, load = moe_ops._held_part(x, w1, w2, idx, weight, args.k,
                                           args.experts, 0, args.held)
            return jnp.sum(out * g), load
        (_, load), grads = jax.value_and_grad(
            f_, argnums=(0, 1, 2), has_aux=True)(x, w1, w2, weight)
        return grads, load

    lines, paths = [], [("ragged_dot", None)] + [
        ("megablox", tuple(int(v) for v in s.split(",")))
        for s in args.tilings]
    want = {}
    for path, tiling in paths:
        fa_platform = fa.compute_platform
        if path == "ragged_dot":
            fa.compute_platform = lambda: "cpu"
        else:
            moe_ops.TILING = tiling
        try:
            # a function of its own: a trace is cached by the function traced
            fn = jax.jit(lambda *a: step(*a))
            for load in args.loads:
                for skew in (0.0, 0.8):
                    idx = slots(jax.random.fold_in(key, load), t, args.k,
                                args.experts, args.held, load, skew)
                    try:
                        grads, got = fn(x, w1, w2, weight, idx)
                        jax.block_until_ready(grads)
                    except Exception as e:  # noqa: BLE001 — reported
                        lines.append({"path": path, "tiling": tiling,
                                      "error": str(e)[:300]})
                        print(json.dumps(lines[-1]), flush=True)
                        break
                    times = []
                    for _ in range(args.iters):
                        t0 = time.perf_counter()
                        jax.block_until_ready(fn(x, w1, w2, weight, idx))
                        times.append(time.perf_counter() - t0)
                    ms = 1e3 * statistics.median(times)
                    if path == "ragged_dot":
                        want[load, skew] = grads
                        diff = 0.0
                    else:
                        diff = max(
                            float(jnp.max(jnp.abs(
                                a.astype(jnp.float32) - b.astype(jnp.float32)))
                                / jnp.max(jnp.abs(b.astype(jnp.float32))))
                            for a, b in zip(grads, want[load, skew]))
                    flops = 6 * 2 * load * d * f
                    lines.append({
                        "path": path, "tiling": tiling, "load": load,
                        "skew": skew, "held_slots": int(jnp.sum(got[:-1])),
                        "slow_branch": int(got[-1]), "ms": round(ms, 3),
                        "tflops": round(flops / ms / 1e9, 2),
                        "rel_diff": diff})
                    print(json.dumps(lines[-1]), flush=True)
        finally:
            fa.compute_platform = fa_platform
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(lines, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
