"""Attention's core alone, forward + backward, on the chip: the short-path
kernels of ``ops/pallas/flash_attention.py``, head-major over the heads a grid
step takes and token-major over their blocks, against the streaming kernels and
XLA's dense lowering on the same inputs.

    chiprun --chips 1 -- python3 tools/attn_bench.py            # times, on the chip
    JAX_PLATFORMS=cpu python3 tools/attn_bench.py --compile-only  # v5e compiler, no chip

``short_h<n>`` takes n heads a grid step of [B, H, T, D] operands;
``tokens_r<rows>c<lanes>`` takes that block of [B, T, H*D] operands; what
``_short_heads`` and ``_tokens_blocks`` pick for the shape is starred. One line a
variant: milliseconds of one forward + backward and of the forward alone, and the
relative error of out, dq, dk, dv against the dense float32 math.

The shapes that stream (the three decoder cells': shared K/V heads, a value dim
of its own, a selection) run the streaming forward with each backward:
``stream_bwd_fused`` is the one kernel that makes each score tile once,
``stream_bwd_split`` the dQ and dK+dV pair (``_fused_bwd_fits`` answered for the
trace: the rule itself picks the one kernel at the two shapes with shared K/V
heads and the pair at the latent cell's 32 / 32). The dense math of such a
shape runs a query head at a time (32 heads x 16,384^2 float32 scores do not
fit the chip).
"""
from __future__ import annotations

import argparse
import importlib
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
f32 = jnp.float32

SHAPES = {   # name: (B, H, T, D, causal, with lengths)
    "bert_s512": (32, 12, 512, 64, False, False),
    "bert_dp4_s128": (128, 12, 128, 64, False, False),
    "wmt_s256": (64, 8, 256, 64, True, True),
    "s1024": (8, 12, 1024, 64, False, False),
}
STREAM_SHAPES = {   # name: (H, H_kv, T, D, Dv, keys a query selects or 0)
    "keye_s16384": (32, 4, 16384, 128, 128, 2048),
    "xing4_s4096": (32, 32, 4096, 192, 128, 0),
    "nemotron_s8192": (32, 2, 8192, 128, 128, 0),
}


def token_blocks(B, H, T, D):
    """(rows, lanes) of the token-major kernels: the rule's own choice first,
    then half and double its rows and half its lanes."""
    chosen = fa._tokens_blocks(B, T, H, D, 2)
    if chosen is None:
        return []
    rows, lanes = chosen
    found = []
    for r, c in (chosen, (max(1, rows // 2), lanes), (2 * rows, lanes),
                 (rows, lanes // 2)):
        if ((r, c) not in found and B % r == 0
                and c % max(128, D) == 0 and (H * D) % c == 0
                and fa._tokens_vmem_bytes(r, T, c, 2)
                <= fa.SHORT_VMEM_BUDGET):
            found.append((r, c))
    return found


def variants(B, H, T, D, causal, lengths):
    """(name, forward + backward) of each variant; a name that starts with
    ``tokens`` takes [B, T, H*D] operands, every other [B, H, T, D]."""
    scale = float(D) ** -0.5

    def grads(attn):
        def both(q, k, v, ct):   # one forward, one backward
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(ct.astype(out.dtype))
        both.forward = lambda q, k, v, ct: attn(q, k, v)
        return both

    yield "dense", grads(lambda q, k, v: fa._dense_attention(
        q, k, v, causal, scale, lengths))
    # what the model wrote before: bf16 scores, float32 softmax
    if not causal and lengths is None:
        def xla(q, k, v):
            s = jnp.einsum("bhqd,bhkd->bhqk", q * scale, k)
            p = jax.nn.softmax(s.astype(f32), axis=-1).astype(q.dtype)
            return jnp.einsum("bhqk,bhkd->bhqd", p, v)
        yield "xla_bf16_scores", grads(xla)
    yield "stream", grads(lambda q, k, v: fa._flash(
        q, k, v, lengths, causal, scale, min(512, T), min(1024, T), 0,
        False)[0])
    chosen = fa._short_heads(B * H, T, D, 2)
    for heads in (1, 2, 4, 8, 16):
        if (B * H) % heads == 0 and fa._short_vmem_bytes(
                heads, T, D, 2) <= fa.SHORT_VMEM_BUDGET:
            yield ("short_h%d%s" % (heads, "*" if heads == chosen else ""),
                   grads(lambda q, k, v, heads=heads: fa._flash(
                       q, k, v, lengths, causal, scale, 512, 1024, heads,
                       False)[0]))
    for n, blocks in enumerate(token_blocks(B, H, T, D)):
        yield ("tokens_r%dc%d%s" % (blocks + ("" if n else "*",)),
               grads(lambda q, k, v, blocks=blocks: fa._flash_tokens(
                   q, k, v, lengths, causal, scale, H, blocks, False)[0]))


def stream_variants(D):
    """(name, forward + backward) of the streaming kernels at a decoder cell's
    shape, causal; each takes (q, k, v, ct) and, after them, the [1, T, T] int8
    selection where the shape has one."""
    from unittest import mock

    scale = float(D) ** -0.5

    def attn(q, k, v, *select):
        if select:
            return fa._flash_selected(q, k, v, select[0], True, scale, 512,
                                      1024, False)[0]
        return fa._flash(q, k, v, None, True, scale, 512, 1024, 0, False)[0]

    def grads(patch):
        def both(q, k, v, ct, *select):
            with patch():   # the kernels are built while this traces
                out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, *select),
                                   q, k, v)
                return (out,) + vjp(ct.astype(out.dtype))

        def forward(q, k, v, ct, *select):
            with patch():
                return attn(q, k, v, *select)
        both.forward = forward
        return both

    for name, fused in (("stream_bwd_split", False), ("stream_bwd_fused", True)):
        yield name, grads(lambda fused=fused: mock.patch.object(
            fa, "_fused_bwd_fits", lambda *shapes: fused))


def dense_by_head(H, H_kv, D):
    """(out, dq, dk, dv) of the dense float32 math, a query head at a time."""
    scale, group = float(D) ** -0.5, H // H_kv

    def run(q, k, v, ct, *select):
        def one(h):
            take = lambda x, i: jax.lax.dynamic_slice_in_dim(x, i, 1, axis=1)
            out, vjp = jax.vjp(
                lambda q, k, v: fa._dense_attention(
                    q, k, v, True, scale, select=select[0] if select else None),
                take(q, h), take(k, h // group), take(v, h // group))
            return (out,) + vjp(take(ct, h))
        out, dq, dk, dv = (jnp.moveaxis(x[:, :, 0], 0, 1) for x in
                           jax.lax.map(one, jnp.arange(H)))
        shared = lambda x: x.reshape(x.shape[0], H_kv, group,
                                     *x.shape[2:]).sum(axis=2)
        return out, dq, shared(dk), shared(dv)
    return run


def random_selection(key, T, topk):
    """[1, T, T] int8: row r sees each of its r + 1 causal keys with
    probability topk / (r + 1) (all of them up to row topk), scattered as an
    untrained indexer's selection is."""
    rows = jnp.arange(T)[:, None]
    keep = jax.random.uniform(key, (T, T)) * (rows + 1) < topk
    return (keep & (jnp.arange(T)[None, :] <= rows)).astype(jnp.int8)[None]


def compile_report(name, vname, fn, specs):
    """Compile one variant for the described chip and print what it took."""
    t0 = time.perf_counter()
    try:
        c = jax.jit(fn).lower(*specs).compile()
        print("%s %s OK %.1f s mosaic=%d temp=%.1f MiB" % (
            name, vname, time.perf_counter() - t0,
            c.as_text().count("tpu_custom_call"),
            c.memory_analysis().temp_size_in_bytes / 2**20), flush=True)
    except Exception as e:  # noqa: BLE001 — reported per variant
        print("%s %s FAIL %s" % (name, vname,
                                 str(e)[:600].replace("\n", " | ")),
              flush=True)


def report(name, vname, fn, args_, reps, inner, ref, tokens_heads=0):
    """Run, time and print one variant of (q, k, v, ct, *rest) -> (out, dq,
    dk, dv); returns those, or None where it failed."""
    def forward(*a):   # q <- out (or, where out has a dim of its own, q + out)
        out = fn.forward(*a).astype(a[0].dtype)
        return (out if out.shape == a[0].shape else a[0] + out[..., :1],
                ) + a[1:]
    try:
        out = jax.block_until_ready(jax.jit(fn)(*args_))
        if tokens_heads:
            out = tuple(fa.split_heads(x, tokens_heads) for x in out)
        # (q, k, v, ct) <- (dq, dk, dv, out)
        ms = timed(lambda *a: (lambda r: r[1:] + r[:1])(fn(*a)) + a[4:],
                   args_, reps, inner)
        fwd_ms = timed(forward, args_, reps, inner)
    except Exception as e:  # noqa: BLE001 — reported per variant
        print("%s %s FAIL %s" % (name, vname,
                                 str(e)[:600].replace("\n", " | ")),
              flush=True)
        return None
    print("%s %s %.3f ms (forward alone %.3f)  rel err out/dq/dk/dv %s"
          % (name, vname, ms, fwd_ms,
             " ".join("%.4f" % rel(a, b) for a, b in zip(out, ref or out))),
          flush=True)
    return out


def run_stream(name, args, sharding):
    H, H_kv, T, D, Dv, topk = STREAM_SHAPES[name]
    shapes = [(1, H, T, D), (1, H_kv, T, D), (1, H_kv, T, Dv), (1, H, T, Dv)]
    if args.compile_only:
        specs = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
                 for s in shapes]
        if topk:
            specs.append(jax.ShapeDtypeStruct((1, T, T), jnp.int8,
                                              sharding=sharding))
        for vname, fn in stream_variants(D):
            if args.only in vname:
                compile_report(name, vname, fn, specs)
        return
    keys = jax.random.split(jax.random.key(T), 5)
    q, k, v, ct = (jax.random.normal(kk, s, f32).astype(jnp.bfloat16)
                   for kk, s in zip(keys, shapes))
    select = (random_selection(keys[4], T, topk),) if topk else ()
    ref = jax.block_until_ready(jax.jit(dense_by_head(H, H_kv, D))(
        *(x.astype(f32) for x in (q, k, v, ct)), *select))
    for vname, fn in stream_variants(D):
        if args.only in vname:
            report(name, vname, fn, (q, k, v, ct) + select, args.reps, 4, ref)


def timed(step, args, reps, inner=20):
    """Median milliseconds of one ``step``, from ``reps`` calls of a jitted
    loop that runs it ``inner`` times on the device, each iteration on the
    last one's results (``step`` maps its arguments to as many values of the
    same shapes), so that the host's part of a call is not in it: one launch
    costs this host 0.3-0.7 ms, as much as a kernel."""
    looped = jax.jit(lambda *a: jax.lax.fori_loop(
        0, inner, lambda _, c: tuple(step(*c)), tuple(a)))
    jax.block_until_ready(looped(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(looped(*args))
        times.append((time.perf_counter() - t0) / inner)
    return 1e3 * statistics.median(times)


def rel(a, b):
    a, b = a.astype(f32), b.astype(f32)
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--shapes", default=",".join(list(SHAPES)
                                                 + list(STREAM_SHAPES)))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", default="",
                    help="run the variants whose name contains this")
    args = ap.parse_args()

    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        print("# device %s" % jax.devices()[0].device_kind, flush=True)

    for name in args.shapes.split(","):
        if name in STREAM_SHAPES:
            run_stream(name, args, sharding)
            continue
        B, H, T, D, causal, with_len = SHAPES[name]
        shape = (B, H, T, D)
        if args.compile_only:
            lengths = (jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sharding)
                       if with_len else None)
            specs = {tokens: jax.ShapeDtypeStruct(
                (B, T, H * D) if tokens else shape, jnp.bfloat16,
                sharding=sharding) for tokens in (False, True)}
            for vname, fn in variants(B, H, T, D, causal, None):
                if with_len or args.only not in vname:
                    continue   # lengths is closed over: needs an array
                compile_report(name, vname, fn,
                               [specs[vname.startswith("tokens")]] * 4)
            continue
        keys = jax.random.split(jax.random.key(T), 5)
        q, k, v, ct = (jax.random.normal(kk, shape, f32).astype(jnp.bfloat16)
                       for kk in keys[:4])
        lengths = (jax.random.randint(keys[4], (B,), 1, T + 1)
                   if with_len else None)
        ref = None
        for vname, fn in variants(B, H, T, D, causal, lengths):
            if args.only not in vname and vname != "dense":
                continue   # dense is what the errors are taken against
            args_ = (q, k, v, ct)
            tokens = vname.startswith("tokens")
            if vname == "dense":
                args_ = tuple(x.astype(f32) for x in args_)
            elif tokens:   # the same numbers, as the projections leave them
                args_ = tuple(fa.merge_heads(x) for x in args_)
            out = report(name, vname, fn, args_, args.reps, 20, ref,
                         H if tokens else 0)
            if ref is None:
                ref = out


if __name__ == "__main__":
    main()
