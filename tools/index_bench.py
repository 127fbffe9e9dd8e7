"""One ``S`` layer's indexer alone, on the chip: the index scores as
``ops/sparse_attn_ops.py`` makes them (the six bfloat16 partial products of
a float32 product packed along the contraction; the VJP's transposed
products at ``Precision.HIGHEST``) against the same product at ``HIGHEST``
with its automatic VJP, at the sparse-attention cell's shape unless told
otherwise.

    chiprun --chips 1 -- python3 tools/index_bench.py [--out <file>]
    JAX_PLATFORMS=cpu python3 tools/index_bench.py --compile-only   # v5e compiler, no chip

One JSON line a case and form: ``block`` is one block of ``--rows`` query rows
over all the keys, forward alone (``scores``) and forward with its VJP
(``scores_vjp``: their difference is the two transposed products); then the
three loops of a layer as the ops run them, ``attn_index_select`` (scores and
threshold search), ``attn_index_loss`` and ``attn_index_loss_grad`` (scores,
attention's probabilities, and in the gradient the scores' VJP). Each with
milliseconds on the host's clock (median of ``--iters``) and the device's own
time a call with its five longest operations (a profiler trace of ``--iters``
calls; a loop's ``while`` and its body's operations are both events, so a
loop's device time counts twice: read the host's clock there). Then the
largest difference of the packed form's results from the ``HIGHEST`` form's,
relative to the largest entry.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops import sparse_attn_ops as sa
from ssm_bench import device_ms, ms_of, rel


def highest_scores(qi, ki, w, ks):
    """``packed_scores``' result from one float32 product at ``HIGHEST``
    (six half-filled passes at d = 64), its VJP the automatic one."""
    del ks
    s = jnp.einsum("rhd,sd->hrs", qi, ki, precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w.T[:, :, None], 0) + 0.0


FORMS = {"highest": highest_scores, "packed": sa.packed_scores}


def in_form(form, fn):
    """``fn`` jitted, traced with the ops' product in that form: a function
    of its own, since a trace is cached by the function traced."""
    def traced(*args):
        kept = sa.packed_scores
        sa.packed_scores = FORMS[form]
        try:
            return fn(*args)
        finally:
            sa.packed_scores = kept
    return jax.jit(traced)


def inputs(key, t, heads, d, att_heads, kv_heads, att_dim):
    """The ops' slots of one sequence as the model feeds them: the indexer's
    operands float32, attention's q and k bfloat16, the log-sum-exp of
    unit-variance scores over all the keys."""
    k = jax.random.split(key, 6)
    f32, bf16 = jnp.float32, jnp.bfloat16
    return {"QI": jax.random.normal(k[0], (1, t, heads, d), f32),
            "KI": jax.random.normal(k[1], (1, t, d), f32),
            "W": jax.random.normal(k[2], (1, t, heads), f32) / heads,
            "Q": jax.random.normal(k[3], (1, att_heads, t, att_dim),
                                   f32).astype(bf16),
            "K": jax.random.normal(k[4], (1, kv_heads, t, att_dim),
                                   f32).astype(bf16),
            "LSE": jnp.full((att_heads, t, 1), math.log(t) + 0.5, f32),
            "Loss@GRAD": jnp.ones((1,), f32)}


def cases(rows, topk, scale):
    """{name: fn(ins)} over the ops' slots (``Select`` among them)."""
    reg = OpInfoMap.instance()

    def block(ins):
        qi, ki, w = ins["QI"][0, -rows:], ins["KI"][0], ins["W"][0, -rows:]
        return qi, ki, w, sa.pack_keys(ki)

    def scores(ins):
        return sa.packed_scores(*block(ins))

    def scores_vjp(ins):
        out, vjp = jax.vjp(sa.packed_scores, *block(ins))
        return vjp(jnp.cos(out))[:3]

    def select(ins):
        return reg.get("attn_index_select").fn(ins, {"topk": topk})["Select"]

    def loss(ins):
        return reg.get("attn_index_loss").fn(ins, {"scale": scale})["Loss"]

    def loss_grad(ins):
        got = reg.get("attn_index_loss_grad").fn(ins, {"scale": scale})
        return tuple(got[n + "@GRAD"] for n in ("QI", "KI", "W"))

    return {"block/scores": scores, "block/scores_vjp": scores_vjp,
            "attn_index_select": select, "attn_index_loss": loss,
            "attn_index_loss_grad": loss_grad}


def compile_only(ins, fns, hlo_dir):
    """Every case in either form through the TPU's compiler for a described
    v5e: seconds, how many matrix products the optimized program holds and
    its temporaries; the optimized HLO under ``hlo_dir`` where given."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one), ins)
    for name, fn in fns.items():
        for form in FORMS:
            t0 = time.perf_counter()
            compiled = in_form(form, fn).lower(shapes).compile()
            hlo = compiled.as_text()
            mem = compiled.memory_analysis()
            print(json.dumps({
                "compiled": name, "form": form,
                "s": round(time.perf_counter() - t0, 2),
                "convolutions": hlo.count(" convolution("),
                "temporaries_mib": round(mem.temp_size_in_bytes / 2 ** 20),
            }), flush=True)
            if hlo_dir:
                os.makedirs(hlo_dir, exist_ok=True)
                with open(os.path.join(hlo_dir, "%s.%s.txt" % (
                        name.replace("/", "_"), form)), "w") as fh:
                    fh.write(hlo)
    return 0


def main(argv):
    p = argparse.ArgumentParser(prog="tools/index_bench.py")
    p.add_argument("--tokens", type=int, default=16384)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--topk", type=int, default=2048)
    p.add_argument("--rows", type=int, default=sa.SELECT_ROWS)
    p.add_argument("--attention-heads", type=int, default=32)
    p.add_argument("--kv-heads", type=int, default=4)
    p.add_argument("--attention-dim", type=int, default=128)
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--cases", nargs="+", help="some of the cases' names "
                   "(block/scores, attn_index_select, ...; default: all)")
    p.add_argument("--compile-only", action="store_true")
    p.add_argument("--hlo-dir")
    p.add_argument("--out")
    args = p.parse_args(argv)

    ins = inputs(jax.random.key(7), args.tokens, args.heads, args.head_dim,
                 args.attention_heads, args.kv_heads, args.attention_dim)
    every = cases(args.rows, args.topk, args.attention_dim ** -0.5)
    fns = {name: every[name] for name in args.cases or every}
    if args.compile_only:
        ins["Select"] = jnp.zeros((1, args.tokens, args.tokens), jnp.int8)
        return compile_only(ins, fns, args.hlo_dir)
    platform = jax.devices()[0].platform
    lines = [{"platform": platform, "shape": {
        k: getattr(args, k) for k in (
            "tokens", "heads", "head_dim", "topk", "rows", "attention_heads",
            "kv_heads", "attention_dim")}}]
    print(json.dumps(lines[0]), flush=True)
    # one selection for both forms' losses: the packed form's
    ins["Select"] = in_form("packed", every["attn_index_select"])(ins)
    results = {}
    for name, fn in fns.items():
        for form in FORMS:
            jitted = in_form(form, fn)
            results[name, form] = jitted(ins)
            line = {"case": name, "form": form,
                    "host_ms": ms_of(jitted, (ins,), args.iters)}
            if platform == "tpu":
                line["device"] = device_ms(jitted, (ins,), args.iters)
            lines.append(line)
            print(json.dumps(line), flush=True)
    diffs = {}
    for name in fns:
        got, want = results[name, "packed"], results[name, "highest"]
        if name == "attn_index_select":
            diffs[name] = {"pairs_that_differ": int(jnp.sum(got != want)),
                           "selected": int(jnp.sum(want != 0))}
        else:
            diffs[name] = [rel(a, b) for a, b in zip(
                jax.tree.leaves(got), jax.tree.leaves(want))]
    lines.append({"packed_against_highest": diffs})
    print(json.dumps(lines[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(lines, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
