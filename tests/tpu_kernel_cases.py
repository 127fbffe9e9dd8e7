"""Every Pallas kernel under ops/pallas/ at the shapes its callers use,
as (name, function, argument specs) — for checking WITHOUT a chip that
the TPU compiler still accepts them.

``tests/test_chip_smoke.py`` uses this two ways:

- in-process, cross-lowering each case with
  ``lowering_platforms=("tpu",)`` (catches a Mosaic-lowering break);
- as a script (``python tests/tpu_kernel_cases.py``), compiling each
  case for a compile-only v5e topology — libtpu's real Mosaic and XLA
  TPU back ends run on this CPU host, so a scoped-VMEM overflow or a
  layout Mosaic refuses shows up here too. Run in a process of its own:
  it loads libtpu.

Numerics are the chip's to check (chip_smoke.py's kernels phase).
"""
from __future__ import annotations

import importlib
import sys

import jax
import jax.numpy as jnp

bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


# the streaming gradient at the sparse-attention, latent-attention and hybrid
# cells' shapes: name -> (H, H_kv, T, D, Dv, with a selection)
STREAM_GRADS = {
    "flash_grad_gqa_selected_s16384_d128": (32, 4, 16384, 128, 128, True),
    "flash_grad_causal_s4096_d192_v128": (32, 32, 4096, 192, 128, False),
    "flash_grad_gqa_causal_s8192_d128": (32, 2, 8192, 128, 128, False),
    # the one kernel itself with a value dim of its own and lanes that pad
    # (192): shared K/V heads, so the rule picks it
    "flash_grad_gqa_causal_s4096_d192_v128": (32, 8, 4096, 192, 128, False),
}


def cases():
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    pa = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")

    def flash(causal, block_q, block_k):
        """Forward + backward of the kernels ``flash_attention`` picks
        for the shapes (``_plan``), as the grad op runs them."""
        def both(q, k, v, *lengths):
            heads, bq, bk = fa._plan(q, k, block_q, block_k, 0, v.shape[3])
            out, vjp = jax.vjp(
                lambda q, k, v: fa._flash(q, k, v, *(lengths or (None,)),
                                          causal, 0.125, bq, bk, heads,
                                          False)[0], q, k, v)
            return (out,) + vjp(out)
        return both

    # streaming fwd + dQ + dK/dV, causal, the gpt_long shape
    qkv = ((2, 16, 4096, 64), bf16)
    yield "flash_causal_s4096", flash(True, 512, 1024), [qkv, qkv, qkv]

    # masked streaming fwd+bwd at the transformer_wmt shape with blocks
    # smaller than the sequence: the whole [B*H, 1] lengths operand
    # rides in SMEM (512 rows here)
    qkv = ((64, 8, 256, 64), bf16)
    yield ("flash_masked_b64_s256", flash(False, 128, 128),
           [qkv, qkv, qkv, ((64,), i32)])

    # the short path (whole score tile in VMEM, one backward kernel):
    # BERT's cell, the dp4 shape, and transformer_wmt as the model
    # routes it now (causal + lengths, default blocks)
    qkv = ((32, 12, 512, 64), bf16)
    yield "flash_short_b32_s512", flash(False, 512, 1024), [qkv, qkv, qkv]
    qkv = ((128, 12, 128, 64), bf16)
    yield "flash_short_b128_s128", flash(False, 512, 1024), [qkv, qkv, qkv]
    qkv = ((64, 8, 256, 64), bf16)
    yield ("flash_short_masked_b64_s256", flash(True, 512, 1024),
           [qkv, qkv, qkv, ((64,), i32)])

    # the token-major short kernels ([B, T, H*hd] operands, a head's lanes
    # taken inside): BERT's cell, a replica of the dp4 cell, the longest the
    # short path takes, and the encoder-decoder's shape with both masks
    def tokens(causal, num_heads):
        def both(q, k, v, *lengths):
            blocks = fa._plan(q, k, 512, 1024, num_heads)[0]
            out, vjp = jax.vjp(
                lambda q, k, v: fa._flash_tokens(
                    q, k, v, *(lengths or (None,)), causal, 0.125,
                    num_heads, blocks, False)[0], q, k, v)
            return (out,) + vjp(out)
        return both

    for name, (b, t) in (("b32_s512", (32, 512)), ("b128_s128", (128, 128)),
                         ("b8_s1024", (8, 1024))):
        qkv = ((b, t, 768), bf16)
        yield "flash_tokens_" + name, tokens(False, 12), [qkv, qkv, qkv]
    qkv = ((64, 256, 512), bf16)
    yield ("flash_tokens_masked_b64_s256", tokens(True, 8),
           [qkv, qkv, qkv, ((64,), i32)])

    # shared K/V heads (32 over 2) at head dim 128, causal, T = 8192: the
    # streaming kernels read the shared head through their index maps and
    # the backward kernel sums over the group in its dK, dV accumulators
    q, kv = ((1, 32, 8192, 128), bf16), ((1, 2, 8192, 128), bf16)
    yield "flash_gqa_causal_s8192_d128", flash(True, 512, 1024), [q, kv, kv]

    # latent attention's core: 32 heads, q and k at 192 (128 + the rotary
    # 64), v and the context at 128, causal, T = 4096: the streaming
    # kernels with a value dim of its own, forward, dQ and dK/dV; and the
    # same call at equal dims
    qk, v = ((1, 32, 4096, 192), bf16), ((1, 32, 4096, 128), bf16)
    yield "flash_causal_s4096_d192_v128", flash(True, 512, 1024), [qk, qk, v]
    yield "flash_causal_s4096_d128_h32", flash(True, 512, 1024), [v, v, v]

    # the same kernels with a per-query key selection (32 over 4 heads, head
    # dim 128, T = 2048, and T = 16,384 as the sparse-attention cell runs
    # it: 16 MiB of float32 dK, dV a K/V head in VMEM): the [B, S, S] int8
    # operand's tiles ride beside the K blocks, forward and backward
    def selected(q, k, v, select):
        out, vjp = jax.vjp(
            lambda q, k, v: fa._flash_selected(q, k, v, select, True, 0.125,
                                               512, 1024, False)[0], q, k, v)
        return (out,) + vjp(out)

    for t in (2048, 16384):
        q, kv = ((1, 32, t, 128), bf16), ((1, 4, t, 128), bf16)
        yield ("flash_gqa_selected_s%d_d128" % t, selected,
               [q, kv, kv, ((1, t, t), jnp.int8)])

    # the gradient op's kernels alone, from the forward's Out and LSE, at the
    # three decoder cells' shapes: ONE Mosaic call with shared K/V heads, the
    # dQ and dK+dV pair at latent attention's one K/V head a query head
    def grad_alone(q, k, v, out, lse, g, *select):
        return fa._flash_backward(q, k, v, out, lse, g, True, 0.125, 512,
                                  1024, False, select=(select or (None,))[0])

    for name, (h, h_kv, t, d, dv, sel) in STREAM_GRADS.items():
        yield (name, grad_alone,
               [((1, h, t, d), bf16), ((1, h_kv, t, d), bf16),
                ((1, h_kv, t, dv), bf16), ((1, h, t, dv), bf16),
                ((h, t, 1), f32), ((1, h, t, dv), bf16)]
               + [((1, t, t), jnp.int8)] * sel)

    # the held experts' grouped products on the megablox kernels, forward
    # and both backward kernels, at the hybrid cell's shapes: a buffer of
    # 6,144 sorted slots, 8 experts of 2688 x 1856 and back
    mo = importlib.import_module("paddle_tpu.ops.moe_ops")

    def grouped(xs, w, sizes):
        out, vjp = jax.vjp(lambda xs, w: mo._megablox_dot(xs, w, sizes),
                           xs, w)
        return (out,) + vjp(out)

    for name, k, n in (("up", 2688, 1856), ("down", 1856, 2688)):
        yield ("moe_grouped_r6144_" + name, grouped,
               [((6144, k), bf16), ((8, k, n), bf16), ((8,), i32)])

    # the selective scan's kernels at the hybrid cell's shape (64 heads of
    # 64 in 8 groups, states of 128, chunks of 128, T = 8192), as its two
    # ops run them: the forward; the state pass and the backward kernel
    ss = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
    x, dt = ((1, 8192, 64, 64), bf16), ((1, 8192, 64), f32)
    bc, d = ((1, 8192, 8, 128), bf16), ((64,), f32)
    yield ("ssd_scan_fwd_s8192",
           lambda *a: ss.forward(*a, chunk=128), [x, dt, dt, bc, bc, d])
    yield ("ssd_scan_state_bwd_s8192",
           lambda *a: ss.backward(*a, chunk=128), [x, dt, dt, bc, bc, d, x])

    # the hyper-connections' passes at the latent-attention cell's shape
    # (four float32 streams of 4,096 x 3,584, 24 products a token), an entry
    # of ``ops/pallas/hyper_connection.py`` each
    hk = importlib.import_module("paddle_tpu.ops.pallas.hyper_connection")
    xs, phi = ((1, 4, 4096, 3584), f32), ((4, 24, 3584), f32)
    y, coef = ((1, 4096, 3584), f32), ((1, 4096, 20), f32)
    yield ("mhc_pre_fwd_s4096", lambda *a: hk.pre_forward(*a, eps=1e-6),
           [xs, phi, ((2, 24), f32)])
    yield ("mhc_pre_reads_s4096",
           lambda *a: hk.pre_grad_reads(*a, eps=1e-6), [xs, phi, y])
    yield ("mhc_pre_writes_s4096", hk.pre_grad_writes,
           [xs, phi, y, ((1, 4096, 24), f32), ((1, 4096, 5), f32)])
    yield "mhc_post_fwd_s4096", hk.post_forward, [xs, coef, y]
    yield "mhc_post_bwd_s4096", hk.post_backward, [xs, coef, y, xs]

    # the delta rule's kernels at the delta-rule cell's shape (32 heads of
    # 128, chunks of 64, T = 8192), as its two ops run them: the forward;
    # the state pass with the tiles it keeps and the backward kernel that
    # reads them
    kd = importlib.import_module("paddle_tpu.ops.pallas.kda")
    qk, gk = ((1, 8192, 32, 128), bf16), ((1, 8192, 32, 128), f32)
    yield ("kda_fwd_s8192", lambda *a: kd.forward(*a, chunk=64),
           [qk, qk, qk, gk, ((1, 8192, 32), f32)])
    yield ("kda_state_bwd_s8192", lambda *a: kd.backward(*a, chunk=64),
           [qk, qk, qk, gk, ((1, 8192, 32), f32), qk])

    # paged attention at the decode engine's geometry
    def paged(q, k_arena, v_arena, tables, lens):
        return pa._paged_pallas(q, k_arena, v_arena, tables, lens,
                                block_tokens=16, scale=8.0 ** -0.5,
                                interpret=False)

    arena = ((128, 16, 2, 8), f32)
    yield "paged_b8_h2_d8", paged, [((8, 2, 8), f32), arena, arena,
                                    ((8, 5), i32), ((8,), i32)]


def compile_step(main, startup, loss, feeds, topo_sharding):
    """The compiled training step of ``main`` for the described chip, fed
    zeros of ``feeds`` {name: (shape, dtype)}. The program asks
    ``compute_platform()`` where it runs and would take the dense path on
    this CPU host: that one question is answered as the chip would."""
    from unittest import mock

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.compiler_engine import _stage_compiled_call
    from paddle_tpu.core.tensor import LoDTensor

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {name: LoDTensor(jnp.asarray(np.zeros(shape, dtype)))
                for name, (shape, dtype) in feeds.items()}
        fn, args, _ = _stage_compiled_call(
            exe._core, jax.devices()[0], main, scope, feed, [loss])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=topo_sharding), args)
    with mock.patch.object(fa, "compute_platform", lambda: "tpu"):
        return fn.lower(*shapes).compile()


def bert_step(topo_sharding, layers=2, batch=4, seq=512):
    """A BERT-base-wide training step of ``layers`` layers (bf16 AMP,
    Adam) at T = ``seq``, compiled for the described chip. Returns the
    optimized HLO."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    b, t, m, v = batch, seq, 8, 512
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, t], dtype="int64")
        pos = fluid.data(name="pos", shape=[b, t], dtype="int64")
        mpos = fluid.data(name="mpos", shape=[b, m], dtype="int64")
        labels = fluid.data(name="labels", shape=[b, m, 1], dtype="int64")
        logits = models.bert_base_pretrain(
            src, pos, mpos, vocab_size=v, max_len=t, num_layers=layers,
            num_heads=12, d_model=768, d_ff=3072)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * m, v]),
            fluid.layers.reshape(labels, [b * m, 1])))
        mp.decorate(fluid.optimizer.AdamOptimizer(1e-4)).minimize(loss)
    feeds = {"src": ((b, t), "int64"), "pos": ((b, t), "int64"),
             "mpos": ((b, m), "int64"), "labels": ((b, m, 1), "int64")}
    return compile_step(main, startup, loss, feeds, topo_sharding).as_text()


def hybrid_step_temporaries(topo_sharding, recompute, state_size, seq=2048):
    """``temp_size_in_bytes`` of a six-layer hybrid state-space / MoE
    training step (bf16 AMP, Adam) compiled for the described chip, with
    or without ``RecomputeOptimizer`` over the layers' inputs; its Mosaic
    calls, ``ragged-dot`` instructions, the selective scan's kernels by
    name and the streaming attention's backward kernels (``flash_stream_bwd``:
    one a ``*`` layer). States of 128 fill the scan kernels' blocks; states
    of 64 do not, and the scan is then the XLA form on the TPU too."""
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.contrib import mixed_precision as mp

    t, v = seq, 1024
    main, startup = fluid.Program(), fluid.Program()
    checkpoints = []
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[1, t], dtype="int64")
        labels = fluid.data(name="labels", shape=[t, 1], dtype="int64")
        logits = models.hybrid_ssm_moe(
            src, "MEM*EM", v, 512, mamba_heads=16, mamba_head_dim=64,
            n_groups=2, state_size=state_size, num_experts=16, top_k=2,
            expert_dim=256, shared_dim=512, held=[0, 4], num_heads=8,
            num_kv_heads=2, head_dim=128, checkpoints=checkpoints)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [t, v]), labels))
        optimizer = fluid.optimizer.AdamOptimizer(1e-4)
        if recompute:
            optimizer = fluid.optimizer.RecomputeOptimizer(optimizer)
            optimizer._set_checkpoints(checkpoints)
        mp.decorate(optimizer).minimize(loss)
    compiled = compile_step(
        main, startup, loss,
        {"src": ((1, t), "int64"), "labels": ((t, 1), "int64")},
        topo_sharding)
    hlo = compiled.as_text()
    calls = [x.split("=")[0] for x in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in x]
    scans = [sum(name in x for x in calls)
             for name in ("ssd_scan_fwd", "ssd_scan_state", "ssd_scan_bwd")]
    return (compiled.memory_analysis().temp_size_in_bytes,
            hlo.count("tpu_custom_call"), hlo.count("ragged-dot"), scans,
            sum("flash_stream_bwd" in x for x in calls))


def bert_step_report(hlo, seq=512) -> str:
    """``BERT_STEP fwd=<n> bwd=<n> other_mosaic=<n> tt_buffers=<n>
    head_major=<n>``: the program's own forward and backward kernels by
    their names, the Mosaic calls that are neither (XLA's own attention
    rewrite made such), the distinct [*, *, T, T] buffers of any dtype,
    and the distinct [*, heads, T, 64] buffers: what a head split or
    merge, or a layout copy around head-major kernels, would leave."""
    import re

    calls = [x for x in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in x]
    fwd = sum("flash_short_fwd" in x.split("=")[0] for x in calls)
    bwd = sum("flash_short_bwd" in x.split("=")[0] for x in calls)
    tt = set(re.findall(r"\w+\[\d+,\d+,%d,%d\]" % (seq, seq), hlo))
    split = set(re.findall(r"\w+\[\d+,(?:\d+,)?%d,64\]" % seq, hlo))
    return ("BERT_STEP fwd=%d bwd=%d other_mosaic=%d tt_buffers=%d "
            "head_major=%d" % (fwd, bwd, len(calls) - fwd - bwd, len(tt),
                               len(split)))


def index_scores_report(sharding, rows=512, keys=16384, heads=16, d=64) -> str:
    """``INDEX_SCORES products=<n> highest=<n> packed_keys=<n>
    temporaries_mib=<n>`` of the indexer's scores for one block of query
    rows at the sparse-attention cell's shape, compiled for the chip: the
    matrix products the program holds, how many of them run at ``HIGHEST``,
    whether the keys' bfloat16 pieces side by side ([keys, 6 d]) reach the
    product, and the program's temporaries (the epilogue fused into the
    product's output leaves no [heads, rows, keys] array: 512 MiB)."""
    from paddle_tpu.ops import sparse_attn_ops as sa

    args = [jax.ShapeDtypeStruct(shape, f32, sharding=sharding)
            for shape in ((rows, heads, d), (keys, d), (rows, heads))]
    compiled = jax.jit(sa.index_scores).lower(*args).compile()
    hlo = compiled.as_text()
    products = [x for x in hlo.splitlines() if " convolution(" in x]
    return ("INDEX_SCORES products=%d highest=%d packed_keys=%d "
            "temporaries_mib=%d" % (
                len(products), sum("highest" in x for x in products),
                "bf16[%d,%d" % (keys, 6 * d) in hlo,
                compiled.memory_analysis().temp_size_in_bytes >> 20))


def compile_all_for_v5e() -> int:
    """Compile every case and the BERT step for a compile-only v5e
    topology; print one OK/FAIL line each and the BERT_STEP, INDEX_SCORES
    and HYBRID_STEP lines. Exit
    codes: 0 all compiled, 1 something was refused, 3 no TPU compiler
    could be set up on this host."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:  # noqa: BLE001 — reported, exit code 3
        print("NO_TPU_COMPILER %r" % (e,))
        return 3
    sharding = SingleDeviceSharding(topo.devices[0])
    failed = 0
    for name, fn, specs in cases():
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for shape, dtype in specs]
        try:
            compiled = jax.jit(fn).lower(*args).compile()
        except Exception as e:  # noqa: BLE001 — each case is reported
            failed += 1
            print("FAIL %s %s: %s" % (name, type(e).__name__,
                                      str(e)[:800].replace("\n", " | ")))
            continue
        print("OK %s mosaic_calls=%d"
              % (name, compiled.as_text().count("tpu_custom_call")))
    # both BERT cells' lengths (a replica of the dp4 cell runs T = 128)
    for layers, batch, seq in ((2, 4, 512), (1, 8, 128)):
        try:
            print(bert_step_report(bert_step(sharding, layers, batch, seq),
                                   seq))
        except Exception as e:  # noqa: BLE001 — reported like a case
            failed += 1
            print("FAIL bert_step T=%d %s: %s" % (
                seq, type(e).__name__, str(e)[:800].replace("\n", " | ")))
    try:
        print(index_scores_report(sharding))
    except Exception as e:  # noqa: BLE001 — reported like a case
        failed += 1
        print("FAIL index_scores %s: %s" % (
            type(e).__name__, str(e)[:800].replace("\n", " | ")))
    # both sides of ``ssm_ops.scan_path`` on the TPU: the kernels, and the
    # XLA form with its ``jax.vjp`` behind a barrier in the gradient op
    for state_size in (128, 64):
        try:
            plain = hybrid_step_temporaries(sharding, False, state_size)
            saved = hybrid_step_temporaries(sharding, True, state_size)
            print("HYBRID_STEP state=%d plain=%d checkpoints=%d "
                  "mosaic_calls=%d ragged_dots=%d scans=%s "
                  "scans_recomputing=%s flash_bwd=%d"
                  % (state_size, plain[0], saved[0], plain[1], plain[2],
                     "/".join(map(str, plain[3])),
                     "/".join(map(str, saved[3])), saved[4]))
        except Exception as e:  # noqa: BLE001 — reported like a case
            failed += 1
            print("FAIL hybrid_step state=%d %s: %s" % (
                state_size, type(e).__name__,
                str(e)[:800].replace("\n", " | ")))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(compile_all_for_v5e())
