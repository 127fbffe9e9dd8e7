"""The token-major form of flash_attention's short path: q, k, v and the
context as the projections leave them, [B, T, H*hd], a head's lanes taken
inside the kernels, no head split or merge in the program.

The kernels run in interpret mode on the CPU (``force_pallas``) against the
dense float32 math and against the head-major kernels on the same data; the
op, its grad op, the fallback for shapes the token-major kernels do not take,
the layout counter and the BERT program are driven as the executor drives
them. What the real Mosaic compiler makes of the kernels is in
``test_chip_smoke.py`` (``tpu_kernel_cases.py``).

The file's name sorts it last on purpose: kernels on the interpreter keep
every core busy for a minute and a half, and beside ``test_fault_tolerance``
and ``test_fluid_modules`` (where ``test_flash_*`` is collected) their barrier
and timing tests ran out of time in four whole runs of five.
"""
import collections
import importlib
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (
    SHORT_VMEM_BUDGET, _dense_attention, _plan, _tokens_blocks,
    _tokens_vmem_bytes, attention_path, flash_attention,
    flash_attention_with_lse, merge_heads, split_heads)

fa_mod = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

f32 = jnp.float32
# absolute = relative tolerance of (out, the gradients) against the dense
# math in float32 on the same (rounded) inputs, and of every result against
# the head-major kernels' on the same data (the same arithmetic; delta =
# rowsum(dO * O) is summed in another order, and a bf16 result may round
# the other way)
TOL = {"float32": (2e-5, 5e-5, 2e-6), "bfloat16": (2e-2, 6e-2, 2e-2)}
MASKS = {"none": (False, False), "causal": (True, False),
         "lengths": (False, True), "causal_lengths": (True, True)}
# (B, T, H, hd): the dp4 cell's rows, the encoder-decoder's, BERT's cell's,
# and the longest the short path takes
SHAPES = [(2, 128, 12, 64), (2, 256, 8, 64), (1, 512, 12, 64),
          (1, 1024, 4, 64)]
# interpret mode is slow, so the masks are spread over the shapes: the dp4
# cell's shape takes every mask in float32, each other shape both masks
# together; bf16 bare at every shape and with both masks at the short ones
CASES = [(SHAPES[0], "float32", mask) for mask in sorted(MASKS)]
CASES += [(shape, "float32", "causal_lengths") for shape in SHAPES[1:]]
CASES += [(shape, "bfloat16", "none") for shape in SHAPES]
CASES += [(shape, "bfloat16", "causal_lengths") for shape in SHAPES[:2]]


def _inputs(B, T, H, hd, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, T, H * hd).astype("float32"))
                 .astype(dtype) for _ in range(4))


def _lengths(B, T, with_lengths):
    # a ragged row and, where the batch has a second row, one with no
    # visible key
    return (jnp.asarray([T // 2 + 3, 0][:B], jnp.int32) if with_lengths
            else None)


def _dense_tokens(q, k, v, H, causal, scale, lengths):
    return merge_heads(_dense_attention(
        *(split_heads(x, H) for x in (q, k, v)), causal, scale, lengths))


@pytest.mark.parametrize("shape,dtype,mask", CASES,
                         ids=lambda x: "x".join(map(str, x))
                         if isinstance(x, tuple) else x)
def test_tokens_forward_and_grads_match_dense_and_head_major(shape, dtype,
                                                             mask):
    B, T, H, hd = shape
    causal, with_lengths = MASKS[mask]
    q, k, v, ct = _inputs(*shape, dtype)
    lengths = _lengths(B, T, with_lengths)
    scale = float(hd) ** -0.5
    assert isinstance(_plan(q, k, 512, 1024, H)[0], tuple)
    assert attention_path(q, k, force_pallas=True, num_heads=H) == "short"

    def tokens(q, k, v):
        return flash_attention(q, k, v, causal=causal, lengths=lengths,
                               force_pallas=True, num_heads=H)

    def heads(q, k, v):   # the head-major kernels around a split and merge
        return merge_heads(flash_attention(
            *(split_heads(x, H) for x in (q, k, v)), causal=causal,
            lengths=lengths, force_pallas=True))

    out, vjp = jax.vjp(tokens, q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: _dense_tokens(q, k, v, H, causal, scale, lengths),
        *(x.astype(f32) for x in (q, k, v)))
    tol_out, tol_grad, tol_same = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out.astype(f32)), np.asarray(ref),
                               atol=tol_out, rtol=tol_out)
    grads = vjp(ct)
    for got, want, name in zip(grads, vjp_ref(ct.astype(f32)), "qkv"):
        assert got.shape == q.shape and got.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(got.astype(f32)), np.asarray(want), atol=tol_grad,
            rtol=tol_grad, err_msg="d%s against dense" % name)
    if dtype == "bfloat16" and T > 128:
        return   # the float32 cases hold the two kernels equal far tighter
    same, vjp_same = jax.vjp(heads, q, k, v)
    for got, other, name in zip((out,) + grads, (same,) + vjp_same(ct),
                                ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(got.astype(f32)), np.asarray(other.astype(f32)),
            atol=tol_same, rtol=tol_same,
            err_msg="%s against the head-major kernels" % name)


@pytest.mark.parametrize("hd,rows,lanes", [
    (64, 1, 128), (64, 2, 128), (64, 1, 256), (32, 1, 128), (32, 2, 256),
    (128, 1, 256)])
def test_any_block_of_whole_heads_agrees_with_dense(hd, rows, lanes):
    """Heads narrower than a lane tile are sliced off the row's loaded value
    and stored together, whole tiles off the references one by one; one batch
    row a step or two: the results are the dense math's."""
    B, T, H = 2, 128, 256 // hd
    q, k, v, ct = _inputs(B, T, H, hd, "float32", seed=3)
    lengths = _lengths(B, T, True)
    scale = float(hd) ** -0.5

    def attn(q, k, v):
        return fa_mod._flash_tokens(q, k, v, lengths, True, scale, H,
                                    (rows, lanes), True)[0]

    out, vjp = jax.vjp(attn, q, k, v)
    ref, vjp_ref = jax.vjp(
        lambda q, k, v: _dense_tokens(q, k, v, H, True, scale, lengths),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for got, want in zip(vjp(ct), vjp_ref(ct)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)


PLANNED = {   # (B, T, H, hd): what _plan answers for token-major operands
    "bert_s512": ((32, 512, 12, 64), (1, 768)),
    "dp4_s128": ((128, 128, 12, 64), (1, 768)),
    "wmt_s256": ((64, 256, 8, 64), (1, 512)),
    "s1024": ((8, 1024, 12, 64), (1, 384)),
    "s1024_h4": ((1, 1024, 4, 64), (1, 256)),
    "odd_batch_s128": ((3, 128, 12, 64), (1, 768)),
    "few_heads_s128": ((16, 128, 2, 64), (4, 128)),
    "head_dim_128": ((4, 256, 16, 128), (1, 1024)),
    "head_dim_32": ((4, 128, 8, 32), (1, 256)),
    # no block of whole heads is a multiple of 128 lanes, or a head dim that
    # neither divides 128 nor is a multiple of it: the head-major short
    # kernels, around a split and merge inside the op
    "three_heads_of_32": ((4, 128, 3, 32), 12),
    "head_dim_96": ((4, 128, 4, 96), 16),
    # past the short path: the streaming kernels
    "s2048": ((2, 2048, 12, 64), 0),
    "unaligned_s100": ((2, 100, 12, 64), 0),
}


@pytest.mark.parametrize("case", sorted(PLANNED))
def test_blocks_are_whole_heads_in_multiples_of_128_lanes(case):
    (B, T, H, hd), want = PLANNED[case]
    q = jax.ShapeDtypeStruct((B, T, H * hd), jnp.bfloat16)
    short = _plan(q, q, 512, 1024, H)[0]
    assert short == want
    assert attention_path(q, q, force_pallas=True, num_heads=H) == (
        "short" if want else "stream")
    if isinstance(short, tuple):
        rows, lanes = short
        assert short == _tokens_blocks(B, T, H, hd, 2)
        assert lanes % 128 == 0 and lanes % hd == 0
        assert (H * hd) % lanes == 0 and B % rows == 0
        assert _tokens_vmem_bytes(rows, T, lanes, 2) <= SHORT_VMEM_BUDGET
    # head-major operands of the same sizes keep the plan they had
    q4 = jax.ShapeDtypeStruct((B, H, T, hd), jnp.bfloat16)
    assert isinstance(_plan(q4, q4, 512, 1024)[0], int)


def test_token_major_operands_name_their_heads():
    q = jnp.zeros((2, 128, 768), f32)
    with pytest.raises(ValueError, match="num_heads"):
        flash_attention(q, q, q)
    with pytest.raises(ValueError, match="num_heads"):
        flash_attention(q, q, q, num_heads=7)
    q4 = jnp.zeros((2, 12, 128, 64), f32)
    with pytest.raises(ValueError, match="num_heads"):
        flash_attention(q4, q4, q4, num_heads=4)


def _op(name):
    from paddle_tpu.core.registry import OpInfoMap

    return OpInfoMap.instance().get(name).fn


def _on_the_interpreter(platform="tpu"):
    """The op as it runs where the computation is placed on ``platform``,
    the kernels on the interpreter."""
    real, real_bwd, real_tokens, real_tokens_bwd = (
        fa_mod._flash, fa_mod._flash_bwd, fa_mod._flash_tokens,
        fa_mod._flash_tokens_bwd)
    return mock.patch.multiple(
        fa_mod,
        compute_platform=lambda: platform,
        _flash=lambda *a: real(*a[:-1], True),
        _flash_bwd=lambda *a: real_bwd(*a[:5], True, *a[6:]),
        _flash_tokens=lambda *a: real_tokens(*a[:-1], True),
        _flash_tokens_bwd=lambda *a: real_tokens_bwd(*a[:4], True, *a[5:]))


def _counters_grown(run):
    from paddle_tpu import observability as obs

    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.dump()["counters"])
        result = run()
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    return result, {name: after[name] - before.get(name, 0) for name in after
                    if name.startswith("kernels.flash_attention")
                    and after[name] != before.get(name, 0)}


@pytest.mark.parametrize("T,path", [(1536, "stream"), (100, "stream"),
                                    (128, "short"), (128, "dense")])
def test_token_major_op_falls_back_inside_the_op(T, path):
    """A token-major op whose shapes the token-major kernels do not take
    (T > 1024, T no multiple of 128, off the TPU) splits and merges heads
    inside the op and matches the dense math; it counts its path and its
    layout."""
    B, H, hd = 1, 2, 64
    q, k, v, _ = _inputs(B, T, H, hd, "float32", seed=T)
    attrs = {"causal": True, "scale": 0.0, "num_heads": H}
    with _on_the_interpreter("cpu" if path == "dense" else "tpu"):
        outs, grown = _counters_grown(lambda: _op("flash_attention")(
            {"Q": q, "K": k, "V": v, "Lengths": None}, attrs))
    assert grown == {"kernels.flash_attention{path=%s}" % path: 1,
                     "kernels.flash_attention_layout{layout=tokens}": 1,
                     "kernels.flash_attention_select{form=none}": 1}
    assert outs["Out"].shape == q.shape
    assert (outs["LSE"] is None) == (path == "dense")
    ref = _dense_tokens(q, k, v, H, True, float(hd) ** -0.5, None)
    np.testing.assert_allclose(np.asarray(outs["Out"]), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("T", [128, 100], ids=["kernels", "fallback"])
@pytest.mark.parametrize("with_lse", [True, False], ids=["lse", "no_lse"])
def test_grad_op_follows_the_forwards_layout(T, with_lse):
    """flash_attention_grad on token-major operands: from the forward op's
    own Out and LSE the backward kernels alone, without LSE a forward under
    jax.vjp; both at a length the token-major kernels take and at one they
    do not."""
    B, H, hd = 2, 2, 64
    q, k, v, ct = _inputs(B, T, H, hd, "float32", seed=5)
    lengths = jnp.asarray([T - 7, T // 2], jnp.int32)
    attrs = {"causal": False, "scale": 0.0, "num_heads": H}
    ins = {"Q": q, "K": k, "V": v, "Lengths": lengths}
    with _on_the_interpreter():
        fwd = _op("flash_attention")(ins, attrs)
        again = mock.patch.object(
            fa_mod, "_tokens_fwd_kernel",
            side_effect=AssertionError("second forward"))
        grad_ins = dict(ins, **{"Out@GRAD": ct})
        if with_lse:
            grad_ins.update(Out=fwd["Out"], LSE=fwd["LSE"])
            with again:
                grads = _op("flash_attention_grad")(grad_ins, attrs)
        else:
            grads = _op("flash_attention_grad")(grad_ins, attrs)
    _, vjp = jax.vjp(lambda q, k, v: _dense_tokens(
        q, k, v, H, False, float(hd) ** -0.5, lengths), q, k, v)
    for name, want in zip(("Q@GRAD", "K@GRAD", "V@GRAD"), vjp(ct)):
        assert grads[name].shape == q.shape
        np.testing.assert_allclose(np.asarray(grads[name]),
                                   np.asarray(want), atol=5e-5,
                                   err_msg=name)


def test_lse_is_a_lane_dense_row_per_head():
    q, k, v, _ = _inputs(2, 128, 4, 64, "float32", seed=1)
    out, lse = flash_attention_with_lse(q, k, v, force_pallas=True,
                                        num_heads=4)
    assert lse.shape == (2, 4, 1, 128) and lse.dtype == f32
    _, lse4 = flash_attention_with_lse(
        *(split_heads(x, 4) for x in (q, k, v)), force_pallas=True)
    np.testing.assert_allclose(np.asarray(lse).reshape(8, 1, 128),
                               np.asarray(lse4), atol=1e-6)


# -- the program ---------------------------------------------------------------


def _bert(T=128, heads=2, d_model=32, layers_=2, head_major=False):
    """The tiny BERT of ``models.bert_base_pretrain`` with a masked-LM
    loss; ``head_major`` builds it as before this layout existed: heads
    split and merged by reshape2 / transpose2 around a rank-4 op."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, models

    b, m, v = 2, 4, 50
    token_major = layers.flash_attention

    def split_op_merge(q, k, v, causal=False, scale=0.0, lengths=None,
                       num_heads=0):
        B, t, E = q.shape

        def split(x):
            return layers.transpose(layers.reshape(
                x, [B, t, num_heads, E // num_heads]), [0, 2, 1, 3])

        ctx = token_major(split(q), split(k), split(v), causal=causal,
                          scale=scale, lengths=lengths)
        return layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                              [B, t, E])

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup), \
            mock.patch.object(layers, "flash_attention",
                              split_op_merge if head_major
                              else token_major):
        src = fluid.data(name="src", shape=[b, T], dtype="int64")
        pos = fluid.data(name="pos", shape=[b, T], dtype="int64")
        mpos = fluid.data(name="mpos", shape=[b, m], dtype="int64")
        labels = fluid.data(name="labels", shape=[b * m, 1], dtype="int64")
        logits = models.bert_base_pretrain(
            src, pos, mpos, vocab_size=v, max_len=T, num_layers=layers_,
            num_heads=heads, d_model=d_model, d_ff=2 * d_model)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * m, v]), labels))
    rng = np.random.RandomState(0)
    feed = {"src": rng.randint(0, v, (b, T)).astype("int64"),
            "pos": np.tile(np.arange(T), (b, 1)).astype("int64"),
            "mpos": rng.randint(0, T, (b, m)).astype("int64"),
            "labels": rng.randint(0, v, (b * m, 1)).astype("int64")}
    return main, startup, loss, feed


def test_bert_holds_no_head_split_or_merge_on_the_flash_branch():
    """Between the q, k, v projections and the output projection stands the
    flash_attention op alone: its operands come from the projections' bias
    adds, its context goes into the output projection's matmul, and the
    program has no transpose2 at all."""
    main, _, _, _ = _bert(layers_=3)
    block = main.global_block()
    producers = {n: op for op in block.ops for n in op.output_arg_names}
    consumers = collections.defaultdict(list)
    for op in block.ops:
        for n in op.input_arg_names:
            consumers[n].append(op.type)
    types = [op.type for op in block.ops]
    assert types.count("flash_attention") == 3
    assert types.count("transpose2") == 0
    for op in block.ops:
        if op.type != "flash_attention":
            continue
        assert op.attrs["num_heads"] == 2
        for slot in "QKV":
            (name,) = op.input(slot)
            assert len(block.var(name).shape) == 3
            assert producers[name].type == "elementwise_add"   # fc's bias
            (x,) = producers[name].input("X")
            assert producers[x].type == "mul"
        assert consumers[op.output("Out")[0]] == ["mul"]
    head_major = [op.type for op in _bert(
        layers_=3, head_major=True)[0].global_block().ops]
    assert head_major.count("transpose2") == 3 * 4
    assert len(head_major) == len(types) + 3 * 8


def test_bert_trains_to_the_head_major_programs_losses():
    """Three steps under bf16 AMP + Adam: the token-major program and one
    built with the head-major op between a split and a merge reach the same
    losses within 1e-3 relative (the same math on the same bf16 values; what
    differs is the order of a few float32 sums)."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    losses, weights = {}, {}
    for head_major in (False, True):
        main, startup, loss, feed = _bert(head_major=head_major)
        with fluid.program_guard(main, startup):
            mp.decorate(fluid.optimizer.AdamOptimizer(1e-2)).minimize(loss)
        types = [op.type for op in main.global_block().ops]
        assert types.count("flash_attention_grad") == 2
        assert types.count("transpose2_grad") == (8 if head_major else 0)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            # both from the first program's weights (an initializer's
            # seed follows its op's place in the program)
            for param in main.all_parameters():
                tensor = scope.var(param.name).get_tensor()
                tensor._array = jnp.asarray(weights.setdefault(
                    param.name, np.asarray(tensor._array)))
            losses[head_major] = [
                float(exe.run(main, feed=feed, fetch_list=[loss])[0])
                for _ in range(3)]
    assert losses[False][2] < losses[False][0]
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-3)


@pytest.mark.parametrize("layout", ["tokens", "heads"])
def test_counter_names_the_layout_a_trace_was_given(layout):
    """kernels.flash_attention_layout{layout=...}: one count per traced op,
    beside the count of the path it took."""
    H, hd = 2, 64
    q, k, v, _ = _inputs(1, 128, H, hd, "float32")
    attrs = {"causal": False, "scale": 0.0, "num_heads": H}
    if layout == "heads":
        q, k, v = (split_heads(x, H) for x in (q, k, v))
        attrs["num_heads"] = 0
    with _on_the_interpreter():
        _, grown = _counters_grown(lambda: _op("flash_attention")(
            {"Q": q, "K": k, "V": v, "Lengths": None}, attrs))
    assert grown == {"kernels.flash_attention{path=short}": 1,
                     "kernels.flash_attention_layout{layout=%s}" % layout: 1,
                     "kernels.flash_attention_select{form=none}": 1}


@pytest.mark.parametrize("layout", ["tokens", "heads"])
def test_sequence_parallel_reads_the_sequence_axis_of_either_layout(layout):
    """apply_sequence_parallel checks the ring's degree against the axis the
    layout keeps the sequence on, and hands the ring op the head count."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel.transpiler import apply_sequence_parallel

    B, H, T, hd = 2, 3, 16, 8
    tokens = layout == "tokens"
    shape = [B, T, H * hd] if tokens else [B, H, T, hd]

    def program():
        main = fluid.Program()
        with fluid.program_guard(main, fluid.Program()):
            x = fluid.data(name="x", shape=shape, dtype="float32")
            fluid.layers.flash_attention(x, x, x,
                                         num_heads=H if tokens else 0)
        return main

    # 3 divides neither layout's T = 16, though it divides H (heads) and
    # H * hd (tokens): the check reads the sequence axis
    with pytest.raises(ValueError, match="seq len 16"):
        apply_sequence_parallel(program(), degree=3)
    main = program()
    assert apply_sequence_parallel(main, degree=4) == 1
    (ring,) = [op for op in main.global_block().ops
               if op.type == "c_ring_attention"]
    assert ring.attrs["num_heads"] == (H if tokens else 0)
    assert "LSE" not in ring.outputs
