"""The short-sequence path of flash_attention (a head's whole score tile
in VMEM: plain softmax, several heads a grid step, one backward kernel),
its routing from the transformer model, its place in the AMP lists and
the counter that says which path a trace took.

The kernels run in interpret mode on the CPU (``force_pallas``); what
the real Mosaic compiler makes of them is in ``test_chip_smoke.py``, and
their numbers on the chip in ``chip_smoke.py``'s kernels phase.
"""
import importlib
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (
    SHORT_VMEM_BUDGET, _dense_attention, _plan, _short_vmem_bytes,
    attention_path, flash_attention, flash_attention_bwd,
    flash_attention_with_lse)

# the package exports the function under the module's name
fa_mod = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

f32 = jnp.float32
D = 64
# (absolute tolerance of out, of the gradients) against the dense math in
# float32 on the same (rounded) inputs
TOL = {"float32": (2e-5, 5e-5), "bfloat16": (2e-2, 6e-2)}
MASKS = {"none": (False, False), "causal": (True, False),
         "lengths": (False, True), "causal_lengths": (True, True)}


def _inputs(T, dtype, B=2, H=2, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, H, T, D).astype("float32"))
                 .astype(dtype) for _ in range(4))


def _lengths(T, with_lengths):
    # one ragged row, one row with no visible key
    return jnp.asarray([T // 2 + 3, 0], jnp.int32) if with_lengths else None


# every mask at the two cheap lengths; T = 512 (slow in interpret mode) as
# BERT runs it
CASES = [(T, dtype, mask) for T in (128, 256)
         for dtype in ("float32", "bfloat16") for mask in sorted(MASKS)]
CASES += [(512, "bfloat16", "none"), (512, "float32", "none")]


@pytest.mark.parametrize("T,dtype,mask", CASES)
def test_short_forward_and_grads_match_dense(T, dtype, mask):
    causal, with_lengths = MASKS[mask]
    q, k, v, ct = _inputs(T, dtype)
    lengths = _lengths(T, with_lengths)
    scale = float(D) ** -0.5
    assert attention_path(q, k, force_pallas=True) == "short"

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, lengths=lengths,
                                  force_pallas=True)

    def dense(q, k, v):
        return _dense_attention(q, k, v, causal, scale, lengths)

    out, vjp = jax.vjp(flash, q, k, v)
    ref, vjp_ref = jax.vjp(dense, *(x.astype(f32) for x in (q, k, v)))
    assert out.dtype == q.dtype
    tol_out, tol_grad = TOL[dtype]
    np.testing.assert_allclose(np.asarray(out.astype(f32)), np.asarray(ref),
                               atol=tol_out, rtol=tol_out)
    for got, want, name in zip(vjp(ct), vjp_ref(ct.astype(f32)), "qkv"):
        assert got.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(got.astype(f32)), np.asarray(want), atol=tol_grad,
            rtol=tol_grad, err_msg="d%s (T=%d %s %s)" % (name, T, dtype, mask))


def test_backward_from_the_forwards_residuals():
    """flash_attention_bwd(out, lse) is the custom VJP's backward: the
    grad op runs it on the forward op's own outputs, no second forward."""
    q, k, v, ct = _inputs(128, "float32", seed=1)
    out, lse = flash_attention_with_lse(q, k, v, force_pallas=True)
    assert lse.shape == (4, 1, 128) and lse.dtype == f32   # rows, lane-dense
    _, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, force_pallas=True), q, k, v)
    with mock.patch.object(fa_mod, "_short_forward",
                           side_effect=AssertionError("second forward")):
        got = flash_attention_bwd(q, k, v, None, out, lse, ct, False,
                                  float(D) ** -0.5)
    for a, b in zip(got, vjp(ct)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


@pytest.mark.parametrize("case,want", [
    ("bert_s512", "short"), ("dp4_s128", "short"), ("s1024", "short"),
    ("gpt_long_s4096", "stream"), ("s2048", "stream"),
    ("small_k_block", "stream"), ("unaligned_s200", "stream"),
    ("cross_attention", "stream"), ("off_tpu", "dense")])
def test_path_is_a_function_of_the_shapes(case, want):
    shapes = {   # (B, H, S, S_kv), block_k, force_pallas
        "bert_s512": ((32, 12, 512, 512), 1024, True),
        "dp4_s128": ((128, 12, 128, 128), 1024, True),
        "s1024": ((8, 12, 1024, 1024), 1024, True),
        "gpt_long_s4096": ((2, 16, 4096, 4096), 1024, True),
        "s2048": ((2, 16, 2048, 2048), 2048, True),   # tiles over the budget
        "small_k_block": ((2, 2, 512, 512), 256, True),
        "unaligned_s200": ((2, 2, 200, 200), 1024, True),
        "cross_attention": ((2, 2, 128, 256), 1024, True),
        "off_tpu": ((32, 12, 512, 512), 1024, False),
    }
    (B, H, S, S_kv), block_k, force = shapes[case]
    q = jax.ShapeDtypeStruct((B, H, S, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((B, H, S_kv, D), jnp.bfloat16)
    assert attention_path(q, k, block_k=block_k, force_pallas=force) == want
    heads = _plan(q, k, 512, block_k)[0]
    if want == "short":
        assert (B * H) % heads == 0
        assert _short_vmem_bytes(heads, S, D, 2) <= SHORT_VMEM_BUDGET


def _bert(T, **attention):
    import paddle_tpu as fluid
    from paddle_tpu import models
    from paddle_tpu.models import transformer

    b, m, v = 2, 4, 50
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, T], dtype="int64")
        pos = fluid.data(name="pos", shape=[b, T], dtype="int64")
        mpos = fluid.data(name="mpos", shape=[b, m], dtype="int64")
        if attention.pop("bias", False):
            attention["attn_bias"] = fluid.data(
                name="bias", shape=[b, 1, T, T], dtype="float32")
        logits = models.bert_base_pretrain(
            src, pos, mpos, vocab_size=v, max_len=T, num_layers=12,
            num_heads=2, d_model=32, d_ff=64, **attention)
        loss = fluid.layers.mean(logits)
    return main, startup, loss


def _count(program, *types):
    got = [op.type for op in program.global_block().ops]
    return [got.count(t) for t in types]


@pytest.mark.parametrize("attention,flash,dense", [
    ({}, 12, 0),
    ({"bias": True}, 0, 12),
    ({"dropout": 0.1}, 0, 12),
    ({"dropout": 0.1, "is_test": True}, 12, 0),
])
def test_bert_routes_attention_by_its_mask(attention, flash, dense):
    """bert_base_pretrain at T = 512 holds 12 flash_attention ops; an
    additive attn_bias or attention dropout in training keeps the dense
    ops (scale -> matmul -> softmax -> matmul)."""
    main, _, _ = _bert(512, **attention)
    assert _count(main, "flash_attention", "softmax") == [flash, dense]
    assert _count(main, "matmul") == [2 * dense]


def test_amp_puts_no_float32_cast_before_flash_attention():
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision as mp

    main, startup, loss = _bert(128)
    with fluid.program_guard(main, startup):
        mp.decorate(fluid.optimizer.SGD(0.1)).minimize(loss)
    block = main.global_block()
    producers = {n: op for op in block.ops for n in op.output_arg_names}
    n = 0
    for op in block.ops:
        if op.type != "flash_attention":
            continue
        n += 1
        for name in op.input_arg_names:
            assert block.var(name).dtype == "bfloat16", name
            assert producers[name].type != "cast", (
                "q, k, v come from the bf16 projections, not from a cast")
        assert block.var(op.output("Out")[0]).dtype == "bfloat16"
    assert n == 12
    assert _count(main, "flash_attention_grad") == [12]


@pytest.mark.parametrize("path", ["short", "stream", "dense"])
def test_counter_names_the_path_a_trace_took(path):
    """kernels.flash_attention{path=...}: one count per traced op (and one
    of the layout it was given: head-major here, token-major in
    test_flash_tokens.py)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.core.registry import OpInfoMap

    T = {"short": 128, "stream": 32, "dense": 128}[path]
    q, k, v, _ = _inputs(T, "float32", B=1, H=2)
    op = OpInfoMap.instance().get("flash_attention").fn
    was_on = obs.enabled()
    obs.enable()
    try:
        key = "kernels.flash_attention{path=%s}" % path
        before = dict(obs.dump()["counters"])
        # the kernels run where the computation is placed on a TPU; here the
        # interpreter stands in for it
        platform = "cpu" if path == "dense" else "tpu"
        real = fa_mod._flash
        interpret = (lambda *a: real(*a[:-1], True))
        with mock.patch.object(fa_mod, "compute_platform", lambda: platform), \
                mock.patch.object(fa_mod, "_flash", interpret):
            outs = op({"Q": q, "K": k, "V": v, "Lengths": None},
                      {"causal": False, "scale": 0.0})
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    grown = {name: after[name] - before.get(name, 0) for name in after
             if name.startswith("kernels.flash_attention")
             and after[name] != before.get(name, 0)}
    assert grown == {key: 1,
                     "kernels.flash_attention_layout{layout=heads}": 1,
                     "kernels.flash_attention_select{form=none}": 1}
    assert (outs["LSE"] is None) == (path == "dense")
    ref = _dense_attention(q, k, v, False, float(D) ** -0.5)
    np.testing.assert_allclose(np.asarray(outs["Out"]), np.asarray(ref),
                               atol=2e-5)
