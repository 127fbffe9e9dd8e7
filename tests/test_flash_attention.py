"""Pallas flash attention vs dense oracle.

The pallas kernel runs in interpret mode on CPU (force_pallas) so the
exact streaming/log-sum-exp code path is exercised without TPU
hardware; on-device it compiles to the real kernel.
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (
    _dense_attention, flash_attention)

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")

B, H, S, D = 2, 3, 32, 16


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(B, H, S, D).astype("float32")),
            jnp.asarray(rng.randn(B, H, S, D).astype("float32")),
            jnp.asarray(rng.randn(B, H, S, D).astype("float32")))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [8, 16, 32])
def test_kernel_matches_dense(causal, block):
    q, k, v = _inputs(0)
    ref = _dense_attention(q, k, v, causal, float(D) ** -0.5)
    got = flash_attention(q, k, v, causal=causal, block_q=block,
                          block_k=block, force_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [8, 16])
def test_backward_kernels_match_dense_vjp(causal, block):
    """The pallas dQ / dK+dV kernels (blockwise recompute from saved
    LSE) must agree with the dense-attention VJP on all three grads —
    including the causal masking and the non-uniform cotangent."""
    q, k, v = _inputs(2)
    rng = np.random.RandomState(3)
    ct = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    scale = float(D) ** -0.5

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=block,
                              block_k=block, force_pallas=True)
        return jnp.sum(out * ct)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, causal, scale) * ct)

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg="d%s mismatch (causal=%s block=%d)"
                    % (name, causal, block))


def test_backward_ragged_tail_falls_back_dense():
    """S not divisible by the block -> the fallback path must still
    deliver exact grads."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 2, 20, 8).astype("float32"))
    k = jnp.asarray(rng.randn(1, 2, 20, 8).astype("float32"))
    v = jnp.asarray(rng.randn(1, 2, 20, 8).astype("float32"))
    scale = 8.0 ** -0.5

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=16, block_k=16,
                                       force_pallas=True))

    def dense_loss(q, k, v):
        return jnp.sum(_dense_attention(q, k, v, False, scale))

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_grads_flow():
    q, k, v = _inputs(1)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=16,
                            block_k=16, force_pallas=True)
        return (o.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        o = _dense_attention(q, k, v, True, float(D) ** -0.5)
        return (o.astype(jnp.float32) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_transformer_model_uses_flash_path():
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T, Dm = 2, 16, 32
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[Bm, T, Dm], dtype="float32")
        out = models.transformer.multi_head_attention(
            x, num_heads=4, d_model=Dm, dropout=0.0, is_test=True)
    types = [op.type for op in prog.global_block().ops]
    assert "flash_attention" in types
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (o,) = exe.run(
            prog,
            feed={"x": np.random.RandomState(0).randn(
                Bm, T, Dm).astype("float32")},
            fetch_list=[out])
    assert np.asarray(o).shape == (Bm, T, Dm)
    assert np.isfinite(np.asarray(o)).all()


def test_training_path_uses_flash_when_unmasked():
    """TRAINING attention (no additive mask, no attention dropout)
    routes through flash_attention at any length with no force, and a
    grad op for it lands in the program, reading the forward op's own
    Out and LSE."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T, Dm = 2, 8, 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[Bm, T, Dm], dtype="float32")
        out = models.transformer.multi_head_attention(
            x, num_heads=2, d_model=Dm, dropout=0.0, is_test=False)
        loss = fluid.layers.reduce_mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    types = [op.type for op in prog.global_block().ops]
    assert "flash_attention" in types
    assert "flash_attention_grad" in types
    assert "softmax" not in types and "matmul" not in types
    (grad_op,) = [op for op in prog.global_block().ops
                  if op.type == "flash_attention_grad"]
    assert {"Out", "LSE"} <= set(grad_op.inputs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        x0 = np.random.RandomState(0).randn(Bm, T, Dm).astype("float32")
        l0 = exe.run(prog, feed={"x": x0}, fetch_list=[loss])[0]
        for _ in range(3):
            l1 = exe.run(prog, feed={"x": x0}, fetch_list=[loss])[0]
    assert np.isfinite(np.asarray(l1)).all()
    assert float(np.asarray(l1)) != float(np.asarray(l0))  # trained


def test_masked_path_still_dense():
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T, Dm = 2, 8, 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[Bm, T, Dm], dtype="float32")
        bias = fluid.data(name="b", shape=[Bm, 1, T, T], dtype="float32")
        models.transformer.multi_head_attention(
            x, num_heads=2, d_model=Dm, attn_bias=bias, is_test=True)
    types = [op.type for op in prog.global_block().ops]
    assert "flash_attention" not in types
    assert "softmax" in types


def test_fit_block_shrinks_to_aligned_divisor():
    """S not a multiple of the tuned block must shrink the block, not
    silently drop to dense (advisor r4): 2560 with the 512/1024
    defaults stays on the flash path via 640-wide K blocks."""
    from paddle_tpu.ops.pallas.flash_attention import _fit_block

    assert _fit_block(2560, 512) == 512     # already divides
    assert _fit_block(2560, 1024) == 640    # largest 128-aligned divisor
    assert _fit_block(2688, 1024) == 896
    assert _fit_block(768, 512) == 384
    assert _fit_block(640, 512) == 128
    assert _fit_block(100, 512) == 100      # short seq: block = S
    assert _fit_block(200, 512) == 200
    assert _fit_block(48, 32) == 24         # sub-128: 8-aligned
    # no aligned divisor below the cap -> 0 (caller goes dense, warns)
    assert _fit_block(770, 512) == 0


def test_nonmultiple_seq_still_flash():
    """S=48 with block 32 previously fell back to dense silently; the
    fitted 16-wide block must keep the pallas path and stay exact."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, 2, 48, 8).astype("float32"))
    k = jnp.asarray(rng.randn(1, 2, 48, 8).astype("float32"))
    v = jnp.asarray(rng.randn(1, 2, 48, 8).astype("float32"))
    ref = _dense_attention(q, k, v, False, 8.0 ** -0.5)
    got = flash_attention(q, k, v, block_q=32, block_k=32,
                          force_pallas=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_masked_flash_matches_dense(causal):
    """Per-row KV lengths (the padding mask, VERDICT r4 #7): masked
    rows must match the dense additive-mask oracle on visible QUERY
    rows, forward and backward."""
    Bm, Hm, Sm, Dm = 3, 2, 32, 16
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(Bm, Hm, Sm, Dm).astype("float32"))
    k = jnp.asarray(rng.randn(Bm, Hm, Sm, Dm).astype("float32"))
    v = jnp.asarray(rng.randn(Bm, Hm, Sm, Dm).astype("float32"))
    lengths = jnp.asarray([32, 20, 7], dtype=jnp.int32)
    scale = float(Dm) ** -0.5
    ct = jnp.asarray(rng.randn(Bm, Hm, Sm, Dm).astype("float32"))
    # only visible query rows contribute (padded-query outputs are
    # unspecified, exactly like the additive-mask formulation)
    row_ok = np.zeros((Bm, 1, Sm, 1), dtype="float32")
    for b, L in enumerate([32, 20, 7]):
        row_ok[b, :, :L] = 1.0
    ctv = ct * jnp.asarray(row_ok)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16,
                            block_k=16, force_pallas=True,
                            lengths=lengths)
        return jnp.sum(o * ctv)

    def loss_dense(q, k, v):
        o = _dense_attention(q, k, v, causal, scale, lengths=lengths)
        return jnp.sum(o * ctv)

    o_f = flash_attention(q, k, v, causal=causal, block_q=16,
                          block_k=16, force_pallas=True, lengths=lengths)
    o_d = _dense_attention(q, k, v, causal, scale, lengths=lengths)
    np.testing.assert_allclose(np.asarray(o_f) * row_ok,
                               np.asarray(o_d) * row_ok,
                               rtol=2e-5, atol=2e-5)
    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_f, g_d, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
            err_msg="d%s mismatch (causal=%s)" % (name, causal))


def test_masked_flash_zero_length_row():
    """A fully padded example must not NaN anything."""
    q = jnp.asarray(np.ones((2, 1, 16, 8), dtype="float32"))
    lengths = jnp.asarray([16, 0], dtype=jnp.int32)

    def loss(q):
        o = flash_attention(q, q, q, block_q=8, block_k=8,
                            force_pallas=True, lengths=lengths)
        return jnp.sum(o[0])   # loss over the valid example only

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()


def test_masked_training_routes_flash():
    """With kv_lengths, MASKED training attention routes flash at any
    length — the round-4 gap (padding-masked training always fell
    dense) closed."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T, Dm = 2, 16, 32
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[Bm, T, Dm], dtype="float32")
        lens = fluid.data(name="lens", shape=[Bm], dtype="int32")
        out = models.transformer.multi_head_attention(
            x, num_heads=4, d_model=Dm, dropout=0.0, is_test=False,
            kv_lengths=lens)
        loss = fluid.layers.reduce_mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss)
    types = [op.type for op in prog.global_block().ops]
    assert "flash_attention" in types
    assert "flash_attention_grad" in types
    assert "softmax" not in types and "matmul" not in types
    (grad_op,) = [op for op in prog.global_block().ops
                  if op.type == "flash_attention_grad"]
    assert {"Out", "LSE"} <= set(grad_op.inputs)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        l0 = exe.run(prog, feed={
            "x": rng.randn(Bm, T, Dm).astype("float32"),
            "lens": np.array([16, 9], dtype="int32")},
            fetch_list=[loss])[0]
    assert np.isfinite(np.asarray(l0)).all()


def test_wmt_model_with_lengths_routes_flash():
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T = 2, 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        src = fluid.data(name="src", shape=[Bm, T], dtype="int64")
        srcp = fluid.data(name="srcp", shape=[Bm, T], dtype="int64")
        tgt = fluid.data(name="tgt", shape=[Bm, T], dtype="int64")
        tgtp = fluid.data(name="tgtp", shape=[Bm, T], dtype="int64")
        slen = fluid.data(name="slen", shape=[Bm], dtype="int32")
        tlen = fluid.data(name="tlen", shape=[Bm], dtype="int32")
        logits = models.transformer.transformer_wmt(
            src, srcp, tgt, tgtp, vocab_size=64, max_len=T,
            num_layers=1, num_heads=2, d_model=16, d_ff=32,
            src_lengths=slen, tgt_lengths=tlen)
    types = [op.type for op in prog.global_block().ops]
    # encoder self-attn + decoder self-attn route flash; cross stays
    # dense (rectangular) with the additive bias
    assert types.count("flash_attention") == 2
    assert "softmax" in types


def test_dense_kv_lengths_mask_actually_masks():
    """Review r5: the additive pad bias computed (vis-1e9)*1e9 which
    collapses to the same float32 constant for visible AND masked keys
    (a silent no-op mask). Contract: with kv_lengths, the output on
    valid rows must be INVARIANT to the content of padded positions —
    checked on the forced-dense path (the flash path has its own
    oracle test)."""
    import paddle_tpu as fluid
    from paddle_tpu import models

    Bm, T, Dm = 2, 8, 16

    def run(x_np):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            fluid.default_startup_program().random_seed = 5
            prog.random_seed = 5
            startup.random_seed = 5
            x = fluid.data(name="x", shape=[Bm, T, Dm], dtype="float32")
            lens = fluid.data(name="lens", shape=[Bm], dtype="int32")
            out = models.transformer.multi_head_attention(
                x, num_heads=2, d_model=Dm, dropout=0.0, is_test=True,
                kv_lengths=lens, use_flash=False)
        types = [op.type for op in prog.global_block().ops]
        assert "flash_attention" not in types  # the dense fallback
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            (o,) = exe.run(prog, feed={
                "x": x_np, "lens": np.array([8, 4], dtype="int32")},
                fetch_list=[out])
        return np.asarray(o)

    rng = np.random.RandomState(0)
    x1 = rng.randn(Bm, T, Dm).astype("float32")
    x2 = x1.copy()
    x2[1, 4:] = 77.0   # change ONLY padded positions of example 1
    np.random.seed(0)
    o1 = run(x1)
    np.random.seed(0)
    o2 = run(x2)
    # example 0 (full length) unchanged input -> identical output;
    # example 1 valid rows must ignore the padded-key change
    np.testing.assert_allclose(o1[0], o2[0], rtol=1e-5)
    np.testing.assert_allclose(o1[1, :4], o2[1, :4], rtol=1e-4,
                               atol=1e-4)


# -- the streaming backward: one kernel for dQ, dK, dV ------------------------


def _stream_case(causal, mask, group, dims, dtype, seed=5):
    """Inputs of a streaming call (S = 32 in blocks of 16 x 8, B = 2, four
    query heads over 4 / group K/V heads, head dims (D, Dv)), the flash and
    the dense float32 loss over them, and the tolerance of the dtype."""
    Bs, Hs, Ss = 2, 4, 32
    D, Dv = dims
    rng = np.random.RandomState(seed)
    draw = lambda *shape: jnp.asarray(
        rng.randn(*shape).astype("float32")).astype(dtype)
    q, k, v = (draw(Bs, Hs, Ss, D), draw(Bs, Hs // group, Ss, D),
               draw(Bs, Hs // group, Ss, Dv))
    ct = np.asarray(rng.randn(Bs, Hs, Ss, Dv).astype("float32"))
    lengths = select = None
    if mask == "lengths":
        lengths = jnp.asarray([Ss, 13], dtype=jnp.int32)
        ct[1, :, 13:] = 0.0   # padded query rows are unspecified: no weight
    if mask == "select":
        keep = rng.rand(Bs, Ss, Ss) < 0.4
        keep |= np.eye(Ss, dtype=bool)[None]   # every row sees its own key
        select = jnp.asarray(keep.astype("int8"))
    ct = jnp.asarray(ct)
    scale = float(D) ** -0.5

    def flash_loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=causal, block_q=16,
                                 block_k=8, force_pallas=True,
                                 lengths=lengths, select=select)
        return jnp.sum(out.astype(jnp.float32) * ct)

    def dense_loss(q, k, v):
        out = fa._dense_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal, scale, lengths, select)
        return jnp.sum(out * ct)

    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    return (q, k, v), flash_loss, dense_loss, tol, select


def _backward_paths(loss, *args):
    """{path: traces} that ``kernels.flash_attention_grad`` grew by over one
    trace of ``loss``'s gradient: the counter is made at the branch the
    backward takes (traced only: nothing is lowered or run)."""
    from paddle_tpu import observability as obs

    prefix = "kernels.flash_attention_grad{path="
    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.dump()["counters"])
        jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), *args)
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    return {name[len(prefix):-1]: count - before.get(name, 0)
            for name, count in after.items()
            if name.startswith(prefix) and count != before.get(name, 0)}


def _assert_grads_match(flash_loss, dense_loss, args, tol, what):
    got = jax.grad(flash_loss, argnums=(0, 1, 2))(*args)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(*args)
    for a, b, name in zip(got, want, "qkv"):
        assert a.dtype == args[0].dtype and a.shape == b.shape
        b = np.asarray(b, dtype="float32")
        np.testing.assert_allclose(
            np.asarray(a, dtype="float32"), b, rtol=tol,
            atol=tol * max(1.0, float(np.abs(b).max())),
            err_msg="d%s mismatch (%s)" % (name, what))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("dims", [(16, 16), (24, 16)], ids=["d16", "d24v16"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("mask", ["none", "lengths", "select"])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_matches_dense_vjp(causal, mask, group, dims, dtype):
    """The streaming backward the rule picks (with shared K/V heads the one
    kernel: each score tile made once, dK and dV of a K/V head summed over
    its group in VMEM; the pair with one K/V head a query head) against the
    dense VJP: every mask, a value dim of its own, both dtypes."""
    args, flash_loss, dense_loss, tol, select = _stream_case(
        causal, mask, group, dims, dtype)
    assert _backward_paths(flash_loss, *args) == {
        "fused" if group > 1 else "split": 1}
    _assert_grads_match(flash_loss, dense_loss, args, tol,
                        (causal, mask, group, dims))


@pytest.mark.parametrize("dims", [(16, 16), (24, 16)], ids=["d16", "d24v16"])
@pytest.mark.parametrize("mask", ["none", "lengths", "select"])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_backward_with_one_kv_head_a_query_head(monkeypatch, causal,
                                                      mask, dims):
    """The one kernel's math holds without shared K/V heads too (the rule
    keeps such a call on the pair for the chip's sake: ``_fused_bwd_fits``)."""
    args, flash_loss, dense_loss, tol, select = _stream_case(
        causal, mask, 1, dims, jnp.bfloat16)
    monkeypatch.setattr(fa, "_fused_bwd_fits", lambda *shapes: True)
    assert _backward_paths(flash_loss, *args) == {"fused": 1}
    _assert_grads_match(flash_loss, dense_loss, args, tol,
                        (causal, mask, dims))


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("mask", ["none", "lengths", "select"])
@pytest.mark.parametrize("causal", [False, True])
def test_split_backward_over_the_budget_matches_dense_vjp(
        monkeypatch, causal, mask, group):
    """Past the VMEM budget the dQ and dK+dV pair is what runs, and it is
    still the dense VJP (the budget is forced under a toy shape's need)."""
    args, flash_loss, dense_loss, tol, select = _stream_case(
        causal, mask, group, (16, 16), jnp.float32)
    monkeypatch.setattr(fa, "STREAM_VMEM_BUDGET", 1 << 10)
    assert _backward_paths(flash_loss, *args) == {"split": 1}
    calls = jax.make_jaxpr(jax.grad(flash_loss, argnums=(0, 1, 2)))(
        *args).pretty_print(use_color=False).count("pallas_call")
    assert calls == 3   # forward, dQ, dK+dV
    _assert_grads_match(flash_loss, dense_loss, args, tol,
                        (causal, mask, group))


def test_fused_backward_is_one_kernel_a_call():
    args, flash_loss, _, _, _ = _stream_case(True, "none", 4, (16, 16),
                                             jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(flash_loss, argnums=(0, 1, 2)))(*args)
    assert jaxpr.pretty_print(use_color=False).count("pallas_call") == 2


@pytest.mark.parametrize("group", [1, 4])
def test_selecting_every_causal_key_gives_the_unselected_gradients(group):
    """Bit for bit: the selection's mask changes no visible score."""
    (q, k, v), _, _, _, _ = _stream_case(True, "none", group, (16, 16),
                                         jnp.bfloat16)
    every = jnp.asarray(np.tril(np.ones((q.shape[0], 32, 32), "int8")))

    def grads(select):
        return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, block_q=16, block_k=8, force_pallas=True,
            select=select).astype(jnp.float32) ** 2), argnums=(0, 1, 2))(
                q, k, v)

    for a, b in zip(grads(every), grads(None)):
        np.testing.assert_array_equal(np.asarray(a, dtype="float32"),
                                      np.asarray(b, dtype="float32"))


def test_fused_or_split_is_a_function_of_the_shapes():
    """The one kernel for calls with shared K/V heads wherever a K/V head's
    float32 dK and dV (and the tiles) fit the budget, the two decoder cells
    with shared heads among them; the pair beyond, and with one K/V head a
    query head (the latent-attention cell); nothing but S, D, Dv, the
    blocks, the dtype and the heads' ratio decides."""
    fits = lambda S, D, Dv, group, itemsize=2: fa._fused_bwd_fits(
        S, D, Dv, 512, 1024, itemsize, group)
    assert fits(16384, 128, 128, 8) and fits(8192, 128, 128, 16) \
        and fits(4096, 192, 128, 4) and fits(4096, 64, 64, 2)
    assert fits(16384, 128, 128, 8, 4)       # float32 operands too
    assert not fits(262144, 128, 128, 8) and not fits(65536, 192, 192, 8)
    # one K/V head a query head: the pair, whatever the head dims
    assert not fits(4096, 192, 128, 1) and not fits(4096, 128, 128, 1) \
        and not fits(2048, 64, 64, 1)
    # monotone in each of S, D, Dv: one threshold, no island
    sizes = [fa._fused_bwd_vmem_bytes(S, D, Dv, 512, 1024, 2)
             for S in (4096, 8192, 16384) for D in (64, 128, 192)
             for Dv in (64, 128)]
    assert sizes == sorted(sizes)
    assert fa._fused_bwd_vmem_bytes(16384, 128, 128, 512, 1024, 2) \
        >= (16384 * 128 + 16384 * 128) * 4    # the accumulators are in it
    # the backward that a trace takes follows the rule (shapes only: nothing
    # is lowered or run), at the sparse-attention cell's shape and beyond
    def paths(q, k, v, **kw):
        return _backward_paths(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, **kw).astype(jnp.float32)), q, k, v)

    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    assert paths(q, kv, kv, force_pallas=True) == {"fused": 1}
    long_q = jax.ShapeDtypeStruct((1, 8, 262144, 128), jnp.bfloat16)
    long_kv = jax.ShapeDtypeStruct((1, 1, 262144, 128), jnp.bfloat16)
    assert paths(long_q, long_kv, long_kv, force_pallas=True) == {"split": 1}
    latent_qk = jax.ShapeDtypeStruct((1, 32, 4096, 192), jnp.bfloat16)
    latent_v = jax.ShapeDtypeStruct((1, 32, 4096, 128), jnp.bfloat16)
    assert paths(latent_qk, latent_qk, latent_v,
                 force_pallas=True) == {"split": 1}
    # off the TPU the dense math runs and JAX differentiates it: no kernel's
    # branch is taken (the grad op counts that one, tests/test_chip_smoke.py)
    small = jax.ShapeDtypeStruct((1, 4, 2048, 64), jnp.bfloat16)
    assert paths(small, small, small) == {}
    short = jax.ShapeDtypeStruct((2, 4, 256, 64), jnp.bfloat16)
    assert paths(short, short, short, force_pallas=True) == {"short": 1}
    ragged = jax.ShapeDtypeStruct((2, 4, 770, 64), jnp.bfloat16)
    with pytest.warns(UserWarning):
        assert paths(ragged, ragged, ragged,
                     force_pallas=True) == {"dense": 1}
