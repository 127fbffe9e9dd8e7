"""``Executor.run`` traces the program its user built: nothing between
``Program`` and the trace rewrites it, whatever the environment holds
(PR 30 removed the single-chip rewrites and their switches). And the
op chains those rewrites used to collapse (``add -> act [-> dropout]``,
``add -> layer_norm``) train, as the plain ops they are, like
``jax.numpy``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import layers, models
from paddle_tpu.backward import gradients

# the four names PR 30 retired, each at a value that used to switch its
# mechanism on
RETIRED = {"PADDLE_TPU_FUSED_OPTIMIZER": "1",
           "PADDLE_TPU_FUSED_EPILOGUE": "1",
           "PADDLE_TPU_ASYNC_FEED": "1",
           "FLAGS_use_pallas_conv": "all"}


def _ids(rng, high, *shape):
    return rng.randint(0, high, shape).astype("int64")


def _classify(pred, label):
    return layers.mean(layers.cross_entropy(pred, label))


def _token_loss(logits, labels, rows, vocab):
    return layers.mean(layers.softmax_with_cross_entropy(
        layers.reshape(logits, [rows, vocab]),
        layers.reshape(labels, [rows, 1])))


def _mlp(rng):
    x = fluid.data(name="x", shape=[4, 16], dtype="float32")
    y = fluid.data(name="y", shape=[4, 1], dtype="int64")
    loss = _classify(models.mlp(x, hidden_sizes=(32, 16), act="gelu"), y)
    return loss, {"x": rng.rand(4, 16).astype("float32"),
                  "y": _ids(rng, 10, 4, 1)}


def _lenet(rng):
    x = fluid.data(name="x", shape=[2, 1, 28, 28], dtype="float32")
    y = fluid.data(name="y", shape=[2, 1], dtype="int64")
    loss = _classify(models.lenet(x), y)
    return loss, {"x": rng.rand(2, 1, 28, 28).astype("float32"),
                  "y": _ids(rng, 10, 2, 1)}


def _resnet(rng):
    x = fluid.data(name="x", shape=[2, 3, 8, 8], dtype="float32")
    y = fluid.data(name="y", shape=[2, 1], dtype="int64")
    loss = _classify(models.resnet_cifar(x, n=1), y)
    return loss, {"x": rng.rand(2, 3, 8, 8).astype("float32"),
                  "y": _ids(rng, 10, 2, 1)}


def _bert(rng):
    b, t, m, v = 2, 8, 2, 32
    src = fluid.data(name="src", shape=[b, t], dtype="int64")
    pos = fluid.data(name="pos", shape=[b, t], dtype="int64")
    mpos = fluid.data(name="mpos", shape=[b, m], dtype="int64")
    lbl = fluid.data(name="lbl", shape=[b, m, 1], dtype="int64")
    logits = models.bert_base_pretrain(src, pos, mpos, vocab_size=v,
                                       max_len=t, num_layers=1, num_heads=2,
                                       d_model=16, d_ff=32, dropout=0.1)
    return _token_loss(logits, lbl, b * m, v), {
        "src": _ids(rng, v, b, t),
        "pos": np.tile(np.arange(t), (b, 1)).astype("int64"),
        "mpos": _ids(rng, t, b, m), "lbl": _ids(rng, v, b, m, 1)}


def _transformer(rng):
    b, t, v = 2, 8, 20
    names = ("src", "spos", "tgt", "tpos")
    src, spos, tgt, tpos = (fluid.data(name=n, shape=[b, t], dtype="int64")
                            for n in names)
    lbl = fluid.data(name="lbl", shape=[b, t, 1], dtype="int64")
    logits = models.transformer_wmt(src, spos, tgt, tpos, vocab_size=v,
                                    max_len=t, num_layers=1, num_heads=2,
                                    d_model=16, d_ff=32)
    pos = np.tile(np.arange(t), (b, 1)).astype("int64")
    return _token_loss(logits, lbl, b * t, v), {
        "src": _ids(rng, v, b, t), "spos": pos, "tgt": _ids(rng, v, b, t),
        "tpos": pos, "lbl": _ids(rng, v, b, t, 1)}


def _wide_deep(rng):
    dense = fluid.data(name="dense", shape=[4, 8], dtype="float32")
    sparse = fluid.data(name="sparse", shape=[4, 3], dtype="int64")
    y = fluid.data(name="y", shape=[4, 1], dtype="int64")
    pred = models.wide_deep(dense, sparse, vocab_size=50, embed_dim=4,
                            hidden_sizes=(16, 8))
    return _classify(pred, y), {
        "dense": rng.rand(4, 8).astype("float32"),
        "sparse": _ids(rng, 50, 4, 3), "y": _ids(rng, 2, 4, 1)}


def _hybrid(rng):
    t, v = 16, 64
    src = fluid.data(name="src", shape=[1, t], dtype="int64")
    lbl = fluid.data(name="lbl", shape=[t, 1], dtype="int64")
    logits = models.hybrid_ssm_moe(
        src, "ME*", v, 32, mamba_heads=8, mamba_head_dim=8, n_groups=2,
        state_size=16, chunk=8, num_experts=16, top_k=3, expert_dim=24,
        shared_dim=48, held=[0, 4], num_heads=4, num_kv_heads=2,
        head_dim=16)
    return _token_loss(logits, lbl, t, v), {"src": _ids(rng, v, 1, t),
                                            "lbl": _ids(rng, v, t, 1)}


MODELS = {"mlp": _mlp, "lenet": _lenet, "resnet": _resnet, "bert": _bert,
          "transformer": _transformer, "wide_deep": _wide_deep,
          "hybrid_ssm_moe": _hybrid}


@pytest.mark.parametrize("model", list(MODELS))
def test_run_leaves_program_as_built(model, monkeypatch):
    for name, value in RETIRED.items():
        monkeypatch.setenv(name, value)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        loss, feed = MODELS[model](np.random.RandomState(0))
        fluid.optimizer.AdamOptimizer(1e-3).minimize(loss)
    block = main.global_block()
    built = [(op.type, sorted(op.attrs)) for op in block.ops]
    assert sum(t == "adam" for t, _ in built) >= 2
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        (value,) = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(np.asarray(value)).all()
    assert [(op.type, sorted(op.attrs)) for op in block.ops] == built


# -- the chains, as plain ops ---------------------------------------------------

ACTS = {"relu": jax.nn.relu,
        "gelu": lambda v: jax.nn.gelu(v, approximate=False),
        "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}
CHAINS = [(act, drop) for act in ACTS for drop in (False, True)] \
    + [("layer_norm", False)]


@pytest.mark.parametrize("act,dropout", CHAINS,
                         ids=["%s%s" % (a, "-dropout" if d else "")
                              for a, d in CHAINS])
def test_epilogue_chains_train_like_reference(act, dropout):
    """Forward value and the gradients of both operands of the add. The
    dropout's mask is fetched from the forward op: a ``dropout_grad``
    that drew another mask would give another gradient."""
    rng = np.random.RandomState(3)
    xv = rng.randn(8, 16).astype("float32")
    bv = rng.randn(16).astype("float32")
    wv = rng.randn(8, 16).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[8, 16], dtype="float32")
        b = fluid.data(name="b", shape=[16], dtype="float32")
        w = fluid.data(name="w", shape=[8, 16], dtype="float32")
        x.stop_gradient = b.stop_gradient = False
        h = layers.elementwise_add(x, b)
        if act == "layer_norm":
            out = layers.layer_norm(h, begin_norm_axis=1)
        else:
            out = getattr(layers, act)(h)
        if dropout:
            out = layers.dropout(out, dropout_prob=0.3)
        loss = layers.reduce_sum(layers.elementwise_mul(out, w))
        gx, gb = gradients(loss, [x, b])
    block = main.global_block()
    fetch = [out, gx, gb]
    if dropout:
        (drop_op,) = [op for op in block.ops if op.type == "dropout"]
        fetch.append(drop_op.output("Mask")[0])
        assert "dropout_grad" in [op.type for op in block.ops]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got = [np.asarray(v) for v in exe.run(
            main, feed={"x": xv, "b": bv, "w": wv}, fetch_list=fetch)]
        ln_params = [np.asarray(scope.find_var(p.name).raw().array)
                     for p in main.all_parameters()]

    if dropout:
        mask = got[3].astype("float32")
        kept = float(mask.mean())
        assert 0.5 < kept < 0.9 and set(np.unique(mask)) == {0.0, 1.0}
    else:
        mask = np.float32(1.0)

    def plain(x, b):
        h = x + b
        if act == "layer_norm":
            scale, bias = ln_params
            mean = h.mean(-1, keepdims=True)
            var = ((h - mean) ** 2).mean(-1, keepdims=True)
            y = (h - mean) / jnp.sqrt(var + 1e-5) * scale + bias
        else:
            y = ACTS[act](h)
        return y * mask

    want = plain(xv, bv)
    want_gx, want_gb = jax.grad(
        lambda x, b: jnp.sum(plain(x, b) * wv), argnums=(0, 1))(xv, bv)
    for name, g, r in zip(("out", "dx", "db"), got, (want, want_gx, want_gb)):
        np.testing.assert_allclose(g, np.asarray(r), rtol=2e-5, atol=2e-5,
                                   err_msg=name)
