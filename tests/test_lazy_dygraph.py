"""Lazy (queued) dygraph dispatch vs eager parity.

The contract (dygraph/lazy.py): with ``guard(lazy=True)`` every eager
op queues onto a LazyEngine; a flush compiles the queued graph into one
jitted call, cached by structure, so steady-state training is ONE
device dispatch per step — while numerics match the eager tracer
exactly (same op fns, same tape-walk backward).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.dygraph import Linear, to_variable


def _train(lazy, iters=5, opt_name="sgd", read_mid=False):
    with fluid.dygraph.guard(lazy=lazy):
        np.random.seed(0)
        fluid.default_startup_program().random_seed = 7
        l1 = Linear(16, 32, act="relu")
        l2 = Linear(32, 4)
        params = l1.parameters() + l2.parameters()
        if opt_name == "sgd":
            opt = fluid.optimizer.SGDOptimizer(0.1, parameter_list=params)
        else:
            opt = fluid.optimizer.AdamOptimizer(1e-2,
                                                parameter_list=params)
        rng = np.random.RandomState(1)
        x = rng.rand(8, 16).astype("float32")
        y = rng.randint(0, 4, (8, 1)).astype("int64")
        losses = []
        for i in range(iters):
            h = l1(to_variable(x))
            if read_mid:
                # host read mid-step: forces a partial flush; the rest
                # of the step must still work (tape-held activations
                # materialize)
                assert np.isfinite(h.numpy()).all()
            logits = l2(h)
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    logits, to_variable(y)))
            loss.backward()
            opt.minimize(loss, parameter_list=params)
            for p in params:
                p.clear_gradient()
            losses.append(float(loss.numpy()))
        return losses, [np.asarray(p.numpy()) for p in params]


@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_lazy_matches_eager(opt_name):
    le, pe = _train(False, opt_name=opt_name)
    ll, pl = _train(True, opt_name=opt_name)
    np.testing.assert_allclose(le, ll, rtol=1e-5, atol=1e-6)
    for a, b in zip(pe, pl):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_midstep_host_read_partial_flush():
    le, _ = _train(False, read_mid=True)
    ll, _ = _train(True, read_mid=True)
    np.testing.assert_allclose(le, ll, rtol=1e-5, atol=1e-6)


def test_steady_state_is_one_compile():
    """After the first step, later steps must HIT the structure-keyed
    jit cache (that cache hit is the whole point: 1 dispatch/step)."""
    with fluid.dygraph.guard(lazy=True):
        l1 = Linear(8, 8)
        params = l1.parameters()
        opt = fluid.optimizer.SGDOptimizer(0.1, parameter_list=params)
        tracer = fluid.framework._dygraph_tracer()
        rng = np.random.RandomState(0)
        x = rng.rand(4, 8).astype("float32")
        for i in range(4):
            loss = fluid.layers.mean(l1(to_variable(x)))
            loss.backward()
            opt.minimize(loss, parameter_list=params)
            for p in params:
                p.clear_gradient()
            float(loss.numpy())
        n_graphs = len(tracer.lazy_engine._jit_cache)
        assert n_graphs <= 2, (
            "expected steady-state cache hits, got %d distinct graphs"
            % n_graphs)


def test_gradient_read_forces_flush():
    with fluid.dygraph.guard(lazy=True):
        l1 = Linear(8, 4)
        params = l1.parameters()
        x = to_variable(np.ones((2, 8), dtype="float32"))
        loss = fluid.layers.mean(l1(x))
        loss.backward()
        g = params[0].gradient()
        assert g is not None and g.shape == (8, 4)
        assert np.isfinite(g).all()


def test_dropout_rng_varies_per_step():
    """RNG seeds are external inputs: masks must vary per step WITHOUT
    recompiling (cache stays hot)."""
    with fluid.dygraph.guard(lazy=True):
        tracer = fluid.framework._dygraph_tracer()
        x = to_variable(np.ones((4, 64), dtype="float32"))
        outs = []
        for _ in range(3):
            d = fluid.layers.dropout(x, dropout_prob=0.5)
            outs.append(d.numpy())
        assert not np.allclose(outs[0], outs[1])
        assert len(tracer.lazy_engine._jit_cache) <= 1


def test_lazy_shapes_without_flush():
    """Shape/dtype reads must not force a flush."""
    with fluid.dygraph.guard(lazy=True):
        tracer = fluid.framework._dygraph_tracer()
        x = to_variable(np.ones((4, 8), dtype="float32"))
        y = fluid.layers.relu(x)
        assert y.shape == (4, 8)
        assert y.dtype in ("float32",)
        assert len(tracer.lazy_engine.nodes) == 1  # still queued
        assert np.allclose(y.numpy(), 1.0)          # forces
        assert len(tracer.lazy_engine.nodes) == 0


def test_getitem_stays_queued():
    """x[...] must queue, not flush (review r5): slicing per step is a
    common pattern (CLS-token pooling) and a flush would defeat the
    one-dispatch-per-step contract."""
    with fluid.dygraph.guard(lazy=True):
        tracer = fluid.framework._dygraph_tracer()
        x = to_variable(np.arange(24, dtype="float32").reshape(4, 6))
        y = fluid.layers.relu(x)
        z = y[:, 0]
        assert len(tracer.lazy_engine.nodes) == 2  # relu + getitem
        np.testing.assert_allclose(z.numpy(),
                                   np.arange(24).reshape(4, 6)[:, 0])


def test_getitem_grads_under_lazy():
    from paddle_tpu.dygraph import Linear

    def run(lazy):
        with fluid.dygraph.guard(lazy=lazy):
            np.random.seed(0)
            l1 = Linear(6, 6)
            params = l1.parameters()
            x = to_variable(np.ones((4, 6), dtype="float32"))
            h = l1(x)
            loss = fluid.layers.mean(h[:, 0])
            loss.backward()
            return params[0].gradient()

    np.testing.assert_allclose(run(False), run(True), rtol=1e-6)


def test_dygraph_grad_api_under_lazy():
    """fluid.dygraph.grad() must work (first-order) under lazy mode
    and match eager (review r5: it crashed with NoneType call)."""
    from paddle_tpu.dygraph import Linear

    def run(lazy):
        with fluid.dygraph.guard(lazy=lazy):
            np.random.seed(0)
            l1 = Linear(5, 3)
            x = to_variable(np.ones((2, 5), dtype="float32"))
            x.stop_gradient = False
            y = fluid.layers.reduce_sum(l1(x))
            (g,) = fluid.dygraph.grad(y, x)
            return g.numpy()

    np.testing.assert_allclose(run(False), run(True), rtol=1e-6)


def test_dygraph_grad_create_graph_raises_clearly_under_lazy():
    with fluid.dygraph.guard(lazy=True):
        x = to_variable(np.ones((2, 2), dtype="float32"))
        x.stop_gradient = False
        y = fluid.layers.reduce_sum(x * x)
        with pytest.raises(NotImplementedError, match="lazy=False"):
            fluid.dygraph.grad(y, x, create_graph=True)


def test_max_nodes_valve_is_conservative():
    """Review r5: the safety-valve flush fires before owners attach;
    it must materialize everything (a precise-liveness flush there
    loses the in-flight node's outputs)."""
    from paddle_tpu.dygraph import Linear

    def run(cap):
        with fluid.dygraph.guard(lazy=True):
            np.random.seed(0)
            tracer = fluid.framework._dygraph_tracer()
            if cap:
                tracer.lazy_engine.MAX_NODES = cap
            l1 = Linear(8, 8)
            params = l1.parameters()
            opt = fluid.optimizer.SGDOptimizer(0.1,
                                               parameter_list=params)
            x = to_variable(np.ones((2, 8), dtype="float32"))
            for _ in range(2):
                loss = fluid.layers.mean(l1(l1(l1(x))))
                loss.backward()
                opt.minimize(loss, parameter_list=params)
                for p in params:
                    p.clear_gradient()
            return float(loss.numpy())

    ref = run(None)
    # valve fires many times mid-step (including mid-backward)
    assert np.allclose(run(3), ref, rtol=1e-5)
    assert np.allclose(run(7), ref, rtol=1e-5)


# -- flush overhead -----------------------------------------------------------


def test_lazy_recompiles_stay_flat():
    """Steady-state lazy training: after warmup, further steps add
    ZERO lazy.recompiles (the structure signature — including cached
    ndarray attr digests — is stable across flushes)."""
    from paddle_tpu import observability as obs

    obs.enable()
    with fluid.dygraph.guard(lazy=True):
        l1 = Linear(16, 32, act="relu")
        l2 = Linear(32, 10)
        params = l1.parameters() + l2.parameters()
        opt = fluid.optimizer.AdamOptimizer(1e-3, parameter_list=params)
        rng = np.random.RandomState(0)
        x = rng.rand(8, 16).astype("float32")
        y = rng.randint(0, 10, (8, 1)).astype("int64")

        def step():
            logits = l2(l1(to_variable(x)))
            loss = fluid.layers.mean(
                fluid.layers.softmax_with_cross_entropy(
                    logits, to_variable(y)))
            loss.backward()
            opt.minimize(loss, parameter_list=params)
            for p in params:
                p.clear_gradient()
            return loss

        for _ in range(3):
            loss = step()
        float(np.asarray(loss.numpy()).ravel()[0])
        before = obs.counter_value("lazy.recompiles") or 0
        for _ in range(3):
            loss = step()
        float(np.asarray(loss.numpy()).ravel()[0])
        after = obs.counter_value("lazy.recompiles") or 0
    assert after == before, (
        "lazy steady state recompiled %d times" % (after - before))


def test_ndarray_attr_digest_cached():
    from paddle_tpu.dygraph import tracer as tr

    arr = np.arange(64, dtype="f4").reshape(8, 8)
    d1 = tr._canon_attr(arr)
    assert id(arr) in tr._ndarray_digests
    import hashlib

    calls = []
    real = hashlib.sha1

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    hashlib.sha1 = counting
    try:
        d2 = tr._canon_attr(arr)
    finally:
        hashlib.sha1 = real
    assert d1 == d2
    assert not calls, "cached ndarray attr was re-hashed"
    # a DIFFERENT array with identical content still hashes by content
    arr2 = arr.copy()
    assert tr._canon_attr(arr2) == d1
