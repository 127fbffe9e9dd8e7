"""The per-parameter optimizer ops (sgd, momentum, adam, adamw) against a
plain statement of each update: float64 numpy at the op level, and
``jax.grad`` of the same model + the same plain update at the program
level (float32, bf16 AMP over float32 master weights, parameters of
uneven sizes), and across a save / reload of the optimizer's state."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core.registry import OpInfoMap

OPS = ["sgd", "momentum", "adam", "adamw"]
HYPER = {"mu": 0.9, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
         "weight_decay": 0.01}
SEED = 4242


def plain_update(op, p, g, state, lr, step):
    """One update in float64. ``state`` is the tuple of accumulators
    the op keeps (none, velocity, two moments); ``step`` counts from 1
    (Adam's bias correction)."""
    p, g = np.float64(p), np.float64(g)
    if op == "sgd":
        return p - lr * g, ()
    if op == "momentum":
        v = HYPER["mu"] * state[0] + g
        return p - lr * v, (v,)
    b1, b2, eps = HYPER["beta1"], HYPER["beta2"], HYPER["epsilon"]
    m1 = b1 * state[0] + (1 - b1) * g
    m2 = b2 * state[1] + (1 - b2) * g * g
    lr_t = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
    out = p - lr_t * m1 / (np.sqrt(m2) + eps)
    if op == "adamw":
        out = out - lr * HYPER["weight_decay"] * p
    return out, (m1, m2)


def _n_state(op):
    return {"sgd": 0, "momentum": 1}.get(op, 2)


# -- op level -----------------------------------------------------------------


@pytest.mark.parametrize("grad_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("op", OPS)
def test_update_matches_plain_reference(op, grad_dtype):
    """The registered op on float32 weights, with float32 and bf16
    gradients, at odd sizes; third step of a run (both bias
    corrections differ from 1)."""
    fn = OpInfoMap.instance().get(op).fn
    rng = np.random.RandomState(0)
    lr, step = 0.01, 3
    for size in (7, 129, 1024, 33):
        p = rng.randn(size).astype("float32")
        g = jnp.asarray(rng.randn(size), grad_dtype)
        state = tuple(np.abs(rng.randn(size)).astype("float32")
                      for _ in range(_n_state(op)))
        ins = {"Param": jnp.asarray(p), "Grad": g,
               "LearningRate": jnp.asarray([lr], jnp.float32)}
        if op == "momentum":
            ins["Velocity"] = jnp.asarray(state[0])
        elif _n_state(op) == 2:
            ins.update(
                Moment1=jnp.asarray(state[0]), Moment2=jnp.asarray(state[1]),
                Beta1Pow=jnp.asarray([HYPER["beta1"] ** step], jnp.float32),
                Beta2Pow=jnp.asarray([HYPER["beta2"] ** step], jnp.float32))
        got = fn(ins, dict(HYPER))
        want_p, want_state = plain_update(
            op, p, np.asarray(g.astype(jnp.float32)), state, lr, step)
        # float32 rounding; a bf16 gradient is squared in its own type
        # before it meets Adam's float32 second moment: one rounding
        # of 2^-9 there, and what that moves in the step
        tol = 2e-6 if grad_dtype == "float32" or _n_state(op) < 2 else 4e-3
        assert got["ParamOut"].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got["ParamOut"]), want_p,
                                   rtol=tol, atol=min(tol, 1e-4))
        slots = {1: ("VelocityOut",), 2: ("Moment1Out", "Moment2Out")}
        for slot, want in zip(slots.get(_n_state(op), ()), want_state):
            assert got[slot].dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(got[slot]), want,
                                       rtol=tol, atol=tol)
        if _n_state(op) == 2:
            np.testing.assert_allclose(
                np.asarray(got["Beta1PowOut"]), HYPER["beta1"] ** (step + 1),
                rtol=1e-6)


# -- program level ------------------------------------------------------------


LR = {"sgd": 0.1, "momentum": 0.1, "adam": 1e-3, "adamw": 1e-3}


def _optimizer(op):
    if op == "momentum":
        return fluid.optimizer.MomentumOptimizer(LR[op], HYPER["mu"])
    return {"sgd": fluid.optimizer.SGD, "adam": fluid.optimizer.AdamOptimizer,
            "adamw": fluid.optimizer.AdamW}[op](LR[op])


def _build_mlp(op, sizes=(32, 16), amp=False, batch=8):
    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = SEED
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[batch, 16], dtype="float32")
        lbl = fluid.data(name="lbl", shape=[batch, 1], dtype="int64")
        h = x
        for s in sizes:
            h = fluid.layers.fc(h, size=s, act="gelu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, lbl))
        opt = _optimizer(op)
        if amp:
            from paddle_tpu.contrib import mixed_precision as mp

            opt = mp.decorate(opt)
        opt.minimize(loss)
    rng = np.random.RandomState(7)
    feed = {"x": rng.rand(batch, 16).astype("float32"),
            "lbl": rng.randint(0, 10, (batch, 1)).astype("int64")}
    return main, startup, loss, feed


def _persistables(main, scope):
    got = {}
    for v in main.global_block().vars.values():
        if not v.persistable:
            continue
        var = scope.find_var(v.name)
        if var is not None and var.is_initialized():
            got[v.name] = np.asarray(var.raw().array).copy()
    return got


def _reference_loss(params, x, lbl, amp):
    """The same MLP in jax.numpy; under AMP the matmuls take bf16
    operands, as the program's ``mul`` ops do."""
    def dense(h, w, b):
        if amp:
            return jnp.dot(h.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) + b
        return jnp.dot(h, w, precision="highest") + b

    h = x
    for w, b in zip(params[:-2:2], params[1:-2:2]):
        h = jax.nn.gelu(dense(h, w, b), approximate=False)
    logp = jax.nn.log_softmax(dense(h, params[-2], params[-1]))
    return -jnp.mean(jnp.take_along_axis(logp, lbl, axis=1))


CONFIGS = {
    "float32": dict(sizes=(32, 16)),
    "bf16_amp_master_weights": dict(sizes=(32, 16), amp=True),
    "uneven_sizes": dict(sizes=(33, 17), batch=7),
}


@pytest.mark.parametrize("config", list(CONFIGS))
@pytest.mark.parametrize("op", OPS)
def test_program_optimizer_parity(op, config):
    """Three steps through Executor.run against jax.grad of the same
    model + the plain float64 update."""
    kw = CONFIGS[config]
    amp = kw.get("amp", False)
    main, startup, loss, feed = _build_mlp(op, **kw)
    names = [p.name for p in main.all_parameters()]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        start = [np.asarray(scope.find_var(n).raw().array).copy()
                 for n in names]
        losses = [float(np.asarray(exe.run(main, feed=feed,
                                           fetch_list=[loss])[0]))
                  for _ in range(3)]
        got = [np.asarray(scope.find_var(n).raw().array) for n in names]
    # master weights stay float32 under AMP
    assert all(a.dtype == np.float32 for a in got)

    x, lbl = jnp.asarray(feed["x"]), jnp.asarray(feed["lbl"])
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda ps: _reference_loss(ps, x, lbl, amp)))
    ref = [np.float64(a) for a in start]
    state = [tuple(np.zeros_like(a) for _ in range(_n_state(op)))
             for a in ref]
    ref_losses = []
    for step in (1, 2, 3):
        value, grads = value_and_grad([jnp.asarray(a, jnp.float32)
                                       for a in ref])
        ref_losses.append(float(value))
        for i, g in enumerate(grads):
            ref[i], state[i] = plain_update(op, ref[i], np.asarray(g),
                                            state[i], LR[op], step)

    # bf16 operands: three digits; float32: rounding only
    loss_tol, delta_tol = (2e-2, 0.1) if amp else (1e-5, 1e-3)
    np.testing.assert_allclose(losses, ref_losses, rtol=loss_tol)
    for name, a0, a, r in zip(names, start, got, ref):
        want = r - np.float64(a0)
        gap = np.linalg.norm(np.float64(a) - np.float64(a0) - want)
        assert gap <= delta_tol * np.linalg.norm(want), (name, gap)


@pytest.mark.parametrize("op", OPS)
def test_restart_resumes_optimizer_state(op, tmp_path):
    """Two steps, save, a new scope and executor, reload, two more:
    bit for bit the uninterrupted four steps (weights, accumulators,
    Adam's beta powers, losses)."""
    def steps(exe, main, loss, feed, n):
        return [float(np.asarray(exe.run(main, feed=feed,
                                         fetch_list=[loss])[0]))
                for _ in range(n)]

    main, startup, loss, feed = _build_mlp(op, sizes=(33, 17))
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        want_losses = steps(exe, main, loss, feed, 4)
        want = _persistables(main, scope)

    main, startup, loss, feed = _build_mlp(op, sizes=(33, 17))
    first = fluid.Scope()
    with fluid.scope_guard(first):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        got_losses = steps(exe, main, loss, feed, 2)
        fluid.io.save_persistables(exe, str(tmp_path), main)
    second = fluid.Scope()
    with fluid.scope_guard(second):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.load_persistables(exe, str(tmp_path), main)
        got_losses += steps(exe, main, loss, feed, 2)
        got = _persistables(main, second)

    assert got_losses == want_losses
    assert set(got) == set(want)
    if op != "sgd":
        assert len(want) > len(main.all_parameters()) + 1
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
