"""Follows the first training steps with a configuration's plain reference.

Generic over configurations: it is given the reference's ``loss`` and runs
plain Adam or Momentum (the update rules of the published optimizers, as the
configuration's ``optimizer`` states them) in float32 with every matrix
multiplication at ``highest`` precision. A batch too large for one pass is
taken in blocks of rows whose losses and gradients are averaged, which is the
same mathematics wherever the loss is a mean over rows.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp


def identity(x):
    return x


def narrow_cast(dtype_name):
    """Operand rounding of a control: a round trip through the float type
    ``dtype_name`` with one scale for the tensor (its largest magnitude lands
    on the type's largest), as an fp8 matrix multiplication is fed in
    practice."""
    dtype = jnp.dtype(dtype_name)
    top = float(jnp.finfo(dtype).max)

    def cast(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        y = x / scale
        return jax.lax.stop_gradient(scale) * _straight_through(
            y, y.astype(dtype).astype(jnp.float32))

    return cast


def _straight_through(x, rounded):
    """``rounded`` forward, the identity's gradient backward: rounding has no
    useful derivative."""
    return x + jax.lax.stop_gradient(rounded - x)


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def diff_norms(a, b):
    return leaf_norms({k: a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
                       for k in a})


def _update(opt, params, grads, state, step):
    kind = opt["type"]
    lr = opt["learning_rate"]
    if kind == "adam":
        b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
        m1 = {k: b1 * state["m1"][k] + (1 - b1) * grads[k] for k in params}
        m2 = {k: b2 * state["m2"][k] + (1 - b2) * jnp.square(grads[k])
              for k in params}
        lr_t = lr * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)
        new = {k: params[k] - lr_t * m1[k] / (jnp.sqrt(m2[k]) + eps)
               for k in params}
        return new, {"m1": m1, "m2": m2}
    if kind == "momentum":
        vel = {k: opt["momentum"] * state["v"][k] + grads[k] for k in params}
        return {k: params[k] - lr * vel[k] for k in params}, {"v": vel}
    raise ValueError("reference has no optimizer %r" % kind)


def _init_state(opt, params):
    def zeros():   # fresh buffers each time: the step donates its state
        return {k: jnp.zeros_like(v) for k, v in params.items()}

    if opt["type"] == "adam":
        return {"m1": zeros(), "m2": zeros()}
    return {"v": zeros()}


def follow(loss_fn, optimizer, params, batches, rows_per_block=None,
           cast=identity):
    """Train ``len(batches)`` steps from ``params``. Returns the loss of each
    step, the per-leaf norm of the first step's gradient and the per-leaf norm
    of the parameters' change after the last, as Python floats."""

    def one_step(params, state, batch, step):
        rows = next(iter(batch.values())).shape[0]
        rb = rows_per_block or rows
        nb = rows // rb
        blocks = {k: v.reshape((nb, rb) + v.shape[1:])
                  for k, v in batch.items()}

        def body(carry, block):
            value, grads = jax.value_and_grad(
                lambda p: loss_fn(p, block, cast))(params)
            return (carry[0] + value,
                    {k: carry[1][k] + grads[k] for k in grads}), None

        zero = (jnp.zeros((), jnp.float32),
                {k: jnp.zeros_like(v) for k, v in params.items()})
        (value, grads), _ = jax.lax.scan(body, zero, blocks)
        value = value / nb
        grads = {k: g / nb for k, g in grads.items()}
        new, state = _update(optimizer, params, grads, state, step)
        return value, leaf_norms(grads), new, state

    with jax.default_matmul_precision("highest"):
        step_fn = jax.jit(one_step, donate_argnums=(1,))
        state = _init_state(optimizer, params)
        start = params
        losses, first_norms = [], None
        for i, batch in enumerate(batches):
            value, norms, params, state = step_fn(
                params, state, batch, jnp.float32(i + 1))
            losses.append(float(value))
            if first_norms is None:
                first_norms = {k: float(v) for k, v in norms.items()}
        delta = jax.jit(diff_norms)(params, start)
        delta = {k: float(v) for k, v in delta.items()}
    return {"losses": losses, "grad_norms": first_norms,
            "delta_norms": delta}
