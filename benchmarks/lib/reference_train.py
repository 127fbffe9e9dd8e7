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
    def zeros():   # fresh buffers each time: the update donates its state
        return {k: jnp.zeros_like(v) for k, v in params.items()}

    if opt["type"] == "adam":
        return {"m1": zeros(), "m2": zeros()}
    return {"v": zeros()}


def _at_highest(fn):
    """``fn`` traced with every matrix multiplication at ``highest``."""
    def traced(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)

    return traced


def change_program(init_fn):
    """Jitted ``(params, key) -> per-leaf norm of params - init_fn(key)``. The
    starting point is made again from the key inside the program that
    subtracts it, so that no copy of it is kept while the steps run."""
    return jax.jit(_at_highest(
        lambda params, key: diff_norms(params, init_fn(key))))


def programs(loss_fn, optimizer, init_fn, rows_per_block=None, cast=identity):
    """The jitted programs ``follow`` runs, by name. Between them they hold
    four float32 copies of the parameter tree at most: the weights, the
    optimizer's state (two moments or one velocity) and the gradient. A batch
    of one block of rows takes ``gradient``; a batch of several takes ``zero``,
    then ``accumulate`` once a block, which adds to the sum in place, then
    ``mean``. ``update`` writes weights and state in place; ``change`` makes
    the starting point again as it subtracts it.
    ``benchmarks/aot_sizing.py --reference`` prints what the TPU's compiler
    makes of each."""

    def start(key):
        params = init_fn(key)
        return params, _init_state(optimizer, params)

    def value_and_grad(params, block):
        return jax.value_and_grad(lambda p: loss_fn(p, block, cast))(params)

    def gradient(params, batch):
        value, grads = value_and_grad(params, batch)
        return value, leaf_norms(grads), grads

    def zero(params):
        return (jnp.zeros((), jnp.float32),
                {k: jnp.zeros_like(v) for k, v in params.items()})

    def accumulate(params, total, batch, index):
        value, grads = value_and_grad(params, {
            k: jax.lax.dynamic_slice_in_dim(v, index * rows_per_block,
                                            rows_per_block)
            for k, v in batch.items()})
        return total[0] + value, {k: total[1][k] + grads[k] for k in grads}

    def mean(total, blocks):
        grads = {k: g / blocks for k, g in total[1].items()}
        return total[0] / blocks, leaf_norms(grads), grads

    def update(params, state, grads, step):
        return _update(optimizer, params, grads, state, step)

    return {"start": jax.jit(_at_highest(start)),
            "gradient": jax.jit(_at_highest(gradient)),
            "zero": jax.jit(zero),
            "accumulate": jax.jit(_at_highest(accumulate),
                                  donate_argnums=(1,)),
            "mean": jax.jit(mean, static_argnums=(1,), donate_argnums=(0,)),
            "update": jax.jit(update, donate_argnums=(0, 1)),
            "change": change_program(init_fn)}


def block_count(batch, rows_per_block):
    """How many blocks of ``rows_per_block`` rows the batch is taken in."""
    rows = next(iter(batch.values())).shape[0]
    if rows_per_block and rows % rows_per_block:
        raise ValueError("%d rows are not whole blocks of %d"
                         % (rows, rows_per_block))
    return rows // (rows_per_block or rows)


def follow(loss_fn, optimizer, init_fn, key, batches, rows_per_block=None,
           cast=identity):
    """Train ``len(batches)`` steps from ``init_fn(key)``. Returns the loss of
    each step, the per-leaf norm of the first step's gradient and the per-leaf
    norm of the parameters' change after the last, as Python floats."""
    run = programs(loss_fn, optimizer, init_fn, rows_per_block, cast)
    params, state = run["start"](key)
    losses, first_norms = [], None
    for i, batch in enumerate(batches):
        blocks = block_count(batch, rows_per_block)
        if blocks == 1:
            value, norms, grads = run["gradient"](params, batch)
        else:
            total = run["zero"](params)
            for b in range(blocks):
                total = run["accumulate"](params, total, batch, jnp.int32(b))
            value, norms, grads = run["mean"](total, blocks)
            del total
        params, state = run["update"](params, state, grads,
                                      jnp.float32(i + 1))
        del grads
        # a program's outputs are allocated when it is enqueued: the next
        # gradient would lie beside this one, which the update still reads
        jax.block_until_ready(params)
        losses.append(float(value))
        if first_norms is None:
            first_norms = {k: float(v) for k, v in norms.items()}
    del state
    delta = {k: float(v) for k, v in run["change"](params, key).items()}
    return {"losses": losses, "grad_norms": first_norms,
            "delta_norms": delta}
