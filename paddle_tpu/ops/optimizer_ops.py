"""Optimizer update ops.

Parity: /root/reference/paddle/fluid/operators/optimizers/ (sgd, momentum,
lars_momentum, adam, adamax, adagrad, decayed_adagrad, adadelta, rmsprop,
ftrl, lamb, dpsgd). Contract kept from the reference: Param/Moment inputs
are re-bound through same-named *Out outputs (is_ref), so the executor's
rebinding (and buffer donation in compiled mode) realises in-place update.
All are grad=None (never differentiated).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.registry import In, Out, register_op


def _op(name, inputs, outputs, attrs, fn):
    register_op(
        name,
        inputs=[In(i) if isinstance(i, str) else i for i in inputs],
        outputs=[Out(o, is_ref=True) for o in outputs],
        attrs=attrs,
        grad=None,
    )(fn)


def _lr(ins):
    return ins["LearningRate"].reshape(())


def _maybe_densify_grad(ins):
    """SelectedRows grad (sparse embedding path) → dense, for optimizers
    whose reference kernels have no row-wise sparse variant. Exact
    non-lazy semantics: densify accumulates duplicate rows."""
    from ..core.tensor import SelectedRows

    g = ins["Grad"]
    if isinstance(g, SelectedRows):
        ins = dict(ins)
        ins["Grad"] = g.to_dense()
    return ins


def _sgd(ins, attrs):
    from ..core.tensor import SelectedRows

    g = ins["Grad"]
    if isinstance(g, SelectedRows):
        # reference sgd_op.h SelectedRows kernel: update only the
        # touched rows (duplicates accumulate via scatter-add)
        rows = jnp.asarray(g.rows(), dtype=jnp.int32)
        vals = g.get_tensor().array
        p = ins["Param"].at[rows].add(-_lr(ins) * vals)
        return {"ParamOut": p}
    return {"ParamOut": ins["Param"] - _lr(ins) * g}


_op("sgd", ["Param", "Grad", "LearningRate"], ["ParamOut"], {}, _sgd)


def _momentum(ins, attrs):
    ins = _maybe_densify_grad(ins)
    mu = attrs.get("mu", 0.9)
    v = mu * ins["Velocity"] + ins["Grad"]
    if attrs.get("use_nesterov", False):
        p = ins["Param"] - (ins["Grad"] + mu * v) * _lr(ins)
    else:
        p = ins["Param"] - _lr(ins) * v
    return {"ParamOut": p, "VelocityOut": v}


_op(
    "momentum",
    ["Param", "Grad", "Velocity", "LearningRate"],
    ["ParamOut", "VelocityOut"],
    {"mu": 0.9, "use_nesterov": False, "regularization_method": "",
     "regularization_coeff": 0.0},
    _momentum,
)


def _lars_momentum(ins, attrs):
    mu = attrs.get("mu", 0.9)
    lars_coeff = attrs.get("lars_coeff", 0.001)
    wd = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 0.0)
    p, g, v = ins["Param"], ins["Grad"], ins["Velocity"]
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(
        (p_norm > 0) & (g_norm > 0),
        lars_coeff * p_norm / (g_norm + wd * p_norm + eps),
        jnp.ones_like(p_norm),
    )
    v_out = mu * v + _lr(ins) * local_lr * (g + wd * p)
    return {"ParamOut": p - v_out, "VelocityOut": v_out}


_op(
    "lars_momentum",
    ["Param", "Grad", "Velocity", "LearningRate"],
    ["ParamOut", "VelocityOut"],
    {"mu": 0.9, "lars_coeff": 0.001, "lars_weight_decay": 0.0005, "epsilon": 0.0},
    _lars_momentum,
)


def _adam(ins, attrs):
    ins = _maybe_densify_grad(ins)
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    p, g = ins["Param"], ins["Grad"]
    m1 = b1 * ins["Moment1"] + (1 - b1) * g
    m2 = b2 * ins["Moment2"] + (1 - b2) * jnp.square(g)
    b1pow, b2pow = ins["Beta1Pow"].reshape(()), ins["Beta2Pow"].reshape(())
    lr = _lr(ins) * jnp.sqrt(1 - b2pow) / (1 - b1pow)
    p_out = p - lr * m1 / (jnp.sqrt(m2) + eps)
    return {
        "ParamOut": p_out,
        "Moment1Out": m1,
        "Moment2Out": m2,
        "Beta1PowOut": (b1pow * b1).reshape(ins["Beta1Pow"].shape),
        "Beta2PowOut": (b2pow * b2).reshape(ins["Beta2Pow"].shape),
    }


_op(
    "adam",
    ["Param", "Grad", "LearningRate", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow"],
    ["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut", "Beta2PowOut"],
    {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8, "lazy_mode": False,
     "min_row_size_to_use_multithread": 1000},
    _adam,
)


def _adamw(ins, attrs):
    # AdamW decoupled weight decay (not in the v1.7 op set; provided for the
    # 2.0-alpha paddle.optimizer surface and BERT configs).
    out = _adam(ins, attrs)
    wd = attrs.get("weight_decay", 0.01)
    lr = _lr(ins)
    out["ParamOut"] = out["ParamOut"] - lr * wd * ins["Param"]
    return out


_op(
    "adamw",
    ["Param", "Grad", "LearningRate", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow"],
    ["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut", "Beta2PowOut"],
    {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8, "weight_decay": 0.01},
    _adamw,
)


def _adamax(ins, attrs):
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    g = ins["Grad"]
    m = b1 * ins["Moment"] + (1 - b1) * g
    inf_norm = jnp.maximum(b2 * ins["InfNorm"], jnp.abs(g))
    b1pow = ins["Beta1Pow"].reshape(())
    lr = _lr(ins) / (1 - b1pow)
    p_out = ins["Param"] - lr * m / (inf_norm + eps)
    return {"ParamOut": p_out, "MomentOut": m, "InfNormOut": inf_norm}


_op(
    "adamax",
    ["Param", "Grad", "LearningRate", "Moment", "InfNorm", "Beta1Pow"],
    ["ParamOut", "MomentOut", "InfNormOut"],
    {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
    _adamax,
)


def _adagrad(ins, attrs):
    ins = _maybe_densify_grad(ins)
    eps = attrs.get("epsilon", 1e-6)
    g = ins["Grad"]
    moment = ins["Moment"] + jnp.square(g)
    p_out = ins["Param"] - _lr(ins) * g / (jnp.sqrt(moment) + eps)
    return {"ParamOut": p_out, "MomentOut": moment}


_op(
    "adagrad",
    ["Param", "Grad", "Moment", "LearningRate"],
    ["ParamOut", "MomentOut"],
    {"epsilon": 1e-6},
    _adagrad,
)


def _decayed_adagrad(ins, attrs):
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g = ins["Grad"]
    moment = decay * ins["Moment"] + (1 - decay) * jnp.square(g)
    p_out = ins["Param"] - _lr(ins) * g / (jnp.sqrt(moment) + eps)
    return {"ParamOut": p_out, "MomentOut": moment}


_op(
    "decayed_adagrad",
    ["Param", "Grad", "Moment", "LearningRate"],
    ["ParamOut", "MomentOut"],
    {"decay": 0.95, "epsilon": 1e-6},
    _decayed_adagrad,
)


def _adadelta(ins, attrs):
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    g = ins["Grad"]
    avg_sq = rho * ins["AvgSquaredGrad"] + (1 - rho) * jnp.square(g)
    update = -jnp.sqrt((ins["AvgSquaredUpdate"] + eps) / (avg_sq + eps)) * g
    avg_upd = rho * ins["AvgSquaredUpdate"] + (1 - rho) * jnp.square(update)
    return {
        "ParamOut": ins["Param"] + update,
        "AvgSquaredGradOut": avg_sq,
        "AvgSquaredUpdateOut": avg_upd,
    }


_op(
    "adadelta",
    ["Param", "Grad", "AvgSquaredGrad", "AvgSquaredUpdate"],
    ["ParamOut", "AvgSquaredGradOut", "AvgSquaredUpdateOut"],
    {"rho": 0.95, "epsilon": 1e-6},
    _adadelta,
)


def _rmsprop(ins, attrs):
    ins = _maybe_densify_grad(ins)
    eps = attrs.get("epsilon", 1e-10)
    decay = attrs.get("decay", 0.9)
    momentum = attrs.get("momentum", 0.0)
    centered = attrs.get("centered", False)
    g = ins["Grad"]
    ms = decay * ins["MeanSquare"] + (1 - decay) * jnp.square(g)
    if centered:
        mg = decay * ins["MeanGrad"] + (1 - decay) * g
        denom = ms - jnp.square(mg) + eps
    else:
        mg = ins["MeanGrad"]
        denom = ms + eps
    mom = momentum * ins["Moment"] + _lr(ins) * g * jax.lax.rsqrt(denom)
    return {
        "ParamOut": ins["Param"] - mom,
        "MomentOut": mom,
        "MeanSquareOut": ms,
        "MeanGradOut": mg,
    }


_op(
    "rmsprop",
    ["Param", "Grad", "LearningRate", "Moment", "MeanSquare", "MeanGrad"],
    ["ParamOut", "MomentOut", "MeanSquareOut", "MeanGradOut"],
    {"epsilon": 1e-10, "decay": 0.9, "momentum": 0.0, "centered": False},
    _rmsprop,
)


def _ftrl(ins, attrs):
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr_power = attrs.get("lr_power", -0.5)
    g = ins["Grad"]
    lr = _lr(ins)
    sq_accum = ins["SquaredAccumulator"]
    lin_accum = ins["LinearAccumulator"]
    new_accum = sq_accum + jnp.square(g)
    if lr_power == -0.5:
        sigma = (jnp.sqrt(new_accum) - jnp.sqrt(sq_accum)) / lr
    else:
        sigma = (jnp.power(new_accum, -lr_power) - jnp.power(sq_accum, -lr_power)) / lr
    lin_out = lin_accum + g - sigma * ins["Param"]
    # reference ftrl_op.h shrink denominator uses 2*l2: y = sqrt/lr + 2*l2
    if lr_power == -0.5:
        x = 2.0 * l2 + jnp.sqrt(new_accum) / lr
    else:
        x = 2.0 * l2 + jnp.power(new_accum, -lr_power) / lr
    pre = jnp.clip(lin_out, -l1, l1) - lin_out
    p_out = jnp.where(jnp.abs(lin_out) > l1, pre / x, jnp.zeros_like(pre))
    return {
        "ParamOut": p_out,
        "SquaredAccumOut": new_accum,
        "LinearAccumOut": lin_out,
    }


_op(
    "ftrl",
    ["Param", "SquaredAccumulator", "LinearAccumulator", "Grad", "LearningRate"],
    ["ParamOut", "SquaredAccumOut", "LinearAccumOut"],
    {"l1": 0.0, "l2": 0.0, "lr_power": -0.5},
    _ftrl,
)


def _lamb(ins, attrs):
    b1 = attrs.get("beta1", 0.9)
    b2 = attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    p, g = ins["Param"], ins["Grad"]
    m1 = b1 * ins["Moment1"] + (1 - b1) * g
    m2 = b2 * ins["Moment2"] + (1 - b2) * jnp.square(g)
    b1pow, b2pow = ins["Beta1Pow"].reshape(()), ins["Beta2Pow"].reshape(())
    m1_hat = m1 / (1 - b1pow)
    m2_hat = m2 / (1 - b2pow)
    r = m1_hat / (jnp.sqrt(m2_hat) + eps) + wd * p
    p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
    r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
    ratio = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
    return {
        "ParamOut": p - _lr(ins) * ratio * r,
        "Moment1Out": m1,
        "Moment2Out": m2,
        "Beta1PowOut": (b1pow * b1).reshape(ins["Beta1Pow"].shape),
        "Beta2PowOut": (b2pow * b2).reshape(ins["Beta2Pow"].shape),
    }


_op(
    "lamb",
    ["Param", "Grad", "LearningRate", "Moment1", "Moment2", "Beta1Pow", "Beta2Pow"],
    ["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut", "Beta2PowOut"],
    {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-6, "weight_decay": 0.01},
    _lamb,
)


def _dpsgd(ins, attrs):
    # Differentially-private SGD (operators/optimizers/dpsgd_op.cc):
    # clip-by-norm then noised update. Noise omitted in deterministic mode.
    clip = attrs.get("clip", 10.0)
    g = ins["Grad"]
    norm = jnp.sqrt(jnp.sum(jnp.square(g)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(norm, 1e-12))
    return {"ParamOut": ins["Param"] - _lr(ins) * g * scale}


_op(
    "dpsgd",
    ["Param", "Grad", "LearningRate"],
    ["ParamOut"],
    {"clip": 10.0, "batch_size": 16.0, "sigma": 1.0, "seed": 0},
    _dpsgd,
)


def _proximal_gd(ins, attrs):
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    prox = ins["Param"] - lr * ins["Grad"]
    p_out = (
        jnp.sign(prox)
        * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
        / (1.0 + lr * l2)
    )
    return {"ParamOut": p_out}


_op(
    "proximal_gd",
    ["Param", "Grad", "LearningRate"],
    ["ParamOut"],
    {"l1": 0.0, "l2": 0.0},
    _proximal_gd,
)


def _proximal_adagrad(ins, attrs):
    """Adagrad moment + proximal soft-threshold step (reference
    optimizers/proximal_adagrad_op.h)."""
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    lr = _lr(ins)
    m_out = ins["Moment"] + jnp.square(ins["Grad"])
    prox = ins["Param"] - lr * ins["Grad"] / jnp.sqrt(m_out)
    if l1 > 0.0:
        p_out = (jnp.sign(prox)
                 * jnp.maximum(jnp.abs(prox) - lr * l1, 0.0)
                 / (1.0 + lr * l2))
    else:
        p_out = prox / (1.0 + lr * l2)
    return {"ParamOut": p_out, "MomentOut": m_out}


_op(
    "proximal_adagrad",
    ["Param", "Moment", "Grad", "LearningRate"],
    ["ParamOut", "MomentOut"],
    {"l1": 0.0, "l2": 0.0},
    _proximal_adagrad,
)


def _dgc_momentum(ins, attrs):
    """Momentum before the DGC rampup step, plain SGD after — with the
    1/nranks grad rescale dgc_op pre-multiplied (reference
    optimizers/dgc_momentum_op.h)."""
    rampup = float(attrs.get("rampup_begin_step", 0.0))
    if rampup < 0:
        # reference dgc_momentum_op.h:34: negative rampup disables the
        # whole update (early return, outputs untouched)
        return {"ParamOut": ins["Param"], "VelocityOut": ins["Velocity"],
                "Grad_out": ins["Grad"]}
    mu = attrs.get("mu", 0.9)
    nranks = ins["nranks"].reshape(()).astype(jnp.float32)
    g = ins["Grad"] / nranks
    step = ins["current_step"].reshape(()).astype(jnp.float32)
    before_rampup = step < rampup
    v = mu * ins["Velocity"] + g
    if attrs.get("use_nesterov", False):
        p_momentum = ins["Param"] - (g + mu * v) * _lr(ins)
    else:
        p_momentum = ins["Param"] - _lr(ins) * v
    p_sgd = ins["Param"] - _lr(ins) * g
    p_out = jnp.where(before_rampup, p_momentum, p_sgd)
    v_out = jnp.where(before_rampup, v, ins["Velocity"])
    return {"ParamOut": p_out, "VelocityOut": v_out, "Grad_out": g}


_op(
    "dgc_momentum",
    ["Param", "Grad", "Velocity", "LearningRate", "current_step",
     "nranks"],
    ["ParamOut", "VelocityOut", "Grad_out"],
    {"mu": 0.9, "use_nesterov": False, "rampup_begin_step": 0.0},
    _dgc_momentum,
)


def _dgc_clip_by_norm(ins, attrs):
    """clip_by_norm gated on the DGC rampup step (reference
    dgc_clip_by_norm_op.h: a no-op until current_step reaches
    rampup_begin_step)."""
    x = ins["X"]
    rampup = float(attrs.get("rampup_begin_step", 0.0))
    if rampup < 0:
        return {"Out": x}  # dgc_clip_by_norm_op.h:27 disable path
    max_norm = attrs.get("max_norm", 1.0)
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    clipped = jnp.where(norm > max_norm, x * (max_norm / norm), x)
    step = ins["current_step"].reshape(()).astype(jnp.float32)
    active = step >= rampup
    return {"Out": jnp.where(active, clipped, x)}


register_op(
    "dgc_clip_by_norm",
    inputs=[In("X"), In("current_step", no_grad=True)],
    outputs=[Out("Out")],
    attrs={"max_norm": 1.0, "rampup_begin_step": 0.0},
    grad=None,
)(_dgc_clip_by_norm)


@register_op(
    "ema_accumulate",
    inputs=[In("Param", no_grad=True), In("Shadow", no_grad=True),
            In("Decay", dispensable=True, no_grad=True)],
    outputs=[Out("ShadowOut")],
    attrs={"decay": 0.999},
)
def _ema_accumulate(ins, attrs):
    """shadow = decay*shadow + (1-decay)*param (reference
    optimizer.py:3174 ExponentialMovingAverage update block). The
    optional Decay input (a scalar var) overrides the attr — used by the
    thres_steps-adaptive schedule."""
    d = ins.get("Decay")
    if d is None:
        d = attrs.get("decay", 0.999)
    else:
        d = d.reshape(())
    return {"ShadowOut": d * ins["Shadow"] + (1.0 - d) * ins["Param"]}


@register_op(
    "ema_adaptive_decay",
    inputs=[In("ThresSteps", no_grad=True)],
    outputs=[Out("Decay")],
    attrs={"decay": 0.999},
)
def _ema_adaptive_decay(ins, attrs):
    """Step-adaptive EMA decay min(decay, (1+t)/(10+t)) — the reference
    thres_steps warm-up schedule (optimizer.py:3174)."""
    t = ins["ThresSteps"].reshape(()).astype(jnp.float32)
    d = jnp.minimum(jnp.float32(attrs.get("decay", 0.999)),
                    (1.0 + t) / (10.0 + t))
    return {"Decay": d.reshape(1)}


@register_op(
    "lookahead_update",
    inputs=[In("Param", no_grad=True), In("Slow", no_grad=True),
            In("Step", no_grad=True)],
    outputs=[Out("ParamOut"), Out("SlowOut")],
    attrs={"alpha": 0.5, "k": 5},
)
def _lookahead_update(ins, attrs):
    """Every k steps: slow += alpha*(fast-slow); fast = slow (reference
    optimizer.py:4018 Lookahead, functional select instead of cond)."""
    p, slow, step = ins["Param"], ins["Slow"], ins["Step"]
    alpha = attrs.get("alpha", 0.5)
    k = attrs.get("k", 5)
    sync = (step.reshape(()).astype(jnp.int32) % k) == 0
    slow_new = slow + alpha * (p - slow)
    return {"ParamOut": jnp.where(sync, slow_new, p),
            "SlowOut": jnp.where(sync, slow_new, slow)}


@register_op(
    "model_average_accumulate",
    inputs=[In("Param", no_grad=True), In("Sum", no_grad=True),
            In("Count", no_grad=True), In("NumUpdates", no_grad=True)],
    outputs=[Out("SumOut"), Out("CountOut")],
    attrs={"average_window": 0.15, "min_average_window": 10000,
           "max_average_window": 10000},
)
def _model_average_accumulate(ins, attrs):
    """Sliding-window parameter-sum accumulator (reference
    optimizer.py:2870 ModelAverage): when the count would exceed
    min(max_average_window, num_updates * average_window_rate), the
    window restarts at the current parameter value."""
    p, s, c = ins["Param"], ins["Sum"], ins["Count"]
    upd = ins["NumUpdates"].reshape(())
    rate = attrs.get("average_window", 0.15)
    max_w = attrs.get("max_average_window", 10000)
    min_w = attrs.get("min_average_window", 10000)
    # reference average_accumulates_op.h: restart only once the count
    # passes BOTH min_average_window and min(max_window, updates*rate)
    window = jnp.minimum(jnp.float32(max_w), upd * rate)
    c_new = c + 1.0
    cn = c_new.reshape(())
    restart = (cn >= min_w) & (cn >= window)
    sum_out = jnp.where(restart, p, s + p)
    cnt_out = jnp.where(restart, jnp.ones_like(c), c_new)
    return {"SumOut": sum_out, "CountOut": cnt_out}


@register_op(
    "dgc",
    inputs=[In("U", no_grad=True), In("V", no_grad=True),
            In("Grad", no_grad=True), In("CurrentStep", no_grad=True)],
    outputs=[Out("UOut"), Out("VOut"), Out("EncodeGrad"),
             Out("GradOut")],
    attrs={"m": 0.9, "use_nesterov": False, "sparsity": [0.999],
           "rampup_begin_step": 0.0, "rampup_step": 1.0},
    grad=None,
)
def _dgc(ins, attrs):
    """Deep gradient compression (reference dgc_op.h semantics):
    momentum correction (u = m*u + g), velocity accumulation
    (v = v + u), top-k selection by |v|; selected entries emit as the
    (dense-but-mostly-zero) EncodeGrad for the allreduce while local
    u/v zero at selected slots. On TPU the collective stays dense —
    XLA collectives have no sparse wire format — so DGC here preserves
    the ALGORITHM (delayed small-gradient accumulation), not wire
    compression."""
    m = attrs.get("m", 0.9)
    g = ins["Grad"]
    if attrs.get("use_nesterov", False):
        u = m * (ins["U"] + g)  # reference dgc_op.h:138
        v = ins["V"] + u + g
    else:
        u = m * ins["U"] + g
        v = ins["V"] + u
    step = ins["CurrentStep"].reshape(()).astype(jnp.float32)
    sparsity = [float(x) for x in attrs.get("sparsity", [0.999])] or \
        [0.999]
    begin = attrs.get("rampup_begin_step", 0.0)
    period = max(float(attrs.get("rampup_step", 1.0)), 1.0)
    # warm-up schedule (reference dgc_op GetDgcSparsity): walk the
    # sparsity list across the rampup period, then hold the last value
    prog = jnp.clip((step - begin) / period, 0.0, 1.0 - 1e-6)
    idx = (prog * len(sparsity)).astype(jnp.int32)
    s_now = jnp.asarray(sparsity)[idx]
    in_rampup = step < begin
    flat = jnp.abs(v).reshape(-1)
    # dynamic sparsity -> dynamic k is not traceable; use the quantile
    # of |v| as the selection threshold instead of an exact top-k
    thresh = jnp.quantile(flat, s_now)
    mask = (jnp.abs(v) >= thresh) | in_rampup  # no compression pre-rampup
    encoded = jnp.where(mask, v, 0.0)
    return {"UOut": jnp.where(mask, 0.0, u),
            "VOut": jnp.where(mask, 0.0, v),
            "EncodeGrad": encoded,
            "GradOut": encoded}
