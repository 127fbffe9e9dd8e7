"""The doubly gated short convolution beside rotary grouped-query attention
with q/k head norms, a tied head and ``moe_topk``'s epsilon, at small sizes
on the CPU, against plain definitions and the benchmark's plain reference:
the ``short_conv_gate`` op and its gradient op against three shifted
products, both mixers against the reference's, the table's gradient as the
sum of both uses, the tiny model through ``fluid.Executor`` with Adam against
the reference's steps, and the eight shares of the experts against the uncut
layer."""
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.moe_ops import moe_topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the module: the package's attribute of that name is the function
decoder = importlib.import_module("paddle_tpu.models.hybrid_ssm_moe")


def op(name):
    return OpInfoMap.instance().get(name).fn


def keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def rel(a, b):
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


def tiny(**changed):
    pkg = "benchmarks.configs.lfm2_24b_a2b_ep8."
    model = importlib.import_module(pkg + "model")
    reference = importlib.import_module(pkg + "reference")
    preset = os.path.join(ROOT, "benchmarks", "tests", "preset")
    with open(os.path.join(preset, "configs", "tiny_lfm2",
                           "config.json")) as f:
        cfg = dict(json.load(f), **changed)
    with open(os.path.join(preset, "traffic", "tiny_lfm2.static.json")) as f:
        traffic = json.load(f)
    return cfg, traffic, model, reference


# -- the op -------------------------------------------------------------------

def three_shifted_products(x, w):
    """The definition, in float64 numpy: ``C * sum_j w[:, j] (B * z) shifted
    by 2 - j``, zeros before position 0."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    c = w.shape[0]
    b, gate, z = x[..., :c], x[..., c:2 * c], x[..., 2 * c:]
    bz = b * z
    y = np.zeros_like(bz)
    for j in range(w.shape[1]):
        shift = w.shape[1] - 1 - j
        y[:, shift:] += bz[:, :bz.shape[1] - shift] * w[:, j]
    return gate * y


@pytest.mark.parametrize("low", [False, True], ids=["float32", "bf16"])
@pytest.mark.parametrize("t,taps", [(13, 3), (1, 3), (2, 3), (37, 4)])
def test_the_op_and_its_gradient_op(t, taps, low):
    """``short_conv_gate`` and the registered ``short_conv_gate_grad``: the
    gradient op is no automatic VJP, keeps nothing but the forward's inputs
    and gives both inputs' gradients in their types; T is no multiple of
    anything, and shorter than the taps."""
    from paddle_tpu.core import registry

    assert "short_conv_gate_grad" not in registry._AUTO_VJP_TYPES
    k = keys(3, t)
    c = 8
    x = jax.random.normal(k[0], (2, t, 3 * c))
    w = jax.random.normal(k[1], (c, taps))
    cot = jax.random.normal(k[2], (2, t, c))
    if low:
        x, cot = x.astype(jnp.bfloat16), cot.astype(jnp.bfloat16)
    out = op("short_conv_gate")({"X": x, "W": w}, {})["Out"]
    grads = op("short_conv_gate_grad")({"X": x, "W": w, "Out@GRAD": cot}, {})
    wide = x.astype(jnp.float32)
    assert out.dtype == x.dtype and out.shape == (2, t, c)
    assert rel(out, three_shifted_products(wide, w)) < (1e-2 if low else 1e-6)

    def plain(x, w):
        bz = jnp.pad(x[..., :c] * x[..., 2 * c:],
                     ((0, 0), (taps - 1, 0), (0, 0)))
        return x[..., c:2 * c] * sum(bz[:, j:j + t] * w[:, j]
                                     for j in range(taps))

    want = jax.vjp(plain, wide, w)[1](cot.astype(jnp.float32))
    for name, arg, b in zip(("X", "W"), (x, w), want):
        got = grads[name + "@GRAD"]
        assert got.dtype == arg.dtype and got.shape == arg.shape, name
        assert rel(got, b) < (2e-2 if low else 1e-5), name


def test_each_trace_counts_the_op():
    from paddle_tpu import observability as obs

    name = "kernels.short_conv_gate"
    x, w = jnp.ones((1, 4, 6)), jnp.ones((2, 3))
    was_on = obs.enabled()
    obs.enable()
    try:
        before = obs.dump()["counters"].get(name, 0)
        op("short_conv_gate")({"X": x, "W": w}, {})
        assert obs.dump()["counters"][name] - before == 1
    finally:
        if not was_on:
            obs.disable()


def test_the_layer_refuses_what_is_no_three_streams():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[1, 4, 8], dtype="float32")
        with pytest.raises(ValueError):
            fluid.layers.short_conv_gate(x)


# -- the router's epsilon -----------------------------------------------------

@pytest.mark.parametrize("eps", [None, 1e-6, 0.5])
def test_moe_topk_takes_the_sums_epsilon(eps):
    """The chosen scores over ``their sum + route_eps``; without the attr
    the op's own 1e-20, as every other configuration's program has it."""
    k = keys(2, 5)
    x, router = jax.random.normal(k[0], (6, 8)), jax.random.normal(k[1],
                                                                   (8, 5))
    more = {} if eps is None else {"route_eps": eps}
    _, w = moe_ops.route(x, router, None, 2, 1.0, True, **more)
    s = np.sort(np.asarray(jax.nn.sigmoid(x @ router)), -1)[:, ::-1][:, :2]
    want = s / (s.sum(-1, keepdims=True) + (eps or 1e-20))
    np.testing.assert_allclose(w, want, rtol=1e-5)
    assert moe_ops.ROUTE_EPS == 1e-20
    assert OpInfoMap.instance().get("moe_topk").attrs["route_eps"] == 1e-20


def test_the_layer_passes_the_epsilon_only_where_it_is_given():
    def attrs(**more):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data(name="x", shape=[6, 8], dtype="float32")
            fluid.layers.moe_topk(x, 4, 2, 8, **more)
        return next(o for o in main.global_block().ops
                    if o.type == "moe_topk").attrs

    assert "route_eps" not in attrs()
    assert attrs(route_eps=1e-6)["route_eps"] == 1e-6


# -- the mixers ---------------------------------------------------------------

def run_program(build, feeds, leaves):
    """Build ``build()`` -> outputs, set the parameters from ``leaves`` (in
    the order the program created them), run once on the CPU."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        outs = build()
    names = [p.name for p in main.all_parameters()]
    assert len(names) == len(leaves)
    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        for name, value in zip(names, leaves):
            tensor = scope.find_var(name).get_tensor()
            assert tuple(tensor.array.shape) == tuple(value.shape), name
            tensor.set(np.asarray(value))    # a copy: the run may donate it
        return exe.run(main, feed=feeds, fetch_list=list(outs))


def mixer_leaves(reference, cfg, layer, kind, seed=2):
    params = reference.init_params(jax.random.key(seed), cfg)
    prefix = "l%d." % layer
    p = {k[len(prefix):]: v for k, v in params.items()
         if k.startswith(prefix)}
    return p, [p[leaf] for leaf in reference.KINDS[kind]]


def test_short_conv_mixer_follows_the_reference():
    cfg, _, _, reference = tiny()
    p, leaves = mixer_leaves(reference, cfg, 0, "C")
    u = jax.random.normal(jax.random.key(9), (2, 37, cfg["hidden_size"]))

    def build():
        x = fluid.data(name="u", shape=list(u.shape), dtype="float32")
        out = decoder.short_conv_mixer(x, cfg["hidden_size"],
                                       cfg["conv_L_cache"])
        types = [o.type for o in
                 fluid.default_main_program().global_block().ops]
        # one projection in, the op, one projection out: nothing is sliced
        assert types == ["mul", "short_conv_gate", "mul"]
        return [out]

    with jax.default_matmul_precision("highest"):
        (got,) = run_program(build, {"u": np.asarray(u)}, leaves)
        want = reference.short_conv(u, p, cfg, jnp.matmul, lambda x: x)
    assert rel(got, want) < 2e-5


def test_gqa_mixer_with_head_norms_and_rotary_at_heads_of_64():
    """4 query heads over 2 K/V heads of 64, an RMS norm with a stirred
    weight on every q and k head, rotary positions at theta 1e6, against the
    reference's dense masked softmax."""
    cfg, _, _, reference = tiny(hidden_size=256)
    assert reference.head_dim(cfg) == 64
    layer = cfg["hybrid_override_pattern"].index("*")
    p, leaves = mixer_leaves(reference, cfg, layer, "*")
    for i, leaf in enumerate(reference.KINDS["*"]):
        if leaf.endswith("_norm"):   # seeded as ones: stir, so it is seen
            p[leaf] = 1 + 0.3 * jax.random.normal(keys(1, i)[0],
                                                  p[leaf].shape)
            leaves[i] = p[leaf]
    u = jax.random.normal(jax.random.key(9), (2, 40, cfg["hidden_size"]))

    def build():
        x = fluid.data(name="u", shape=list(u.shape), dtype="float32")
        out = decoder.gqa_mixer(
            x, cfg["hidden_size"], 4, 2, 64, qk_norm_eps=cfg["norm_eps"],
            rope_theta=cfg["rope_parameters"]["rope_theta"])
        block = fluid.default_main_program().global_block()
        types = [o.type for o in block.ops]
        assert types.count("rms_norm") == 2 \
            and types.count("rotary_embedding") == 2
        return [out]

    with jax.default_matmul_precision("highest"):
        (got,) = run_program(build, {"u": np.asarray(u)}, leaves)
        want = reference.attention(u, p, cfg, jnp.matmul, lambda x: x)
    assert rel(got, want) < 2e-5


def test_gqa_mixer_without_either_builds_what_it_built():
    """No norm and no positions unless they are asked for: the ops of the
    attention letter of every other configuration."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="u", shape=[2, 8, 32], dtype="float32")
        decoder.gqa_mixer(x, 32, 4, 2, 8)
    assert not any(o.attrs.get("op_namescope")
                   for o in main.global_block().ops)
    assert [o.type for o in main.global_block().ops] == [
        "mul", "reshape2", "transpose2", "mul", "reshape2", "transpose2",
        "mul", "reshape2", "transpose2", "flash_attention", "transpose2",
        "reshape2", "mul"]


# -- the tied head ------------------------------------------------------------

@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("recompute", [False, True])
def test_the_tied_tables_gradient_is_the_sum_of_both_uses(amp, recompute):
    """One parameter, used by the lookup and by the head: its gradient as
    Adam gets it is ``jax.grad`` of the same function of ONE table, which is
    the sum of the lookup's scatter and the head's product."""
    from paddle_tpu.contrib import mixed_precision as mp

    v, d, b, t = 24, 16, 2, 6
    k = keys(3, 11)
    table = 0.5 * jax.random.normal(k[0], (v, d))
    ids = jax.random.randint(k[1], (b, t), 0, v)
    labels = jax.random.randint(k[2], (b * t, 1), 0, v)
    checkpoints = []
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        src = fluid.data(name="src", shape=[b, t], dtype="int64")
        lab = fluid.data(name="labels", shape=[b * t, 1], dtype="int64")
        logits = decoder.hybrid_ssm_moe(src, "", v, d, tied_head=True,
                                        checkpoints=checkpoints)
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, [b * t, v]), lab))
        optimizer = fluid.optimizer.AdamOptimizer(1e-3, beta1=0.9)
        if recompute:
            optimizer = fluid.optimizer.RecomputeOptimizer(optimizer)
            optimizer._set_checkpoints(checkpoints)
        (mp.decorate(optimizer) if amp else optimizer).minimize(loss)
    params = [p.name for p in main.all_parameters()]
    assert len(params) == 2 and params[0].startswith("tied_embedding")
    types = [o.type for o in main.global_block().ops]
    assert "matmul" not in types and types.count("lookup_table") == 1
    # both uses' gradients meet in one sum before Adam
    assert types.count("sum") >= 1 and types.count("adam") == 2

    def plain(table):
        return plain_parts(table, table, ids, labels, b, t, d)

    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    with jax.default_matmul_precision("highest"), fluid.scope_guard(scope):
        exe.run(startup)
        scope.find_var(params[0]).get_tensor().set(np.asarray(table))
        (got,) = exe.run(main, feed={"src": np.asarray(ids, np.int64),
                                     "labels": np.asarray(labels, np.int64)},
                         fetch_list=[loss])
        moment = np.asarray(scope.find_var(
            params[0] + "_moment1_0").get_tensor().array)
        want, grad = jax.value_and_grad(plain)(table)
    assert float(np.mean(got)) == pytest.approx(float(want),
                                                rel=2e-2 if amp else 1e-5)
    assert rel(moment / (1 - 0.9), grad) < (5e-2 if amp else 1e-5)
    # and it is neither use alone: the head's part is most of it
    lookup_only = jax.grad(lambda tb: plain_parts(tb, table, ids, labels,
                                                  b, t, d))(table)
    assert rel(moment / (1 - 0.9), lookup_only) > 0.3


def plain_parts(lookup_table, head_table, ids, labels, b, t, d):
    """The same loss with the lookup's table and the head's held apart."""
    x = lookup_table[ids]
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
    logp = jax.nn.log_softmax(x.reshape(b * t, d) @ head_table.T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels, -1))


# -- the AMP rewrite and the model --------------------------------------------

def test_amp_keeps_the_streams_low_and_scopes_the_convolution():
    cfg, traffic, model, _ = tiny()
    block = model.build_static(cfg, traffic)["main"].global_block()

    def dtypes(op_type, slots=None):
        o = next(o for o in block.ops if o.type == op_type)
        return {slot: str(block._find_var_recursive(names[0]).dtype)
                for slot, names in {**o.inputs, **o.outputs}.items()
                if slots is None or slot in slots}

    # the streams as the projection leaves them; the taps stay float32
    assert dtypes("short_conv_gate") == {"X": "bfloat16", "W": "float32",
                                         "Out": "bfloat16"}
    assert dtypes("moe_topk", ("X", "RouterW", "W1", "W3")) == {
        "X": "float32", "RouterW": "float32", "W1": "bfloat16",
        "W3": "bfloat16"}
    assert next(o for o in block.ops
                if o.type == "moe_topk").attrs["route_eps"] == 1e-6
    # the head norms and the rotary are float32; the kernels' q, k are low
    for o in block.ops:
        if o.type in ("rotary_embedding",) or (
                o.type == "rms_norm"
                and len(block._find_var_recursive(
                    o.inputs["X"][0]).shape) == 4):
            assert {str(block._find_var_recursive(n[0]).dtype)
                    for n in o.inputs.values()} == {"float32"}, o.type
    assert dtypes("flash_attention", ("Q", "K", "V")) == {
        "Q": "bfloat16", "K": "bfloat16", "V": "bfloat16"}
    # every op of the convolution mixer, its gradient ops and its recomputed
    # copies carry the mixer's name scope; no other op does
    conv = {o.type for o in block.ops
            if o.attrs.get("op_namescope") == "/shortconv/"}
    assert conv == {"mul", "mul_grad", "short_conv_gate",
                    "short_conv_gate_grad"}
    assert {o.attrs.get("op_namescope") for o in block.ops
            if o.type.startswith(("flash_attention", "rotary_embedding",
                                  "moe_topk", "lookup_table", "adam"))
            } <= {None, "", "/"}


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("recompute", [False, True])
def test_tiny_model_follows_the_plain_reference(monkeypatch, amp, recompute):
    """``models.hybrid_ssm_moe`` (the gated short convolution, a dense
    layer, rotary attention with head norms, experts with the source's
    epsilon, a tied head) through ``fluid.Executor`` with Adam, float32 and
    under bf16 AMP, with and without recomputation, against the float32
    reference: the losses of three steps, the first gradient leaf by leaf,
    the parameters' change."""
    from benchmarks.lib import check
    from benchmarks.lib.reference_train import follow, identity
    from paddle_tpu.contrib import mixed_precision as mp

    cfg, traffic, model, reference = tiny()
    if not amp:
        monkeypatch.setattr(mp, "decorate", lambda optimizer: optimizer)
    loads = []
    built = model.build_static(cfg, dict(traffic, recompute=recompute), loads)
    types = [o.type for o in built["main"].global_block().ops]
    assert ("recompute_barrier" in types) == recompute
    assert ("cast" in types) == amp
    # the pattern is CD*ECE: two convolution sublayers, one of attention
    assert types.count("short_conv_gate") == 2 * (1 + recompute) \
        and types.count("short_conv_gate_grad") == 2 \
        and types.count("flash_attention") == 1 + recompute \
        and types.count("moe_topk") == 2 * (1 + recompute) \
        and "matmul" not in types and "slice" not in types
    assert list(built["leaves"]) == list(reference.leaf_shapes(cfg))
    assert "head" not in built["leaves"]
    key = jax.random.key(3)
    start = reference.init_params(key, cfg)
    kept = {k: np.asarray(v) for k, v in start.items()}
    batches = [reference.make_batch(k, cfg, traffic) for k in keys(3, 4)]
    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    losses = []
    with jax.default_matmul_precision("highest"), fluid.scope_guard(scope):
        exe.run(built["startup"])

        def array(name):
            return jnp.asarray(scope.find_var(name).get_tensor().array)

        for leaf, name in built["leaves"].items():
            assert array(name).shape == kept[leaf].shape, leaf
            scope.find_var(name).get_tensor().set(start[leaf])
        for i, batch in enumerate(batches):
            feed = {k: np.asarray(v) for k, v in model.to_feed(batch).items()}
            out = exe.run(built["main"], feed=feed,
                          fetch_list=[built["loss"]] + loads)
            losses.append(float(np.mean(out[0])))
            if i == 0:
                first_loads = out[1:]
                grads = {leaf: built["moment_scale"] * float(jnp.linalg.norm(
                    array(built["moment"] % name)))
                    for leaf, name in built["leaves"].items()}
        delta = {leaf: float(jnp.linalg.norm(array(name) - kept[leaf]))
                 for leaf, name in built["leaves"].items()}
    # 2 x 36 tokens x 4 slots x 4 of 16 experts = 72 expected a layer
    for load in first_loads:
        assert 36 < int(load[:4].sum()) < 120 and int(load[4]) == 0
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], lambda k: reference.init_params(k, cfg),
                 key, batches, None, identity)
    limits = (traffic["limits"] if amp else
              {"loss": 1e-5, "grad_norm": 1e-3, "delta_norm": 1e-3})
    rows = check.compare({"losses": losses, "grad_norms": grads,
                          "delta_norms": delta}, ref, limits)
    assert all(ok for *_, ok, _ in rows), rows


def test_the_real_configuration_holds_469_million_parameters():
    import math

    _, _, _, reference = tiny()
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2_24b_a2b_ep8",
                           "config.json")) as f:
        cfg = json.load(f)
    leaves = reference.leaf_shapes(cfg)
    assert sum(math.prod(s) for s in leaves.values()) == 469_284_992
    assert "head" not in leaves and leaves["emb"] == (8192, 2048)
    assert leaves["l0.in_w"] == (2048, 6144) and leaves["l0.taps"] == (2048, 3)
    assert leaves["l2.q_norm"] == (64,) and leaves["l2.k"] == (2048, 512)


# -- the share and the uncut layer --------------------------------------------

def test_the_8_shares_are_the_uncut_layer():
    """What ties one rank's share to the model: over the 8 shares of the 64
    experts (8 each, as the configuration cuts them; top-4, a bias of zeros,
    the sum's epsilon 1e-6) the routed parts summed equal the uncut
    reference's layer. There is no shared expert, so nothing is counted
    once."""
    cfg, _, _, reference = tiny(num_experts=64, num_experts_per_tok=4)
    e, d, f = 64, cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = keys(5, 21)
    p = {"router": jax.random.normal(k[0], (d, e)),
         "gate": 0.3 * jax.random.normal(k[1], (e, d, f)),
         "up": 0.3 * jax.random.normal(k[2], (e, d, f)),
         "down": 0.3 * jax.random.normal(k[3], (e, f, d))}
    u = jax.random.normal(k[4], (1, 96, d))
    uncut = dict(cfg, first_expert_held=0, num_experts_held=e)
    eps = cfg["assumed"]["route_eps"]
    assert eps == 1e-6
    with jax.default_matmul_precision("highest"):
        whole = reference.experts(u, p, uncut, jnp.matmul)[0]
        parts, slots = [], 0
        for first in range(0, e, 8):
            held = slice(first, first + 8)
            out, load = moe_topk(
                u[0], p["router"], jnp.zeros((e,)), p["gate"][held],
                p["down"][held], 4, [first, 8],
                cfg["routed_scaling_factor"], w3=p["up"][held], route_eps=eps)
            parts.append(out)
            slots += int(load[:-1].sum())
            assert int(load[-1]) == 0
    assert len(parts) == 8
    assert rel(sum(parts), whole) < 1e-5
    # every routed slot landed in exactly one share
    assert slots == 96 * 4
