"""Kimi Delta Attention beside latent attention without positions, at small
sizes on the CPU, against plain definitions and the benchmark's plain
reference: the chunked ``kda_chunk`` op and its gradient op against the
recurrence run position by position (several chunkings, lengths that are no
multiple of the chunk, decays so strong that a chunk's cumulative sum passes
-300, where the recurrence is untroubled and a form that exponentiates a
negated cumulative sum overflows), the triangular inverse, ``rms_norm``'s
sigmoid gate, both mixers against the reference's, the AMP rewrite's float32
slots, the tiny model through ``fluid.Executor`` with Adam against the
reference's steps, and the 32 shares of the experts against the uncut
layer."""
import hashlib
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
from paddle_tpu.ops import kda_ops
from paddle_tpu.ops.kda_ops import kda_chunk
from paddle_tpu.ops.moe_ops import moe_topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the module: the package's attribute of that name is the function
decoder = importlib.import_module("paddle_tpu.models.hybrid_ssm_moe")
SLOTS = ("Q", "K", "V", "G", "Beta", "ALog", "DtBias")


def op(name):
    return OpInfoMap.instance().get(name).fn


def keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def rel(a, b):
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


def tiny(family="kimi_linear_48b_a3b_ep32", name="tiny_kimi_linear"):
    pkg = "benchmarks.configs.%s." % family
    model = importlib.import_module(pkg + "model")
    reference = importlib.import_module(pkg + "reference")
    preset = os.path.join(ROOT, "benchmarks", "tests", "preset")
    with open(os.path.join(preset, "configs", name, "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(preset, "traffic", name + ".static.json")) as f:
        traffic = json.load(f)
    return cfg, traffic, model, reference


# -- the delta rule -----------------------------------------------------------

def recurrence(q, k, v, g, beta, a_log, dt_bias):
    """The definition, position by position: decay every channel's row of
    the state, take from ``v_t`` what the state already holds for ``k_t``,
    write ``beta_t`` of the rest along ``k_t``, read with ``q_t``."""
    g, beta = kda_ops.gates(g, beta, a_log, dt_bias)
    q = kda_ops.l2norm(q) * q.shape[-1] ** -0.5
    k = kda_ops.l2norm(k)

    def step(state, inp):
        qt, kt, vt, gt, bt = inp
        state = state * jnp.exp(gt)[..., None]
        u = bt[..., None] * (vt - jnp.einsum("bhk,bhkv->bhv", kt, state))
        state = state + kt[..., None] * u[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", qt, state)

    bsz, _, h, dk = q.shape
    zero = jnp.zeros((bsz, h, dk, v.shape[-1]))
    _, o = jax.lax.scan(step, zero, tuple(jnp.moveaxis(x, 1, 0)
                                          for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def kda_inputs(t, seed=0, strong=False):
    """q, k, v, the raw decay and write-strength projections, A_log,
    dt_bias: 2 sequences, 2 heads of 16. ``strong``: gates whose log-decays
    reach -5 a position and lower."""
    k = keys(7, seed)
    bsz, h, d = 2, 2, 16
    return (jax.random.normal(k[0], (bsz, t, h, d)),
            jax.random.normal(k[1], (bsz, t, h, d)),
            jax.random.normal(k[2], (bsz, t, h, d)),
            jax.random.normal(k[3], (bsz, t, h, d)) + (3.0 if strong
                                                       else -2.0),
            jax.random.normal(k[4], (bsz, t, h)),
            jnp.log(jax.random.uniform(k[5], (h,), minval=1.0,
                                       maxval=2.0 if strong else 16.0)),
            0.1 * jax.random.normal(k[6], (h * d,)))


def both_with_gradients(args, chunk):
    cot = jax.random.normal(keys(1, 9)[0], args[2].shape)

    @jax.jit
    def run(*args):
        got, got_vjp = jax.vjp(
            lambda *a: kda_chunk(*a, chunk=chunk), *args)
        want, want_vjp = jax.vjp(recurrence, *args)
        return got, want, got_vjp(cot), want_vjp(cot)

    with jax.default_matmul_precision("highest"):
        return run(*args)


# several chunks cut into sub-blocks, a length that is no multiple of the
# chunk, one chunk that is one sub-block, a length under a chunk, a chunk
# the sub-block does not halve
@pytest.mark.parametrize("t,chunk,sub", [(32, 8, 4), (29, 8, 4), (8, 8, 16),
                                         (5, 8, 16), (36, 12, 4)])
def test_kda_chunk_is_the_recurrence(monkeypatch, t, chunk, sub):
    monkeypatch.setattr(kda_ops, "SUB", sub)
    args = kda_inputs(t)
    got, want, grads, ref = both_with_gradients(args, chunk)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    for name, a, b in zip(SLOTS, grads, ref):
        assert a.shape == b.shape and rel(a, b) < 5e-5, name
    # the MXU's operands in bf16: the result keeps their type
    low = kda_chunk(*(a.astype(jnp.bfloat16) for a in args[:3]), *args[3:],
                    chunk=chunk)
    assert low.dtype == jnp.bfloat16 and rel(low, want) < 5e-2


def test_heads_a_pass_at_a_time_are_all_heads_at_once(monkeypatch):
    """Heads share nothing: a head a pass, each pass made again in the
    backward, gives what both heads at once give, forward and gradients."""
    monkeypatch.setattr(kda_ops, "SUB", 4)
    args = kda_inputs(29, seed=4)
    cot = jax.random.normal(keys(1, 9)[0], args[2].shape)

    def run():
        out, vjp = jax.vjp(lambda *a: kda_chunk(*a, chunk=8), *args)
        return (out,) + vjp(cot)

    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(kda_ops, "HEADS_A_PASS", 1)
        passes = jax.jit(run)()
        monkeypatch.setattr(kda_ops, "HEADS_A_PASS", 2)
        at_once = jax.jit(run)()
    for a, b in zip(passes, at_once):
        assert a.shape == b.shape and rel(a, b) < 1e-6


def test_decays_past_float32s_range_at_the_published_chunk():
    """Chunks of 64 with log-decays down to -5 a position and lower: a
    chunk's cumulative sum passes -300, ``exp`` of its negation is past
    float32, and the chunked form still is the recurrence, forward and every
    gradient, because it never makes a positive exponent."""
    args = kda_inputs(128, strong=True)
    g, _ = kda_ops.gates(*args[3:])
    assert float(jnp.min(g)) < -5.0
    total = jnp.cumsum(g.reshape(2, 2, 64, 2, 16), 2)[:, :, -1]
    assert float(jnp.min(total)) < -300.0
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-total))))
    assert kda_ops.SUB == 8
    got, want, grads, ref = both_with_gradients(args, 64)
    assert bool(jnp.all(jnp.isfinite(got))) and rel(got, want) < 1e-5
    for name, a, b in zip(SLOTS, grads, ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert rel(a, b) < 5e-4, name


def test_the_seeded_gates_alone_sum_to_minus_100_a_chunk():
    """The configuration's own ``A_log`` and ``dt_bias`` with a zero decay
    projection: up to 16 x 0.1 a position, over -88 in a chunk of 64."""
    _, _, _, reference = tiny()
    cfg = {"assumed": {"initializer_range": 0.02},
           "hybrid_override_pattern": "K", "hidden_size": 8, "vocab_size": 8,
           "num_attention_heads": 1, "qk_nope_head_dim": 1,
           "qk_rope_head_dim": 1, "v_head_dim": 1, "kv_lora_rank": 1,
           "linear_attn_config": {"num_heads": 32, "head_dim": 128,
                                  "short_conv_kernel_size": 4},
           "num_experts_held": 1, "moe_intermediate_size": 1,
           "num_shared_experts": 1, "intermediate_size": 1, "num_experts": 1}
    p = reference.init_params(jax.random.key(5), cfg)
    a, dt = np.exp(p["l0.a_log"]), np.asarray(
        jax.nn.softplus(p["l0.dt_bias"])).reshape(32, 128)
    assert 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert 64 * float((a[:, None] * dt).max()) > 88.0


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16"])
def test_the_op_and_its_gradient_op(amp):
    """``kda_chunk`` and the registered ``kda_chunk_grad`` over the op's
    slots: the gradient op is no automatic VJP, keeps nothing but the
    forward's inputs, and gives every input's gradient in its type."""
    from paddle_tpu.core import registry

    assert "kda_chunk_grad" not in registry._AUTO_VJP_TYPES
    args = kda_inputs(24, seed=3)
    if amp:
        args = tuple(a.astype(jnp.bfloat16) for a in args[:5]) + args[5:]
    ins = dict(zip(SLOTS, args))
    attrs = {"chunk": 8}
    cot = jax.random.normal(keys(1, 9)[0], args[2].shape)
    with jax.default_matmul_precision("highest"):
        out = op("kda_chunk")(ins, attrs)["Out"]
        grads = op("kda_chunk_grad")(
            dict(ins, **{"Out@GRAD": cot.astype(out.dtype)}), attrs)
        wide = tuple(a.astype(jnp.float32) for a in args)
        want, vjp = jax.vjp(recurrence, *wide)
        ref = vjp(cot)
    assert out.dtype == args[2].dtype
    assert rel(out, want) < (3e-2 if amp else 1e-5)
    for name, arg, b in zip(SLOTS, args, ref):
        got = grads[name + "@GRAD"]
        assert got.dtype == arg.dtype and got.shape == arg.shape, name
        assert rel(got, b) < (8e-2 if amp else 5e-5), name


def test_each_trace_counts_the_form_it_took():
    from paddle_tpu import observability as obs

    name = "kernels.kda_chunk{path=xla_chunked}"
    ins = dict(zip(SLOTS, kda_inputs(8)))
    was_on = obs.enabled()
    obs.enable()
    try:
        before = obs.dump()["counters"].get(name, 0)
        op("kda_chunk")(ins, {"chunk": 8})
        op("kda_chunk_grad")(dict(ins, **{"Out@GRAD": ins["V"]}),
                             {"chunk": 8})
        assert obs.dump()["counters"][name] - before == 2
    finally:
        if not was_on:
            obs.disable()


@pytest.mark.parametrize("c", [5, 8, 24, 64])
def test_the_triangular_inverse_and_its_gradient(c):
    # 5: rows alone; 8: one block; 24: pairs down to rows of 3; 64: three
    # levels of pairs
    k = keys(2, c)
    # entries of beta (k_t . k_s): under 1, a tenth or so as a rule
    low = jnp.tril(0.15 * jax.random.normal(k[0], (3, c, c)), -1)
    cot = jax.random.normal(k[1], low.shape)
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(jax.jit(kda_ops.inv_unit_lower), low)
        (grad,) = vjp(cot)
    want = np.linalg.inv(np.eye(c) + np.asarray(low, np.float64))
    assert rel(got, want) < 1e-5
    # d(M^-1) = -M^-1 dM M^-1, so the cotangent of M is -M^-T cot M^-T
    t = want.transpose(0, 2, 1)
    assert rel(grad, np.tril(-t @ np.asarray(cot, np.float64) @ t, -1)) < 1e-5


def test_the_triangular_inverse_where_powers_would_cancel():
    """Every key the same, every write whole: ``I + L`` is the all-ones
    lower triangle, whose inverse has 1 on the diagonal, -1 under it and
    nothing else; the powers of L that a doubling product would form reach
    1e17 at C = 64 and cancel to that. Forward substitution gives it to the
    digit."""
    c = 64
    ones = jnp.tril(jnp.ones((c, c)), -1)
    got = kda_ops.inv_unit_lower(ones)
    want = jnp.eye(c) - jnp.eye(c, k=-1)
    assert float(jnp.max(jnp.abs(got - want))) == 0.0


# -- rms_norm's gates ---------------------------------------------------------

def test_rms_norm_with_a_sigmoid_gate_after_the_norm():
    k = keys(3, 2)
    x = jax.random.normal(k[0], (2, 5, 4, 16))
    w = 1 + 0.1 * jax.random.normal(k[1], (16,))
    z = jax.random.normal(k[2], (2, 5, 4, 16))
    got = op("rms_norm")({"X": x, "Scale": w, "Gate": z},
                         {"epsilon": 1e-5, "gating": "sigmoid_after"})["Y"]
    v = np.asarray(x, np.float64)
    want = (v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
            * np.asarray(w) / (1 + np.exp(-np.asarray(z, np.float64))))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # the default is Mamba-2's: silu of the gate, before the norm
    silu = op("rms_norm")({"X": x, "Scale": w, "Gate": z},
                          {"epsilon": 1e-5})["Y"]
    v = v * np.asarray(z) / (1 + np.exp(-np.asarray(z, np.float64)))
    np.testing.assert_allclose(
        silu, v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
        * np.asarray(w), rtol=2e-5, atol=2e-6)
    with pytest.raises(ValueError):
        op("rms_norm")({"X": x, "Scale": w, "Gate": z}, {"gating": "tanh"})


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


def test_kda_mixer_follows_the_reference():
    cfg, _, _, reference = tiny()
    lin = cfg["linear_attn_config"]
    p, leaves = mixer_leaves(reference, cfg, 0, "K")
    # the seeded gate bias is zero: stir it, so that it is seen
    p["g_bias"] = 0.5 * jax.random.normal(keys(1, 8)[0], p["g_bias"].shape)
    leaves[reference.KINDS["K"].index("g_bias")] = p["g_bias"]
    u = jax.random.normal(jax.random.key(9), (2, 36, cfg["hidden_size"]))

    def build():
        x = fluid.data(name="u", shape=list(u.shape), dtype="float32")
        return [decoder.kda_mixer(
            x, cfg["hidden_size"], lin["num_heads"], lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"], chunk=8,
            eps=cfg["rms_norm_eps"])]

    with jax.default_matmul_precision("highest"):
        (got,) = run_program(build, {"u": np.asarray(u)}, leaves)
        want = reference.kda(u, p, cfg, jnp.matmul, lambda x: x)
    assert rel(got, want) < 2e-5


def test_latent_mixer_without_a_query_latent_and_without_positions():
    cfg, _, _, reference = tiny()
    layer = cfg["hybrid_override_pattern"].index("L")
    p, leaves = mixer_leaves(reference, cfg, layer, "L")
    u = jax.random.normal(jax.random.key(9), (2, 32, cfg["hidden_size"]))

    def build():
        x = fluid.data(name="u", shape=list(u.shape), dtype="float32")
        out = decoder.latent_mixer(
            x, cfg["hidden_size"], cfg["num_attention_heads"],
            q_rank=None, inv_freq=None, kv_rank=cfg["kv_lora_rank"],
            nope_dim=cfg["qk_nope_head_dim"],
            rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            eps=cfg["rms_norm_eps"])
        types = [o.type for o in
                 fluid.default_main_program().global_block().ops]
        # one projection to q, no latent norm of it, no rotary op
        assert "rotary_embedding" not in types
        assert types.count("rms_norm") == 1 and types.count("mul") == 4
        return [out]

    with jax.default_matmul_precision("highest"):
        (got,) = run_program(build, {"u": np.asarray(u)}, leaves)
        want = reference.latent_attention(u, p, cfg, jnp.matmul,
                                          lambda x: x)
    assert rel(got, want) < 2e-5


@pytest.mark.parametrize("recompute,ops,digest", [
    (False, 334, "26b27ab785661748664dc765ee29d6173ddc4721"),
    (True, 458, "60bdf4c24309d3c7c744569cef83865ccca355bc")])
def test_latent_mixer_with_both_given_builds_what_it_built(recompute, ops,
                                                           digest):
    """With a query latent and frequencies the mixer's program is op for op
    what it was before either could be left out (PR 42's list of the
    latent-attention configuration at toy widths, by its digest)."""
    cfg, traffic, model, _ = tiny("xing4_29b_a4b_ep8", "tiny_xing4")
    built = model.build_static(cfg, dict(traffic, recompute=recompute))
    types = [o.type for o in built["main"].global_block().ops]
    assert (len(types), hashlib.sha1(
        " ".join(types).encode()).hexdigest()) == (ops, digest)


# -- the AMP rewrite and the model --------------------------------------------

def test_amp_keeps_the_decays_leaves_float32_and_scopes_the_mixer():
    cfg, traffic, model, _ = tiny()
    block = model.build_static(cfg, traffic)["main"].global_block()

    def dtypes(op_type, slots=None):
        o = next(o for o in block.ops if o.type == op_type)
        return {slot: str(block._find_var_recursive(names[0]).dtype)
                for slot, names in {**o.inputs, **o.outputs}.items()
                if slots is None or slot in slots}

    assert dtypes("kda_chunk") == {
        "Q": "bfloat16", "K": "bfloat16", "V": "bfloat16", "G": "bfloat16",
        "Beta": "bfloat16", "ALog": "float32", "DtBias": "float32",
        "Out": "bfloat16"}
    assert dtypes("moe_topk", ("X", "RouterW", "W1", "W3")) == {
        "X": "float32", "RouterW": "float32", "W1": "bfloat16",
        "W3": "bfloat16"}
    gated = next(o for o in block.ops if o.type == "rms_norm"
                 and o.attrs.get("gating") == "sigmoid_after")
    assert {str(block._find_var_recursive(names[0]).dtype)
            for names in gated.inputs.values()} == {"float32"}
    # every op of the mixer, its gradient ops and its recomputed copies
    # carry the mixer's name scope; no other op does
    scoped = {o.type for o in block.ops
              if o.attrs.get("op_namescope") == "/kda/"}
    assert {"mul", "mul_grad", "causal_conv1d", "causal_conv1d_grad",
            "kda_chunk", "kda_chunk_grad", "rms_norm", "rms_norm_grad",
            "elementwise_add"} <= scoped
    assert not scoped & {"flash_attention", "moe_topk", "swish",
                         "lookup_table", "adam"}
    latent = {o.type for o in block.ops
              if o.attrs.get("op_namescope") == "/latent/"}
    assert "flash_attention" in latent and "rotary_embedding" not in latent


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("recompute", [False, True])
def test_tiny_model_follows_the_plain_reference(monkeypatch, amp, recompute):
    """``models.hybrid_ssm_moe`` (the delta-rule mixer, a dense layer,
    experts beside a shared one, latent attention without positions) through
    ``fluid.Executor`` with Adam, float32 and under bf16 AMP, with and
    without recomputation, against the float32 reference whose delta rule
    is the recurrence: the losses of three steps, the first gradient leaf by
    leaf, the parameters' change."""
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
    # the pattern is KDKELE: two delta-rule sublayers, one of attention;
    # the delta rule's output is a checkpoint, so the op is not run again
    assert types.count("kda_chunk") == 2 \
        and types.count("kda_chunk_grad") == 2 \
        and types.count("causal_conv1d") == 6 * (1 + recompute) \
        and types.count("flash_attention") == 1 + recompute \
        and types.count("moe_topk") == 2 * (1 + recompute)
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
    # 2 x 36 tokens x 3 slots x 4 of 8 experts = 108 expected a layer
    for load in first_loads:
        assert 60 < int(load[:4].sum()) < 160 and int(load[4]) == 0
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], lambda k: reference.init_params(k, cfg),
                 key, batches, None, identity)
    limits = (traffic["limits"] if amp else
              {"loss": 1e-5, "grad_norm": 1e-3, "delta_norm": 1e-3})
    rows = check.compare({"losses": losses, "grad_norms": grads,
                          "delta_norms": delta}, ref, limits)
    assert all(ok for *_, ok, _ in rows), rows


# -- the share and the uncut layer --------------------------------------------

def test_the_32_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """What ties one rank's share to the model: over the 32 shares of the
    256 experts (8 each, as the configuration cuts them) the routed parts
    summed, and the shared expert counted once, equal the uncut reference's
    layer."""
    cfg, _, _, reference = tiny()
    cfg = dict(cfg, num_experts=256, num_experts_per_token=8)
    e, d, f = 256, cfg["hidden_size"], cfg["moe_intermediate_size"]
    k = keys(8, 21)
    p = {"router": jax.random.normal(k[0], (d, e)),
         "gate": 0.3 * jax.random.normal(k[1], (e, d, f)),
         "up": 0.3 * jax.random.normal(k[2], (e, d, f)),
         "down": 0.3 * jax.random.normal(k[3], (e, f, d)),
         "s_w1": 0.3 * jax.random.normal(k[4], (d, f)),
         "s_w3": 0.3 * jax.random.normal(k[5], (d, f)),
         "s_w2": 0.3 * jax.random.normal(k[6], (f, d))}
    u = jax.random.normal(k[7], (1, 96, d))
    uncut = dict(cfg, first_expert_held=0, num_experts_held=e)
    with jax.default_matmul_precision("highest"):
        whole = reference.experts(u, p, uncut, jnp.matmul)[0]
        shared = reference.shared_part(u[0], p, jnp.matmul)
        parts, slots = [], 0
        for first in range(0, e, 8):
            held = slice(first, first + 8)
            out, load = moe_topk(
                u[0], p["router"], None, p["gate"][held], p["down"][held],
                8, [first, 8], cfg["routed_scaling_factor"],
                w3=p["up"][held])
            parts.append(out)
            slots += int(load[:-1].sum())
            assert int(load[-1]) == 0
    assert len(parts) == 32
    assert rel(sum(parts) + shared, whole) < 1e-5
    # every routed slot landed in exactly one share
    assert slots == 96 * 8
