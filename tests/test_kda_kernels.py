"""The delta rule's Pallas kernels (``ops/pallas/kda.py``) in interpret mode
on the CPU, at heads of 128 as the kernels take them: against the
recurrence run position by position, forward and every gradient, at several
chunks, with decays so strong that a chunk's cumulative sum passes -300;
the op and its gradient op on the kernels (a length that is no multiple of
the chunk, the counter's path) against the same ops in XLA einsums; what the
gradient op's state pass keeps of every chunk against one chunk at a time
made as the forward kernel makes it; what the gradient op's two kernels
hold, by their jaxprs; and which operands the kernels take."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_kda_decoder import SLOTS, keys, op, recurrence, rel  # noqa: E402

from paddle_tpu.ops import kda_ops  # noqa: E402
from paddle_tpu.ops.pallas import kda as kernels  # noqa: E402


def inputs(t, h=4, d=128, strong=False, seed=0):
    """q, k, v, the raw decay and write-strength projections, A_log,
    dt_bias: one sequence, ``h`` heads of ``d``. ``strong``: gates whose
    log-decays reach -5 a position and lower."""
    k = keys(7, seed)
    return (jax.random.normal(k[0], (1, t, h, d)),
            jax.random.normal(k[1], (1, t, h, d)),
            jax.random.normal(k[2], (1, t, h, d)),
            jax.random.normal(k[3], (1, t, h, d)) + (3.0 if strong else -2.0),
            jax.random.normal(k[4], (1, t, h)),
            jnp.log(jax.random.uniform(k[5], (h,), minval=1.0,
                                       maxval=2.0 if strong else 16.0)),
            0.1 * jax.random.normal(k[6], (h * d,)))


def on_kernels(chunk):
    def rule(q, k, v, g, beta, a_log, dt_bias):
        g, beta = kda_ops.gates(g, beta, a_log, dt_bias)
        return kernels.delta_rule(q, k, v, g, beta, chunk, True)
    return rule


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The ops take the kernels' form, as on a TPU, and the kernels run in
    interpret mode."""
    rule = kernels.delta_rule
    monkeypatch.setattr(kda_ops._fa, "compute_platform", lambda: "tpu")
    monkeypatch.setattr(kernels, "delta_rule",
                        lambda *a: rule(*a, True))


# the published chunk; the same with gates past float32's range; a chunk
# with two levels and one with five; four chunks, so that the backward reads
# what the state pass kept in reverse over more than two
@pytest.mark.parametrize("t,chunk,strong", [
    (128, 64, False), (128, 64, True), (64, 16, False), (256, 128, False),
    (256, 64, False)])
def test_the_kernels_are_the_recurrence(t, chunk, strong):
    args = inputs(t, strong=strong)
    if strong:
        g, _ = kda_ops.gates(*args[3:])
        total = jnp.cumsum(g.reshape(t // chunk, chunk, -1), 1)[:, -1]
        assert float(jnp.min(g)) < -5.0 and float(jnp.min(total)) < -300.0
        assert not bool(jnp.all(jnp.isfinite(jnp.exp(-total))))
    cot = jax.random.normal(keys(1, 9)[0], args[2].shape)

    @jax.jit
    def run(*args):
        got, got_vjp = jax.vjp(on_kernels(chunk), *args)
        want, want_vjp = jax.vjp(recurrence, *args)
        return got, want, got_vjp(cot), want_vjp(cot)

    with jax.default_matmul_precision("highest"):
        got, want, grads, ref = run(*args)
    assert got.shape == want.shape and rel(got, want) < 1e-5
    for name, a, b in zip(SLOTS, grads, ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # a_log's and dt_bias's are sums over every position of a head
        assert a.shape == b.shape and rel(a, b) < (1e-3 if strong
                                                   else 2e-4), name


def test_the_ops_on_the_kernels_are_the_ops_in_xla(as_on_a_tpu, monkeypatch):
    """100 positions in chunks of 64: the second chunk is padded; the MXU's
    operands in bf16 as under AMP."""
    from paddle_tpu import observability as obs

    args = inputs(100, seed=3)
    ins = dict(zip(SLOTS, (a.astype(jnp.bfloat16) for a in args[:3])),
               **dict(zip(SLOTS[3:], args[3:])))
    ins["Out@GRAD"] = jax.random.normal(keys(1, 5)[0], args[2].shape)
    attrs = {"chunk": 64}
    assert kda_ops.kda_path(ins["Q"], ins["V"], 64) == "pallas"
    name = "kernels.kda_chunk{path=pallas}"
    was_on = obs.enabled()
    obs.enable()
    try:
        before = obs.dump()["counters"].get(name, 0)
        got = op("kda_chunk")(ins, attrs)["Out"]
        grads = op("kda_chunk_grad")(ins, attrs)
        assert obs.dump()["counters"][name] - before == 2
    finally:
        if not was_on:
            obs.disable()
    monkeypatch.setattr(kda_ops._fa, "compute_platform", lambda: "cpu")
    assert kda_ops.kda_path(ins["Q"], ins["V"], 64) == "xla_chunked"
    want = op("kda_chunk")(ins, attrs)["Out"]
    ref = op("kda_chunk_grad")(ins, attrs)
    # both forms in bf16 against the XLA form in float32. The kernels
    # round every cotangent to bf16 before a product, as the chip does to
    # both forms; on the CPU the einsums' gradient products stay float32,
    # so the kernels are judged alone. a_log's and dt_bias's gradients are
    # sums over every position that cancel, the least exact of the seven
    exact = dict(ins, **dict(zip(SLOTS, args)))
    true = op("kda_chunk")(exact, attrs)["Out"]
    true_grads = op("kda_chunk_grad")(exact, attrs)
    assert got.dtype == want.dtype == jnp.bfloat16 and got.shape == want.shape
    assert rel(got, true) < 2e-2 and rel(want, true) < 2e-2
    for slot in SLOTS:
        a, b, c = (x[slot + "@GRAD"] for x in (grads, ref, true_grads))
        assert a.shape == b.shape and a.dtype == b.dtype, slot
        assert rel(a, c) < (0.15 if slot in ("ALog", "DtBias")
                            else 5e-2), slot


def one_chunk(q, k, v, g, beta, state):
    """(T, A, W, U0, the state that leaves) of one head's chunk, q, k, g
    [C, K], v [C, V], beta [C, 1], for the state [V, K] that enters it:
    ``_Chunk`` with both Gram matrices from one product a level, as the
    forward kernel builds it."""
    C, K = g.shape
    V = v.shape[1]

    def body(q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref, *out_refs):
        c = kernels._Chunk(q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                           beta_ref[...], *kernels._tri(C))
        c.solve(c.grams(True, True)[1])
        st = s_ref[...]
        u = c.written(st.astype(c.mxu)).astype(c.mxu)
        for ref, x in zip(out_refs, (c.T, c.A, c.W, c.U0, c.leaving(st, u))):
            ref[...] = x

    f32 = jnp.float32
    return pl.pallas_call(body, interpret=True, out_shape=[
        jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
            ((C, C), f32), ((C, C), f32), ((C, K), v.dtype), ((C, V), f32),
            ((V, K), f32))])(q, k, v, g, beta, state)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
def test_the_state_pass_keeps_what_a_chunk_is_made_of(dtype):
    """Four chunks of four heads: the entering states, ``T | A``, ``W`` and
    ``U0`` that ``kda_states`` writes without q and without ``P`` are, to
    the bit, what the forward's ``_Chunk`` makes one chunk at a time."""
    t, chunk, h, d = 256, 64, 4, 128
    q, k, v, *raw_gates = inputs(t, h, d)
    g, beta = kda_ops.gates(*raw_gates)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    entering, ta, w, u0 = kernels.states(k, v, g, beta, chunk=chunk,
                                         interpret=True)
    assert ta.shape == (1, t // chunk, h, chunk, 2 * chunk)
    assert (w.dtype, u0.dtype, entering.dtype) == (dtype, jnp.float32,
                                                   jnp.float32)
    for head in range(h):
        state = jnp.zeros((d, d), jnp.float32)
        for n in range(t // chunk):
            at = slice(n * chunk, (n + 1) * chunk)
            got = (ta[0, n, head, :, :chunk], ta[0, n, head, :, chunk:],
                   w[0, n, head], u0[0, n, head], entering[0, n, head])
            *want, after = one_chunk(
                q[0, at, head], k[0, at, head], v[0, at, head],
                g[0, at, head], beta[0, at, head, None], state)
            for name, a, b in zip(("T", "A", "W", "U0", "entering"), got,
                                  want + [state]):
                assert a.dtype == b.dtype and np.array_equal(
                    np.asarray(a, np.float32), np.asarray(b, np.float32)), \
                    (name, head, n)
            state = after
    assert float(jnp.max(jnp.abs(entering[0, -1]))) > 0.0


def kernel_calls(jaxpr, primitive="pallas_call"):
    """The equations of ``primitive`` in a jaxpr and every jaxpr inside it,
    in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found.append(eqn)
        for param in eqn.params.values():
            for inner in param if isinstance(param, (list, tuple)) \
                    else (param,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    found.extend(kernel_calls(inner, primitive))
    return found


def test_what_the_gradient_ops_kernels_hold():
    """The gradient op is two kernels. The state pass takes k, v, g and
    beta, no q, and writes what it keeps a (chunk, head), no array of the
    output's shape ``[B, T, H V]``. The backward makes no inverse, no ``A``,
    ``W`` or ``U0`` again: of float32 products at ``HIGHEST`` a head it
    holds the cumulative decays, ``tri . g``, and its own seven (``dbv``,
    ``dbKd``, two of ``dT``, two of ``dM``, ``tri^T dG``), where it held
    20; the forward op's kernel and the state pass hold the inverse's ten,
    ``W``, ``U0`` and ``tri . g``."""
    b, t, h, d, chunk = 1, 256, 2 * kernels.HEADS, 128, 64
    x = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((b, t, h, d), jnp.float32)
    beta = jax.ShapeDtypeStruct((b, t, h), jnp.float32)

    def highest_a_head(call):
        dots = kernel_calls(call.params["jaxpr"], "dot_general")
        count = sum("HIGHEST" in str(dot.params["precision"]) for dot in dots)
        assert count % kernels.HEADS == 0
        return count // kernels.HEADS

    def shapes(variables):
        return [tuple(var.aval.shape) for var in variables]

    states, bwd = kernel_calls(jax.make_jaxpr(
        lambda *a: kernels.backward(*a, chunk=chunk))(x, x, x, g, beta,
                                                      x).jaxpr)
    assert (states.params["name"], bwd.params["name"]) == ("kda_states",
                                                           "kda_bwd")
    assert highest_a_head(bwd) == 8 and highest_a_head(states) == 13
    tokens, kept = (b, t, h * d), (b, t // chunk, h)
    assert shapes(states.invars) == [tokens] * 3 + [(b, t, h)]
    assert shapes(states.outvars) == [
        kept + (d, d), kept + (chunk, 2 * chunk), kept + (chunk, d),
        kept + (chunk, d)]
    assert shapes(bwd.invars) == [tokens] * 4 + [(b, t, h), tokens] \
        + shapes(states.outvars)
    fwd, = kernel_calls(jax.make_jaxpr(
        lambda *a: kernels.forward(*a, chunk=chunk))(x, x, x, g, beta).jaxpr)
    assert fwd.params["name"] == "kda_fwd" and highest_a_head(fwd) == 13
    assert shapes(fwd.outvars) == [tokens]


def test_which_operands_the_kernels_take(as_on_a_tpu):
    def path(t, h, d, chunk, dtype=jnp.bfloat16):
        x = jax.ShapeDtypeStruct((1, t, h, d), dtype)
        return kda_ops.kda_path(x, x, chunk)

    assert path(8192, 32, 128, 64) == "pallas"      # the delta-rule cell
    assert path(8100, 32, 128, 64) == "pallas"      # padded to whole chunks
    assert path(8192, 32, 128, 64, jnp.float32) == "pallas"
    assert path(8192, 32, 64, 64) == "xla_chunked"  # half a lane tile a head
    assert path(8192, 2, 128, 64) == "xla_chunked"  # under a step's heads
    assert path(8192, 32, 128, 48) == "xla_chunked"  # no power of two
    assert path(32, 32, 128, 64) == "pallas"        # one chunk of 32
    assert path(8, 32, 128, 64) == "xla_chunked"    # under a chunk of 16
