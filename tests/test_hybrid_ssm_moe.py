"""The ops of the hybrid state-space / mixture-of-experts model against
plain definitions, at small sizes on the CPU: ``rms_norm``,
``causal_conv1d``, ``ssd_chunk_scan`` and its gradient op against the
literal recurrence (the XLA form, and the Pallas kernels in interpret mode
with what decides between them), ``moe_topk`` against a dense loop over all experts
(shares add up, full skew, no held expert), grouped-query heads on the
streaming ``flash_attention`` kernels in interpret mode, the AMP rewrite's
float32 slots, and the tiny model through ``fluid.Executor`` with AMP and
Adam against the benchmark's plain reference."""
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
from paddle_tpu.ops.moe_ops import buffer_rows, moe_topk
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import ssd_scan
from paddle_tpu.ops.ssm_ops import ssd_chunk_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def op(name):
    return OpInfoMap.instance().get(name).fn


def keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def rel(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


# -- rms_norm, causal_conv1d --------------------------------------------------

@pytest.mark.parametrize("groups,gated", [(1, False), (4, False), (4, True)])
def test_rms_norm(groups, gated):
    k = keys(3)
    x = jax.random.normal(k[0], (2, 5, 32))
    w = 1 + 0.1 * jax.random.normal(k[1], (32,))
    z = jax.random.normal(k[2], (2, 5, 32)) if gated else None
    got = op("rms_norm")({"X": x, "Scale": w, "Gate": z},
                         {"epsilon": 1e-5, "groups": groups})["Y"]
    v = np.asarray(x if z is None else x * z / (1 + np.exp(-np.asarray(z))),
                   np.float64).reshape(2, 5, groups, -1)
    want = (v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(2, 5, 32) * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # a bf16 input keeps its type, the statistics are float32 inside
    low = op("rms_norm")({"X": x.astype(jnp.bfloat16), "Scale": w,
                          "Gate": z}, {"groups": groups})["Y"]
    assert low.dtype == jnp.bfloat16 and rel(low, want) < 2e-2


@pytest.mark.parametrize("T", [9, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("act", ["", "silu"], ids=["linear", "silu"])
def test_causal_conv1d_and_its_gradient(act, bias, dtype, T):
    """The op and its gradient op (registered, not an automatic VJP; it
    reads the forward's inputs and the cotangent) against the definition
    position by position and ``jax.grad`` of it, in float32 on the
    operands as they arrive; T = 2 is shorter than the K = 4 taps."""
    from paddle_tpu import observability as obs
    from paddle_tpu.core import registry

    assert "causal_conv1d_grad" not in registry._AUTO_VJP_TYPES
    assert [s.name for s in OpInfoMap.instance().get(
        "causal_conv1d_grad").inputs] == ["X", "W", "Bias", "Out@GRAD"]
    dtype = jnp.dtype(dtype)
    k = keys(4, 1)
    x = jax.random.normal(k[0], (2, T, 6)).astype(dtype)
    w = jax.random.normal(k[1], (6, 4)).astype(dtype)
    b = jax.random.normal(k[2], (6,)).astype(dtype) if bias else None
    g = jax.random.normal(k[3], x.shape).astype(dtype)
    operands = (x, w) + ((b,) if bias else ())

    def plain(x, w, b=None):
        rows = []
        for t in range(T):
            acc = jnp.zeros((2, 6)) if b is None else b
            for j in range(4):
                if t - 3 + j >= 0:
                    acc = acc + w[:, j] * x[:, t - 3 + j]
            rows.append(acc)
        y = jnp.stack(rows, 1)
        return jax.nn.silu(y) if act else y

    # the forward at float32 to the limits it has always had; the
    # gradients, sums over positions, to ten times those
    fwd_tol, tol = (dict(rtol=1e-5, atol=1e-6), dict(rtol=1e-4, atol=1e-5)) \
        if dtype == jnp.float32 else (dict(rtol=2e-2, atol=2e-2),) * 2
    f32 = [a.astype(jnp.float32) for a in operands]
    ins = dict(zip(("X", "W", "Bias"), operands))
    attrs = {"activation": act}
    got = op("causal_conv1d")(ins, attrs)["Out"]
    assert got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32), plain(*f32),
                               **fwd_tol)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * g.astype(jnp.float32)),
                    tuple(range(len(f32))))(*f32)
    was_on = obs.enabled()
    obs.enable()
    try:
        before = obs.counter_value("kernels.causal_conv1d_grad") or 0
        grads = op("causal_conv1d_grad")(dict(ins, **{"Out@GRAD": g}), attrs)
        assert obs.counter_value("kernels.causal_conv1d_grad") == before + 1
    finally:
        if not was_on:
            obs.disable()
    assert sorted(grads) == sorted(n + "@GRAD" for n in ins)
    for name, ref in zip(("X@GRAD", "W@GRAD", "Bias@GRAD"), want):
        assert grads[name].dtype == dtype
        np.testing.assert_allclose(grads[name].astype(jnp.float32), ref,
                                   **tol)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("act", ["", "silu"], ids=["linear", "silu"])
def test_causal_conv1d_is_the_padded_copy_and_slices_to_the_bit(
        act, bias, dtype, jit):
    """The forward reads its taps through ``_tap`` (a ``pad`` of X in X's
    own type that XLA fuses into the reader), where until PR 47 it made a
    float32 padded copy and sliced it. Same products, summed in the same
    order: op by op the results are equal bit for bit, T = 2 < K = 4
    included. Under ``jit`` XLA's CPU backend contracts the multiply-adds
    of a fusion, and the two forms fuse differently, so float32 results
    may differ in the last bit there (the old form's jitted result differs
    from its own eager one just so): held to the forward's limits."""
    dtype = jnp.dtype(dtype)
    attrs = {"activation": act}

    def padded_and_sliced(x, w, b=None):
        w = w.astype(jnp.float32)
        T, K = x.shape[1], w.shape[1]
        xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
        out = sum(xp[:, k:k + T, :] * w[:, k] for k in range(K))
        if b is not None:
            out = out + b.astype(jnp.float32)
        return (jax.nn.silu(out) if act else out).astype(x.dtype)

    def the_op(x, w, b=None):
        return op("causal_conv1d")({"X": x, "W": w, "Bias": b}, attrs)["Out"]

    for T in (9, 2):
        k = keys(3, 10 + T)
        x = jax.random.normal(k[0], (2, T, 6)).astype(dtype)
        w = jax.random.normal(k[1], (6, 4)).astype(dtype)
        b = (jax.random.normal(k[2], (6,)).astype(dtype),) if bias else ()
        fns = [jax.jit(f) if jit else f for f in (the_op, padded_and_sliced)]
        got, want = (f(x, w, *b) for f in fns)
        assert got.dtype == want.dtype == dtype
        got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
        if jit and dtype == jnp.float32:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(got, want)


def test_causal_conv1d_grad_takes_silu_and_nothing_else():
    """Both ops refuse an activation that is not theirs: a gradient op built
    by hand does not take silu's derivative for another function's."""
    x, w = jnp.ones((1, 5, 3)), jnp.ones((3, 4))
    for name, more in (("causal_conv1d", {}),
                       ("causal_conv1d_grad", {"Out@GRAD": x})):
        with pytest.raises(NotImplementedError, match="gelu"):
            op(name)(dict({"X": x, "W": w}, **more), {"activation": "gelu"})


def test_the_benchmarks_reader_of_the_convolutions(monkeypatch, capsys):
    """``conv1d.taps_ms``: the union a step of the operations scoped
    ``causal_conv1d`` (forward, and emitted again inside the backward) or
    ``causal_conv1d_grad``, whatever mixer's name scope they carry; a
    program without the op reads nothing."""
    from benchmarks.layer_metrics import _scoped as S
    from benchmarks.layer_metrics import conv1d

    ms, jit = 1_000_000, "jit(step_s1)/jit(main)/"
    names = {"fwd": jit + "forward/causal_conv1d/kda/mul",
             "again": jit + "backward/causal_conv1d/add",
             "grad": jit + "backward/causal_conv1d_grad/kda/reduce_sum",
             "gate": jit + "backward/short_conv_gate_grad/shortconv/mul",
             "fc": jit + "forward/mul/kda/dot_general"}
    for name, mine in (("fwd", True), ("again", True), ("grad", True),
                       ("gate", False), ("fc", False)):
        assert conv1d.is_taps("%f", names[name]) == mine, name
    assert not conv1d.is_taps("%f", "")
    steps = [(0, 100 * ms), (100 * ms, 200 * ms)]
    events = [("fwd", 0, 2 * ms), ("fc", 2 * ms, 30 * ms),
              ("again", 40 * ms, 42 * ms), ("grad", 41 * ms, 46 * ms),
              ("gate", 46 * ms, 50 * ms),
              ("fwd", 100 * ms, 103 * ms), ("again", 150 * ms, 152 * ms),
              ("grad", 152 * ms, 157 * ms)]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, events, names))
    assert conv1d.read({"suffix": "tokens"}) == {
        "conv1d.taps_ms.tokens": pytest.approx(9.0)}
    line = capsys.readouterr().out
    assert "backward/causal_conv1d 2.0000" in line \
        and "backward/causal_conv1d_grad 5.0000" in line
    others = [e for e in events if e[0] in ("gate", "fc")]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, others, names))
    assert conv1d.read({"suffix": "tokens"}) == {}
    monkeypatch.setattr(S, "load", lambda: None)
    assert conv1d.read({"suffix": "tokens"}) == {}


# -- the selective scan -------------------------------------------------------

def recurrence(x, raw_dt, a, b, c, d):
    """The definition, position by position."""
    dt = jax.nn.softplus(raw_dt)
    bsz, t, h, p = x.shape
    r = h // b.shape[2]

    def step(state, inp):
        xt, dtt, bt, ct = inp
        bt, ct = jnp.repeat(bt, r, 1), jnp.repeat(ct, r, 1)
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.sum(state * ct[:, :, None, :], -1) + d[:, None] * xt

    zero = jnp.zeros((bsz, h, p, b.shape[3]))
    _, ys = jax.lax.scan(step, zero, tuple(jnp.moveaxis(z, 1, 0)
                                           for z in (x, dt, b, c)))
    return jnp.moveaxis(ys, 0, 1)


def scan_inputs(t, seed=0):
    """x, raw step sizes (the op takes their softplus), A, B, C, D."""
    k = keys(6, seed)
    bsz, h, p, g, n = 2, 4, 8, 2, 16
    return (jax.random.normal(k[0], (bsz, t, h, p)),
            jax.random.normal(k[1], (bsz, t, h)) - 2,
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (bsz, t, g, n)),
            jax.random.normal(k[4], (bsz, t, g, n)),
            1 + 0.1 * jax.random.normal(k[5], (h,)))


# several chunk counts, a length that is no multiple of the chunk, one chunk
@pytest.mark.parametrize("t,chunk", [(32, 16), (96, 16), (50, 16), (24, 128)])
def test_ssd_chunk_scan_is_the_recurrence(t, chunk):
    args = scan_inputs(t)
    with jax.default_matmul_precision("highest"):
        got = ssd_chunk_scan(*args, chunk=chunk)
        want = recurrence(*args)
    assert rel(got, want) < 1e-5
    # bf16 operands, float32 decays and state: the MXU's rounding of x, B, C
    # (2^-9 each, three products deep) and no more
    low = ssd_chunk_scan(args[0].astype(jnp.bfloat16), args[1], args[2],
                         args[3].astype(jnp.bfloat16),
                         args[4].astype(jnp.bfloat16), args[5], chunk=chunk)
    assert low.dtype == jnp.bfloat16 and rel(low, want) < 3e-2


@pytest.mark.parametrize("t,chunk", [(32, 16), (50, 16)])
def test_ssd_chunk_scan_grad_op_is_the_recurrences_gradient(t, chunk):
    x, dt, a, b, c, d = scan_inputs(t, 3)
    g = jax.random.normal(keys(1, 4)[0], x.shape)
    with jax.default_matmul_precision("highest"):
        got = op("ssd_chunk_scan_grad")(
            {"X": x, "Dt": dt, "A": a, "B": b, "C": c, "D": d,
             "Out@GRAD": g}, {"chunk": chunk})
        want = jax.grad(lambda *v: jnp.sum(recurrence(*v) * g),
                        tuple(range(6)))(x, dt, a, b, c, d)
    for name, ref in zip(("X", "Dt", "A", "B", "C", "D"), want):
        assert rel(got[name + "@GRAD"], ref) < 1e-5, name


def test_ssd_chunk_scan_op_adds_the_step_sizes_bias():
    x, dt, a, b, c, d = scan_inputs(32, 5)
    raw = jax.random.normal(keys(1, 6)[0], dt.shape)
    bias = jnp.linspace(-3.0, -1.0, 4)
    with jax.default_matmul_precision("highest"):
        got = op("ssd_chunk_scan")(
            {"X": x, "Dt": raw, "A": a, "B": b, "C": c, "D": d,
             "DtBias": bias}, {"chunk": 16})["Out"]
        want = recurrence(x, raw + bias, a, b, c, d)
    assert rel(got, want) < 1e-5


# -- the same scan on the Pallas kernels (interpret mode) ---------------------

SLOTS = ("X", "Dt", "A", "B", "C", "D", "DtBias")
# (heads, head dim, groups): the hybrid model's head width, and one above and
# one below it
LAYOUTS = {"p64": (4, 64, 2), "p128": (2, 128, 2), "p32": (4, 32, 1)}


def tile_inputs(t, seed=0, layout="p64", n=128):
    """``scan_inputs`` at shapes that fill the kernels' blocks, and the step
    sizes' bias."""
    k = keys(7, seed)
    bsz, (h, p, g) = 2, LAYOUTS[layout]
    return (jax.random.normal(k[0], (bsz, t, h, p)),
            jax.random.normal(k[1], (bsz, t, h)) - 2,
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (bsz, t, g, n)),
            jax.random.normal(k[4], (bsz, t, g, n)),
            1 + 0.1 * jax.random.normal(k[5], (h,)),
            0.5 * jax.random.normal(k[6], (h,)) - 0.5)


@pytest.fixture
def kernels_here(monkeypatch):
    """The program's question answered as a TPU would, and the kernels it
    then takes run in interpret mode, as the grouped kernels' test does."""
    scan = ssd_scan.scan
    monkeypatch.setattr(ssm_ops._fa, "compute_platform", lambda: "tpu")
    monkeypatch.setattr(ssd_scan, "scan", lambda *a: scan(*a, True))


def slots(args):
    return dict(zip(SLOTS, args))


# several chunks, one chunk, a length that is no multiple of the chunk; the
# other two head widths
@pytest.mark.parametrize("t,layout", [(384, "p64"), (128, "p64"),
                                      (200, "p64"), (256, "p128"),
                                      (256, "p32")])
def test_the_scans_kernels_are_the_recurrence(kernels_here, t, layout):
    args = tile_inputs(t, 1, layout)
    x, dt, a, b, c, d, bias = args
    assert ssm_ops.scan_path(x, b, 128) == "pallas"
    with jax.default_matmul_precision("highest"):
        got = ssd_chunk_scan(x, dt, a, b, c, d, dt_bias=bias)
        want = recurrence(x, dt + bias, a, b, c, d)
    assert rel(got, want) < 1e-5
    low = ssd_chunk_scan(x.astype(jnp.bfloat16), dt, a,
                         b.astype(jnp.bfloat16), c.astype(jnp.bfloat16), d,
                         dt_bias=bias)
    assert low.dtype == jnp.bfloat16 and rel(low, want) < 3e-2


@pytest.mark.parametrize("t,layout", [(256, "p64"), (200, "p64"),
                                      (256, "p128"), (256, "p32")])
def test_the_kernels_gradients_are_the_recurrences(kernels_here, t, layout):
    """Every gradient, from the gradient op and from ``jax.grad`` of the
    function: the state pass and the backward kernel between the prologue
    and its gradient, through the kernels' ``custom_vjp``."""
    args = tile_inputs(t, 3, layout)
    g = jax.random.normal(keys(1, 4)[0], args[0].shape)

    def plain(x, dt, a, b, c, d, bias):
        return jnp.sum(recurrence(x, dt + bias, a, b, c, d) * g)

    def mine(x, dt, a, b, c, d, bias):
        return jnp.sum(ssd_chunk_scan(x, dt, a, b, c, d, dt_bias=bias) * g)

    with jax.default_matmul_precision("highest"):
        got = op("ssd_chunk_scan_grad")(dict(slots(args), **{"Out@GRAD": g}),
                                        {"chunk": 128})
        also = jax.grad(mine, tuple(range(7)))(*args)
        want = jax.grad(plain, tuple(range(7)))(*args)
    for name, ref, fn in zip(SLOTS, want, also):
        # A's and DtBias's are sums over every position, of both signs: at
        # these lengths the chunked algorithm's float32 sums differ from the
        # recurrence's by up to 1.3e-5 of the largest entry in either form
        # (against a float64 recurrence: XLA form 1.1e-5, kernels 1.3e-5)
        tol = 5e-5 if name in ("A", "DtBias") else 1e-5
        assert rel(got[name + "@GRAD"], ref) < tol, name
        assert rel(fn, ref) < tol, name


def test_the_kernels_and_the_xla_form_agree_on_bf16_operands(monkeypatch,
                                                             kernels_here):
    """The same inputs through both forms, as the chip's check makes it:
    bf16 x, B, C and cotangent, float32 decays. D and DtBias left out."""
    x, dt, a, b, c, _, _ = tile_inputs(256, 5)
    bf16 = jnp.bfloat16
    ins = {"X": x.astype(bf16), "Dt": dt.astype(bf16), "A": a,
           "B": b.astype(bf16), "C": c.astype(bf16),
           "Out@GRAD": jax.random.normal(keys(1, 6)[0], x.shape, bf16)}
    got = (op("ssd_chunk_scan")(ins, {"chunk": 128})["Out"],
           op("ssd_chunk_scan_grad")(ins, {"chunk": 128}))
    monkeypatch.setattr(ssm_ops._fa, "compute_platform", lambda: "cpu")
    want = (op("ssd_chunk_scan")(ins, {"chunk": 128})["Out"],
            op("ssd_chunk_scan_grad")(ins, {"chunk": 128}))
    assert got[0].dtype == bf16 and rel(got[0], want[0]) < 2e-2
    assert set(got[1]) == set(want[1]) == {
        n + "@GRAD" for n in ("X", "Dt", "A", "B", "C")}
    for name, ref in want[1].items():
        assert got[1][name].dtype == ref.dtype, name
        assert rel(got[1][name], ref.astype(jnp.float32)) < 2e-2, name


def like(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("platform,x,b,chunk,want", [
    # the hybrid cell's shape, where the computation runs on a TPU ...
    ("tpu", (1, 8192, 64, 64), (1, 8192, 8, 128), 128, "pallas"),
    # ... and where it does not
    ("cpu", (1, 8192, 64, 64), (1, 8192, 8, 128), 128, "xla_chunked"),
    # a length the existing padding makes whole chunks of
    ("tpu", (2, 1000, 8, 64), (2, 1000, 2, 128), 128, "pallas"),
    # ... and any number of heads a group: the kernels walk them one by one
    ("tpu", (2, 512, 6, 64), (2, 512, 2, 128), 128, "pallas"),
    # what the blocks cannot take: a short sequence (its chunk is T), a
    # chunk of no whole lane tiles, a narrow state, heads of no whole
    # 32-sublane slices
    ("tpu", (2, 96, 8, 64), (2, 96, 2, 128), 128, "xla_chunked"),
    ("tpu", (2, 512, 8, 64), (2, 512, 2, 128), 64, "xla_chunked"),
    ("tpu", (2, 512, 8, 64), (2, 512, 2, 64), 128, "xla_chunked"),
    ("tpu", (2, 512, 8, 48), (2, 512, 2, 128), 128, "xla_chunked"),
])
def test_scan_path_reads_the_platform_and_the_shapes(monkeypatch, platform,
                                                     x, b, chunk, want):
    monkeypatch.setattr(ssm_ops._fa, "compute_platform", lambda: platform)
    assert ssm_ops.scan_path(like(x), like(b), chunk) == want
    assert ssm_ops.scan_path(like(x, jnp.float32), like(b, jnp.float32),
                             chunk) == want
    assert ssm_ops.scan_path(like(x, jnp.float16), like(b, jnp.float16),
                             chunk) == "xla_chunked"


def test_each_traced_scan_op_counts_the_form_it_took(kernels_here,
                                                     monkeypatch):
    from paddle_tpu import observability as obs

    args = tile_inputs(128, 7)
    ins = dict(slots(args), **{"Out@GRAD": args[0]})

    def counted():
        got = obs.dump()["counters"]
        return {k.split("path=")[1].rstrip("}"): v for k, v in got.items()
                if k.startswith("kernels.ssd_chunk_scan{")}

    obs.enable()
    try:
        before = counted()
        op("ssd_chunk_scan")(ins, {"chunk": 128})
        op("ssd_chunk_scan_grad")(ins, {"chunk": 128})
        assert counted().get("pallas", 0) == before.get("pallas", 0) + 2
        monkeypatch.setattr(ssm_ops._fa, "compute_platform", lambda: "cpu")
        op("ssd_chunk_scan_grad")(ins, {"chunk": 128})
        after = counted()
    finally:
        obs.disable()
    assert after["pallas"] == before.get("pallas", 0) + 2
    assert after["xla_chunked"] == before.get("xla_chunked", 0) + 1


# -- top-k routing over the experts held here ---------------------------------

T, D, E, F, K = 256, 16, 16, 24, 3


def moe_inputs(seed=0):
    k = keys(5, seed)
    return (jax.random.normal(k[0], (T, D)), jax.random.normal(k[1], (D, E)),
            0.1 * jax.random.normal(k[2], (E,)),
            0.3 * jax.random.normal(k[3], (E, D, F)),
            0.3 * jax.random.normal(k[4], (E, F, D)))


def every_expert(x, rw, bias, w1, w2, held=(0, E)):
    """The layer as written: each chosen expert that is held, weighed."""
    s = jax.nn.sigmoid(x @ rw)
    _, idx = jax.lax.top_k(s + bias, K)
    w = jnp.take_along_axis(s, idx, -1)
    w = 2.5 * w / w.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held[0], held[0] + held[1]):
        m = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        out = out + m[:, None] * (jnp.square(jax.nn.relu(x @ w1[e])) @ w2[e])
    return out


def held_part(x, rw, bias, w1, w2, first, count):
    return moe_topk(x, rw, bias, w1[first:first + count],
                    w2[first:first + count], K, [first, count], 2.5)


def test_the_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    x, rw, bias, w1, w2 = moe_inputs()
    shared = jnp.square(jax.nn.relu(x @ w1[0])) @ w2[0]   # any dense expert
    with jax.default_matmul_precision("highest"):
        whole = every_expert(x, rw, bias, w1, w2) + shared
        parts, loads = [], []
        for first in range(0, E, 2):                      # 8 shares of 2
            out, load = held_part(x, rw, bias, w1, w2, first, 2)
            parts.append(out)
            loads.append(load)
    assert rel(sum(parts) + shared, whole) < 1e-5
    # every routed slot landed in exactly one share, none in a slow branch
    assert int(sum(l[:2].sum() for l in loads)) == T * K
    assert not any(int(l[2]) for l in loads)


@pytest.mark.parametrize("favoured,slow", [([1], 0), ([0, 1, 2], 1)])
def test_full_skew_drops_no_slot(favoured, slow):
    """Every token to one held expert and to no other held one (256 slots,
    inside the row buffer) and to three (768 slots against a buffer of
    384: the exact slower branch)."""
    x, rw, bias, w1, w2 = moe_inputs(1)
    bias = bias.at[:4].set(-100.0).at[jnp.array(favoured)].set(100.0)
    with jax.default_matmul_precision("highest"):
        got, load = held_part(x, rw, bias, w1, w2, 0, 4)
        want = every_expert(x, rw, bias, w1, w2, (0, 4))
    assert buffer_rows(T, K, E, 4) == 384
    assert [int(load[e]) for e in favoured] == [T] * len(favoured)
    assert int(load[4]) == slow
    assert rel(got, want) < 1e-5


def test_no_held_expert_chosen_gives_zero():
    x, rw, bias, w1, w2 = moe_inputs(2)
    got, load = held_part(x, rw, bias.at[:4].set(-100.0), w1, w2, 0, 4)
    assert not np.asarray(load).any() and not np.asarray(got).any()


@pytest.mark.parametrize("count", [E, 4])
@pytest.mark.parametrize("skewed", [False, True])
def test_moe_topk_grad_op_is_the_layers_gradient(skewed, count):
    """With every expert held, the layer's gradient; with part of them, the
    same but for the router's weight, which then takes none."""
    x, rw, bias, w1, w2 = moe_inputs(3)
    if skewed:
        bias = bias.at[:3].set(100.0)
    g = jax.random.normal(keys(1, 9)[0], x.shape)
    attrs = {"k": K, "held": [0, count], "scaling": 2.5, "norm_topk": True}
    with jax.default_matmul_precision("highest"):
        got = op("moe_topk_grad")(
            {"X": x, "RouterW": rw, "Bias": bias, "W1": w1[:count],
             "W2": w2[:count], "Out@GRAD": g}, attrs)
        want = jax.grad(
            lambda x, rw, a, b: jnp.sum(every_expert(
                x, rw, bias, a, b, (0, count)) * g), (0, 1, 2, 3))(
                    x, rw, w1[:count], w2[:count])
    assert "Bias@GRAD" not in got          # a buffer: no gradient
    if count < E:
        assert not np.asarray(got.pop("RouterW@GRAD")).any()
        want = (want[0], None) + want[2:]
    for name, ref in zip(("X", "RouterW", "W1", "W2"), want):
        assert ref is None or rel(got[name + "@GRAD"], ref) < 1e-5, name


@pytest.mark.parametrize("favoured", [[], [1]])
def test_the_tpus_grouped_kernels_give_the_layers_result_and_gradient(
        monkeypatch, favoured):
    """Where the computation runs on a TPU the sorted slots go through the
    megablox kernels (here in interpret mode, tiles smaller than the
    widths, neither width a whole number of tiles): the held experts' rows
    only, the rows past the held slots owned by a group no kernel visits."""
    import functools

    from paddle_tpu.ops import moe_ops

    gmm = moe_ops._megablox()
    monkeypatch.setattr(moe_ops._fa, "compute_platform", lambda: "tpu")
    monkeypatch.setattr(moe_ops, "TILING", (128, 128, 128))
    monkeypatch.setattr(gmm, "gmm", functools.partial(gmm.gmm,
                                                      interpret=True))
    monkeypatch.setattr(gmm, "tgmm", functools.partial(gmm.tgmm,
                                                       interpret=True))
    d, f = 192, 160
    k = keys(6, 5)
    x, rw = jax.random.normal(k[0], (T, d)), jax.random.normal(k[1], (d, E))
    bias = 0.1 * jax.random.normal(k[2], (E,))
    bias = bias.at[jnp.array(favoured, jnp.int32)].set(100.0)
    w1 = 0.1 * jax.random.normal(k[3], (E, d, f))
    w2 = 0.1 * jax.random.normal(k[4], (E, f, d))
    g = jax.random.normal(k[5], x.shape)
    assert moe_ops.grouped_path(buffer_rows(T, K, E, 4)) == "megablox"

    def mine(x, a, b):
        return jnp.sum(moe_topk(x, rw, bias, a, b, K, [0, 4], 2.5)[0] * g)

    def plain(x, a, b):
        return jnp.sum(every_expert(x, rw, bias, a, b, (0, 4)) * g)

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(mine, (0, 1, 2))(x, w1[:4], w2[:4])
        want = jax.value_and_grad(plain, (0, 1, 2))(x, w1[:4], w2[:4])
        load = moe_topk(x, rw, bias, w1[:4], w2[:4], K, [0, 4], 2.5)[1]
    assert int(load[4]) == 0 and all(int(load[e]) == T for e in favoured)
    assert abs(float(got[0] - want[0])) < 1e-4 * abs(float(want[0]))
    for a, b in zip(got[1], want[1]):
        assert rel(a, b) < 1e-5


# -- grouped-query heads on the streaming kernels -----------------------------

@pytest.mark.parametrize("heads,kv_heads", [(4, 1), (8, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_with_shared_kv_heads(heads, kv_heads, causal):
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    k = keys(4, 7)
    b, t, d = 2, 256, 128
    q = jax.random.normal(k[0], (b, heads, t, d))
    kk = jax.random.normal(k[1], (b, kv_heads, t, d))
    v = jax.random.normal(k[2], (b, kv_heads, t, d))
    g = jax.random.normal(k[3], q.shape)

    def dense(q, kk, v):
        r = heads // kv_heads
        s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(kk, r, 1)) * d ** -0.5
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                          jnp.repeat(v, r, 1))

    assert fa.attention_path(q, kk, force_pallas=True) == "stream"
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, causal=causal, block_q=128, block_k=128, force_pallas=True),
            q, kk, v)
        want, ref_vjp = jax.vjp(dense, q, kk, v)
        assert rel(out, want) < 1e-5
        for got, ref in zip(vjp(g), ref_vjp(g)):
            assert got.shape == ref.shape and rel(got, ref) < 1e-5
        # the dense math off the TPU takes the same arguments
        assert rel(fa.flash_attention(q, kk, v, causal=causal), want) < 1e-5


# -- the AMP rewrite and the model --------------------------------------------

def tiny():
    from benchmarks.configs.nemotron3_nano_ep16 import model, reference

    preset = os.path.join(ROOT, "benchmarks", "tests", "preset")
    with open(os.path.join(preset, "configs", "tiny_nemotron",
                           "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(preset, "traffic",
                           "tiny_nemotron.static.json")) as f:
        traffic = json.load(f)
    return cfg, traffic, model, reference


def test_amp_keeps_the_routers_and_the_decays_slots_float32():
    cfg, traffic, model, _ = tiny()
    block = model.build_static(cfg, traffic)["main"].global_block()

    def dtypes(op_type):
        o = next(o for o in block.ops if o.type == op_type)
        return {slot: str(block._find_var_recursive(names[0]).dtype)
                for slot, names in o.inputs.items()}

    assert dtypes("moe_topk") == {
        "X": "float32", "RouterW": "float32", "Bias": "float32",
        "W1": "bfloat16", "W2": "bfloat16"}
    assert dtypes("ssd_chunk_scan") == {
        "X": "bfloat16", "Dt": "bfloat16", "B": "bfloat16", "C": "bfloat16",
        "A": "float32", "D": "float32", "DtBias": "float32"}
    assert dtypes("rms_norm")["X"] == "float32"
    types = [o.type for o in block.ops]
    for grad in ("ssd_chunk_scan_grad", "moe_topk_grad",
                 "flash_attention_grad", "rms_norm_grad",
                 "causal_conv1d_grad"):
        assert grad in types


@pytest.mark.parametrize("recompute,ops,digest", [
    (False, 182, "13df2a3b715357e21e7ae77021801a7874df048a"),
    (True, 247, "d0152a133e08d6605a537067a6d3ffaeb179c92f")])
def test_the_model_keeps_its_head_major_attention(recompute, ops, digest):
    """``flash_attention`` reads the layout off its operands' rank, and this
    model's are rank 4 with shared K/V heads: its program is op for op what
    it was before the token-major layout existed (PR 27's list, by its
    digest), and each traced attention op counts ``layout=heads``."""
    import hashlib

    from paddle_tpu import observability as obs

    cfg, traffic, model, reference = tiny()
    built = model.build_static(cfg, dict(traffic, recompute=recompute))
    block = built["main"].global_block()
    types = [o.type for o in block.ops]
    assert (len(types), hashlib.sha1(
        " ".join(types).encode()).hexdigest()) == (ops, digest)
    attention = [o for o in block.ops if o.type == "flash_attention"]
    assert len(attention) == 1 + recompute
    for o in attention:
        q, k = (block._find_var_recursive(o.input(slot)[0]).shape
                for slot in "QK")
        assert len(q) == 4 and k[1] < q[1] and o.attrs["num_heads"] == 0
    feed = {k: np.asarray(v) for k, v in model.to_feed(
        reference.make_batch(jax.random.key(1), cfg, traffic)).items()}
    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.dump()["counters"])
        scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            exe.run(built["startup"])
            exe.lower(built["main"], feed=feed, fetch_list=[built["loss"]])
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    grown = {name: after[name] - before.get(name, 0) for name in after
             if name.startswith("kernels.flash_attention")
             and after[name] != before.get(name, 0)}
    assert grown == {"kernels.flash_attention{path=dense}": 1 + recompute,
                     "kernels.flash_attention_layout{layout=heads}":
                     1 + recompute,
                     "kernels.flash_attention_select{form=none}":
                     1 + recompute,
                     "kernels.flash_attention_grad{path=dense}": 1}


@pytest.mark.parametrize("expert,act,matrices", [("relu2", "relu", 2),
                                                 ("swiglu", "swish", 3)])
def test_the_shared_expert_takes_the_experts_form(expert, act, matrices):
    """``moe_mixer``'s shared expert is ``relu(u W1)^2 W2`` beside ``relu2``
    experts, as the hybrid configuration has it (whose program the digests
    above hold to PR 27's), and gated, ``(silu(u W1) * (u W3)) W2``, beside
    ``swiglu`` ones: against the form written out, with no routed expert
    held so that the layer is its shared expert alone."""
    mixer = importlib.import_module("paddle_tpu.models.hybrid_ssm_moe")
    d, f = 16, 24
    k = keys(4, 31)
    u = jax.random.normal(k[0], (2, 8, d))
    w = [0.3 * jax.random.normal(kk, sh) for kk, sh in zip(
        k[1:], [(d, f), (d, f), (f, d)])]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="u", shape=[2, 8, d], dtype="float32")
        out = mixer.moe_mixer(x, d, 8, 2, f, f, held=[0, 0], expert=expert)
    types = [o.type for o in main.global_block().ops]
    assert act in types and ("swish" in types) == (expert == "swiglu")
    shared = [p.name for p in main.all_parameters()][-matrices:]
    values = [w[0], w[2]] if expert == "relu2" else w
    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    with jax.default_matmul_precision("highest"), fluid.scope_guard(scope):
        exe.run(startup)
        for name, value in zip(shared, values):
            scope.find_var(name).get_tensor().set(np.asarray(value))
        (got,) = exe.run(main, feed={"u": np.asarray(u)}, fetch_list=[out])
        if expert == "relu2":
            want = jnp.square(jax.nn.relu(u @ w[0])) @ w[2]
        else:
            want = (jax.nn.silu(u @ w[0]) * (u @ w[1])) @ w[2]
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("recompute", [False, True])
def test_tiny_model_follows_the_plain_reference_for_three_steps(recompute):
    """``models.hybrid_ssm_moe`` through ``fluid.Executor`` with bf16 AMP and
    Adam, with and without recomputation, against the float32 reference's
    three steps: the comparison that decides a cell's ``correct``."""
    from benchmarks.lib import check
    from benchmarks.lib.reference_train import follow, identity

    cfg, traffic, model, reference = tiny()
    loads = []
    built = model.build_static(cfg, dict(traffic, recompute=recompute), loads)
    types = [o.type for o in built["main"].global_block().ops]
    assert ("recompute_barrier" in types) == recompute
    key = jax.random.key(3)
    start = reference.init_params(key, cfg)
    kept = {k: np.asarray(v) for k, v in start.items()}
    batches = [reference.make_batch(k, cfg, traffic) for k in keys(3, 4)]
    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    losses = []
    with fluid.scope_guard(scope):
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
                (load,) = out[1:]
                grads = {leaf: built["moment_scale"] * float(jnp.linalg.norm(
                    array(built["moment"] % name)))
                    for leaf, name in built["leaves"].items()}
        delta = {leaf: float(jnp.linalg.norm(array(name) - kept[leaf]))
                 for leaf, name in built["leaves"].items()}
    # 2 x 24 tokens x 3 slots x 4 of 16 experts = 36 expected
    assert 10 < int(load[:4].sum()) < 80 and int(load[4]) == 0
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], lambda k: reference.init_params(k, cfg),
                 key, batches, None, identity)
    rows = check.compare({"losses": losses, "grad_norms": grads,
                          "delta_norms": delta}, ref, traffic["limits"])
    assert all(ok for *_, ok, _ in rows), rows
