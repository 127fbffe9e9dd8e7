"""Latent attention under manifold-constrained hyper-connections, at small
sizes on the CPU, against plain definitions and the benchmark's plain
reference: the ``flash_attention`` op with a value dim of its own (streaming
kernels in interpret mode, the dense math, the op and its gradient op),
YaRN's frequencies, ``rotary_embedding`` on a slice of the head, the latent
mixer, the hyper-connection ops (at the seeded start and at a stirred point,
where four near misses of the equations each fail), the tiny model through
``fluid.Executor`` with Adam against the reference's steps, and the share of
the experts against the uncut layer."""
import importlib
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.registry import OpInfoMap
from paddle_tpu.ops.moe_ops import moe_topk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
# the modules: the packages' attributes of these names are the functions
decoder = importlib.import_module("paddle_tpu.models.hybrid_ssm_moe")


def op(name):
    return OpInfoMap.instance().get(name).fn


def keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def rel(a, b):
    b = jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


def tiny():
    from benchmarks.configs.xing4_29b_a4b_ep8 import model, reference

    preset = os.path.join(ROOT, "benchmarks", "tests", "preset")
    with open(os.path.join(preset, "configs", "tiny_xing4",
                           "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(preset, "traffic",
                           "tiny_xing4.static.json")) as f:
        traffic = json.load(f)
    return cfg, traffic, model, reference


# -- a value dim of its own in the flash kernels and the op -------------------

def dense_attention(q, k, v, scale, causal=True):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        t = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def qkvg(d, dv, dtype=jnp.float32, t=256, heads=2, seed=11):
    k = keys(4, seed)
    shapes = [(1, heads, t, d), (1, heads, t, d), (1, heads, t, dv),
              (1, heads, t, dv)]
    return [jax.random.normal(kk, s).astype(dtype)
            for kk, s in zip(k, shapes)]


@pytest.mark.parametrize("d,dv", [(48, 32), (192, 128), (64, 128)])
@pytest.mark.parametrize("path", ["stream", "dense"])
def test_flash_attention_with_a_value_dim_of_its_own(d, dv, path):
    """Forward and all three gradients against dense attention: the
    streaming kernels in interpret mode, and the dense math a call takes
    off the TPU."""
    q, k, v, g = qkvg(d, dv)
    force = path == "stream"
    assert fa.attention_path(q, k, force_pallas=force, v=v) == path
    # with equal dims such a call would take the short kernels: they take
    # one head dim, and an unequal call streams instead
    assert fa.attention_path(q, k, force_pallas=True) == "short"
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda *a: fa.flash_attention(
            *a, causal=True, scale=d ** -0.5, block_q=128, block_k=128,
            force_pallas=force), q, k, v)
        want, ref_vjp = jax.vjp(
            lambda *a: dense_attention(*a, d ** -0.5), q, k, v)
    assert out.shape == (1, 2, 256, dv) and rel(out, want) < 1e-5
    for got, ref in zip(vjp(g), ref_vjp(g)):
        assert got.shape == ref.shape and rel(got, ref) < 1e-5


def test_equal_dims_still_plan_as_before():
    """``_plan`` without a value dim, and with v's equal to q's, is the same
    plan: the short kernels at a length they take, the streaming ones with
    the same blocks beyond."""
    q, k, v, _ = qkvg(64, 64)
    assert fa._plan(q, k, 512, 1024) == fa._plan(q, k, 512, 1024, 0, 64)
    assert fa._plan(q, k, 512, 1024)[0] > 0
    assert fa._plan(q, k, 512, 1024, 0, 128) == (0, 256, 256)
    long_q = jnp.zeros((1, 2, 4096, 192), jnp.bfloat16)
    assert fa._plan(long_q, long_q, 512, 1024, 0, 128) == (0, 512, 1024) \
        == fa._plan(long_q, long_q, 512, 1024)


@pytest.mark.parametrize("amp", [False, True], ids=["float32", "bf16"])
@pytest.mark.parametrize("kernels", [False, True], ids=["dense", "stream"])
def test_the_op_and_its_gradient_op_with_a_value_dim(monkeypatch, amp,
                                                     kernels):
    """The registered op binds ``Out`` with v's head dim and an LSE where the
    kernels ran; ``flash_attention_grad`` gives the three gradients from
    them (the backward kernels alone, interpret mode here), or from a
    second forward where the dense math ran. bf16 operands as AMP hands
    them over."""
    dtype = jnp.bfloat16 if amp else jnp.float32
    q, k, v, g = qkvg(48, 32, dtype)
    if kernels:
        monkeypatch.setattr(fa, "compute_platform", lambda: "tpu")
        # interpret mode: the kernels cannot compile for this CPU
        real = fa._flash
        monkeypatch.setattr(fa, "_flash", lambda *a: real(*a[:-1], True))
    attrs = {"causal": True, "scale": 48 ** -0.5, "num_heads": 0}
    fwd = op("flash_attention")({"Q": q, "K": k, "V": v}, attrs)
    assert fwd["Out"].shape == (1, 2, 256, 32) and fwd["Out"].dtype == dtype
    assert (fwd["LSE"] is not None) == kernels
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(
            lambda *a: dense_attention(*a, 48 ** -0.5), *f32)
    tol = 3e-2 if amp else 1e-5
    assert rel(fwd["Out"], want) < tol
    if kernels:   # the backward kernels, interpret mode off the TPU
        monkeypatch.setattr(fa, "compute_platform", lambda: "cpu")
        real_bwd = fa._flash_bwd
        monkeypatch.setattr(
            fa, "_flash_bwd",
            lambda c, s, bq, bk, h, i, res, cts: real_bwd(
                c, s, bq, bk, h, True, res, cts))
    grads = op("flash_attention_grad")(
        {"Q": q, "K": k, "V": v, "Out": fwd["Out"], "LSE": fwd["LSE"],
         "Out@GRAD": g}, attrs)
    for slot, ref in zip(("Q@GRAD", "K@GRAD", "V@GRAD"),
                         ref_vjp(g.astype(jnp.float32))):
        assert grads[slot].shape == ref.shape
        assert rel(grads[slot], ref) < tol, slot


def test_the_layer_declares_the_context_with_vs_head_dim():
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = fluid.data(name="q", shape=[1, 2, 256, 48], dtype="float32")
        v = fluid.data(name="v", shape=[1, 2, 256, 32], dtype="float32")
        assert fluid.layers.flash_attention(q, q, v).shape == (1, 2, 256, 32)
        assert fluid.layers.flash_attention(q, q, q).shape == (1, 2, 256, 48)


def test_each_unequal_call_counts_the_kernels_it_took():
    from paddle_tpu import observability as obs

    q, k, v, _ = qkvg(48, 32)
    was_on = obs.enabled()
    obs.enable()
    try:
        name = "kernels.flash_attention_value_dim{path=dense}"
        before = dict(obs.dump()["counters"])
        op("flash_attention")({"Q": q, "K": k, "V": v}, {"causal": True})
        op("flash_attention")({"Q": q, "K": k, "V": q}, {"causal": True})
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    assert after[name] - before.get(name, 0) == 1


# -- YaRN's frequencies, rotary positions on a slice of the head --------------

def yarn_by_hand(dim, theta, factor, span, fast, slow):
    def pair(turns):
        return dim * math.log(span / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    lo, hi = max(math.floor(pair(fast)), 0), min(math.ceil(pair(slow)),
                                                 dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2 * i / dim)
        m = 1 - min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append((1 - m) * plain / factor + m * plain)
    return lo, hi, out


@pytest.mark.parametrize("dim,factor,span", [(64, 64, 4096), (8, 64, 4096),
                                             (64, 4, 32768)])
def test_yarn_frequencies_are_the_formula(dim, factor, span):
    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    lo, hi, want = yarn_by_hand(dim, 10000.0, factor, span, 32, 1)
    got = fluid.layers.yarn_inv_freq(dim, 10000.0, factor, span, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    if (dim, span) == (64, 4096):    # the published sizes
        assert (lo, hi) == (10, 23)
        # fast pairs keep their frequency, slow ones are divided by the factor
        np.testing.assert_allclose(
            got[:11], [10000.0 ** (-2 * i / 64) for i in range(11)])
        np.testing.assert_allclose(
            got[23:], [10000.0 ** (-2 * i / 64) / 64 for i in range(23, 32)])
    cfg = {"qk_rope_head_dim": dim, "rope_theta": 10000, "rope_scaling": {
        "factor": factor, "original_max_position_embeddings": span,
        "beta_fast": 32, "beta_slow": 1}}
    np.testing.assert_allclose(reference.yarn_frequencies(cfg), want,
                               rtol=2e-6)
    assert fluid.layers.yarn_mscale(64) == 0.1 * math.log(64) + 1
    assert fluid.layers.yarn_mscale(1) == 1.0


@pytest.mark.parametrize("given", [False, True], ids=["theta", "inv_freq"])
def test_rotary_embedding_on_a_slice_of_the_head(given):
    """The last 8 of 24 dims turn (rotate-half within the slice), positions
    0..T-1 where none are fed, by the given frequencies or theta's."""
    x = jax.random.normal(keys(1, 5)[0], (2, 6, 3, 24))
    freqs = ([0.5, 0.25, 0.125, 0.01] if given
             else [100.0 ** (-i / 4) for i in range(4)])
    got = op("rotary_embedding")({"X": x}, {
        "theta": 100.0, "offset": 16,
        "inv_freq": freqs if given else []})["Out"]
    ang = np.arange(6)[:, None] * np.asarray(freqs)[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    a, b = np.asarray(x[..., 16:20]), np.asarray(x[..., 20:])
    want = np.concatenate([np.asarray(x[..., :16]), a * cos - b * sin,
                           b * cos + a * sin], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # fed positions, three equal components: the same
    pos = jnp.broadcast_to(jnp.arange(6, dtype=jnp.int32), (3, 2, 6))
    fed = op("rotary_embedding")({"X": x, "Pos": pos}, {
        "theta": 100.0, "offset": 16,
        "inv_freq": freqs if given else []})["Out"]
    np.testing.assert_array_equal(got, fed)
    with pytest.raises(ValueError):
        op("rotary_embedding")({"X": x}, {"offset": 16, "inv_freq": [1.0]})


# -- the latent mixer ---------------------------------------------------------

def run_program(build, feeds, leaves, fetch_grads=False):
    """Build ``build()`` -> (outputs, parameters in order), set the
    parameters from ``leaves`` (in order), run once on the CPU."""
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


def test_latent_mixer_follows_the_reference():
    cfg, _, _, reference = tiny()
    shapes = reference.leaf_shapes(cfg)
    params = reference.init_params(jax.random.key(2), cfg)
    p = {k[3:]: v for k, v in params.items() if k.startswith("l0.")}
    order = [k for k in shapes if k.startswith("l0.") and
             k[3:] in reference.KINDS["L"]]
    u = jax.random.normal(jax.random.key(9), (2, 32, cfg["hidden_size"]))
    rs = cfg["rope_scaling"]
    scale = reference.score_scale(cfg)
    assert abs(scale - 24 ** -0.5 * (0.1 * math.log(64) + 1) ** 2) < 1e-12

    def build():
        x = fluid.data(name="u", shape=list(u.shape), dtype="float32")
        return [decoder.latent_mixer(
            x, cfg["hidden_size"], cfg["num_attention_heads"],
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope_dim=cfg["qk_nope_head_dim"],
            rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            inv_freq=fluid.layers.yarn_inv_freq(
                cfg["qk_rope_head_dim"], 10000.0, rs["factor"],
                rs["original_max_position_embeddings"]),
            scale=scale, eps=cfg["rms_norm_eps"])]

    with jax.default_matmul_precision("highest"):
        (got,) = run_program(build, {"u": np.asarray(u)},
                             [params[k] for k in order])
        want = reference.latent_attention(u, p, cfg, jnp.matmul,
                                          lambda x: x)
    assert rel(got, want) < 2e-5


# -- the hyper-connection ops -------------------------------------------------

N, C = 4, 24


def hyper_point(stirred, seed=3):
    """(streams [B, T, n, C], a sublayer's output, the hyper-connection
    leaves): the seeded start over equal streams, or a stirred point: every
    leaf N(0, 1), the streams unequal (seed 3: ``alpha_res`` 1.39, where the
    rounds have not converged by the nineteenth)."""
    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    cfg = {"hc_mult": N, "hidden_size": C, "hc_eps": 1e-6,
           "hc_sinkhorn_iters": 20, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6}
    k = keys(8, seed)
    x = jax.random.normal(k[0], (2, 5, N, C))
    if stirred:
        p = {"hc.phi": jax.random.normal(k[1], (N * C, 2 * N + N * N)),
             "hc.alpha": jax.random.normal(k[2], (3,)),
             "hc.b_pre": jax.random.normal(k[3], (N,)),
             "hc.b_post": jax.random.normal(k[4], (N,)),
             "hc.b_res": jax.random.normal(k[5], (N, N))}
    else:
        x = jnp.broadcast_to(x[:, :, :1], x.shape)
        full = dict(tiny()[0], hc_mult=N, hidden_size=C,
                    hybrid_override_pattern="D")
        params = reference.init_params(k[1], full)
        p = {leaf: params["l0." + leaf] for leaf in reference.HYPER}
    p["norm"] = jnp.ones((C,))
    y = jax.random.normal(k[6], (2, 5, C))
    return cfg, x, y, p


def mhc_ops(x, y, p, cfg, iters=None):
    """(h, the streams after the sublayer) by the two ops, in the
    reference's layout."""
    pre = op("mhc_pre")(
        {"X": jnp.swapaxes(x, 1, 2), "Phi": p["hc.phi"],
         "Alpha": p["hc.alpha"], "BPre": p["hc.b_pre"],
         "BPost": p["hc.b_post"], "BRes": p["hc.b_res"]},
        {"sinkhorn_iters": iters or cfg["hc_sinkhorn_iters"],
         "epsilon": cfg["hc_eps"],
         "clamp_min": cfg["mhc_h_res_clamp_min"],
         "clamp_max": cfg["mhc_h_res_clamp_max"]})
    out = op("mhc_post")({"X": jnp.swapaxes(x, 1, 2), "HRes": pre["HRes"],
                          "HPost": pre["HPost"], "Y": y}, {})["Out"]
    return pre["H"], jnp.swapaxes(out, 1, 2), pre


def near_miss(x, y, p, cfg, miss):
    """The sublayer's two results with one thing wrong."""
    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    c = dict(cfg, hc_sinkhorn_iters=19) if miss == "19 rounds" else cfg
    h_pre, h_post, h_res = reference.hyper_maps(x, p, c)
    if miss == "transposed H_res":
        h_res = jnp.swapaxes(h_res, -1, -2)
    elif miss == "H_post without its 2":
        h_post = h_post / 2
    elif miss == "columns before rows":
        # columns-then-rows of M is rows-then-columns of M^T, transposed
        pt = dict(p, **{"hc.b_res": p["hc.b_res"].T})
        perm = jnp.arange(N * N).reshape(N, N).T.reshape(-1)
        phi = p["hc.phi"]
        pt["hc.phi"] = jnp.concatenate(
            [phi[:, :2 * N], phi[:, 2 * N:][:, perm]], 1)
        h_res = jnp.swapaxes(reference.hyper_maps(x, pt, cfg)[2], -1, -2)
    h = jnp.einsum("btn,btnc->btc", h_pre, x)
    out = (jnp.einsum("btij,btjc->btic", h_res, x)
           + h_post[..., None] * y[:, :, None, :])
    return h, out


@pytest.mark.parametrize("stirred", [False, True], ids=["start", "stirred"])
def test_the_hyper_connection_ops_are_the_references_equations(stirred):
    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    cfg, x, y, p = hyper_point(stirred)
    with jax.default_matmul_precision("highest"):
        h, out, pre = mhc_ops(x, y, p, cfg)
        h_pre, h_post, h_res = reference.hyper_maps(x, p, cfg)
        want_h, want = near_miss(x, y, p, cfg, None)
        whole = reference.hyper_sublayer(x, p, cfg, lambda u: y)
    assert rel(want, whole) < 1e-6       # the helper is the reference
    assert rel(h, want_h) < 1e-5 and rel(out, want) < 1e-5
    assert rel(jnp.moveaxis(pre["HRes"], -1, 1), h_res) < 1e-5
    assert rel(jnp.swapaxes(pre["HPost"], 1, 2), h_post) < 1e-5
    # the rounds end on the columns, which sum to one; the rows nearly do
    np.testing.assert_allclose(h_res.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(h_res.sum(-1), 1.0,
                               atol=0.2 if stirred else 1e-3)
    if not stirred:   # the identity mapping, the streams' mean, H_post 1
        np.testing.assert_allclose(
            h_res, jnp.broadcast_to(jnp.eye(N), h_res.shape), atol=2e-3)
        np.testing.assert_allclose(h_pre, 0.25, atol=1e-2)
        np.testing.assert_allclose(h_post, 1.0, atol=5e-2)


@pytest.mark.parametrize("miss", ["transposed H_res", "columns before rows",
                                  "19 rounds", "H_post without its 2"])
def test_a_near_miss_of_the_equations_fails_at_the_stirred_point(miss):
    """What the tolerance of the test above is worth: each of these, tried,
    lies a hundred times further from the ops than the reference does."""
    cfg, x, y, p = hyper_point(True)
    with jax.default_matmul_precision("highest"):
        _, out, _ = mhc_ops(x, y, p, cfg)
        _, wrong = near_miss(x, y, p, cfg, miss)
        if miss == "19 rounds":
            assert rel(mhc_ops(x, y, p, cfg, iters=19)[1], wrong) < 1e-5
    assert rel(out, wrong) > 1e-3, miss


@pytest.mark.parametrize("stirred", [False, True], ids=["start", "stirred"])
def test_the_hyper_connection_gradient_ops_are_the_references(stirred):
    """``mhc_pre_grad`` and ``mhc_post_grad``, through the twenty rounds,
    against the gradient of the reference's sublayer."""
    from benchmarks.configs.xing4_29b_a4b_ep8 import reference

    cfg, x, y, p = hyper_point(stirred)
    g = jax.random.normal(keys(1, 8)[0], x.shape)
    leaves = ("hc.phi", "hc.alpha", "hc.b_pre", "hc.b_post", "hc.b_res")

    def plain(x, y, *hyper):
        q = dict(zip(leaves, hyper), norm=p["norm"])
        # F(u) = y * mean(u): the sublayer's output depends on h too
        return reference.hyper_sublayer(
            x, q, cfg, lambda u: y * jnp.mean(u, -1, keepdims=True))

    with jax.default_matmul_precision("highest"):
        _, vjp = jax.vjp(plain, x, y, *(p[k] for k in leaves))
        want = vjp(g)
        xs = jnp.swapaxes(x, 1, 2)
        ins = {"X": xs, "Phi": p["hc.phi"], "Alpha": p["hc.alpha"],
               "BPre": p["hc.b_pre"], "BPost": p["hc.b_post"],
               "BRes": p["hc.b_res"]}
        pre = op("mhc_pre")(ins, {})
        f = lambda h: y * jnp.mean(   # noqa: E731
            h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + 1e-6), -1,
            keepdims=True)
        yy, f_vjp = jax.vjp(f, pre["H"])
        post_in = {"X": xs, "HRes": pre["HRes"], "HPost": pre["HPost"],
                   "Y": yy}
        d_post = op("mhc_post_grad")(
            dict(post_in, **{"Out@GRAD": jnp.swapaxes(g, 1, 2)}), {})
        (d_h,) = f_vjp(d_post["Y@GRAD"])
        d_pre = op("mhc_pre_grad")(dict(ins, **{
            "H@GRAD": d_h, "HPost@GRAD": d_post["HPost@GRAD"],
            "HRes@GRAD": d_post["HRes@GRAD"]}), {})
    got_x = jnp.swapaxes(d_pre["X@GRAD"] + d_post["X@GRAD"], 1, 2)
    assert rel(got_x, want[0]) < 2e-4
    # at the start the streams are equal, so h is theirs whatever H_pre is
    # and b_pre's gradient is rounding (1e-6): the floor leaves it out
    for slot, ref in zip(("Phi", "Alpha", "BPre", "BPost", "BRes"), want[2:]):
        gap = jnp.max(jnp.abs(d_pre[slot + "@GRAD"] - ref))
        assert float(gap / (jnp.max(jnp.abs(ref)) + 1e-3)) < 2e-4, slot


def test_amp_keeps_the_streams_and_the_maps_float32():
    cfg, traffic, model, _ = tiny()
    block = model.build_static(cfg, traffic)["main"].global_block()

    def dtypes(op_type, slots):
        o = next(o for o in block.ops if o.type == op_type)
        return {slot: str(block._find_var_recursive(names[0]).dtype)
                for slot, names in {**o.inputs, **o.outputs}.items()
                if slot in slots}

    assert set(dtypes("mhc_pre", ("X", "Phi", "Alpha", "BRes", "H", "HRes",
                                  "HPost")).values()) == {"float32"}
    assert set(dtypes("mhc_post", ("X", "HRes", "HPost", "Y",
                                   "Out")).values()) == {"float32"}
    assert dtypes("flash_attention", "QKV") == {
        "Q": "bfloat16", "K": "bfloat16", "V": "bfloat16"}
    assert dtypes("moe_topk", ("X", "RouterW", "W1", "W3")) == {
        "X": "float32", "RouterW": "float32", "W1": "bfloat16",
        "W3": "bfloat16"}
    # every op of the latent mixer, its gradient ops and its recomputed
    # copies carry the mixer's name scope; no other op does
    scoped = {o.type for o in block.ops
              if o.attrs.get("op_namescope") == "/latent/"}
    assert {"mul", "mul_grad", "rms_norm", "rotary_embedding", "concat",
            "flash_attention", "flash_attention_grad"} <= scoped
    assert not scoped & {"mhc_pre", "mhc_post", "moe_topk", "swish",
                         "lookup_table", "adam"}


# -- the model ----------------------------------------------------------------

@pytest.mark.parametrize("amp", [False, True], ids=["float32", "amp"])
@pytest.mark.parametrize("recompute", [False, True])
def test_tiny_model_follows_the_plain_reference(monkeypatch, amp, recompute):
    """``models.hybrid_ssm_moe`` (latent attention, a dense layer, experts
    beside a shared one, four residual streams) through ``fluid.Executor``
    with Adam, float32 and under bf16 AMP, with and without recomputation,
    against the float32 reference: the losses of three steps, the first
    gradient leaf by leaf, the parameters' change."""
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
    # ten hyper-connected sublayers would be ten of each; the toy has four
    assert types.count("mhc_pre") == 4 * (1 + recompute) \
        and types.count("mhc_pre_grad") == 4 \
        and types.count("mhc_post") == 4 \
        and types.count("mhc_post_grad") == 4 \
        and types.count("flash_attention") == 2 * (1 + recompute)
    # both gradient ops are the registered ones, not automatic VJPs
    from paddle_tpu.core import registry
    assert not {"mhc_pre_grad", "mhc_post_grad"} & registry._AUTO_VJP_TYPES
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
                (load,) = out[1:]
                grads = {leaf: built["moment_scale"] * float(jnp.linalg.norm(
                    array(built["moment"] % name)))
                    for leaf, name in built["leaves"].items()}
        delta = {leaf: float(jnp.linalg.norm(array(name) - kept[leaf]))
                 for leaf, name in built["leaves"].items()}
    # 2 x 32 tokens x 3 slots x 4 of 16 experts = 48 expected
    assert 15 < int(load[:4].sum()) < 100 and int(load[4]) == 0
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], lambda k: reference.init_params(k, cfg),
                 key, batches, None, identity)
    limits = (traffic["limits"] if amp else
              {"loss": 1e-5, "grad_norm": 1e-3, "delta_norm": 1e-3})
    rows = check.compare({"losses": losses, "grad_norms": grads,
                          "delta_norms": delta}, ref, limits)
    assert all(ok for *_, ok, _ in rows), rows


def test_the_seeded_hyper_leaves_are_the_layers_defaults():
    """``layers.mhc_pre``'s own initialisers give what the reference seeds
    (but Phi, which is random in both)."""
    cfg, _, _, reference = tiny()
    ref = reference.init_params(jax.random.key(0), cfg)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[1, 4, 8, 32], dtype="float32")
        fluid.layers.mhc_pre(x)
    names = [p.name for p in main.all_parameters()]
    scope, exe = fluid.Scope(), fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        got = [np.asarray(scope.find_var(n).get_tensor().array)
               for n in names]
    assert got[0].shape == (128, 24) and abs(got[0].std() - 0.02) < 2e-3
    for value, leaf in zip(got[1:], ("alpha", "b_pre", "b_post", "b_res")):
        np.testing.assert_allclose(value, ref["l0.hc." + leaf], rtol=1e-6)


# -- the share and the uncut layer --------------------------------------------

def test_the_eight_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """What ties one rank's share to the model: over the 8 shares of the
    experts (2 of 16 each) the routed parts summed, and the shared expert
    counted once, equal the uncut reference's layer."""
    cfg, _, _, reference = tiny()
    e, d, f = cfg["n_routed_experts"], cfg["hidden_size"], \
        cfg["moe_intermediate_size"]
    k = keys(8, 21)
    p = {"router": jax.random.normal(k[0], (d, e)),
         "gate": 0.3 * jax.random.normal(k[1], (e, d, f)),
         "up": 0.3 * jax.random.normal(k[2], (e, d, f)),
         "down": 0.3 * jax.random.normal(k[3], (e, f, d)),
         "s_w1": 0.3 * jax.random.normal(k[4], (d, f)),
         "s_w3": 0.3 * jax.random.normal(k[5], (d, f)),
         "s_w2": 0.3 * jax.random.normal(k[6], (f, d))}
    u = jax.random.normal(k[7], (1, 96, d))
    uncut = dict(cfg, first_routed_expert_held=0, n_routed_experts_held=e)
    with jax.default_matmul_precision("highest"):
        whole = reference.experts(u, p, uncut, jnp.matmul)[0]
        shared = reference._swiglu(u[0], p["s_w1"], p["s_w3"], p["s_w2"],
                                   jnp.matmul)
        parts, slots = [], 0
        for first in range(0, e, e // 8):
            held = slice(first, first + e // 8)
            out, load = moe_topk(
                u[0], p["router"], None, p["gate"][held], p["down"][held],
                cfg["num_experts_per_tok"], [first, e // 8],
                cfg["routed_scaling_factor"], w3=p["up"][held])
            parts.append(out)
            slots += int(load[:-1].sum())
            assert int(load[-1]) == 0
    assert rel(sum(parts) + shared, whole) < 1e-5
    # every routed slot landed in exactly one share
    assert slots == 96 * cfg["num_experts_per_tok"]
