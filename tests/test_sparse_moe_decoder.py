"""Learned sparse attention over a softmax-routed gated MoE, at small sizes on
the CPU, against the benchmark's plain reference
(``benchmarks/configs/keye_vl2_a3b_ep8/reference.py``): the rotary op with
unequal position components, the indexer's scores, selection (forced ties
too) and loss with their gradients, ``flash_attention`` with a selection
(dense math and the streaming kernels in interpret mode; a selection of
every causal key is the unselected op bit for bit), ``moe_topk`` with softmax
scores and the gated expert (the overflow branch too, sigmoid + relu2
unchanged), the tiny model through ``fluid.Executor`` with AMP and Adam for
three steps, and the share test: the eight shares of the experts add up to
the uncut layer, with the attention half counted once."""
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
from paddle_tpu.ops import moe_ops, sparse_attn_ops as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def op(name):
    return OpInfoMap.instance().get(name).fn


def keys(n, seed=0):
    return jax.random.split(jax.random.key(seed), n)


def rel(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32) - b))
                 / (jnp.max(jnp.abs(b)) + 1e-30))


def tiny():
    from benchmarks.configs.keye_vl2_a3b_ep8 import model, reference

    preset = os.path.join(ROOT, "benchmarks", "tests", "preset")
    with open(os.path.join(preset, "configs", "tiny_keye",
                           "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(preset, "traffic", "tiny_keye.static.json")) as f:
        traffic = json.load(f)
    return cfg, traffic, model, reference


def unequal_positions(b, t):
    """[B, 3, T]: temporal, height and width components that differ."""
    base = jnp.arange(t, dtype=jnp.int32)
    return jnp.stack([jnp.stack([base + i, 2 * base, base // 3 + 5 * i])
                      for i in range(b)])


# -- rotary positions ---------------------------------------------------------

@pytest.mark.parametrize("dims", [16, 8])
def test_rotary_op_and_its_gradient_with_unequal_components(dims):
    _, _, _, reference = tiny()
    b, t, h, hd = 2, 12, 3, 16
    x = jax.random.normal(keys(1)[0], (b, t, h, hd))
    pos = unequal_positions(b, t)
    attrs = {"theta": 1e4, "sections": [2, 3, 3], "rotary_dims": dims}
    ins = {"X": x, "Pos": jnp.swapaxes(pos, 0, 1)}            # [3, B, T]
    got = op("rotary_embedding")(ins, attrs)["Out"]
    want = reference._rotary(x, pos, 1e4, [2, 3, 3], dims)
    assert rel(got, want) < 1e-6
    # a rotation: norms are kept, the dims past ``dims`` untouched, and the
    # components matter (equal ones give another result)
    np.testing.assert_allclose(jnp.linalg.norm(got, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert jnp.array_equal(got[..., dims:], x[..., dims:])
    same = op("rotary_embedding")(
        {"X": x, "Pos": jnp.broadcast_to(pos[:, :1], pos.shape
                                         ).swapaxes(0, 1)}, attrs)["Out"]
    assert rel(same, want) > 1e-2
    g = jax.random.normal(keys(1, 1)[0], x.shape)
    grad = op("rotary_embedding_grad")({**ins, "Out@GRAD": g}, attrs)
    want_g = jax.grad(lambda x: jnp.sum(reference._rotary(
        x, pos, 1e4, [2, 3, 3], dims) * g))(x)
    assert rel(grad["X@GRAD"], want_g) < 1e-6 and "Pos@GRAD" not in grad


# -- the indexer --------------------------------------------------------------

T, D, HI, DI, H, HKV, HD, TOPK = 48, 32, 2, 8, 4, 2, 16, 8


def indexer_inputs(seed=0, b=2):
    k = keys(8, seed)
    return {"X": jax.random.normal(k[0], (b, T, D)),
            "WQ": 0.3 * jax.random.normal(k[1], (D, HI * DI)),
            "WK": 0.3 * jax.random.normal(k[2], (D, DI)),
            "WW": 0.3 * jax.random.normal(k[3], (D, HI)),
            "LnScale": 1 + 0.1 * jax.random.normal(k[4], (DI,)),
            "LnBias": 0.1 * jax.random.normal(k[5], (DI,)),
            "Pos": jnp.swapaxes(unequal_positions(b, T), 0, 1)}


PROJECT = {"heads": HI, "theta": 1e4, "sections": [2, 3, 3],
           "rotary_dims": 4, "epsilon": 1e-6}


def plain_indexer(ins):
    """The reference's lines for qI, kI, w."""
    _, _, _, reference = tiny()
    pos = jnp.swapaxes(ins["Pos"], 0, 1)
    x = ins["X"]
    b = x.shape[0]
    qi = reference._rotary((x @ ins["WQ"]).reshape(b, T, HI, DI), pos, 1e4,
                           [2, 3, 3], 4)
    ki = reference._layer_norm(x @ ins["WK"], ins["LnScale"], ins["LnBias"],
                               1e-6)
    ki = reference._rotary(ki[:, :, None, :], pos, 1e4, [2, 3, 3], 4)[:, :, 0]
    return qi, ki, (x @ ins["WW"]) * (HI ** -0.5 * DI ** -0.5)


def plain_scores(qi, ki, w):
    """I[b, t, s], -inf above the diagonal."""
    s = jnp.einsum("bqhd,bkd->bhqk", qi, ki)
    index = jnp.sum(jnp.moveaxis(w, 2, 1)[..., None] * jax.nn.relu(s), 1)
    seen = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    return jnp.where(seen, index, -jnp.inf)


def plain_selection(index, topk):
    """bool [B, T, T] by ``jax.lax.top_k`` (ties to the lower key)."""
    _, chosen = jax.lax.top_k(index, topk)
    valid = jnp.arange(topk)[None, :] < (jnp.arange(T) + 1)[:, None]
    b = index.shape[0]
    return jnp.zeros(index.shape, bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(T)[None, :, None],
        chosen].set(jnp.broadcast_to(valid, chosen.shape))


def test_index_project_op_cuts_the_hidden_state_from_the_gradient():
    ins = indexer_inputs()
    with jax.default_matmul_precision("highest"):
        got = op("attn_index_project")(ins, PROJECT)
        want = plain_indexer(ins)
    for name, ref in zip(("QI", "KI", "W"), want):
        assert rel(got[name], ref) < 1e-5, name
    cts = {n + "@GRAD": jax.random.normal(k, got[n].shape)
           for n, k in zip(("QI", "KI", "W"), keys(3, 7))}
    with jax.default_matmul_precision("highest"):
        grads = op("attn_index_project_grad")({**ins, **cts}, PROJECT)
        params = ("WQ", "WK", "WW", "LnScale", "LnBias")
        want_g = jax.grad(lambda *p: sum(
            jnp.sum(o * cts[n + "@GRAD"]) for n, o in zip(
                ("QI", "KI", "W"), plain_indexer({**ins, **dict(
                    zip(params, p))}))), range(5))(*(ins[p] for p in params))
    for name, ref in zip(params, want_g):
        assert rel(grads[name + "@GRAD"], ref) < 1e-5, name
    assert grads.get("X@GRAD") is None or not np.asarray(
        grads["X@GRAD"]).any()


def test_index_scores_are_the_references():
    ins = indexer_inputs(1)
    with jax.default_matmul_precision("highest"):
        qi, ki, w = plain_indexer(ins)
        want = plain_scores(qi, ki, w)
        got = jnp.stack([sa.index_scores(qi[b], ki[b], w[b])
                         for b in range(2)])
    seen = jnp.isfinite(want)
    assert rel(jnp.where(seen, got, 0.0), jnp.where(seen, want, 0.0)) < 1e-6


def highest_scores(qi, ki, w):
    """``index_scores`` as one float32 product at ``HIGHEST``."""
    s = jnp.einsum("rhd,sd->hrs", qi, ki, precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(jax.nn.relu(s) * w.T[:, :, None], 0) + 0.0


def spanning(rng, *shape):
    """float32 normal draws with magnitudes over 1e-4..1e4."""
    return (rng.randn(*shape) * 10 ** rng.uniform(-4, 4, shape)).astype(
        np.float32)


@pytest.mark.parametrize("d", [32, 64, 128])
def test_packed_product_is_a_float32_product(d):
    """Against float64: no further off than twice one float32 product at
    ``HIGHEST`` on the same operands, and under 1e-6 of the result's norm."""
    rng = np.random.RandomState(d)
    qi, ki, w = spanning(rng, 64, 4, d), spanning(rng, 256, d), spanning(
        rng, 64, 4)
    s = np.einsum("rhd,sd->hrs", *(a.astype(np.float64) for a in (qi, ki)))
    want = np.sum(np.maximum(s, 0) * w.astype(np.float64).T[:, :, None], 0)

    def err(scores):
        got = np.asarray(jax.jit(scores)(qi, ki, w), np.float64)
        return np.linalg.norm(got - want) / np.linalg.norm(want)

    packed, plain = err(sa.index_scores), err(highest_scores)
    assert packed < 1e-6 and packed <= 2 * plain, (packed, plain)


def test_the_three_pieces_add_back_to_the_value_under_jit():
    """hi + mid + lo is the float32 value, bit for bit, and the compiler has
    folded no rounding away: mid and lo hold bits."""
    x = spanning(np.random.RandomState(3), 256, 64)
    pieces = jax.jit(sa.split3)(x)
    assert all(p.dtype == jnp.bfloat16 for p in pieces)
    hi, mid, lo = (np.asarray(p.astype(jnp.float32), np.float64)
                   for p in pieces)
    assert np.array_equal(hi + mid + lo, x.astype(np.float64))
    assert np.mean(mid != 0) > 0.9 and np.mean(lo != 0) > 0.9


@pytest.mark.parametrize("keys", [T, T // 2], ids=["full", "group_cut"])
def test_index_scores_vjp_is_the_plain_products_gradient(keys):
    """dq, dk, dw of the packed form's own VJP against ``jax.grad`` of the
    float32 product at ``HIGHEST``, for a block over all the keys and one
    over its causal group's keys only."""
    ins = indexer_inputs(6)
    with jax.default_matmul_precision("highest"):
        qi, ki, w = (x[0] for x in plain_indexer(ins))
    q_b, k_b, w_b = qi[keys - 16:keys], ki[:keys], w[keys - 16:keys]
    g = jax.random.normal(jax.random.key(8), (16, keys))

    def grads(scores):
        return jax.jit(jax.grad(lambda *a: jnp.sum(scores(*a) * g),
                                (0, 1, 2)))(q_b, k_b, w_b)

    for name, got, want in zip(("dq", "dk", "dw"), grads(sa.index_scores),
                               grads(highest_scores)):
        assert got.shape == want.shape and rel(got, want) < 1e-5, name


def test_duplicated_keys_give_bit_equal_scores():
    """Equal keys score equal to the last bit in every row, so ties are cut
    by position alone."""
    rng = np.random.RandomState(9)
    qi, ki, w = spanning(rng, 32, 4, 64), spanning(rng, 128, 64), spanning(
        rng, 32, 4)
    ki[1::2] = ki[0::2]
    scores = np.asarray(jax.jit(sa.index_scores)(qi, ki, w)).view(np.uint32)
    assert np.array_equal(scores[:, 0::2], scores[:, 1::2])


@pytest.mark.parametrize("topk", [TOPK, 1, T])
def test_selection_op_is_top_k_of_the_causal_scores(topk):
    ins = indexer_inputs(2)
    with jax.default_matmul_precision("highest"):
        qi, ki, w = plain_indexer(ins)
        got = op("attn_index_select")({"QI": qi, "KI": ki, "W": w},
                                      {"topk": topk})["Select"]
        want = plain_selection(plain_scores(qi, ki, w), topk)
    assert got.dtype == jnp.int8 and got.shape == (2, T, T)
    assert jnp.array_equal(got != 0, want)
    # query t keeps min(t + 1, topk) causal keys
    assert jnp.array_equal(jnp.sum(got, -1)[0],
                           jnp.minimum(jnp.arange(T) + 1, topk))


def test_selection_with_forced_ties_keeps_the_lower_keys():
    """Scores from a few values only, zeros of both signs among them: ties
    straddle the threshold in most rows, and the cut is by position."""
    k = keys(2, 3)
    scores = jax.random.randint(k[0], (64, 96), -2, 3).astype(jnp.float32)
    scores = scores * jnp.where(jax.random.bernoulli(k[1], 0.5, scores.shape),
                                1.0, -1.0) + 0.0
    row0, topk = 32, 16
    got = sa.select_rows(scores, row0, topk)
    t = row0 + jnp.arange(64)
    seen = jnp.arange(96)[None, :] <= t[:, None]
    _, chosen = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), topk)
    want = jnp.zeros(scores.shape, bool).at[
        jnp.arange(64)[:, None], chosen].set(True)
    assert jnp.array_equal(got != 0, want & seen)
    assert int(jnp.sum(got)) == 64 * topk
    # and -0.0 orders as +0.0 once the scores' ``+ 0.0`` has passed
    assert jnp.array_equal(sa._ordered(jnp.float32(-0.0) + 0.0),
                           sa._ordered(jnp.float32(0.0)))


def attention_inputs(seed=4, b=2, dtype=jnp.float32):
    k = keys(3, seed)
    return (jax.random.normal(k[0], (b, H, T, HD), dtype),
            jax.random.normal(k[1], (b, HKV, T, HD), dtype),
            jax.random.normal(k[2], (b, HKV, T, HD), dtype))


def plain_attention_and_loss(qi, ki, w, q, k, v, topk):
    """The reference's block function over whole sequences (head-major q, k,
    v in, as the op takes them)."""
    _, _, _, reference = tiny()
    to_tokens = lambda z: jnp.swapaxes(z, 1, 2)            # noqa: E731
    ctx, li = reference._selected_attention(
        to_tokens(q), to_tokens(k), to_tokens(v), qi, ki, w, topk,
        lambda z: z)
    return jnp.swapaxes(ctx, 1, 2), li


def test_index_loss_op_and_its_gradient_are_the_references():
    ins = indexer_inputs(5)
    q, k, v = attention_inputs()
    scale = HD ** -0.5
    with jax.default_matmul_precision("highest"):
        qi, ki, w = plain_indexer(ins)
        select = op("attn_index_select")({"QI": qi, "KI": ki, "W": w},
                                         {"topk": TOPK})["Select"]
        out, lse = fa.flash_attention_with_lse(q, k, v, causal=True,
                                               scale=scale, select=select)
        loss_ins = {"QI": qi, "KI": ki, "W": w, "Select": select, "Q": q,
                    "K": k, "LSE": lse}
        got = op("attn_index_loss")(loss_ins, {"scale": scale})["Loss"]
        want_ctx, want = plain_attention_and_loss(qi, ki, w, q, k, v, TOPK)
        assert got.shape == (1,) and rel(got[0], want) < 1e-5
        assert rel(out, want_ctx) < 1e-5
        g = jnp.asarray([1.7], jnp.float32)
        grads = op("attn_index_loss_grad")({**loss_ins, "Loss@GRAD": g},
                                           {"scale": scale})
        want_g = jax.grad(lambda qi, ki, w: 1.7 * plain_attention_and_loss(
            qi, ki, w, q, k, v, TOPK)[1], (0, 1, 2))(qi, ki, w)
    for name, ref in zip(("QI", "KI", "W"), want_g):
        assert rel(grads[name + "@GRAD"], ref) < 1e-5, name
    assert set(grads) == {"QI@GRAD", "KI@GRAD", "W@GRAD"}


# -- flash_attention with a selection -----------------------------------------

def random_selection(seed, b=2):
    sel = jax.random.bernoulli(keys(1, seed)[0], 0.3, (b, T, T))
    sel = (sel | jnp.eye(T, dtype=bool)) & jnp.tril(jnp.ones((T, T), bool))
    return sel.astype(jnp.int8)


def masked_softmax_attention(q, k, v, select, scale):
    kk, vv = (jnp.repeat(z, H // HKV, axis=1) for z in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kk) * scale
    p = jax.nn.softmax(jnp.where((select != 0)[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv)


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["dense", "interpreted_kernels"])
def test_flash_attention_with_a_selection_and_its_gradients(kernels):
    """The op's math where no TPU is (dense), and the streaming kernels
    themselves in interpret mode with blocks smaller than the sequence, so
    that the selection's tiles move with the K blocks."""
    b, t = 1, 256
    k = keys(4, 11)
    q = jax.random.normal(k[0], (b, H, t, 128))
    kk = jax.random.normal(k[1], (b, HKV, t, 128))
    v = jax.random.normal(k[2], (b, HKV, t, 128))
    sel = jax.random.bernoulli(k[3], 0.2, (b, t, t))
    sel = ((sel | jnp.eye(t, dtype=bool))
           & jnp.tril(jnp.ones((t, t), bool))).astype(jnp.int8)
    scale = 128 ** -0.5
    ct = jax.random.normal(keys(1, 12)[0], q.shape)

    def mine(q, kk, v):
        out, lse = fa.flash_attention_with_lse(
            q, kk, v, causal=True, scale=scale, block_q=128, block_k=128,
            force_pallas=kernels, select=sel)
        return jnp.sum(out * ct), (out, lse)

    def plain(q, kk, v):
        return jnp.sum(masked_softmax_attention(q, kk, v, sel, scale) * ct)

    with jax.default_matmul_precision("highest"):
        (_, (out, lse)), got = jax.value_and_grad(
            mine, (0, 1, 2), has_aux=True)(q, kk, v)
        want = jax.grad(plain, (0, 1, 2))(q, kk, v)
        want_out = masked_softmax_attention(q, kk, v, sel, scale)
        s = jnp.einsum("bhqd,bhkd->bhqk", q,
                       jnp.repeat(kk, H // HKV, axis=1)) * scale
        want_lse = jax.scipy.special.logsumexp(
            jnp.where((sel != 0)[:, None], s, -jnp.inf), -1)
    assert rel(out, want_out) < 1e-5
    assert lse.shape == (b * H, t, 1)
    assert rel(lse[..., 0], want_lse.reshape(b * H, t)) < 1e-5
    for a, ref in zip(got, want):
        assert rel(a, ref) < 1e-5


@pytest.mark.parametrize("kernels", [False, True],
                         ids=["dense", "interpreted_kernels"])
def test_selecting_every_causal_key_is_the_unselected_op_bit_for_bit(kernels):
    b, t = 2, 256
    k = keys(3, 13)
    q = jax.random.normal(k[0], (b, H, t, 128), jnp.bfloat16)
    kk = jax.random.normal(k[1], (b, HKV, t, 128), jnp.bfloat16)
    v = jax.random.normal(k[2], (b, HKV, t, 128), jnp.bfloat16)
    every = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), jnp.int8)), (b, t, t))

    def run(select):
        def f(q, kk, v):
            out = fa.flash_attention(q, kk, v, causal=True, block_q=128,
                                     block_k=128, force_pallas=kernels,
                                     select=select)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        (_, out), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(
            q, kk, v)
        return (out,) + grads

    for a, ref in zip(run(every), run(None)):
        assert jnp.array_equal(a, ref)


def test_the_op_takes_a_selection_and_counts_its_form():
    from paddle_tpu import observability as obs

    q, k, v = attention_inputs(14)
    select = random_selection(15)
    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.dump()["counters"])
        got = op("flash_attention")(
            {"Q": q, "K": k, "V": v, "Select": select},
            {"causal": True, "scale": HD ** -0.5, "num_heads": 0})
        op("flash_attention")({"Q": q, "K": k, "V": v},
                              {"causal": True, "scale": 0.0, "num_heads": 0})
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    grown = {n: after[n] - before.get(n, 0) for n in after
             if n.startswith("kernels.flash_attention_select")
             and after[n] != before.get(n, 0)}
    assert grown == {"kernels.flash_attention_select{form=mask}": 1,
                     "kernels.flash_attention_select{form=none}": 1}
    with jax.default_matmul_precision("highest"):
        want = masked_softmax_attention(q, k, v, select, HD ** -0.5)
    assert rel(got["Out"], want) < 1e-5 and got["LSE"].shape == (2 * H, T, 1)
    # the grad op from the forward's Out and LSE, off the TPU: the dense VJP
    g = jax.random.normal(keys(1, 16)[0], q.shape)
    grads = op("flash_attention_grad")(
        {"Q": q, "K": k, "V": v, "Select": select, "Out": got["Out"],
         "LSE": got["LSE"], "Out@GRAD": g},
        {"causal": True, "scale": HD ** -0.5, "num_heads": 0})
    want_g = jax.grad(lambda *a: jnp.sum(masked_softmax_attention(
        *a, select, HD ** -0.5) * g), (0, 1, 2))(q, k, v)
    for name, ref in zip(("Q", "K", "V"), want_g):
        assert rel(grads[name + "@GRAD"], ref) < 1e-4, name


def test_a_selection_needs_whole_blocks_of_one_length():
    q, k, v = attention_inputs(17)
    with pytest.raises(ValueError, match="selection"):
        fa.flash_attention(q, k[:, :, :24], v[:, :, :24], causal=True,
                           force_pallas=True,
                           select=jnp.ones((2, T, 24), jnp.int8))


# -- softmax-routed gated experts ---------------------------------------------

MT, MD, ME, MF, MK = 256, 16, 16, 24, 3


def moe_inputs(seed=0):
    k = keys(5, seed)
    return (jax.random.normal(k[0], (MT, MD)),
            jax.random.normal(k[1], (MD, ME)),
            0.3 * jax.random.normal(k[2], (ME, MD, MF)),
            0.3 * jax.random.normal(k[3], (ME, MF, MD)),
            0.3 * jax.random.normal(k[4], (ME, MD, MF)))


def gated_layer(x, rw, gate, down, up, held=(0, ME), bias=None):
    """The reference's expert half without its norm and residual."""
    probs = jax.nn.softmax(x @ rw, -1)
    _, idx = jax.lax.top_k(probs if bias is None else probs + bias, MK)
    w = jnp.take_along_axis(probs, idx, -1)
    w = w / w.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(held[0], held[0] + held[1]):
        m = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        hidden = jax.nn.silu(x @ gate[e]) * (x @ up[e])
        out = out + m[:, None] * (hidden @ down[e])
    return out


def gated_part(x, rw, gate, down, up, first, count, bias=None):
    return moe_ops.moe_topk(
        x, rw, bias, gate[first:first + count], down[first:first + count],
        MK, [first, count], 1.0, scoring="softmax",
        w3=up[first:first + count])


def test_the_eight_shares_add_up_to_the_uncut_gated_layer():
    x, rw, gate, down, up = moe_inputs()
    with jax.default_matmul_precision("highest"):
        whole = gated_layer(x, rw, gate, down, up)
        parts, loads = zip(*(gated_part(x, rw, gate, down, up, first, 2)
                             for first in range(0, ME, 2)))
    assert rel(sum(parts), whole) < 1e-5
    assert int(sum(l[:2].sum() for l in loads)) == MT * MK
    assert not any(int(l[2]) for l in loads)


@pytest.mark.parametrize("favoured,slow", [([1], 0), ([0, 1, 2], 1)])
def test_the_gated_expert_under_full_skew_takes_the_overflow_branch(
        favoured, slow):
    x, rw, gate, down, up = moe_inputs(1)
    bias = jnp.zeros((ME,)).at[:4].set(-100.0).at[
        jnp.array(favoured)].set(100.0)
    with jax.default_matmul_precision("highest"):
        got, load = gated_part(x, rw, gate, down, up, 0, 4, bias)
        want = gated_layer(x, rw, gate, down, up, (0, 4), bias)
    assert [int(load[e]) for e in favoured] == [MT] * len(favoured)
    assert int(load[4]) == slow
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("count", [ME, 4])
@pytest.mark.parametrize("skewed", [False, True])
def test_softmax_swiglu_grad_op_is_the_layers_gradient(skewed, count):
    """With every expert held, the layer's gradient, the router's too; with
    part of them the router's weight takes none, under softmax as under
    sigmoid."""
    x, rw, gate, down, up = moe_inputs(3)
    bias = jnp.zeros((ME,))
    if skewed:
        bias = bias.at[:3].set(100.0)
    g = jax.random.normal(keys(1, 9)[0], x.shape)
    attrs = {"k": MK, "held": [0, count], "scaling": 1.0, "norm_topk": True,
             "scoring": "softmax"}
    with jax.default_matmul_precision("highest"):
        got = op("moe_topk_grad")(
            {"X": x, "RouterW": rw, "Bias": bias, "W1": gate[:count],
             "W2": down[:count], "W3": up[:count], "Out@GRAD": g}, attrs)
        want = jax.grad(
            lambda x, rw, a, b, c: jnp.sum(gated_layer(
                x, rw, a, b, c, (0, count), bias) * g), (0, 1, 2, 3, 4))(
                    x, rw, gate[:count], down[:count], up[:count])
    if count < ME:
        assert not np.asarray(got.pop("RouterW@GRAD")).any()
        want = (want[0], None) + want[2:]
    for name, ref in zip(("X", "RouterW", "W1", "W2", "W3"), want):
        assert ref is None or rel(got[name + "@GRAD"], ref) < 1e-5, name


def test_sigmoid_relu2_is_unchanged_and_the_attrs_are_checked():
    """The default attrs are the op as it was: sigmoid scores, two matrices
    (the expert's form is read from whether W3 is bound); an unknown scoring,
    and in the layer an unknown expert form, is refused."""
    x, rw, w1, w2, w3 = moe_inputs(4)
    bias = 0.1 * jax.random.normal(keys(1, 5)[0], (ME,))
    ins = {"X": x, "RouterW": rw, "Bias": bias, "W1": w1[:4], "W2": w2[:4]}
    base = {"k": MK, "held": [0, 4], "scaling": 2.5, "norm_topk": True}
    with jax.default_matmul_precision("highest"):
        got = op("moe_topk")(ins, base)
        named = op("moe_topk")(ins, {**base, "scoring": "sigmoid"})
        s = jax.nn.sigmoid(x @ rw)
        _, idx = jax.lax.top_k(s + bias, MK)
        w = jnp.take_along_axis(s, idx, -1)
        w = 2.5 * w / w.sum(-1, keepdims=True)
        want = sum(jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
                   * (jnp.square(jax.nn.relu(x @ w1[e])) @ w2[e])
                   for e in range(4))
    assert jnp.array_equal(got["Out"], named["Out"])
    assert rel(got["Out"], want) < 1e-5
    with pytest.raises(ValueError, match="scoring"):
        op("moe_topk")(ins, {**base, "scoring": "tanh"})
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        tokens = fluid.layers.data("tokens", [8, MD], "float32",
                                   append_batch_size=False)
        with pytest.raises(ValueError, match="expert"):
            fluid.layers.moe_topk(tokens, ME, MK, 4, expert="geglu")


# -- the model ----------------------------------------------------------------

def test_amp_keeps_what_sets_a_choice_float32():
    cfg, traffic, model, _ = tiny()
    block = model.build_static(cfg, traffic)["main"].global_block()

    def dtypes(op_type):
        o = next(o for o in block.ops if o.type == op_type)
        return {slot: str(block._find_var_recursive(names[0]).dtype)
                for slot, names in o.inputs.items()}

    assert set(dtypes("attn_index_project").values()) == {"float32", "int32"}
    assert set(dtypes("attn_index_select").values()) == {"float32"}
    assert dtypes("attn_index_loss") == {
        "QI": "float32", "KI": "float32", "W": "float32", "Select": "int8",
        "Q": "bfloat16", "K": "bfloat16", "LSE": "float32"}
    assert dtypes("flash_attention") == {
        "Q": "bfloat16", "K": "bfloat16", "V": "bfloat16", "Select": "int8"}
    assert dtypes("moe_topk") == {
        "X": "float32", "RouterW": "float32", "Bias": "float32",
        "W1": "bfloat16", "W2": "bfloat16", "W3": "bfloat16"}
    assert dtypes("rotary_embedding")["X"] == "float32"
    types = [o.type for o in block.ops]
    for grad in ("attn_index_loss_grad", "attn_index_project_grad",
                 "flash_attention_grad", "rotary_embedding_grad",
                 "moe_topk_grad"):
        assert grad in types
    # the selection has no gradient op, and is made again under recomputation
    assert "attn_index_select_grad" not in types
    assert types.count("attn_index_select") == 4


@pytest.mark.parametrize("recompute", [False, True])
def test_tiny_model_follows_the_plain_reference_for_three_steps(recompute):
    """``models.hybrid_ssm_moe`` over ``S`` and ``E`` mixers through
    ``fluid.Executor`` with bf16 AMP and Adam, with and without
    recomputation, unequal position components, against the float32
    reference's three steps: the comparison that decides a cell's
    ``correct``."""
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
    pos = unequal_positions(traffic["batch"], traffic["seq_len"])
    batches = [dict(reference.make_batch(k, cfg, traffic), pos=pos)
               for k in keys(3, 4)]
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
                layer_loads = out[1:]
                grads = {leaf: built["moment_scale"] * float(jnp.linalg.norm(
                    array(built["moment"] % name)))
                    for leaf, name in built["leaves"].items()}
        delta = {leaf: float(jnp.linalg.norm(array(name) - kept[leaf]))
                 for leaf, name in built["leaves"].items()}
    # 2 x 32 tokens x 3 slots x 4 of 16 experts = 48 expected a layer
    for load in layer_loads:
        assert 15 < int(load[:4].sum()) < 110 and int(load[4]) == 0
    # the indexer learns from its own loss; the routers stay where they are
    assert grads["l0.idx.q"] > 0 and grads["l2.idx.w"] > 0
    assert grads["l1.router"] == 0 and delta["l3.router"] == 0
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], lambda k: reference.init_params(k, cfg),
                 key, batches, None, identity)
    rows = check.compare({"losses": losses, "grad_norms": grads,
                          "delta_norms": delta}, ref, traffic["limits"])
    assert all(ok for *_, ok, _ in rows), rows


def test_the_indexer_learns_from_its_loss_alone():
    """In the reference as in the program: the model's cross entropy gives
    the indexer's leaves no gradient, the indexer's loss gives no other leaf
    one, and the routers (part of the experts held) take none at all."""
    cfg, traffic, _, reference = tiny()
    params = reference.init_params(jax.random.key(1), cfg)
    batch = reference.make_batch(jax.random.key(2), cfg, traffic)

    def index_loss(p):
        return reference._attention_half(
            p["emb"][batch["src"]], reference._of_layer(p, 0), batch["pos"],
            cfg, jnp.matmul, lambda z: z)[1]

    g_total = jax.jit(jax.grad(
        lambda p: reference.loss(p, batch, cfg)))(params)
    g_index = jax.jit(jax.grad(index_loss))(params)
    for leaf, g in g_index.items():
        moved = bool(jnp.any(g != 0))
        assert moved == leaf.startswith("l0.idx."), leaf
    for leaf, g in g_total.items():
        if leaf.endswith("router"):
            assert not bool(jnp.any(g != 0)), leaf
        else:
            assert bool(jnp.any(g != 0)), leaf


def test_the_eight_shares_and_the_attention_half_once_add_up_to_the_layer():
    """The share test: one published layer (attention half, then experts) of
    the uncut reference, every expert held, against the attention half
    counted once plus the eight shares of two experts that the program's
    ``moe_topk`` computes from the same normed hidden state."""
    cfg, traffic, _, reference = tiny()
    cfg = dict(cfg, num_experts_held=cfg["num_experts"])
    params = reference.init_params(jax.random.key(6), cfg)
    batch = reference.make_batch(jax.random.key(7), cfg, traffic)
    batch["pos"] = unequal_positions(traffic["batch"], traffic["seq_len"])
    p0, p1 = reference._of_layer(params, 0), reference._of_layer(params, 1)
    x = params["emb"][batch["src"]]
    with jax.default_matmul_precision("highest"):
        after, _ = reference._attention_half(x, p0, batch["pos"], cfg,
                                             jnp.matmul, lambda z: z)
        whole = reference._experts_half(after, p1, cfg, jnp.matmul)
        u = reference._rms_norm(after, p1["norm"], cfg["rms_norm_eps"])
        u2 = u.reshape(-1, u.shape[-1])
        shares = []
        for first in range(0, cfg["num_experts"], 2):     # 8 shares of 2
            out, load = moe_ops.moe_topk(
                u2, p1["router"], None, p1["gate"][first:first + 2],
                p1["down"][first:first + 2], cfg["num_experts_per_tok"],
                [first, 2], 1.0, norm_topk=cfg["norm_topk_prob"],
                scoring="softmax", w3=p1["up"][first:first + 2])
            assert int(load[2]) == 0
            shares.append(out.reshape(u.shape))
    assert len(shares) == 8
    assert rel(after + sum(shares), whole) < 1e-5
    # a share alone is not the layer: the others' part is really missing
    assert rel(after + shares[0], whole) > 1e-2
