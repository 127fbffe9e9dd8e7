"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Runs on the virtual 8-device CPU mesh (conftest.py). Oracle is dense
single-device attention; the parallel paths must match it to float32
tolerances (the math is exact, not approximate).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.mesh_utils import make_mesh
from paddle_tpu.parallel.ring_attention import (
    reference_attention, ring_attention, sequence_parallel_attention,
    ulysses_attention)

B, H, S, D = 2, 8, 32, 16  # S sharded 8-way -> S_local = 4


def _inputs(seed=0, dtype="float32"):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, S, D).astype(dtype))
    k = jnp.asarray(rng.randn(B, H, S, D).astype(dtype))
    v = jnp.asarray(rng.randn(B, H, S, D).astype(dtype))
    return q, k, v


@pytest.fixture(scope="module")
def mesh():
    return make_mesh([8], ["sp"])


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(mesh, causal):
    q, k, v = _inputs(0)
    ref = reference_attention(q, k, v, causal=causal)
    out = sequence_parallel_attention(q, k, v, mesh, "sp", mode="ring",
                                      causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(mesh, causal):
    q, k, v = _inputs(1)
    ref = reference_attention(q, k, v, causal=causal)
    out = sequence_parallel_attention(q, k, v, mesh, "sp", mode="ulysses",
                                      causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_smoke(mesh):
    q, k, v = _inputs(2, "float32")
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = sequence_parallel_attention(qb, kb, vb, mesh, "sp", causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), np.asarray(ref),
        rtol=5e-2, atol=5e-2)


def test_ring_differentiable(mesh):
    """Grads flow through the ppermute ring (training, not just serving)."""
    q, k, v = _inputs(3)

    def loss(q, k, v):
        out = sequence_parallel_attention(q, k, v, mesh, "sp", causal=True)
        return (out.astype(jnp.float32) ** 2).sum()

    def loss_ref(q, k, v):
        out = reference_attention(q, k, v, causal=True)
        return (out.astype(jnp.float32) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_dp_sp_2d_mesh():
    """dp x sp 2-D mesh: batch and sequence sharded simultaneously."""
    from jax.sharding import PartitionSpec as P

    mesh2 = make_mesh([2, 4], ["dp", "sp"])
    q, k, v = _inputs(4)

    def local(q, k, v):
        return ring_attention(q, k, v, "sp", causal=True, axis_size=4)

    spec = P("dp", None, "sp", None)
    smap = jax.shard_map(local, mesh=mesh2, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)
    out = jax.jit(smap)(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mode", ["ring", "ulysses"])
def test_masked_sequence_parallel_matches_dense(mesh, causal, mode):
    """Per-example GLOBAL lengths (the padding mask of the masked flash
    kernels) under sequence parallelism: visible QUERY rows must match
    the dense masked oracle."""
    rng = np.random.RandomState(7)
    B, H, S, D = 2, 8, 32, 8
    q = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, H, S, D).astype("float32"))
    lengths = jnp.asarray([32, 13], dtype=jnp.int32)
    out = sequence_parallel_attention(q, k, v, mesh, "sp", mode=mode,
                                      causal=causal, lengths=lengths)
    ref = reference_attention(q, k, v, causal=causal, lengths=lengths)
    row_ok = np.zeros((B, 1, S, 1), "float32")
    row_ok[0, :, :32] = 1.0
    row_ok[1, :, :13] = 1.0
    np.testing.assert_allclose(np.asarray(out) * row_ok,
                               np.asarray(ref) * row_ok,
                               rtol=2e-5, atol=2e-5)


def test_masked_flash_routes_ring_on_program_path():
    """flash_attention WITH kv_lengths transpiles to masked ring
    attention (the r5 NotImplementedError removed): Program-path loss
    parity vs the dense single-device run."""
    import paddle_tpu as fluid
    from __graft_entry__ import _program_parity_step
    from paddle_tpu.incubate.fleet.collective import (
        CollectiveOptimizer, DistributedStrategy)

    sp, dp = 4, 2
    B, H, S, D = 2 * dp, 4, 8 * sp, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data(name="x", shape=[B, H, S, D], dtype="float32")
        tgt = fluid.data(name="tgt", shape=[B, H, S, D],
                         dtype="float32")
        lens = fluid.data(name="lens", shape=[B], dtype="int32")
        w = fluid.layers.create_parameter([D, D], "float32",
                                          name="w_q2")
        qv = fluid.layers.matmul(x, w)
        o = fluid.layers.flash_attention(qv, x, x, causal=True,
                                         lengths=lens)
        # KEY masking only: every query row still attends its visible
        # keys (lens >= S/2 > 0), so the plain MSE is well-defined and
        # identical on both paths — no query-row loss mask needed
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(fluid.layers.elementwise_sub(o, tgt)))
        strat = DistributedStrategy()
        strat.sequence_parallel = True
        strat.sp_degree = sp
        strat.feed_shard_specs = {"x": ("dp", None, "sp"),
                                  "tgt": ("dp", None, "sp")}
        CollectiveOptimizer(
            fluid.optimizer.SGDOptimizer(0.05), strat).minimize(loss)
    assert any(op.type == "c_ring_attention"
               for op in main.global_block().ops)
    rng = np.random.RandomState(5)
    feed = {"x": rng.randn(B, H, S, D).astype("float32"),
            "tgt": rng.randn(B, H, S, D).astype("float32"),
            "lens": rng.randint(S // 2, S + 1, (B,)).astype("int32")}
    l_dense, l_mesh, p_dense, p_mesh = _program_parity_step(
        main, startup, loss, feed,
        make_mesh([dp, sp], ["dp", "sp"]))
    assert np.isfinite(l_dense) and np.isfinite(l_mesh)
    assert abs(l_dense - l_mesh) / max(abs(l_dense), 1e-6) < 1e-4
    np.testing.assert_allclose(p_mesh["w_q2"], p_dense["w_q2"],
                               rtol=1e-4, atol=1e-6)


def test_zero_length_examples_consistent(mesh):
    """An all-padding example outputs ZEROS on every path (ring,
    ulysses, dense oracle) — the masked flash kernels' contract."""
    rng = np.random.RandomState(9)
    Bm = 2
    q = jnp.asarray(rng.randn(Bm, H, S, D).astype("float32"))
    lengths = jnp.asarray([S, 0], dtype=jnp.int32)
    ref = reference_attention(q, q, q, lengths=lengths)
    assert np.all(np.asarray(ref)[1] == 0)
    for mode in ("ring", "ulysses"):
        out = sequence_parallel_attention(q, q, q, mesh, "sp",
                                          mode=mode, lengths=lengths)
        assert np.all(np.asarray(out)[1] == 0), mode
        np.testing.assert_allclose(np.asarray(out)[0],
                                   np.asarray(ref)[0],
                                   rtol=2e-5, atol=2e-5)
