"""Expert-parallel MoE vs single-device oracle (virtual 8-dev mesh)."""
import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.parallel.mesh_utils import make_mesh
from paddle_tpu.parallel.moe import expert_parallel_moe, moe_reference

N = 4
T_LOCAL, D, H, E_LOCAL = 8, 6, 10, 2
T, E = T_LOCAL * N, E_LOCAL * N


def _inputs(seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(T, D).astype("float32")),
            jnp.asarray(rng.randn(D, E).astype("float32")),
            jnp.asarray(rng.randn(E, D, H).astype("float32") * 0.3),
            jnp.asarray(rng.randn(E, H, D).astype("float32") * 0.3))


def _sharded(cf=2.0):
    mesh = make_mesh([N], ["ep"])

    def local(x, gate_w, w_in, w_out):
        return expert_parallel_moe(x, gate_w, w_in, w_out, "ep", cf, N)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P("ep"), P(), P("ep"), P("ep")),
                         out_specs=P("ep"), check_vma=False)


def test_matches_oracle():
    x, gw, wi, wo = _inputs(0)
    got = np.asarray(jax.jit(_sharded())(x, gw, wi, wo))
    ref = np.asarray(moe_reference(x, gw, wi, wo, 2.0, N))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


def test_capacity_drop_semantics():
    # tiny capacity: overflow tokens must drop identically in both paths
    x, gw, wi, wo = _inputs(1)
    got = np.asarray(jax.jit(_sharded(cf=0.25))(x, gw, wi, wo))
    ref = np.asarray(moe_reference(x, gw, wi, wo, 0.25, N))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
    # some tokens were dropped (zero rows) at this capacity
    assert (np.abs(got).sum(axis=1) == 0).any()


def test_expert_grads_flow():
    x, gw, wi, wo = _inputs(2)
    smap = _sharded()

    def loss(wi, wo):
        return (smap(x, gw, wi, wo) ** 2).sum()

    def loss_ref(wi, wo):
        return (moe_reference(x, gw, wi, wo, 2.0, N) ** 2).sum()

    g = jax.grad(loss, argnums=(0, 1))(wi, wo)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(wi, wo)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
        assert np.abs(np.asarray(a)).sum() > 0
