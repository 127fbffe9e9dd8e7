"""Pallas implicit-GEMM conv kernel vs XLA oracle (interpret mode).

The kernel (ops/pallas/conv.py) is the round-5 conv experiment
(BASELINE.md): exact conv + fused scale/shift/residual/relu for the
ResNet NHWC shape class, routed behind FLAGS_use_pallas_conv.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.ops.pallas.conv import (
    conv2d_bn_act, pallas_conv, pallas_conv_viable, route_pallas)


def _xla(x, w, s, p):
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "HWIO", "NHWC"))
    return lax.conv_general_dilated(
        x, w, (s, s), [(p, p), (p, p)], dimension_numbers=dn)


@pytest.mark.parametrize("case", [
    # (B, H, Cin, Cout, K, stride, pad, relu, residual)
    (2, 8, 128, 128, 3, 1, 1, True, False),
    (2, 8, 128, 256, 1, 1, 0, False, False),
    (2, 16, 128, 128, 3, 2, 1, True, True),
    (1, 8, 256, 128, 1, 2, 0, False, False),
])
def test_kernel_matches_xla(case):
    B, H, C1, C2, K, s, p, relu, res = case
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, H, H, C1).astype("float32"))
    w = jnp.asarray((rng.randn(K, K, C1, C2) * 0.1).astype("float32"))
    sc = rng.rand(C2).astype("float32") + 0.5
    sh = rng.randn(C2).astype("float32")
    Ho = (H + 2 * p - K) // s + 1
    r = (jnp.asarray(rng.randn(B, Ho, Ho, C2).astype("float32"))
         if res else None)
    ref = np.asarray(_xla(x, w, s, p)) * sc + sh
    if res:
        ref = ref + np.asarray(r)
    if relu:
        ref = np.maximum(ref, 0)
    got = conv2d_bn_act(x, w, sc, sh, stride=s, padding=p, relu=relu,
                        residual=r, interpret=True)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                               atol=2e-5)


def test_grads_match_xla_vjp():
    """pallas_conv's custom_vjp (XLA transpose-conv backward) must
    agree with differentiating the XLA conv directly."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(2, 8, 8, 128).astype("float32"))
    w = jnp.asarray((rng.randn(3, 3, 128, 128) * 0.1).astype("float32"))
    ct = jnp.asarray(rng.randn(2, 8, 8, 128).astype("float32"))

    def loss_pallas(x, w):
        return jnp.sum(pallas_conv(x, w, 1, 1) * ct)

    def loss_xla(x, w):
        return jnp.sum(_xla(x, w, 1, 1) * ct)

    gp = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    gx = jax.grad(loss_xla, argnums=(0, 1))(x, w)
    for a, b, name in zip(gp, gx, "xw"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg="d%s mismatch" % name)


def test_routing_decision():
    x = (128, 56, 56, 256)
    expansion = (1, 1, 256, 1024)   # the measured-win class
    reduction = (1, 1, 1024, 256)
    conv3 = (3, 3, 256, 256)
    stem = (7, 7, 3, 64)
    assert route_pallas("auto", x, expansion, 1, 1, [1, 1], "NHWC")
    assert not route_pallas("auto", x, reduction, 1, 1, [1, 1], "NHWC")
    assert not route_pallas("auto", x, conv3, 1, 1, [1, 1], "NHWC")
    assert not route_pallas("off", x, expansion, 1, 1, [1, 1], "NHWC")
    assert route_pallas("all", x, conv3, 1, 1, [1, 1], "NHWC")
    # viability gates
    assert not pallas_conv_viable(x, stem, 2, 1, [1, 1], "NHWC")
    assert not pallas_conv_viable(x, expansion, 1, 2, [1, 1], "NHWC")
    assert not pallas_conv_viable(x, expansion, 1, 1, [2, 2], "NHWC")
    assert not pallas_conv_viable(x, expansion, 1, 1, [1, 1], "NCHW")
    assert not pallas_conv_viable(x, expansion, 3, 1, [1, 1], "NHWC")
