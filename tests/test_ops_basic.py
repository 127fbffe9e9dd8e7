"""Operator unit tests, OpTest style (reference test files:
tests/unittests/test_elementwise_add_op.py, test_mul_op.py,
test_softmax_op.py, test_conv2d_op.py, test_pool2d_op.py, ...)."""
import numpy as np
import pytest

from op_test import OpTest


class TestElementwiseAdd(OpTest):
    op_type = "elementwise_add"

    def setUp(self):
        x = np.random.rand(3, 4).astype("float32")
        y = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {}
        self.outputs = {"Out": x + y}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "Out")


class TestElementwiseAddBcastAxis(OpTest):
    op_type = "elementwise_add"

    def setUp(self):
        x = np.random.rand(2, 3, 4, 5).astype("float32")
        y = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": x + y.reshape(1, 3, 4, 1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "Out", max_relative_error=0.01)


class TestMul(OpTest):
    op_type = "mul"

    def setUp(self):
        x = np.random.rand(4, 5).astype("float32")
        y = np.random.rand(5, 3).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"x_num_col_dims": 1, "y_num_col_dims": 1}
        self.outputs = {"Out": x @ y}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x", "y"], "Out", max_relative_error=0.01)


class TestMulFlatten(OpTest):
    op_type = "mul"

    def setUp(self):
        x = np.random.rand(2, 3, 4).astype("float32")
        y = np.random.rand(4, 6).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"x_num_col_dims": 2, "y_num_col_dims": 1}
        self.outputs = {"Out": (x.reshape(6, 4) @ y).reshape(2, 3, 6)}

    def test_output(self):
        self.check_output()


class TestMatmulTrans(OpTest):
    op_type = "matmul"

    def setUp(self):
        x = np.random.rand(5, 4).astype("float32")
        y = np.random.rand(3, 5).astype("float32")
        self.inputs = {"X": x, "Y": y}
        self.attrs = {"transpose_X": True, "transpose_Y": True, "alpha": 2.0}
        self.outputs = {"Out": 2.0 * (x.T @ y.T)}

    def test_output(self):
        self.check_output(atol=1e-4, rtol=1e-4)


class TestSoftmax(OpTest):
    op_type = "softmax"

    def setUp(self):
        x = np.random.rand(4, 7).astype("float32")
        e = np.exp(x - x.max(-1, keepdims=True))
        self.inputs = {"X": x}
        self.attrs = {}
        self.outputs = {"Out": e / e.sum(-1, keepdims=True)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestRelu(OpTest):
    op_type = "relu"

    def setUp(self):
        x = np.random.uniform(-1, 1, (4, 5)).astype("float32")
        x[np.abs(x) < 0.05] = 0.2  # keep FD away from the kink
        self.inputs = {"X": x}
        self.attrs = {}
        self.outputs = {"Out": np.maximum(x, 0)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestSigmoidTanhGrads(OpTest):
    op_type = "sigmoid"

    def setUp(self):
        x = np.random.uniform(-2, 2, (3, 4)).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {}
        self.outputs = {"Out": 1 / (1 + np.exp(-x))}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestReduceSum(OpTest):
    op_type = "reduce_sum"

    def setUp(self):
        x = np.random.rand(3, 4, 5).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"dim": [1], "keep_dim": False, "reduce_all": False}
        self.outputs = {"Out": x.sum(axis=1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestReduceMeanAll(OpTest):
    op_type = "reduce_mean"

    def setUp(self):
        x = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"dim": [], "reduce_all": True}
        self.outputs = {"Out": np.asarray(x.mean())}

    def test_output(self):
        self.check_output()


class TestConcat(OpTest):
    op_type = "concat"

    def setUp(self):
        a = np.random.rand(2, 3).astype("float32")
        b = np.random.rand(2, 4).astype("float32")
        self.inputs = {"X": [("a", a), ("b", b)]}
        self.attrs = {"axis": 1}
        self.outputs = {"Out": np.concatenate([a, b], axis=1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["a", "b"], "Out", max_relative_error=0.01)


class TestSplit(OpTest):
    op_type = "split"

    def setUp(self):
        x = np.random.rand(4, 6).astype("float32")
        o = np.split(x, [2, 4], axis=1)
        self.inputs = {"X": x}
        self.attrs = {"sections": [2, 2, 2], "axis": 1, "num": 0}
        self.outputs = {"Out": [("o0", o[0]), ("o1", o[1]), ("o2", o[2])]}

    def test_output(self):
        self.check_output()


class TestReshape2(OpTest):
    op_type = "reshape2"

    def setUp(self):
        x = np.random.rand(2, 12).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"shape": [2, 3, 4]}
        self.outputs = {"Out": x.reshape(2, 3, 4),
                        "XShape": np.zeros((0, 2, 12), dtype="float32")}

    def test_output(self):
        self.check_output(no_check_set={"xshape"})

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestTranspose2(OpTest):
    op_type = "transpose2"

    def setUp(self):
        x = np.random.rand(2, 3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"axis": [1, 0, 2]}
        self.outputs = {"Out": x.transpose(1, 0, 2),
                        "XShape": np.zeros((0, 2, 3, 4), dtype="float32")}

    def test_output(self):
        self.check_output(no_check_set={"xshape"})


class TestConv2d(OpTest):
    op_type = "conv2d"

    def setUp(self):
        x = np.random.rand(2, 3, 5, 5).astype("float32")
        w = np.random.rand(4, 3, 3, 3).astype("float32")
        out = np.zeros((2, 4, 3, 3), dtype="float64")
        for n in range(2):
            for o in range(4):
                for i in range(3):
                    for j in range(3):
                        out[n, o, i, j] = np.sum(
                            x[n, :, i:i + 3, j:j + 3] * w[o])
        self.inputs = {"X": [("input", x)], "Filter": [("filter", w)]}
        # slot names must match op spec:
        self.inputs = {"Input": x, "Filter": w}
        self.attrs = {"strides": [1, 1], "paddings": [0, 0],
                      "dilations": [1, 1], "groups": 1}
        self.outputs = {"Output": out.astype("float32")}

    def test_output(self):
        self.check_output(atol=1e-4, rtol=1e-4)

    def test_grad(self):
        self.check_grad(["input", "filter"], "Output",
                        max_relative_error=0.02)


class TestPool2dMax(OpTest):
    op_type = "pool2d"

    def setUp(self):
        x = np.random.rand(2, 3, 4, 4).astype("float32")
        out = x.reshape(2, 3, 2, 2, 2, 2).max(axis=(3, 5))
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "max", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0]}
        self.outputs = {"Out": out}

    def test_output(self):
        self.check_output()


class TestPool2dAvg(OpTest):
    op_type = "pool2d"

    def setUp(self):
        x = np.random.rand(2, 3, 4, 4).astype("float32")
        out = x.reshape(2, 3, 2, 2, 2, 2).mean(axis=(3, 5))
        self.inputs = {"X": x}
        self.attrs = {"pooling_type": "avg", "ksize": [2, 2],
                      "strides": [2, 2], "paddings": [0, 0],
                      "exclusive": True}
        self.outputs = {"Out": out}

    def test_output(self):
        self.check_output()


class TestCrossEntropy(OpTest):
    op_type = "cross_entropy"

    def setUp(self):
        x = np.random.rand(5, 7).astype("float32")
        x = x / x.sum(-1, keepdims=True)
        label = np.random.randint(0, 7, (5, 1)).astype("int64")
        out = -np.log(x[np.arange(5), label[:, 0]]).reshape(5, 1)
        self.inputs = {"X": x, "Label": label}
        self.attrs = {}
        self.outputs = {"Y": out}

    def test_output(self):
        self.check_output(atol=1e-5)


class TestSoftmaxWithCrossEntropy(OpTest):
    op_type = "softmax_with_cross_entropy"

    def setUp(self):
        logits = np.random.rand(5, 7).astype("float32")
        label = np.random.randint(0, 7, (5, 1)).astype("int64")
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(5), label[:, 0]]).reshape(5, 1)
        self.inputs = {"Logits": logits, "Label": label}
        self.attrs = {}
        self.outputs = {"Softmax": sm, "Loss": loss}

    def test_output(self):
        self.check_output(atol=1e-5)

    def test_grad(self):
        self.check_grad(["logits"], "Loss", max_relative_error=0.01)


class TestLookupTable(OpTest):
    op_type = "lookup_table"

    def setUp(self):
        w = np.random.rand(10, 4).astype("float32")
        ids = np.random.randint(0, 10, (5, 1)).astype("int64")
        self.inputs = {"W": w, "Ids": ids}
        self.attrs = {}
        self.outputs = {"Out": w[ids[:, 0]]}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["w"], "Out", max_relative_error=0.01)


class TestTopK(OpTest):
    op_type = "top_k"

    def setUp(self):
        x = np.random.rand(4, 8).astype("float32")
        idx = np.argsort(-x, axis=1)[:, :3]
        vals = np.take_along_axis(x, idx, axis=1)
        self.inputs = {"X": x}
        self.attrs = {"k": 3}
        self.outputs = {"Out": vals, "Indices": idx.astype("int64")}

    def test_output(self):
        self.check_output()


class TestOneHot(OpTest):
    op_type = "one_hot"

    def setUp(self):
        x = np.array([[1], [3], [0]]).astype("int64")
        out = np.zeros((3, 4), dtype="float32")
        out[np.arange(3), x[:, 0]] = 1.0
        self.inputs = {"X": x}
        self.attrs = {"depth": 4}
        self.outputs = {"Out": out}

    def test_output(self):
        self.check_output()


class TestCast(OpTest):
    op_type = "cast"

    def setUp(self):
        x = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"in_dtype": 5, "out_dtype": 6}
        self.outputs = {"Out": x.astype("float64")}

    def test_output(self):
        self.check_output()


class TestScale(OpTest):
    op_type = "scale"

    def setUp(self):
        x = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": x}
        self.attrs = {"scale": 2.5, "bias": 1.0, "bias_after_scale": True}
        self.outputs = {"Out": x * 2.5 + 1.0}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestLayerNorm(OpTest):
    op_type = "layer_norm"

    def setUp(self):
        x = np.random.rand(3, 8).astype("float32")
        scale = np.random.rand(8).astype("float32")
        bias = np.random.rand(8).astype("float32")
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mean) / np.sqrt(var + 1e-5) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.attrs = {"epsilon": 1e-5, "begin_norm_axis": 1}
        self.outputs = {"Y": y, "Mean": mean.reshape(3),
                        "Variance": var.reshape(3)}

    def test_output(self):
        self.check_output(atol=1e-4, rtol=1e-4)

    def test_grad(self):
        self.check_grad(["x", "scale", "bias"], "Y",
                        max_relative_error=0.02)


class TestBatchNormInference(OpTest):
    op_type = "batch_norm"

    def setUp(self):
        x = np.random.rand(2, 3, 4, 4).astype("float32")
        scale = np.random.rand(3).astype("float32")
        bias = np.random.rand(3).astype("float32")
        mean = np.random.rand(3).astype("float32")
        var = np.random.rand(3).astype("float32") + 0.5
        y = (x - mean.reshape(1, 3, 1, 1)) / np.sqrt(
            var.reshape(1, 3, 1, 1) + 1e-5) * scale.reshape(1, 3, 1, 1) \
            + bias.reshape(1, 3, 1, 1)
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": mean, "Variance": var}
        self.attrs = {"is_test": True, "epsilon": 1e-5}
        self.outputs = {"Y": y}

    def test_output(self):
        self.check_output(atol=1e-4, rtol=1e-4)


class TestGather(OpTest):
    op_type = "gather"

    def setUp(self):
        x = np.random.rand(6, 3).astype("float32")
        idx = np.array([0, 2, 5]).astype("int64")
        self.inputs = {"X": x, "Index": idx}
        self.attrs = {}
        self.outputs = {"Out": x[idx]}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["x"], "Out", max_relative_error=0.01)


class TestSliceOp(OpTest):
    op_type = "slice"

    def setUp(self):
        x = np.random.rand(4, 5, 6).astype("float32")
        self.inputs = {"Input": x}
        self.attrs = {"axes": [0, 2], "starts": [1, 2], "ends": [3, 5],
                      "decrease_axis": []}
        self.outputs = {"Out": x[1:3, :, 2:5]}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["input"], "Out", max_relative_error=0.01)


class TestSum(OpTest):
    op_type = "sum"

    def setUp(self):
        a = np.random.rand(3, 4).astype("float32")
        b = np.random.rand(3, 4).astype("float32")
        c = np.random.rand(3, 4).astype("float32")
        self.inputs = {"X": [("a", a), ("b", b), ("c", c)]}
        self.attrs = {}
        self.outputs = {"Out": a + b + c}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["a", "b"], "Out", max_relative_error=0.01)


# ResNet's NHWC shapes (1x1 and 3x3, stride 1 and 2) through the conv2d
# op's one lowering, lax.conv_general_dilated, in bf16 as AMP runs it:
# (batch, size, c_in, c_out, kernel, stride, padding, dtype)
NHWC_CONVS = [
    (2, 8, 128, 128, 3, 1, 1, "bfloat16"),
    (2, 8, 128, 256, 1, 1, 0, "bfloat16"),
    (2, 16, 128, 128, 3, 2, 1, "bfloat16"),
    (1, 8, 256, 128, 1, 2, 0, "bfloat16"),
    (2, 8, 128, 128, 3, 1, 1, "float32"),
]


@pytest.mark.parametrize("case", NHWC_CONVS,
                         ids=["b%d_s%d_%dto%d_k%d_st%d_p%d_%s" % c
                              for c in NHWC_CONVS])
def test_conv2d_nhwc_and_its_gradients_match_float32_reference(case):
    """The registered conv2d and conv2d_grad ops on NHWC operands
    against a float32 convolution at ``highest`` precision and its
    ``jax.grad``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.core.registry import OpInfoMap

    b, size, c_in, c_out, k, stride, pad, dtype = case
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, size, size, c_in), dtype)
    w = jnp.asarray(rng.randn(c_out, c_in, k, k) * 0.1, dtype)   # OIHW
    out_size = (size + 2 * pad - k) // stride + 1
    ct = jnp.asarray(rng.randn(b, out_size, out_size, c_out), dtype)
    attrs = {"strides": [stride, stride], "paddings": [pad, pad],
             "dilations": [1, 1], "groups": 1, "data_format": "NHWC"}

    ops = OpInfoMap.instance()
    out = ops.get("conv2d").fn({"Input": x, "Filter": w}, attrs)["Output"]
    grads = ops.get("conv2d_grad").fn(
        {"Input": x, "Filter": w, "Output@GRAD": ct}, attrs)
    assert out.dtype == x.dtype and out.shape == ct.shape

    def reference(x, w):
        return lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            precision="highest")

    x32, w32, ct32 = (a.astype(jnp.float32) for a in (x, w, ct))
    want = reference(x32, w32)
    want_dx, want_dw = jax.grad(
        lambda x, w: jnp.sum(reference(x, w) * ct32), argnums=(0, 1))(
            x32, w32)

    # each result is rounded once to the operands' type: 2^-9 of its
    # size in bf16 (products exact, float32 accumulator)
    tol = 1e-2 if dtype == "bfloat16" else 1e-5
    for name, got, ref in (("out", out, want),
                           ("dx", grads["Input@GRAD"], want_dx),
                           ("dw", grads["Filter@GRAD"], want_dw)):
        got = np.asarray(got.astype(jnp.float32))
        assert got.shape == ref.shape, name
        gap = np.max(np.abs(got - np.asarray(ref))) / np.max(np.abs(ref))
        assert gap < tol, (name, gap)
