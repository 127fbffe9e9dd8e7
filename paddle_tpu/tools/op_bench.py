"""Per-op device microbenchmark harness.

Parity: /root/reference/paddle/fluid/operators/benchmark/op_tester.cc
(config-driven single-op timing) and operators/jit/benchmark.cc — the
producer for BASELINE.md's "track per-op TPU timings" row.

Usage:
    python -m paddle_tpu.tools.op_bench                 # hot-op table
    python -m paddle_tpu.tools.op_bench --op=conv2d     # one op
    python -m paddle_tpu.tools.op_bench --repeat=50 --json

Each case builds the single op as a jitted XLA callable on the default
device, runs `repeat` timed iterations after warmup, and reports the
per-call wall time with a device sync per timing window (one d2h
fetch).
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

# (name, op_type, input builder -> {slot: array}, attrs)
# the 20 hottest op configs across the five north-star models
_F32 = "float32"


def _rng():
    return np.random.RandomState(0)


def _cases():
    r = _rng()
    B = 64
    return [
        ("matmul_512", "matmul",
         {"X": r.randn(B, 512).astype(_F32),
          "Y": r.randn(512, 512).astype(_F32)},
         {"transpose_X": False, "transpose_Y": False, "alpha": 1.0}),
        ("matmul_bert_ffn", "matmul",
         {"X": r.randn(32 * 128, 768).astype(_F32),
          "Y": r.randn(768, 3072).astype(_F32)},
         {"transpose_X": False, "transpose_Y": False, "alpha": 1.0}),
        ("mul_fc", "mul",
         {"X": r.randn(B, 2048).astype(_F32),
          "Y": r.randn(2048, 1000).astype(_F32)},
         {"x_num_col_dims": 1, "y_num_col_dims": 1}),
        ("conv2d_3x3_s1", "conv2d",
         {"Input": r.randn(B, 64, 56, 56).astype(_F32),
          "Filter": r.randn(64, 64, 3, 3).astype(_F32)},
         {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
          "groups": 1}),
        ("conv2d_1x1", "conv2d",
         {"Input": r.randn(B, 256, 56, 56).astype(_F32),
          "Filter": r.randn(64, 256, 1, 1).astype(_F32)},
         {"strides": [1, 1], "paddings": [0, 0], "dilations": [1, 1],
          "groups": 1}),
        ("conv2d_7x7_s2", "conv2d",
         {"Input": r.randn(B, 3, 224, 224).astype(_F32),
          "Filter": r.randn(64, 3, 7, 7).astype(_F32)},
         {"strides": [2, 2], "paddings": [3, 3], "dilations": [1, 1],
          "groups": 1}),
        ("batch_norm", "batch_norm",
         {"X": r.randn(B, 64, 56, 56).astype(_F32),
          "Scale": r.rand(64).astype(_F32),
          "Bias": r.rand(64).astype(_F32),
          "Mean": np.zeros(64, _F32),
          "Variance": np.ones(64, _F32)},
         {"epsilon": 1e-5, "momentum": 0.9, "is_test": True}),
        ("layer_norm", "layer_norm",
         {"X": r.randn(32 * 128, 768).astype(_F32),
          "Scale": r.rand(768).astype(_F32),
          "Bias": r.rand(768).astype(_F32)},
         {"epsilon": 1e-5, "begin_norm_axis": 1}),
        ("softmax_seq", "softmax",
         {"X": r.randn(32 * 12 * 128, 128).astype(_F32)}, {"axis": -1}),
        ("softmax_with_ce", "softmax_with_cross_entropy",
         {"Logits": r.randn(B, 1000).astype(_F32),
          "Label": r.randint(0, 1000, (B, 1)).astype("int64")},
         {"soft_label": False}),
        ("relu_large", "relu",
         {"X": r.randn(B, 256, 56, 56).astype(_F32)}, {}),
        ("gelu", "gelu",
         {"X": r.randn(32 * 128, 3072).astype(_F32)}, {}),
        ("elementwise_add_bcast", "elementwise_add",
         {"X": r.randn(B, 256, 56, 56).astype(_F32),
          "Y": r.randn(256).astype(_F32)}, {"axis": 1}),
        ("lookup_table", "lookup_table_v2",
         {"W": r.randn(30522, 768).astype(_F32),
          "Ids": r.randint(0, 30522, (32, 128)).astype("int64")},
         {"padding_idx": -1}),
        ("dropout", "dropout",
         {"X": r.randn(32 * 128, 768).astype(_F32)},
         {"dropout_prob": 0.1, "is_test": False,
          "dropout_implementation": "upscale_in_train", "seed": 7}),
        ("reduce_mean", "reduce_mean",
         {"X": r.randn(B, 256, 56, 56).astype(_F32)},
         {"dim": [2, 3], "keep_dim": False}),
        ("transpose_attn", "transpose2",
         {"X": r.randn(32, 128, 12, 64).astype(_F32)},
         {"axis": [0, 2, 1, 3]}),
        ("pool2d_avg_global", "pool2d",
         {"X": r.randn(B, 2048, 7, 7).astype(_F32)},
         {"pooling_type": "avg", "global_pooling": True,
          "ksize": [1, 1]}),
        ("adam_update", "adam",
         {"Param": r.randn(2048, 1000).astype(_F32),
          "Grad": r.randn(2048, 1000).astype(_F32),
          "LearningRate": np.array([1e-3], _F32),
          "Moment1": np.zeros((2048, 1000), _F32),
          "Moment2": np.zeros((2048, 1000), _F32),
          "Beta1Pow": np.array([0.9], _F32),
          "Beta2Pow": np.array([0.999], _F32)},
         {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}),
        ("topk", "top_k",
         {"X": r.randn(B, 1000).astype(_F32)}, {"k": 5}),
    ]


def bench_op(op_type, inputs, attrs, repeat=30, warmup=5):
    """Time one op as a jitted callable; returns (mean_us, result)."""
    import jax
    import jax.numpy as jnp

    from ..core.registry import (BOUND_OUTPUTS_ATTR, RNG_SEED_ATTR,
                                 OpInfoMap)

    info = OpInfoMap.instance().get(op_type)
    attrs = dict(attrs)
    attrs[BOUND_OUTPUTS_ATTR] = tuple(s.name for s in info.outputs)
    dev_inputs = {k: jax.device_put(jnp.asarray(v))
                  for k, v in inputs.items()}
    if info.needs_rng:
        dev_inputs[RNG_SEED_ATTR] = jnp.uint32(attrs.get("seed", 7))

    def call(ins):
        outs = info.fn(ins, attrs)
        return [v for v in outs.values() if v is not None]

    fn = jax.jit(call)
    outs = fn(dev_inputs)
    for _ in range(warmup):
        outs = fn(dev_inputs)
    np.asarray(outs[0]).ravel()[:1]  # sync point
    t0 = time.perf_counter()
    for _ in range(repeat):
        outs = fn(dev_inputs)
    np.asarray(outs[0]).ravel()[:1]  # d2h = the hard sync
    dt = (time.perf_counter() - t0) / repeat
    return dt * 1e6


def main(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.tools.op_bench")
    p.add_argument("--op", default=None,
                   help="bench only cases whose op type matches")
    p.add_argument("--repeat", type=int, default=30)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    import jax

    device = str(jax.devices()[0])
    rows = []
    for name, op_type, inputs, attrs in _cases():
        if args.op and args.op != op_type:
            continue
        try:
            us = bench_op(op_type, inputs, attrs, repeat=args.repeat)
            rows.append({"case": name, "op": op_type,
                         "mean_us": round(us, 1)})
        except Exception as e:  # keep the table going
            rows.append({"case": name, "op": op_type,
                         "error": repr(e)[:120]})
    if args.json:
        print(json.dumps({"device": device, "repeat": args.repeat,
                          "cases": rows}))
    else:
        print("device: %s   repeat: %d" % (device, args.repeat))
        print("%-22s %-28s %12s" % ("case", "op", "mean_us"))
        for r in rows:
            print("%-22s %-28s %12s"
                  % (r["case"], r["op"],
                     r.get("mean_us", "ERR: " + r.get("error", "?"))))
    return rows


if __name__ == "__main__":
    main()
