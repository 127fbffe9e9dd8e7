"""Native C API + C++ train demo.

Builds csrc (g++ baked into the image), then:
- drives libptcapi.so through ctypes: PD_NewPredictor on a model saved
  by save_inference_model, PD_PredictorRun vs the in-process predictor;
- runs the train_demo binary on a saved trainable program and checks
  its convergence exit code.
Both embed CPython, so they are exercised in SUBPROCESSES (ctypes
loading libptcapi into this pytest process would re-enter an already
initialized interpreter — fine — but the demo must own its own).
"""
import ctypes
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

import paddle_tpu as fluid

CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "csrc")


def _built():
    arts = [os.path.join(CSRC, n)
            for n in ("libptcapi.so", "capi_smoke", "train_demo")]
    if not all(os.path.exists(a) for a in arts):
        return False
    # stale-artifact guard: rebuild when any source is newer
    srcs = [os.path.join(CSRC, n)
            for n in ("capi.cc", "capi_smoke.c", "train_demo.cc",
                      "data_feed.cc")]
    newest_src = max(os.path.getmtime(s) for s in srcs)
    return min(os.path.getmtime(a) for a in arts) >= newest_src


@pytest.fixture(scope="module", autouse=True)
def build_native():
    if not _built():
        subprocess.run(["sh", os.path.join(CSRC, "build.sh")],
                       check=True, capture_output=True)


def _save_linear_model(dirname, with_optimizer):
    B = 16
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        x = fluid.data(name="x", shape=[B, 4], dtype="float32")
        y = fluid.data(name="y", shape=[B, 1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="relu"), 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        if with_optimizer:
            fluid.optimizer.SGD(0.05).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if with_optimizer:
            # keep backward+optimizer ops in the saved program: save the
            # FULL program with loss as the fetch target
            fluid.io.save_inference_model(
                dirname, ["x", "y"], [loss], exe, main_program=prog,
                keep_training_ops=True)
        else:
            fluid.io.save_inference_model(dirname, ["x"], [pred], exe,
                                          main_program=prog)
        xb = np.random.RandomState(5).randn(B, 4).astype("float32")
        if not with_optimizer:
            (want,) = exe.run(
                prog, feed={"x": xb,
                            "y": np.zeros((B, 1), "float32")},
                fetch_list=[pred])
            return xb, np.asarray(want)
    return None, None


def test_c_api_predict_matches_python(tmp_path):
    d = str(tmp_path / "model")
    xb, want = _save_linear_model(d, with_optimizer=False)
    # the C API embeds CPython, so it is exercised from a plain C host
    # binary (the actual deployment shape) — loading it into this
    # already-running interpreter would double-initialize libpython
    xpath = str(tmp_path / "x.bin")
    xb.astype("float32").tofile(xpath)
    proc = subprocess.run(
        [os.path.join(CSRC, "capi_smoke"), d, xpath,
         str(xb.shape[0]), str(xb.shape[1])],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(CSRC)})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    got = np.asarray([float(v) for v in proc.stdout.split()])
    np.testing.assert_allclose(got, want.ravel(), rtol=1e-5, atol=1e-6)


def test_train_demo_converges(tmp_path):
    d = str(tmp_path / "trainable")
    _save_linear_model(d, with_optimizer=True)
    proc = subprocess.run(
        [os.path.join(CSRC, "train_demo"), d],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(CSRC)})
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-2000:])
    assert "last_loss" in proc.stdout
