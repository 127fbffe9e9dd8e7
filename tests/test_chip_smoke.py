"""What can be known about the chip path without a chip (ISSUE 21):
chip_smoke.py's CPU rehearsal and its refusal to pass without a TPU,
the compile-cache helper, TPUPlace strictness, and that the TPU
compiler still accepts every Pallas kernel."""
import importlib
import json
import os
import shutil
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache
from paddle_tpu.core.enforce import OutOfRangeError, PreconditionNotMetError

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tpu_kernel_cases  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, cwd=ROOT, devices=4, pythonpath=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=%d" % devices
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def test_rehearsal_runs_every_phase_on_cpu():
    proc = _run([SMOKE, "--rehearse-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()
             if x.startswith("{")]
    # the LAST stdout line is the result, with exactly the keys the
    # driver reads (an extra key gets the PR refused) ...
    result = json.loads(proc.stdout.rstrip("\n").splitlines()[-1])
    assert result == lines[-1]
    assert set(result) == {"ok", "device"}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["ok"] is True
    assert isinstance(result["device"]["kind"], str)
    assert type(result["device"]["count"]) is int
    # ... and never mistakable for a pass on the chip
    assert result["device"]["platform"] == "cpu"
    phases = [rec["phase"] for rec in lines[:-1]]
    assert phases == ["device", "train", "train", "kernels", "dp4",
                      "summary"], phases
    assert lines[-2]["rehearsal"] is True and lines[-2]["wall_s"] > 0
    assert all(rec["platform"] == "cpu" and rec["smoke"]
               for rec in lines[:-1])


def test_without_the_rehearsal_argument_a_cpu_host_fails():
    proc = _run([SMOKE])
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and '"phase"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_it_fails_and_prints_no_result(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py", "--rehearse-cpu"], cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "paddle_tpu" in proc.stderr


# -- compile cache placed from outside --------------------------------------


def test_cache_dir_is_the_env_var_or_the_fixed_checkout_path(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # fixed, in the checkout, the same on every call — never a temp,
    # pid or time-stamped name (the path is part of the cache key)
    want = os.path.join(ROOT, ".jax_compile_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.compile_cache_dir() == want


def test_enable_sets_no_other_dir_when_the_env_var_is_set(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert calls == []          # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == os.path.join(ROOT, ".jax_compile_cache")


# -- places ------------------------------------------------------------------


def test_tpuplace_past_the_device_count_raises():
    n = len(jax.devices())
    assert fluid.TPUPlace(n - 1).jax_device() == jax.devices()[n - 1]
    with pytest.raises(OutOfRangeError):
        fluid.TPUPlace(n).jax_device()      # no modulo wrap


def test_tpuplace_refuses_a_cpu_jax_merely_fell_back_to():
    """The suite's explicit CPU pin lets TPUPlace resolve to a CPU
    device; a process in which JAX just found no accelerator must not
    train on the host under the name TPUPlace."""
    pinned = jax.config.jax_platforms
    assert fluid.TPUPlace(0).jax_device().platform == "cpu"
    try:
        jax.config.update("jax_platforms", None)
        with pytest.raises(PreconditionNotMetError):
            fluid.TPUPlace(0).jax_device()
        assert fluid.CPUPlace().jax_device().platform == "cpu"
    finally:
        jax.config.update("jax_platforms", pinned)


# -- the TPU compiler still accepts the kernels -------------------------------


@pytest.mark.parametrize("case", list(tpu_kernel_cases.cases()),
                         ids=lambda c: c[0])
def test_kernel_cross_lowers_for_tpu(case):
    """Mosaic lowering on the CPU host: the break that kept the paged
    kernel from ever compiling (a 3-D einsum) raises here."""
    name, fn, specs = case
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in specs]
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") >= 1, name


@pytest.fixture(scope="module")
def v5e_compile():
    """The real thing minus the chip: libtpu's Mosaic and XLA:TPU back
    ends compile every kernel, and a BERT step, for a compile-only v5e
    topology — VMEM and layout refusals included. In a process of its
    own (it loads libtpu), once for the tests that read it; they skip
    where no TPU compiler can be set up."""
    proc = _run([os.path.join(ROOT, "tests", "tpu_kernel_cases.py")],
                devices=1, pythonpath=ROOT)
    if proc.returncode == 3:
        pytest.skip("no compile-only TPU topology here: %s"
                    % proc.stdout[-300:])
    return proc


def test_kernels_compile_for_v5e(v5e_compile):
    proc = v5e_compile
    ok = [x for x in proc.stdout.splitlines() if x.startswith("OK ")]
    assert proc.returncode == 0, proc.stdout[-3000:]
    assert len(ok) == len(list(tpu_kernel_cases.cases())), proc.stdout


def test_streaming_gradients_for_v5e_are_one_mosaic_call(v5e_compile):
    """The streaming ``flash_attention_grad`` at the sparse-attention and
    hybrid cells' shapes (a selection over 32 / 4 heads at 16,384; 32 / 2
    heads at 8,192) compiles to ONE Mosaic call, dQ, dK and dV from each
    score tile made once (it was a dQ and a dK+dV call), and forward +
    backward of such a case are two; with one K/V head a query head (the
    latent-attention cell's 32 / 32 at 192 / 128, the gpt_long shape) the
    rule keeps the pair (``_fused_bwd_fits``)."""
    calls = {x.split()[1]: int(x.split("mosaic_calls=")[1])
             for x in v5e_compile.stdout.splitlines() if x.startswith("OK ")}
    assert {name: calls.get(name) for name in
            tpu_kernel_cases.STREAM_GRADS} == {
                name: 1 if h_kv < h else 2 for name, (h, h_kv, _, _, _, _)
                in tpu_kernel_cases.STREAM_GRADS.items()}, v5e_compile.stdout
    for name in ("flash_gqa_causal_s8192_d128",
                 "flash_gqa_selected_s16384_d128"):
        assert calls[name] == 2, (name, calls[name])
    for name in ("flash_causal_s4096", "flash_masked_b64_s256",
                 "flash_causal_s4096_d192_v128"):
        assert calls[name] == 3, (name, calls[name])


@pytest.mark.parametrize("path, shape, heads_kv, platform", [
    ("fused", (1, 4, 2048, 128), 2, "tpu"),
    ("split", (1, 4, 262144, 128), 2, "tpu"),
    ("short", (2, 4, 256, 64), 4, "tpu"),
    ("dense", (1, 4, 2048, 128), 2, "cpu")])
def test_grad_counter_names_the_backward_a_trace_took(path, shape, heads_kv,
                                                      platform):
    """kernels.flash_attention_grad{path=...}: one count a traced gradient
    op, by the backward its shapes took where the computation is placed
    (traced only: nothing is lowered or run)."""
    from unittest import mock

    from paddle_tpu import observability as obs
    from paddle_tpu.core.registry import OpInfoMap

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    B, H, S, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, heads_kv, S, D), jnp.bfloat16)
    # the streaming forward's LSE is a column, the short path's a row
    lse = jax.ShapeDtypeStruct(
        (B * H, 1, S) if path == "short" else (B * H, S, 1), jnp.float32)
    op = OpInfoMap.instance().get("flash_attention_grad").fn
    was_on = obs.enabled()
    obs.enable()
    try:
        before = dict(obs.dump()["counters"])
        with mock.patch.object(fa, "compute_platform", lambda: platform):
            grads = jax.eval_shape(
                lambda q, k, v, out, lse, g: op(
                    {"Q": q, "K": k, "V": v, "Out": out,
                     "LSE": None if path == "dense" else lse, "Out@GRAD": g},
                    {"causal": True, "scale": 0.0}), q, kv, kv, q, lse, q)
        after = obs.dump()["counters"]
    finally:
        if not was_on:
            obs.disable()
    grown = {name: after[name] - before.get(name, 0) for name in after
             if name.startswith("kernels.flash_attention_grad")
             and after[name] != before.get(name, 0)}
    assert grown == {"kernels.flash_attention_grad{path=%s}" % path: 1}
    assert grads["Q@GRAD"].shape == shape
    assert grads["K@GRAD"].shape == grads["V@GRAD"].shape == kv.shape


def test_bert_step_for_v5e_holds_one_forward_kernel_a_layer(v5e_compile):
    """A 2-layer BERT step at T = 512 and a 1-layer one at T = 128 (the two
    cells' lengths): the program's own forward kernel once a layer (the grad op
    reads the forward op's Out and LSE; XLA would not merge a re-run), one
    backward kernel a layer, no Mosaic call the program did not write, no
    [*, *, T, T] buffer, and no head-major [*, heads, T, 64] buffer: the
    kernels read q, k, v and write the context as the projections' matmuls
    leave and take them."""
    lines = [x for x in v5e_compile.stdout.splitlines()
             if x.startswith("BERT_STEP ")]
    assert lines == [
        "BERT_STEP fwd=%d bwd=%d other_mosaic=0 tt_buffers=0 head_major=0"
        % (n, n) for n in (2, 1)]


def test_index_scores_for_v5e_are_one_packed_product(v5e_compile):
    """A block of 512 query rows over 16,384 keys, 16 heads of 64: ONE
    product, of the bfloat16 pieces side by side (contraction 384), none at
    ``HIGHEST``, and the ReLU, the heads' weights and the sum over heads
    fused into its output (no [heads, rows, keys] array: under 64 MiB of
    temporaries where one such array is 512)."""
    (line,) = [x for x in v5e_compile.stdout.splitlines()
               if x.startswith("INDEX_SCORES ")]
    got = {k: int(v) for k, v in (kv.split("=") for kv in line.split()[1:])}
    assert (got["products"], got["highest"], got["packed_keys"]) == (
        1, 0, 1), line
    assert got["temporaries_mib"] < 64, line


@pytest.mark.parametrize("state_size, scan_calls, scans, recomputing", [
    (128, 9, "3/3/3", "6/3/3"), (64, 0, "0/0/0", "0/0/0")])
def test_recomputation_lowers_the_compiled_steps_temporaries(
        v5e_compile, state_size, scan_calls, scans, recomputing):
    """A six-layer hybrid state-space / MoE step with
    ``RecomputeOptimizer`` over the layers' inputs holds fewer temporaries
    than without: the barrier on the checkpoint values keeps XLA from
    folding each re-emitted segment back onto its original (without it the
    real configuration's step compiled to the same 7.898 GiB either way,
    PERF.md section 6, PR 27). The step also holds the streaming attention
    kernels for shared K/V heads (a forward and ONE backward kernel for its
    ``*`` layer, under recomputation too) and the grouped-product kernels of
    the experts (megablox on the TPU, so no ``ragged-dot`` is left). With states
    of 128 it holds the selective scan's kernels: for its three ``M`` layers
    three forwards, state passes and backward kernels, and with
    recomputation each forward once more (the gradient op runs the state
    pass, not a third forward). With states of 64, which the kernels'
    blocks cannot take, the scan is the XLA form on the TPU, its gradient
    op's ``jax.vjp`` behind a barrier included."""
    (line,) = [x for x in v5e_compile.stdout.splitlines()
               if x.startswith("HYBRID_STEP state=%d " % state_size)]
    got = dict(kv.split("=") for kv in line.split()[1:])
    assert int(got["checkpoints"]) < 0.8 * int(got["plain"]), line
    assert int(got["mosaic_calls"]) >= 2 + 6 + scan_calls, line
    assert int(got["flash_bwd"]) == 1, line
    assert int(got["ragged_dots"]) == 0, line
    assert (got["scans"], got["scans_recomputing"]) == (scans, recomputing)


def test_bert_step_report_counts_what_it_names():
    hlo = """
  %flash_short_fwd.2 = (bf16[48,512,64]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %flash_short_bwd.2 = (bf16[48,512,64]{2,1,0}) custom-call(%a), custom_call_target="tpu_custom_call"
  %custom-call.7 = f32[4,12,512,64]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call"
  %fusion.1 = bf16[4,12,512,512]{3,2,1,0} fusion(%b), kind=kOutput
  %fusion.2 = f32[4,12,512,512]{3,2,1,0} fusion(%fusion.1), kind=kLoop
"""
    assert tpu_kernel_cases.bert_step_report(hlo) == \
        "BERT_STEP fwd=1 bwd=1 other_mosaic=1 tt_buffers=2 head_major=2"
