"""Which part of a step stops the device: the latent-attention cell's step cut
down, one variant a process, each killed if its compiled step does not come
back. Made for PR 38's hang (PERF.md section 7 (iii)): an explicit VMEM request
on an attention call beside that model's expert sublayers.

    chiprun --chips 1 -- python3 tools/hang_bisect.py \\
        "--pattern LE --bwd pair" "--pattern LE --bwd fused --vmem 16"
    JAX_PLATFORMS=cpu python3 tools/hang_bisect.py --aot "--pattern LE"

A variant is the cell's own ``build_static`` with the pattern of sublayers (L
latent attention, D dense, E experts) and the head dims replaced, startup
weights, one random batch, three steps. ``--bwd`` forces the streaming
backward's one kernel or the pair whatever ``_fused_bwd_fits`` says (``rule``
leaves it); ``--vmem`` gives every streaming attention call that
``vmem_limit_bytes`` in MiB (-1 takes the one kernel's own away; 0 leaves
all). The parent process never touches JAX: it prints a line a variant, ``ok``
with the steps' seconds, ``hang`` where the step was compiled and ``--wait``
seconds brought no step, ``fail`` with the child's last lines. ``--aot``
compiles the step for a described v5e instead (no chip) and prints its size;
``--dump DIR`` keeps the optimized HLO there.
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4_29b_a4b_ep8.static_s4096"


def child(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--pattern", default="LE")
    p.add_argument("--dims", default="128,64,128")   # nope, rope, v
    p.add_argument("--hyper", type=int, default=1)
    p.add_argument("--recompute", type=int, default=1)
    p.add_argument("--bwd", default="fused", choices=["fused", "pair", "rule"])
    p.add_argument("--vmem", type=int, default=0)
    p.add_argument("--aot", action="store_true")
    p.add_argument("--dump", default="")
    a = p.parse_args(argv)
    if a.aot:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, REPO)
    import importlib
    import types

    import jax
    import numpy as np

    import paddle_tpu as fluid
    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest
    from paddle_tpu import models

    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    if a.bwd != "rule":
        fa._fused_bwd_fits = lambda *shapes: a.bwd == "fused"
    if a.vmem:
        real = fa.pltpu
        shim = types.SimpleNamespace(**{k: getattr(real, k) for k in dir(real)
                                        if not k.startswith("__")})

        def params(**kw):
            if len(kw.get("dimension_semantics", ())) == 3:   # streaming
                kw["vmem_limit_bytes"] = a.vmem << 20
                if a.vmem < 0:
                    del kw["vmem_limit_bytes"]
            return real.CompilerParams(**kw)
        shim.CompilerParams, fa.pltpu = params, shim

    manifest = Manifest(harness.MANIFEST, harness.REPO)
    cfg, traffic, parts = harness.load_cell(manifest, manifest.cell(CELL))
    nope, rope, vdim = map(int, a.dims.split(","))
    cfg = dict(cfg, hybrid_override_pattern=a.pattern, qk_nope_head_dim=nope,
               qk_rope_head_dim=rope, v_head_dim=vdim)
    traffic = dict(traffic, recompute=bool(a.recompute))
    if not a.hyper:
        whole = models.hybrid_ssm_moe
        models.hybrid_ssm_moe = lambda *x, **kw: whole(
            *x, **dict(kw, hyper=None))
    built = parts["model"].build_static(cfg, traffic)
    rng = np.random.RandomState(7)
    b, t, v = traffic["batch"], traffic["seq_len"], cfg["vocab_size"]
    feed = {"src": rng.randint(0, v, (b, t)).astype("int64"),
            "labels": rng.randint(0, v, (b * t, 1)).astype("int64")}
    scope, exe = fluid.Scope(), fluid.Executor(fluid.TPUPlace(0))
    with fluid.scope_guard(scope):
        exe.run(built["startup"])
        print("# startup done", file=sys.stderr, flush=True)
        if a.aot:
            from unittest import mock

            from jax.experimental import topologies
            from jax.sharding import SingleDeviceSharding
            from paddle_tpu.core.compiler_engine import _stage_compiled_call
            from paddle_tpu.core.tensor import LoDTensor

            jax.config.update("jax_enable_compilation_cache", False)
            chip = SingleDeviceSharding(topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2").devices[0])
            fn, args, _ = _stage_compiled_call(
                exe._core, jax.devices()[0], built["main"], scope,
                {k: LoDTensor(x) for k, x in feed.items()}, [built["loss"]])
            with mock.patch.object(fa, "compute_platform", lambda: "tpu"):
                compiled = fn.lower(*jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=chip),
                    args)).compile()
            m = compiled.memory_analysis()
            if a.dump:
                os.makedirs(a.dump, exist_ok=True)
                name = "%s_%s_%s_vmem%d_h%d_r%d.txt" % (
                    a.pattern, a.dims.replace(",", "x"), a.bwd, a.vmem,
                    a.hyper, a.recompute)
                with open(os.path.join(a.dump, name), "w") as f:
                    f.write(compiled.as_text())
            print(json.dumps({"gib": round((
                m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes) / 2 ** 30,
                3)}), flush=True)
            return
        seconds = []
        for _ in range(3):
            t0 = time.time()
            exe.run(built["main"], feed=feed, fetch_list=[built["loss"]])
            seconds.append(round(time.time() - t0, 3))
    print(json.dumps({"step_s": seconds}), flush=True)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("variants", nargs="+", help="a variant's options, quoted")
    p.add_argument("--aot", action="store_true")
    p.add_argument("--dump", default="")
    p.add_argument("--wait", type=int, default=45,
                   help="seconds a compiled step may take to come back")
    p.add_argument("--limit", type=int, default=400,
                   help="seconds a variant may take in all")
    a = p.parse_args()
    env = dict(os.environ, JAX_LOG_COMPILES="1")
    for variant in a.variants:
        argv = variant.split() + ["--aot"] * a.aot + (
            ["--dump", a.dump] if a.dump else [])
        t0 = time.time()
        with tempfile.NamedTemporaryFile("r", suffix=".err") as log:
            proc = subprocess.Popen(
                [sys.executable, __file__, "--child"] + argv,
                stdout=subprocess.PIPE, stderr=open(log.name, "w"), env=env,
                text=True)
            compiled_at, killed = None, False
            while proc.poll() is None:
                time.sleep(1)
                # the ``step`` program compiled after the startup one is
                # the step
                if compiled_at is None and open(log.name).read().partition(
                        "# startup done")[2].count(
                            "Finished XLA compilation of jit(step"):
                    compiled_at = time.time()
                late = compiled_at and time.time() - compiled_at > a.wait
                if not a.aot and (late or time.time() - t0 > a.limit):
                    proc.kill()
                    killed = True
            out = proc.stdout.read().strip().split("\n")[-1]
            err = open(log.name).read()
        status = ("hang" if killed and compiled_at else
                  "ok" if proc.returncode == 0 else "fail")
        print("%-5s %s  %s  (%.0f s)" % (status, variant, out,
                                        time.time() - t0), flush=True)
        if status == "fail":
            print(err[-1500:], flush=True)


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        child(sys.argv[2:])
    else:
        main()
