"""Sizes both sides of a cell before any chip-minute is spent: builds the
cell's program at its real size here on the CPU, compiles the whole step with
the TPU's own compiler for a described (not attached) ``v5e:2x2`` chip and
prints ``memory_analysis()``; with ``--reference`` does the same for every
program the check's ``follow()`` runs for that cell, at the cell's
``reference_rows_per_block``. Nothing runs, so this says what fits, never how
fast. One JSON line a program.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_sizing.py [--reference]
        [workload ...]
    JAX_PLATFORMS=cpu python3 benchmarks/aot_sizing.py --stand-in <file>

``--stand-in`` sizes the reference's programs for a configuration that does
not exist yet: a JSON of leaf shapes and a token count, read by
``benchmarks/lib/standin.py``.

A reference program's line also gives ``tree_gib`` (one float32 copy of the
parameter tree), ``held_beside_gib`` (what ``follow()`` keeps on the device
meanwhile that is no argument of this program: the optimizer's state while
the gradient is taken, the weights too while it is averaged) and
``tree_copies_at_peak``: the program's total plus what is held beside it,
over ``tree_gib``. Four copies are what the arithmetic needs; what is over
four is the loss's activations.

A data-parallel cell is sized by its per-replica program on one chip (the
mesh adds the collectives' buffers, at most one copy of the gradients). A
dygraph cell has no program to lower before it has run and is not sized.
"""
import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GIB = 2.0 ** 30


def memory_of(lowered):
    """A lowered program's ``memory_analysis()`` in GiB, and the seconds the
    TPU's compiler took."""
    t0 = time.perf_counter()
    mem = lowered.compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    return {"compile_s": round(time.perf_counter() - t0, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / GIB, 3),
            "outputs_gib": round(mem.output_size_in_bytes / GIB, 3),
            "aliased_gib": round(mem.alias_size_in_bytes / GIB, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / GIB, 3),
            "total_gib": round(total / GIB, 3)}


def size_step(manifest, cell, one_chip):
    """The program's compiled step. The program asks ``compute_platform()``
    where it runs and would lower ``flash_attention`` to its dense math on
    this CPU host: that one question is answered as the chip would, around
    ``fn.lower`` (as ``tests/tpu_kernel_cases.py:bert_step`` does)."""
    import importlib
    from unittest import mock

    import jax

    from benchmarks.drivers.static_executor import Driver
    from benchmarks.lib.harness import load_cell, make_pool
    from paddle_tpu.core.compiler_engine import _stage_compiled_call
    from paddle_tpu.core.tensor import LoDTensor

    flash_attention = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    cfg, traffic, parts = load_cell(manifest, cell)
    traffic = dict(traffic)
    if traffic["driver"] == "dygraph":
        return {"workload": cell["name"], "program": "step", "sized": False,
                "why": "a lazy dygraph step has no program before it runs"}
    traffic["pool"] = 1
    model, reference = parts["model"], parts["reference"]
    driver = Driver(model, cfg, traffic, jax.devices()[:1])
    driver.build()
    per_replica = dict(traffic, replicas=1)
    batch = make_pool(reference, cfg, per_replica, 0, 1)[0]
    feed = {k: LoDTensor(v) for k, v in model.to_feed(batch).items()}
    fn, args, _ = _stage_compiled_call(
        driver.exe._core, jax.devices()[0], driver.built["main"],
        driver.scope, feed, [driver.built["loss"]])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    with mock.patch.object(flash_attention, "compute_platform",
                           lambda: "tpu"):
        lowered = fn.lower(*shapes)
    return {"workload": cell["name"], "program": "step", "sized": True,
            "per_replica_batch": traffic["batch"], **memory_of(lowered)}


def size_reference(name, reference, cfg, traffic, one_chip):
    """One line for each program ``follow()`` runs for this batch: ``start``,
    then ``gradient`` (one block of rows) or ``zero``, ``accumulate`` (once a
    block) and ``mean``, then ``update`` and ``change`` (which is also what
    the program's side runs for the parameters' change)."""
    import jax

    from benchmarks.lib.harness import FIRST_STEPS, make_pool, start_of
    from benchmarks.lib.reference_train import (block_count, identity,
                                                programs)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    init_fn, key = start_of(reference, cfg, 0)
    run = programs(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                   cfg["optimizer"], init_fn,
                   traffic.get("reference_rows_per_block"), identity)
    key = on_chip(key)
    params, state = on_chip(jax.eval_shape(run["start"], key))
    batch = on_chip(jax.eval_shape(
        lambda: make_pool(reference, cfg, traffic, 0, FIRST_STEPS)[0]))
    tree = sum(math.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(params))
    total = on_chip(jax.eval_shape(run["zero"], params))
    blocks = block_count(batch, traffic.get("reference_rows_per_block"))
    scalar = on_chip(jax.ShapeDtypeStruct((), "float32"))
    index = on_chip(jax.ShapeDtypeStruct((), "int32"))
    # each program's arguments, and the bytes follow() keeps on the device
    # meanwhile that are no argument of it (the state; for ``mean`` the
    # weights too)
    beside = tree * len(state)
    calls = {"start": ((key,), 0)}
    if blocks == 1:
        calls["gradient"] = ((params, batch), beside)
    else:
        calls.update(zero=((params,), beside),
                     accumulate=((params, total, batch, index), beside),
                     mean=((total, blocks), tree + beside))
    calls.update(update=((params, state, params, scalar), 0),
                 change=((params, key), 0))
    for program, (args, held) in calls.items():
        mem = memory_of(run[program].lower(*args))
        yield {"workload": name, "program": "reference." + program,
               "sized": True, **mem,
               "tree_gib": round(tree / GIB, 3),
               "held_beside_gib": round(held / GIB, 3),
               "tree_copies_at_peak": round(
                   (mem["total_gib"] * GIB + held) / tree, 2)}


def main(argv):
    p = argparse.ArgumentParser(prog="benchmarks/aot_sizing.py")
    p.add_argument("workloads", nargs="*")
    p.add_argument("--reference", action="store_true")
    p.add_argument("--stand-in", dest="stand_in")
    args = p.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import harness, standin
    from benchmarks.lib.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def say(line):
        print(json.dumps(line), flush=True)

    if args.stand_in:
        cfg, traffic = standin.load(args.stand_in)
        for line in size_reference(cfg["name"], standin, cfg, traffic,
                                   one_chip):
            say(line)
        return
    manifest = Manifest(harness.MANIFEST, harness.REPO)
    names = args.workloads or [c["name"] for c in manifest.data["workloads"]]
    for name in names:
        cell = manifest.cell(name)
        say(size_step(manifest, cell, one_chip))
        if args.reference:
            cfg, traffic, parts = harness.load_cell(manifest, cell)
            for line in size_reference(name, parts["reference"], cfg, traffic,
                                       one_chip):
                say(line)


if __name__ == "__main__":
    main(sys.argv[1:])
