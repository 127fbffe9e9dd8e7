"""Sizes each cell's step before any chip-minute is spent: builds the cell's
program at its real size here on the CPU, compiles the whole step with the
TPU's own compiler for a described (not attached) ``v5e:2x2`` chip and prints
``memory_analysis()``. Nothing runs, so this says what fits, never how fast.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_sizing.py [workload ...]

A data-parallel cell is sized by its per-replica program on one chip (the
mesh adds the collectives' buffers, at most one copy of the gradients). A
dygraph cell has no program to lower before it has run and is not sized.
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def size_cell(manifest, cell, one_chip):
    import jax

    from benchmarks.drivers.static_executor import Driver
    from benchmarks.lib.harness import load_cell, make_pool
    from paddle_tpu.core.compiler_engine import _stage_compiled_call

    cfg, traffic, parts = load_cell(manifest, cell)
    traffic = dict(traffic)
    if traffic["driver"] == "dygraph":
        return {"workload": cell["name"], "sized": False,
                "why": "a lazy dygraph step has no program before it runs"}
    traffic["pool"] = 1
    model, reference = parts["model"], parts["reference"]
    driver = Driver(model, cfg, traffic, jax.devices()[:1])
    driver.build()
    per_replica = dict(traffic, replicas=1)
    driver.pool = None
    from paddle_tpu.core.tensor import LoDTensor

    batch = make_pool(reference, cfg, per_replica, 0, 1)[0]
    feed = {k: LoDTensor(v) for k, v in model.to_feed(batch).items()}
    fn, args, _ = _stage_compiled_call(
        driver.exe._core, jax.devices()[0], driver.built["main"],
        driver.scope, feed, [driver.built["loss"]])
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    t0 = time.perf_counter()
    mem = fn.lower(*shapes).compile().memory_analysis()
    gib = 2.0 ** 30
    return {"workload": cell["name"], "sized": True,
            "per_replica_batch": traffic["batch"],
            "compile_s": round(time.perf_counter() - t0, 1),
            "arguments_gib": round(mem.argument_size_in_bytes / gib, 3),
            "outputs_gib": round(mem.output_size_in_bytes / gib, 3),
            "aliased_gib": round(mem.alias_size_in_bytes / gib, 3),
            "temporaries_gib": round(mem.temp_size_in_bytes / gib, 3),
            "step_total_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 - mem.alias_size_in_bytes + mem.temp_size_in_bytes) / gib, 3)}


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib.harness import REPO
    from benchmarks.lib.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    manifest = Manifest(os.path.join(REPO, "BENCHMARK.json"), REPO)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = argv or [c["name"] for c in manifest.data["workloads"]]
    for name in names:
        print(json.dumps(size_cell(manifest, manifest.cell(name), one_chip)),
              flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
