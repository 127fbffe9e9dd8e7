"""What the routed experts of a benchmark cell receive, read on the chip.

    python3 tools/moe_load.py --workload <cell> --seeds 11 22 [--out <file>]

Builds the cell's model with its ``moe_topk`` ops' ``Load`` outputs in the
fetch list, loads the seeded weights, runs the pool's batches (training steps;
outside any window; ``--steps`` cycles the pool for longer) and prints, for each expert layer, the mean and the largest
number of slots the held experts received, the largest a single expert
received, the rows of the slot buffer, and whether the exact slower branch ever
ran; and for each seed the median time of a step after the first eight (host
clock, the loads' fetch included: for telling seeds apart, not a rate). What
is decided on the device cannot be a trace-time counter.
"""
import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    p = argparse.ArgumentParser(prog="tools/moe_load.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--steps", type=int, default=0,
                   help="training steps, cycling the pool (default: one "
                        "pass over the pool)")
    p.add_argument("--out")
    args = p.parse_args(argv)

    import paddle_tpu as fluid
    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest
    from paddle_tpu.core.tensor import LoDTensor
    from paddle_tpu.ops.moe_ops import buffer_rows

    harness.enable_cache()
    manifest = Manifest(harness.MANIFEST, harness.REPO)
    cell = manifest.cell(args.workload)
    harness.find_devices(cell["chips"])
    cfg, traffic, parts = harness.load_cell(manifest, cell)
    model, reference = parts["model"], parts["reference"]
    loads = []
    built = model.build_static(cfg, traffic, loads)
    tokens = traffic["batch"] * traffic["seq_len"]
    # the sizes as the program's own op holds them: the configurations
    # name these keys differently
    block = built["main"].global_block()
    routed = next(o for o in block.ops if o.type == "moe_topk")
    experts = block._find_var_recursive(routed.input("RouterW")[0]).shape[1]
    rows = buffer_rows(tokens, routed.attrs["k"], experts,
                       routed.attrs["held"][1])
    exe, lines = fluid.Executor(fluid.TPUPlace(0)), []
    for seed in args.seeds:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(built["startup"])
            params = harness.make_params(reference, cfg, seed)
            for leaf, name in built["leaves"].items():
                scope.find_var(name).get_tensor().set(params[leaf])
            del params
            seen, times, losses = [], [], []
            pool = harness.make_pool(reference, cfg, traffic, seed,
                                     traffic["pool"])
            for i in range(args.steps or len(pool)):
                feed = {k: LoDTensor(v) for k, v in
                        model.to_feed(pool[i % len(pool)]).items()}
                t0 = time.perf_counter()
                out = exe.run(built["main"], feed=feed,
                              fetch_list=[built["loss"]] + loads)
                losses.append(float(np.mean(out[0])))
                seen.append(np.stack([np.asarray(x) for x in out[1:]]))
                times.append(time.perf_counter() - t0)
        seen = np.stack(seen)          # [steps, layers, held + 1]
        held = seen[..., :-1]
        for layer in range(seen.shape[1]):
            total = held[:, layer].sum(-1)
            lines.append({
                "seed": seed, "expert_layer": layer, "buffer_rows": rows,
                "steps": int(seen.shape[0]),
                "slots_mean": float(total.mean()),
                "slots_last": int(total[-1]),
                "slots_max": int(total.max()),
                "expert_max": int(held[:, layer].max()),
                "margin": rows / float(total.max()),
                "slower_branch_ran": int(seen[:, layer, -1].sum())})
            print(json.dumps(lines[-1]), flush=True)
        lines.append({
            "seed": seed, "steps": int(seen.shape[0]),
            "step_ms_median": 1e3 * statistics.median(times[8:] or times),
            "step_ms_by_step": [round(1e3 * t, 1) for t in times],
            "loss_by_step": [round(x, 5) for x in losses],
            "slots_by_step": held.sum(-1).sum(-1).tolist(),
            "largest_layer_by_step": held.sum(-1).max(-1).tolist()})
        print(json.dumps(lines[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
