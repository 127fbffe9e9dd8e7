"""How many of the indexer's selections differ between a cell's program and
its plain reference at the seeded weights, read where the cell runs.

    python3 tools/sparse_select_diff.py --workload <cell> --seeds 11 22
        [--cpu] [--out <file>]

Builds the cell's model with its forward ``attn_index_select`` ops' ``Select``
outputs in the fetch list, loads the seeded weights, runs the pool's first
batch (one training step, outside any window) and fetches each layer's int8
[B, T, T] selection; then runs the reference's forward layer by layer from the
same weights and batch (float32, ``highest``) with ``reference.selection``
before each attention half, and counts, for each layer, the (query, key)
pairs selected by one side only. The program's selection is exact for the
scores it computes; what differs is what its scores differ by: float32
rounding in layer 0, and from then on the hidden state that bf16 AMP has
moved. ``--cpu`` runs a test preset's cell off the chip.
"""
import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def program_selections(built, model, params, batch, place, layers):
    """[bool [B, T, T]] of the ``layers`` forward ``S`` layers, in order,
    from one step of the program."""
    import paddle_tpu as fluid

    block = built["main"].global_block()
    names = [op.output("Select")[0] for op in block.ops
             if op.type == "attn_index_select"]
    # under recomputation each selection is made again in the backward: the
    # forward ones come first
    names = names[:layers]
    scope, exe = fluid.Scope(), fluid.Executor(place)
    with fluid.scope_guard(scope):
        exe.run(built["startup"])
        for leaf, name in built["leaves"].items():
            scope.find_var(name).get_tensor().set(params[leaf])
        feed = {k: np.asarray(v) for k, v in model.to_feed(batch).items()}
        out = exe.run(built["main"], feed=feed,
                      fetch_list=[block.var(n) for n in names])
    return [np.asarray(o) != 0 for o in out]


def reference_selections(reference, params, batch, cfg):
    """[bool [B, T, T]] of the ``S`` layers from the reference's forward."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def select(x, p):
        return reference.selection(x, p, batch["pos"], cfg)

    @jax.jit
    def attend(x, p):
        return reference._attention_half(x, p, batch["pos"], cfg, jnp.matmul,
                                         lambda z: z)[0]

    @jax.jit
    def experts(x, p):
        return reference._experts_half(x, p, cfg, jnp.matmul)

    out = []
    with jax.default_matmul_precision("highest"):
        x = params["emb"][batch["src"]]
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            p = reference._of_layer(params, i)
            if kind == "S":
                out.append(np.asarray(select(x, p)))
                x = attend(x, p)
            else:
                x = experts(x, p)
    return out


def main(argv):
    p = argparse.ArgumentParser(prog="tools/sparse_select_diff.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--out")
    args = p.parse_args(argv)

    import jax

    import paddle_tpu as fluid
    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest

    harness.enable_cache()
    manifest = Manifest(harness.MANIFEST, harness.REPO)
    cell = manifest.cell(args.workload)
    if not args.cpu:
        harness.find_devices(cell["chips"])
    place = fluid.CPUPlace() if args.cpu else fluid.TPUPlace(0)
    cfg, traffic, parts = harness.load_cell(manifest, cell)
    model, reference = parts["model"], parts["reference"]
    built = model.build_static(cfg, traffic)
    layers = cfg["hybrid_override_pattern"].count("S")
    lines = []
    for seed in args.seeds:
        batch = harness.make_pool(reference, cfg, traffic, seed, 1)[0]
        batch = jax.tree.map(np.asarray, batch)
        got = program_selections(
            built, model, harness.make_params(reference, cfg, seed), batch,
            place, layers)
        want = reference_selections(
            reference, harness.make_params(reference, cfg, seed), batch, cfg)
        for layer, (a, b) in enumerate(zip(got, want)):
            line = {"seed": seed, "attention_layer": layer,
                    "selected": int(b.sum()),
                    "program_selected": int(a.sum()),
                    "program_only": int((a & ~b).sum()),
                    "reference_only": int((b & ~a).sum()),
                    "queries_that_differ": int((a != b).any(-1).sum())}
            line["share_pct"] = 100.0 * line["reference_only"] / max(
                1, line["selected"])
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
