"""Readings that the limits of ``correct`` are set from, for one cell, in one
process: over a dozen seeds the gaps between the program's first steps and the
plain reference's (the sound runs), and over a few seeds the gaps of the
control, which is the reference itself with the operands of every matrix
multiplication rounded to the next narrower type (fp8 e4m3 below the bf16 that
the cells multiply in) and must come out as not correct.

    python3 benchmarks/check_outputs.py --workload <cell> [--seeds 12]
        [--control-seeds 3] [--first-seed 1000]

No window is measured: training's readings need none. Each seed prints one
JSON line; the last line is the summary. Run on the chip at the cell's own
size; ``benchmarks/tests`` runs it at the test preset's size on the CPU.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROL_TYPE = "float8_e4m3fn"


def gaps(rows):
    return {name: value for name, value, *_ in rows}


def readings(manifest, cell, devices, seeds, control_seeds, emit=print):
    from benchmarks.lib import check, harness
    from benchmarks.lib.reference_train import follow, identity, narrow_cast

    cfg, traffic, parts = harness.load_cell(manifest, cell)
    reference = parts["reference"]

    def loss_fn(p, b, cast):
        return reference.loss(p, b, cfg, cast)

    def reference_run(seed, cast):
        return follow(loss_fn, cfg["optimizer"],
                      harness.make_params(reference, cfg, seed),
                      harness.make_pool(reference, cfg, traffic, seed,
                                        harness.FIRST_STEPS),
                      traffic.get("reference_rows_per_block"), cast)

    sound, control = [], []
    for seed in seeds:
        driver = parts["driver"].Driver(parts["model"], cfg, traffic,
                                        devices)
        driver.build()
        driver.load(harness.make_params(reference, cfg, seed),
                    harness.make_pool(reference, cfg, traffic, seed,
                                      harness.FIRST_STEPS))
        program, _ = harness.drive_first_steps(
            driver, harness.make_params(reference, cfg, seed))
        driver.close()
        del driver
        gc.collect()
        ref = reference_run(seed, identity)
        row = gaps(check.compare(program, ref, traffic["limits"]))
        sound.append(row)
        emit(json.dumps({"seed": seed, "side": "program", **row,
                         "losses": program["losses"],
                         "reference_losses": ref["losses"]}))
        if seed in control_seeds:
            low = reference_run(seed, narrow_cast(CONTROL_TYPE))
            row = gaps(check.compare(low, ref, traffic["limits"]))
            control.append(row)
            emit(json.dumps({"seed": seed, "side": "control " + CONTROL_TYPE,
                             **row, "losses": low["losses"]}))
    summary = {"workload": cell["name"], "limits": traffic["limits"]}
    for name in traffic["limits"]:
        summary[name] = {
            "sound_max": max(r[name] for r in sound),
            "control_min": min(r[name] for r in control) if control else None}
    return summary


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=1000)
    args = p.parse_args(argv)

    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest

    harness.enable_cache()
    manifest = Manifest(harness.MANIFEST, harness.REPO)
    cell = manifest.cell(args.workload)
    devices, _ = harness.find_devices(cell["chips"])
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    summary = readings(manifest, cell, devices, seeds,
                       set(seeds[:args.control_seeds]),
                       emit=lambda line: print(line, flush=True))
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
