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

    python3 benchmarks/check_outputs.py --stand-in <file> [--first-seed 1000]

runs the reference's ``follow()`` alone over a stand-in for a configuration
that does not exist yet (``benchmarks/lib/standin.py``), twice, and prints the
device's peak beside the seconds each took: what
``benchmarks/aot_sizing.py --stand-in`` sized, on the chip.
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
                      *harness.start_of(reference, cfg, seed),
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
            driver, *harness.start_of(reference, cfg, seed))
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


def stand_in_readings(path, devices, seed, emit=print):
    """``follow()`` over the stand-in, twice: the first pass compiles or
    loads its programs, the second is what a warm run pays. The device's peak
    is the sum of the two parts printed (``harness.peak_bytes``)."""
    import statistics
    import time

    from benchmarks.lib import harness, standin
    from benchmarks.lib.reference_train import follow, identity

    cfg, traffic = standin.load(path)
    out = {"stand_in": cfg["name"],
           "parameters": standin.parameter_count(cfg), "tokens": cfg["tokens"]}
    for name in ("first", "again"):
        t0 = time.perf_counter()
        ref = follow(lambda p, b, cast: standin.loss(p, b, cfg, cast),
                     cfg["optimizer"], *harness.start_of(standin, cfg, seed),
                     harness.make_pool(standin, cfg, traffic, seed,
                                       harness.FIRST_STEPS),
                     traffic["reference_rows_per_block"], identity)
        out["seconds_" + name] = time.perf_counter() - t0
        stats = devices[0].memory_stats() or {}
        out["peak_bytes_in_use_" + name] = stats.get("peak_bytes_in_use")
        out["peak_bytes_reserved_" + name] = stats.get("peak_bytes_reserved")
    out["losses"] = ref["losses"]
    out["delta_norm_median"] = statistics.median(ref["delta_norms"].values())
    emit(json.dumps(out))
    return out


def main(argv):
    p = argparse.ArgumentParser()
    what = p.add_mutually_exclusive_group(required=True)
    what.add_argument("--workload")
    what.add_argument("--stand-in", dest="stand_in")
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=1000)
    args = p.parse_args(argv)

    from benchmarks.lib import harness
    from benchmarks.lib.manifest import Manifest

    harness.enable_cache()
    if args.stand_in:
        devices, _ = harness.find_devices(1)
        stand_in_readings(args.stand_in, devices, args.first_seed,
                          emit=lambda line: print(line, flush=True))
        return
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
