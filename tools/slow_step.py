"""Where the slowest step of a window spends its extra time.

    python3 tools/slow_step.py --workload resnet50.static_b128 --seed 7 \
        --seconds 30 --windows 6 [--out chiprun_out/slow.jsonl]

Builds the cell as ``benchmarks/run.py`` does (same driver, pool and loop, the
program's spans armed, no profiler trace), then measures ``--windows`` windows
in this one process. After each it takes the window's spans from
``observability.tracing.trace_events()`` and prints, for the slowest step and
for the median one, the duration of ``executor/run``, of each span inside it
(``executor/prepare|stage|launch|writeback|fetch`` on one chip,
``parallel/prepare|stage|step|writeback|fetch`` in a mesh cell such as
``bert_base.dp4_s128``: ``Executor.run`` opens the root on both paths), its
self time, and the time between the previous ``executor/run`` and this one
(the caller's loop). Host clock: enough for a step that is tens of ms long.
"""
import argparse
import gc
import json
import os
import statistics
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def step_rows(events):
    """One dict per ``executor/run`` span, in order: its duration, its
    children's durations by name, its self time and the gap before it (ms)."""
    from paddle_tpu.observability import tracing

    tree = tracing.nest(events)
    rows, prev_end = [], None
    for i, e in enumerate(tree):
        if e["name"] != "executor/run":
            continue
        row = {"step": (e["args"] or {}).get("step"),
               "executor/run": e["dur_us"] / 1e3,
               "self": e["self_us"] / 1e3,
               "loop_before": (None if prev_end is None
                               else (e["ts_us"] - prev_end) / 1e3)}
        for k in tree[i + 1:]:
            if k["parent"] == i:
                row[k["name"]] = k["dur_us"] / 1e3
            elif k["depth"] == 0:
                break
        prev_end = e["ts_us"] + e["dur_us"]
        rows.append(row)
    return rows


def main(argv):
    p = argparse.ArgumentParser(prog="tools/slow_step.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--windows", type=int, default=6)
    p.add_argument("--out")
    args = p.parse_args(argv)

    from benchmarks.lib import harness as H
    from benchmarks.lib.manifest import Manifest
    from paddle_tpu import observability as obs

    manifest = Manifest(H.MANIFEST, H.REPO)
    cell = manifest.cell(args.workload)
    H.enable_cache()
    devices, _ = H.find_devices(cell["chips"])
    cfg, traffic, parts = H.load_cell(manifest, cell)
    obs.enable()
    driver = parts["driver"].Driver(parts["model"], cfg, traffic, devices)
    driver.build()
    driver.load(H.make_params(parts["reference"], cfg, args.seed),
                H.make_pool(parts["reference"], cfg, traffic, args.seed,
                            traffic["pool"]))
    for i in range(H.WARM_STEPS):
        driver.step(i)
    print("# set-up %.1f s" % (time.perf_counter() - T_START), flush=True)

    out = open(args.out, "a") if args.out else None
    for w in range(args.windows):
        gc.collect()
        obs.tracing.clear()
        collector = H.CollectorClock()
        times, _, window_s = H.measure(driver, args.seconds, H.WARM_STEPS)
        collector.close()
        rows = step_rows(obs.tracing.trace_events())
        assert len(rows) == len(times), (len(rows), len(times))
        order = sorted(range(len(times)), key=times.__getitem__)
        slow, mid = order[-1], order[len(order) // 2]
        record = {
            "workload": args.workload, "seed": args.seed, "window": w,
            "steps": len(times), "window_s": window_s,
            "step_ms_median": 1e3 * statistics.median(times),
            "collector_s": collector.seconds,
            "collector_full_passes": collector.full_passes,
            "slowest": dict(rows[slow], index=slow,
                            wall_ms=1e3 * times[slow]),
            "second_slowest_wall_ms": 1e3 * times[order[-2]],
            "median": dict(rows[mid], index=mid, wall_ms=1e3 * times[mid]),
        }
        print(json.dumps(record), flush=True)
        if out:
            out.write(json.dumps(record) + "\n")
            out.flush()
    driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
