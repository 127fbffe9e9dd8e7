"""One run of one cell: find the chips, build the model through the program's
normal path, make weights and a pool of batches on the device from the seed,
drive the first steps (they warm every shape up and are what the plain
reference is compared with), measure for ``--seconds``, free the program, run
the reference, and print one JSON object on the last line.

Nothing here belongs to one configuration, traffic mix, driver or per-layer
metric: those are files found by the names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import math
import os
import shutil
import statistics
import sys
import time

from . import check, timing, trace as trace_lib
from .manifest import Manifest

FIRST_STEPS = 3          # steps the reference follows
WARM_STEPS = 8           # steps before the window, the first ones included
TRACE_SECONDS = 5.0      # a traced run measures at most this long
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")


def parse_args(argv):
    p = argparse.ArgumentParser(prog="benchmarks/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def say(*parts):
    print(*parts, flush=True)


def find_devices(chips):
    """The cell's chips and their peaks, or exit: a measurement never falls
    back to the CPU."""
    import jax

    from .peaks import peaks_for

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit("benchmark: JAX found platform %r, a cell needs a TPU"
                         % devices[0].platform)
    if len(devices) < chips:
        raise SystemExit("benchmark: the cell needs %d chips, JAX found %d"
                         % (chips, len(devices)))
    return devices[:chips], peaks_for(devices[0].device_kind)


def enable_cache():
    """JAX's persistent compilation cache at the program's fixed path
    (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_compile_cache``). Every
    program of a run is kept, the quick ones too, so that a second run of a
    cell builds nothing anew."""
    import jax

    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_cell(manifest, cell):
    """What belongs to a cell, found by the names in the manifest: sizes,
    traffic parameters, and the modules of its configuration and driver."""
    cfg, family = manifest.config(cell)
    traffic = manifest.traffic(cell)
    pkg = "benchmarks.configs.%s." % family
    parts = {name: importlib.import_module(pkg + name)
             for name in ("model", "reference", "flops")}
    parts["driver"] = importlib.import_module(
        "benchmarks.drivers." + traffic["driver"])
    return cfg, traffic, parts


def seeded_key(seed):
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def start_of(reference, cfg, seed):
    """(init_fn, key): ``init_fn(key)`` is the seeded weights, and traces
    inside whatever program needs the starting point, so that the point is
    made where it is used and never kept: the program's steps donate it."""
    import jax

    return (lambda key: reference.init_params(key, cfg),
            jax.random.fold_in(seeded_key(seed), 0))


def make_params(reference, cfg, seed):
    """The seeded weights, on the device, in one jitted call."""
    import jax

    init_fn, key = start_of(reference, cfg, seed)
    return jax.jit(init_fn)(key)


def make_pool(reference, cfg, traffic, seed, n):
    """The first ``n`` batches of the seeded pool, in one jitted call."""
    import jax

    key = jax.random.fold_in(seeded_key(seed), 1)
    keys = jax.random.split(key, traffic["pool"])[:n]
    return jax.jit(lambda ks: [reference.make_batch(k, cfg, traffic)
                               for k in ks])(keys)


def norms_of(tree, scale=1.0):
    import jax

    from .reference_train import leaf_norms

    return {k: scale * float(v) for k, v in jax.jit(leaf_norms)(tree).items()}


def drive_first_steps(driver, init_fn, key):
    """The window's own call on the pool's first batches. Returns what the
    reference is compared with, and the wall time of each step. The change of
    the parameters is taken against ``init_fn(key)``, made inside the program
    that subtracts it: the benchmark holds no second tree of weights beside
    the program's state."""
    from .reference_train import change_program

    out = {"losses": []}
    times = []
    for i in range(FIRST_STEPS):
        t0 = time.perf_counter()
        out["losses"].append(driver.step(i))
        times.append(time.perf_counter() - t0)
        if i == 0:
            moments, scale = driver.first_moment()
            out["grad_norms"] = norms_of(moments, scale)
            del moments
    delta = change_program(init_fn)(driver.params(), key)
    out["delta_norms"] = {k: float(v) for k, v in delta.items()}
    return out, times


class CollectorClock:
    """Seconds the interpreter's garbage collector ran, and its full passes:
    the one host stall the benchmark can name without spans in the program."""

    def __init__(self):
        self.seconds, self.full_passes, self._t0 = 0.0, 0, None
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self.full_passes += info["generation"] == 2
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._event)


def measure(driver, seconds, first_index):
    """Steps until ``seconds`` have passed; a step that has started is
    finished. Returns each step's wall time and loss, and the window's wall
    time from the start of the first step to the end of the last."""
    import jax

    times, losses = [], []
    i = first_index
    begin = t0 = time.perf_counter()
    end = begin + seconds
    while t0 < end:
        with jax.profiler.TraceAnnotation("bench.step"):
            losses.append(driver.step(i))
        t1 = time.perf_counter()
        times.append(t1 - t0)
        t0 = t1
        i += 1
    return times, losses, t0 - begin


def peak_bytes(devices):
    """Peak of device memory on the fullest chip. The allocator's
    ``peak_bytes_in_use`` leaves out what loaded programs reserve for their
    temporaries (``peak_bytes_reserved``, most of a training step's memory),
    so the two are added. Both are peaks of the process: the sum is the AOT
    ``memory_analysis()`` total of the step plus whatever the process ever
    held beside it (the pool of batches; nothing of the benchmark's own)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0))
                     + int(stats.get("peak_bytes_reserved", 0)))
    return max(peaks)


def read_layer_metrics(wanted, ctx):
    """One reader for each family (the part of a metric's name before the
    first dot), found by that name; a reader returns what it can read."""
    values = {}
    for family in sorted({m["name"].split(".")[0] for m in wanted}):
        reader = importlib.import_module(
            "benchmarks.layer_metrics." + family)
        values.update(reader.read(ctx))
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted
            if values.get(m["name"]) is not None
            and math.isfinite(values[m["name"]])}


def run_cell(manifest, cell, seed, seconds, traced, devices, peaks, t_start):
    import jax

    from paddle_tpu import observability as obs

    from .compiles import XlaCompiles
    from .reference_train import follow, identity

    cfg, traffic, parts = load_cell(manifest, cell)
    reference = parts["reference"]

    obs.enable()   # counters only count when the program's metrics are on
    xla = XlaCompiles()

    t0 = time.perf_counter()
    driver = parts["driver"].Driver(parts["model"], cfg, traffic, devices)
    driver.build()
    build_s = time.perf_counter() - t0

    init_fn, key = start_of(reference, cfg, seed)
    driver.load(make_params(reference, cfg, seed),
                make_pool(reference, cfg, traffic, seed, traffic["pool"]))
    program, first_times = drive_first_steps(driver, init_fn, key)
    warm_times = [first_times[-1]]
    for i in range(FIRST_STEPS, WARM_STEPS):
        t0 = time.perf_counter()
        driver.step(i)
        warm_times.append(time.perf_counter() - t0)
    step_s = statistics.median(warm_times)
    setup_builds, setup_compile_s = xla.builds, xla.seconds
    say("# set-up: build %.2f s, %d XLA builds (%d from the cache) in %.2f s,"
        " first steps %s ms, warm step %.2f ms"
        % (build_s, xla.builds, xla.cache_hits, xla.seconds,
           [round(1e3 * t, 1) for t in first_times], 1e3 * step_s))

    watched0, counters0 = driver.watched(), driver.counters()
    # the newest trace of a cell stays there, to be read by hand
    trace_dir = os.path.join(REPO, ".bench_trace", cell["name"])
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        seconds = min(seconds, TRACE_SECONDS)
    gc.collect()   # every window starts from the same state of the collector
    collector = CollectorClock()
    setup_s = time.perf_counter() - t_start
    times, losses, window_s = measure(driver, seconds, WARM_STEPS)
    collector.close()
    if traced:
        jax.profiler.stop_trace()
    builds_in_window = xla.builds - setup_builds
    watched1, counters1 = driver.watched(), driver.counters()
    memory_peak = peak_bytes(devices)
    grown = {k: (watched0[k], watched1[k]) for k in watched0
             if (watched1[k] or 0) != (watched0[k] or 0)}
    counters = {k: (counters1[k] or 0) - (counters0[k] or 0)
                for k in counters0}

    # the program's state goes before the reference's comes
    driver.close()
    del driver
    gc.collect()
    t0 = time.perf_counter()
    builds0, build_s0 = xla.builds, xla.seconds
    ref = follow(lambda p, b, cast: reference.loss(p, b, cfg, cast),
                 cfg["optimizer"], init_fn, key,
                 make_pool(reference, cfg, traffic, seed, FIRST_STEPS),
                 traffic.get("reference_rows_per_block"), identity)
    reference_s = time.perf_counter() - t0

    rows = check.compare(program, ref, traffic["limits"])
    failed = sum(1 for x in losses if not math.isfinite(x))
    n = len(losses)
    quarter = max(1, n // 4)
    falling = n >= 2 and (statistics.mean(losses[-quarter:])
                          < statistics.mean(losses[:quarter]))
    say("# window: %d steps in %.3f s; step median %.2f ms, p95 %.2f ms, "
        "slowest %.2f ms; collector %.3f s in %d full passes; loss %.5f -> "
        "%.5f; XLA builds in the window %d; counters grown %s; reference "
        "%.2f s (%d XLA builds, %.2f s)" % (
            n, window_s, 1e3 * statistics.median(times),
            timing.step_ms_p95(times), 1e3 * max(times), collector.seconds,
            collector.full_passes, statistics.mean(losses[:quarter]),
            statistics.mean(losses[-quarter:]), builds_in_window,
            grown or "none", reference_s, xla.builds - builds0,
            xla.seconds - build_s0))
    correct = (all(ok for *_, ok, _ in rows) and failed == 0 and falling
               and builds_in_window == 0 and not grown)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": n, "failed": failed,
              "metrics": {}, "device": device}
    # each number compared beside its limit: the result's last key
    compared = {name: {"value": value, "limit": limit, "ok": bool(ok),
                       "at": note}
                for name, value, limit, ok, note in rows}
    if not traced:
        values = {"setup_s": setup_s,
                  traffic["throughput_metric"]: timing.rate(
                      n, traffic["items_per_step"], window_s)}
        for m in manifest.end_to_end(cell):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        result["compared"] = compared
        return result

    reduction = None
    if peaks is not None:   # the tests' stand-in for a chip has no peaks
        reduction = trace_lib.reduce(
            trace_lib.load(trace_lib.find_xplane(trace_dir)))
        if reduction is None or reduction["busy_s"] <= 0:
            raise SystemExit("benchmark: the trace holds no device operation")
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"][:5]}
    ctx = {"trace": reduction, "peaks": peaks, "chips": len(devices),
           "suffix": traffic["suffix"], "counters": counters,
           "step_times": times, "build_s": build_s,
           "compile_s": setup_compile_s, "memory_peak_bytes": memory_peak,
           "flops_per_step": parts["flops"].flops_per_step(cfg, traffic)}
    result["metrics"] = read_layer_metrics(manifest.per_layer(cell), ctx)
    result["compared"] = compared
    return result


def main(argv, t_start):
    args = parse_args(argv)
    manifest = Manifest(MANIFEST, REPO)
    cell = manifest.cell(args.workload)
    try:
        enable_cache()
    except ImportError as e:
        raise SystemExit("benchmark: the program is not in this checkout "
                         "(%s)" % e)
    devices, peaks = find_devices(cell["chips"])
    result = run_cell(manifest, cell, args.seed, args.seconds,
                      bool(args.trace), devices, peaks, t_start)
    say(json.dumps(result))
    # and as the last lines of standard error: the driver's record of a run
    # that is not correct keeps the end of that and of the result's line
    for name, c in result["compared"].items():
        print("# compared %-17s %.6g  limit %.6g  %s  (%s)" % (
            name, c["value"], c["limit"], "ok" if c["ok"] else "FAILED",
            c["at"]), file=sys.stderr, flush=True)
    return 0
