"""A run end to end, on the CPU, at the toy preset: one cell for each driver
(data_parallel on four virtual host devices). Without a chip a run prints
counts and host-clock numbers, never a device metric; the command itself,
started where JAX finds no TPU, prints no result at all."""
import json
import os
import subprocess
import sys

import pytest

from .conftest import PRESET, REPO

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.mark.parametrize("cell,throughput", [
    ("tiny_bert.static", "tokens_per_s"),
    ("tiny_bert.dygraph", "dygraph_tokens_per_s"),
    ("tiny_bert.dp4", "tokens_per_s"),
])
def test_cell_runs_end_to_end(preset_run, cell, throughput):
    chips = 4 if cell.endswith("dp4") else 1
    out, lines = preset_run(cell, 2 ** 31 + 77, 3, 0)
    assert set(out) == RESULT_KEYS
    assert set(out["device"]) == DEVICE_KEYS
    assert out["device"]["count"] == chips
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 10
    assert set(out["metrics"]) == {"setup_s", throughput}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # the rate is every step of the window over its whole wall time
    window = [l for l in lines if l.startswith("# window:")][0].split()
    steps, seconds = int(window[2]), float(window[5])
    assert steps == out["attempted"] and seconds >= 3
    with open(PRESET.replace("BENCHMARK.json", "traffic/%s.json" % cell)) as f:
        items = json.load(f)["items_per_step"]
    assert out["metrics"][throughput]["value"] == pytest.approx(
        items * steps / seconds, rel=1e-3)
    # each number compared is printed beside its limit: the last lines of
    # standard error, and the last key of the result's line
    assert all(l.startswith("# compared") for l in preset_run.err_lines[-3:])
    assert not any(l.startswith("# compared") for l in lines)
    assert list(out)[-1] == "compared" and len(out["compared"]) == 3
    for c in out["compared"].values():
        assert c["ok"] is True and c["value"] <= c["limit"]


def test_traced_run_without_a_chip_prints_no_device_metric(preset_run):
    out, _ = preset_run("tiny_bert.dygraph", 5, 2, 1)
    assert set(out) == RESULT_KEYS          # no breakdown without a chip
    assert set(out["device"]) == DEVICE_KEYS   # no busy_s, no window_s
    with open(PRESET) as f:
        sources = {m["name"]: m["source"] for m in json.load(f)["per_layer"]}
    assert out["metrics"]
    for name in out["metrics"]:
        assert sources[name] != "device_trace", name
    assert out["metrics"]["dygraph.lazy_cache_hit_pct.dygraph"]["value"] == 100


def test_traced_executor_run_reads_the_single_step_tail(preset_run):
    out, lines = preset_run("tiny_bert.static", 6, 2, 1)
    assert out["metrics"]["executor.step_ms_p95.tokens"]["value"] > 0
    assert "executor.compile_s" in out["metrics"]


def command(args, cwd=REPO):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900)


ARGS = ["--workload", "bert_base.static_s512", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_no_chip_means_no_result():
    """The command on a machine without a TPU: another code than 0 and no
    result line. It has no switch that would let it run there."""
    proc = command(ARGS)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    proc = command(ARGS + ["--manifest", PRESET])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_benchmark_alone_means_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths`` has no program to measure."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = command(ARGS, cwd=str(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "the program is not in this checkout" in proc.stderr
