"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of the repo's tier-1 suite (``tests/``). The cells they
drive are the toy preset under ``benchmarks/tests/preset``, which was added
the way a later PR adds a configuration or a traffic mix: new files and one
entry in a manifest, no edit to the harness. The command itself has no switch
that leaves the TPU: a test puts the preset's manifest and the host's devices
in the harness's way (``preset_run``).
"""
import json
import os
import sys

import pytest

# four virtual host devices for the data-parallel preset cell; read when the
# first test starts JAX's backend
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
PRESET = os.path.join(REPO, "benchmarks", "tests", "preset", "BENCHMARK.json")


@pytest.fixture
def preset_run(monkeypatch, capsys):
    """Runs ``harness.main`` on a preset cell, with the look for a chip
    replaced by the host's devices; returns the result line and all lines."""
    import time

    import jax

    from benchmarks.lib import harness

    monkeypatch.setattr(harness, "MANIFEST", PRESET)
    monkeypatch.setattr(harness, "find_devices",
                        lambda chips: (jax.devices()[:chips], None))

    def run(cell, seed, seconds, trace):
        capsys.readouterr()
        rc = harness.main(["--workload", cell, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          time.perf_counter())
        captured = capsys.readouterr()
        lines = captured.out.strip().splitlines()
        run.err_lines = captured.err.strip().splitlines()
        assert rc == 0
        return json.loads(lines[-1]), lines

    return run
