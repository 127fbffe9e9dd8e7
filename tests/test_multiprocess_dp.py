"""Genuine multi-process data parallelism: 2 OS processes, Gloo-backed
CPU collectives via jax.distributed, launched through
paddle_tpu.distributed.launch.

The reference contract this implements is test_dist_base.py:506
(_run_cluster vs _run_local): per-step losses of the 2-process run must
match the single-process full-batch run, and both ranks must hold
bitwise-identical parameters afterwards. This is the first test where
DataParallel.apply_collective_grads crosses a real process boundary
(round-2 missing #1).
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_dp.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env():
    env = dict(os.environ)
    # force the plain CPU platform in children (the parent's
    # virtual-device XLA_FLAGS must not leak into real multi-process
    # workers)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith(("PADDLE_", "JAX_COORDINATOR", "JAX_NUM_PROC",
                         "JAX_PROCESS")):
            env.pop(k, None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_two_process_dp_matches_single_process(tmp_path):
    env = _clean_env()

    # single-process oracle
    single = subprocess.run(
        [sys.executable, WORKER, str(tmp_path)], env=env,
        capture_output=True, text=True, timeout=240)
    assert single.returncode == 0, single.stderr[-2000:]
    oracle = json.loads(single.stdout.strip().splitlines()[-1])
    assert oracle["nranks"] == 1

    # 2-process cluster via the launcher (exercises launch.py's
    # PADDLE_* + jax.distributed env contract end to end)
    port = _free_port()
    out = tmp_path / "mp"
    out.mkdir()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", "--started_port=%d" % port,
         WORKER, str(out)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, (proc.stdout[-1000:], proc.stderr[-3000:])

    ranks = []
    for r in (0, 1):
        f = out / ("rank%d.json" % r)
        assert f.exists(), proc.stderr[-3000:]
        ranks.append(json.loads(f.read_text()))

    # per-step loss parity: mean of equal-size shard losses == the
    # full-batch loss of the single-process run
    mp_losses = np.mean([r["losses"] for r in ranks], axis=0)
    np.testing.assert_allclose(mp_losses, oracle["losses"],
                               rtol=1e-5, atol=1e-6)
    # ranks stay in sync (allreduced grads -> identical updates)
    assert abs(ranks[0]["checksum"] - ranks[1]["checksum"]) < 1e-6
    # and training actually moved the params identically to the oracle
    assert abs(ranks[0]["checksum"] - oracle["checksum"]) < 1e-4
