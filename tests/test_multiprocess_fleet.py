"""Multi-process STATIC-graph data parallelism (the collective-fleet
arm, round-3 follow-up to the dygraph test): 2 OS processes run
CompiledProgram.with_data_parallel over a global 2-device mesh; per-step
losses must match the single-process full-batch run and both ranks'
params stay identical."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

# jax CPU builds without multiprocess collective support fail the
# 2-process mesh with this marker — an environment limit, not a
# regression (the single-process oracle still runs)
_NO_MP_COLLECTIVES = "aren't implemented on the CPU backend"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_fleet.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith(("PADDLE_", "JAX_COORDINATOR", "JAX_NUM_PROC",
                         "JAX_PROCESS")):
            env.pop(k, None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _single_process_oracle(tmp_path):
    """Same model, full batch, one process (parity target)."""
    out = str(tmp_path / "oracle")
    proc = subprocess.run(
        [sys.executable, WORKER, out],
        env={**_env(), "PADDLE_TRAINERS_NUM": "1",
             "PADDLE_TRAINER_ID": "0", "ORACLE_WORLD": "2"},
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(open(out + ".rank0").read())


def test_two_process_fleet_converges_under_faults(tmp_path):
    """Second fault-injection CI path (ROADMAP): the collective-fleet
    workers pull every step's batch over the ps_rpc transport — every
    frame through distributed/fault.py — with 2% of sends dropped.
    Client retry + seq-matched responses must absorb the losses: the
    job completes and the per-step losses still match the clean
    single-process oracle exactly (a dropped-then-retried pull feeds
    the same bytes)."""
    from paddle_tpu.distributed.ps_rpc import PSServer

    oracle = _single_process_oracle(tmp_path)

    class _Scope(dict):
        def local_var_names(self):
            return list(self)

    class _Exec:
        def _read_var(self, scope, name):
            return scope.get(name)

        def _write_var(self, scope, name, val):
            scope[name] = np.asarray(val)

        def run_block(self, block, scope):
            block(scope)

    # the data server precomputes the same rng(7) batch sequence the
    # workers would have generated locally (world=2 global batches)
    scope = _Scope()
    rng = np.random.RandomState(7)
    for step in range(3):  # dist_worker_fleet.STEPS
        scope["x_s%d" % step] = rng.randn(16, 12).astype("float32")
        scope["y_s%d" % step] = rng.randint(0, 10, (16, 1)).astype(
            "int64")
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, _Exec(), scope, {}, fanin=2,
                      sync_mode=False)
    server.start_background()

    out = str(tmp_path / "fleet_faults")
    env = _env()
    env.update({
        "FLEET_DATA_ENDPOINT": endpoint,
        "PADDLE_TPU_FAULTS": "send.drop:0.02",
        "PADDLE_TPU_FAULT_SEED": "7",
        "PADDLE_PS_RPC_DEADLINE": "2.0",
        "PADDLE_PS_RPC_RETRIES": "12",
        "PADDLE_PS_RPC_BACKOFF_MS": "20",
    })
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", "--max_restarts=0",
             "--started_port=%d" % _free_port(),
             WORKER, out],
            env=env, capture_output=True, text=True, timeout=300)
        if proc.returncode != 0 and _NO_MP_COLLECTIVES in proc.stderr:
            pytest.skip("2-process CPU collectives unavailable: %s"
                        % _NO_MP_COLLECTIVES)
        assert proc.returncode == 0, (proc.stdout[-1000:],
                                      proc.stderr[-3000:])
        ranks = [json.loads(open("%s.rank%d" % (out, r)).read())
                 for r in (0, 1)]
        np.testing.assert_allclose(ranks[0]["losses"],
                                   ranks[1]["losses"], rtol=1e-6)
        np.testing.assert_allclose(ranks[0]["losses"],
                                   oracle["losses"], rtol=1e-5,
                                   atol=1e-6)
        assert abs(ranks[0]["checksum"] - ranks[1]["checksum"]) < 1e-6
    finally:
        server.stop()


def test_two_process_static_dp(tmp_path):
    oracle = _single_process_oracle(tmp_path)
    assert oracle["nranks"] == 1

    out = str(tmp_path / "fleet")
    port = _free_port()
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", "--started_port=%d" % port,
         WORKER, out],
        env=_env(), capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 and _NO_MP_COLLECTIVES in proc.stderr:
        pytest.skip("2-process CPU collectives unavailable: %s"
                    % _NO_MP_COLLECTIVES)
    assert proc.returncode == 0, (proc.stdout[-1000:],
                                  proc.stderr[-3000:])
    ranks = [json.loads(open("%s.rank%d" % (out, r)).read())
             for r in (0, 1)]

    # both ranks observed the same (global) per-step losses, equal to
    # the single-process full-batch run
    np.testing.assert_allclose(ranks[0]["losses"], ranks[1]["losses"],
                               rtol=1e-6)
    np.testing.assert_allclose(ranks[0]["losses"], oracle["losses"],
                               rtol=1e-5, atol=1e-6)
    # replicated updates kept params bitwise-aligned
    assert abs(ranks[0]["checksum"] - ranks[1]["checksum"]) < 1e-6
    assert abs(ranks[0]["checksum"] - oracle["checksum"]) < 1e-4
