"""Multi-process distributed sparse tables (round-4 VERDICT item #4):
Wide&Deep with its embedding tables row-sliced across TWO real pserver
OS processes over the socket RPC; the trainer process pulls rows,
trains to convergence, and pushes sparse grads that each server applies
through its optimizer sub-block.

Reference contract: fleet_wrapper.h:84-156 + dist_ctr.py (the CTR
north-star) trained through test_dist_fleet_base-style localhost
subprocesses.
"""
import json
import os
import socket
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_sparse_ps.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(role, endpoints, my_ep=""):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PADDLE_TRAINING_ROLE"] = role
    env["PSERVER_ENDPOINTS"] = endpoints
    if my_ep:
        env["PSERVER_ENDPOINT"] = my_ep
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_wide_deep_trains_over_two_sparse_pservers(tmp_path):
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    endpoints = ",".join(eps)
    out = tmp_path / "trainer.json"

    servers = [
        subprocess.Popen(
            [sys.executable, WORKER, str(tmp_path / ("ps%d" % i))],
            env=_env("PSERVER", endpoints, ep),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for i, ep in enumerate(eps)
    ]
    try:
        tr = subprocess.run([sys.executable, WORKER, str(out)],
                            env=_env("TRAINER", endpoints),
                            capture_output=True, text=True, timeout=300)
        assert tr.returncode == 0, tr.stderr[-3000:]
        res = json.loads(out.read_text())
        losses = res["losses"]
        assert all(np.isfinite(l) for l in losses), losses
        # convergence: the id->label correlation is learnable
        assert losses[-1] < losses[0] * 0.8, losses
        # BOTH pservers host live, trained slices
        assert len(res["slice_sums"]) == 2
        assert all(s > 0 for s in res["slice_sums"]), res["slice_sums"]
        for p in servers:
            p.wait(timeout=60)
    finally:
        for p in servers:
            if p.poll() is None:
                p.kill()
