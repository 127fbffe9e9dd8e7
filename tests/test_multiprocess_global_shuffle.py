"""True global shuffle (round-4 VERDICT item #7): records must MIGRATE
between real worker OS processes (DatasetImpl::GlobalShuffle,
data_set.h:188 — the reference exchanges via FleetWrapper RPC; here via
distributed/record_shuffle over sockets)."""
import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_shuffle.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_records_migrate_between_workers(tmp_path):
    n_rec = 40
    # worker k's shard has labels in [k*1000, k*1000 + n_rec)
    files = []
    for k in range(2):
        p = tmp_path / ("part-%d" % k)
        with open(p, "w") as f:
            for i in range(n_rec):
                f.write("4 0.1 0.2 0.3 0.4 1 %d\n" % (k * 1000 + i))
        files.append(str(p))

    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    outs = [tmp_path / ("out%d.json" % k) for k in range(2)]
    procs = []
    for k in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env["PADDLE_SHUFFLE_ENDPOINTS"] = ",".join(eps)
        env["PADDLE_TRAINER_ID"] = str(k)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, str(outs[k]), files[k]],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-3000:]

    results = [json.loads(o.read_text()) for o in outs]
    for k, r in enumerate(results):
        assert r["before"] == [k * 1000 + i for i in range(n_rec)]
        # migration happened: this worker now owns records from BOTH
        # origin shards (crc-based routing makes all-same vanishingly
        # unlikely for 40 records)
        origins = {v // 1000 for v in r["after"]}
        assert origins == {0, 1}, r["after"]
    # the union is exactly the original multiset — nothing lost or
    # duplicated in flight
    merged = sorted(results[0]["after"] + results[1]["after"])
    assert merged == sorted(results[0]["before"]
                            + results[1]["before"])
