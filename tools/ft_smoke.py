"""Fault-tolerance CI smoke (ci/check.sh gate 6).

End-to-end recovery drills on one host.

Default (trainer-kill): a real PS server process, two trainer
processes under the ``distributed.launch`` supervisor, rank 1
SIGKILLs itself mid-round 3. PASS requires the whole job to exit 0 —
which can only happen if (a) the server's heartbeat monitor evicted
the dead rank so the survivor's barriers completed, (b) the supervisor
relaunched the rank, and (c) the relaunch resumed from its newest
valid (manifest-verified) checkpoint and finished the remaining
rounds. The final checkpoint is then re-verified here.

``--server-kill``: the 2-trainer / 2-server replicated job. The
PRIMARY pserver SIGKILLs itself while applying round 3 (the round is
summed + optimized locally but never replicated — the worst spot).
PASS requires the job to exit 0 with every trainer failed over to the
backup AND the final params matching the clean single-server
computation BIT-FOR-BIT — retry + failover replay + the replicated
dedup watermark must reconstruct the lost round exactly once. The
supervisor also relaunches the killed server, which rejoins as a
catching-up backup.

Usage: python tools/ft_smoke.py [--rounds 6] [--server-kill]
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_ft.py")
if REPO not in sys.path:  # script-dir sys.path[0] is tools/
    sys.path.insert(0, REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:  # imported by tests, not only run directly
    sys.path.insert(0, _TOOLS)


def _check_telemetry(mdir: str, want_promotion: bool = False,
                     want_delta: bool = False) -> bool:
    """Post-drill: print the cross-process postmortem and require the
    job-level merged artifacts (the launch supervisor writes them even
    though children died by SIGKILL mid-run). ``want_delta`` (the
    replicated drills) additionally requires the merged counters to
    show DELTA replication was actually exercised — ``ps.delta_rounds``
    > 0 — so a silent regression back to full-blob shipping fails CI
    here even before bench_diff sees the bytes."""
    import ft_timeline

    ft_timeline.print_postmortem(mdir, limit=40)
    ok = True
    for name in ("metrics.json", "trace.json"):
        present = os.path.exists(os.path.join(mdir, name))
        print("[ft_smoke] %s: job-level merged %s"
              % ("PASS" if present else "FAIL", name))
        ok = ok and present
    if want_promotion and ok:
        events = ft_timeline.load_events(mdir)
        promo = any(e["kind"] == "ps.promotion" for e in events)
        print("[ft_smoke] %s: promotion visible in the merged timeline"
              % ("PASS" if promo else "FAIL"))
        ok = ok and promo
    if want_delta and ok:
        totals = json.load(open(os.path.join(
            mdir, "metrics.json")))["counters_total"]
        deltas = totals.get("ps.delta_rounds", 0)
        print("[ft_smoke] %s: delta replication exercised "
              "(ps.delta_rounds=%s, anchors=%s)"
              % ("PASS" if deltas > 0 else "FAIL", deltas,
                 totals.get("ps.anchor_rounds")))
        ok = ok and deltas > 0
    return ok


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(**over):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PADDLE_PS_EVICT_AFTER"] = "2.0"
    env["PADDLE_PS_HEARTBEAT_MS"] = "200"
    env.update({k: str(v) for k, v in over.items()})
    return env


def oracle_w(rounds: int, trainers: int = 2, lr: float = 0.1,
             dim: int = 4, var: int = 0) -> np.ndarray:
    """The clean single-server float32 computation the recovered job
    must match bit-for-bit (same ops, same order, as the PS applies).
    ``var`` selects the per-shard var of the sharded drills (var 0 is
    the legacy single-var oracle, bit-identical)."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from dist_worker_ft import grad_for

    w = np.zeros(dim, dtype=np.float32)
    for rnd in range(1, rounds + 1):
        total = grad_for(0, rnd, var)
        for t in range(1, trainers):
            total = total + grad_for(t, rnd, var)
        w = w - np.float32(lr) * total
    return w


def run_server_kill(args) -> int:
    """2 trainers, 2 replicated servers, primary SIGKILLed while
    applying round 3: exit 0 + bit-for-bit params or bust."""
    tmp = tempfile.mkdtemp(prefix="ft_smoke_sk_")
    eps = "127.0.0.1:%d,127.0.0.1:%d" % (_free_port(), _free_port())
    mdir = os.path.join(tmp, "metrics")
    print("[ft_smoke] server-kill drill: pservers at %s, %d rounds, "
          "primary dies applying round 3" % (eps, args.rounds))
    sup = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node=2", "--max_restarts=2",
         "--started_port=%d" % _free_port(),
         "--server_script=%s" % WORKER,
         "--pserver_endpoints=%s" % eps, WORKER],
        env=_env(FT_ROLE="trainer", PSERVER_ENDPOINT=eps,
                 FT_ROUNDS=args.rounds, FT_SERVER_DIE_AT_ROUND=3,
                 FT_OUT=os.path.join(tmp, "out"),
                 FT_CKPT_ROOT=os.path.join(tmp, "ckpt"),
                 PADDLE_TPU_METRICS_DIR=mdir,
                 PADDLE_TPU_DUMP_PERIOD="0.5",
                 PADDLE_PS_CONNECT_TIMEOUT="4",
                 PADDLE_PS_FAILOVER_CONNECT_TIMEOUT="3",
                 # bit-for-bit gate: eviction trades exactness for
                 # availability, and nobody is actually dead here for
                 # more than the failover window — keep it out of the
                 # race (a trainer mid-failover must not be evicted by
                 # the freshly promoted backup)
                 PADDLE_PS_EVICT_AFTER="15"),
        timeout=300, cwd=REPO)
    if sup.returncode != 0:
        print("[ft_smoke] FAIL: supervised job exited %d"
              % sup.returncode)
        return 1
    expected = oracle_w(args.rounds)
    ok = True
    for tid in (0, 1):
        r = json.load(open(os.path.join(tmp, "out.t%d.json" % tid)))
        got = np.asarray(r["w"], dtype=np.float32)
        checks = [
            ("trainer %d finished %d rounds" % (tid, args.rounds),
             r["rounds_done"] == args.rounds),
            ("trainer %d failed over to the backup (idx %s, fo=%s)"
             % (tid, r["ep_idx"], r["failovers"]),
             r["ep_idx"] == 1 and r["failovers"] >= 1),
            ("trainer %d's serving endpoint was promoted" % tid,
             bool(r["server_active"]) and r["server_promotions"] >= 1),
            ("trainer %d final params match the clean run bit-for-bit"
             % tid, got.tobytes() == expected.tobytes()),
        ]
        for what, passed in checks:
            print("[ft_smoke] %s: %s"
                  % ("PASS" if passed else "FAIL", what))
            ok = ok and passed
    ok = _check_telemetry(mdir, want_promotion=True,
                          want_delta=True) and ok
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser("ft_smoke")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--server-kill", action="store_true",
                    help="kill the PRIMARY PSERVER (replicated "
                         "2-server job) instead of a trainer")
    args = ap.parse_args()
    if args.server_kill:
        return run_server_kill(args)

    tmp = tempfile.mkdtemp(prefix="ft_smoke_")
    endpoint = "127.0.0.1:%d" % _free_port()
    mdir = os.path.join(tmp, "metrics")
    print("[ft_smoke] pserver at %s, %d rounds, rank 1 dies at round 3"
          % (endpoint, args.rounds))
    ps = subprocess.Popen(
        [sys.executable, WORKER],
        env=_env(FT_ROLE="pserver", PSERVER_ENDPOINT=endpoint,
                 PADDLE_TRAINERS_NUM=2,
                 PADDLE_TPU_METRICS_DIR=mdir,
                 PADDLE_TPU_DUMP_PERIOD="0.5"))
    try:
        sup = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", "--max_restarts=2",
             "--started_port=%d" % _free_port(), WORKER],
            env=_env(FT_ROLE="trainer", PSERVER_ENDPOINT=endpoint,
                     FT_ROUNDS=args.rounds, FT_DIE_AT_ROUND=3,
                     FT_DIE_RANK=1,
                     FT_OUT=os.path.join(tmp, "out"),
                     FT_CKPT_ROOT=os.path.join(tmp, "ckpt"),
                     PADDLE_TPU_METRICS_DIR=mdir,
                     PADDLE_TPU_DUMP_PERIOD="0.5"),
            timeout=240, cwd=REPO)
        if sup.returncode != 0:
            print("[ft_smoke] FAIL: supervised job exited %d"
                  % sup.returncode)
            return 1
        r1 = json.load(open(os.path.join(tmp, "out.t1.json")))
        checks = [
            ("rank 1 was relaunched", r1["restart"] == 1),
            ("rank 1 resumed from checkpoint round 2",
             r1["resumed_from"] == 2),
        ]
        # which recovery path ran is load-dependent: a slow relaunch
        # means eviction unblocked the survivor first (then the
        # relaunch was re-admitted); a fast one rejoins the round
        # before the eviction deadline. Both are successful recovery —
        # report which happened, gate only on internal consistency.
        if r1["evictions"]:
            print("[ft_smoke] INFO: eviction path (evictions=%d, "
                  "readmissions=%d)"
                  % (r1["evictions"], r1["readmissions"]))
        else:
            print("[ft_smoke] INFO: fast-rejoin path (relaunch beat "
                  "the eviction deadline)")
        checks.append(("eviction/readmission bookkeeping consistent",
                       r1["evictions"] >= r1["readmissions"] >= 0))
        # the relaunched rank's final checkpoint must verify end-to-end
        from paddle_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(os.path.join(tmp, "ckpt", "t1"))
        import numpy as np

        state = {}
        step = mgr.load_latest(lambda d: state.update(
            w=np.load(os.path.join(d, "state.npz"))["w"]))
        checks.append(("final checkpoint verifies at round %d"
                       % args.rounds, step == args.rounds))
        ok = True
        for what, passed in checks:
            print("[ft_smoke] %s: %s" % ("PASS" if passed else "FAIL",
                                         what))
            ok = ok and passed
    finally:
        if ps.poll() is None:
            # SIGTERM, not SIGKILL: the server's dump hook flushes its
            # registry + flight ring on the way out, so the postmortem
            # below includes the server's own view of the drill
            ps.terminate()
        try:
            ps.wait(timeout=10)
        except subprocess.TimeoutExpired:
            ps.kill()
            ps.wait(timeout=10)
    ok = _check_telemetry(mdir) and ok
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
