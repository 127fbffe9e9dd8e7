"""Multi-process training launcher + supervisor.

Parity: /root/reference/python/paddle/distributed/launch.py:353 — spawn
one worker process per device/host slot with the PADDLE_TRAINER_*
environment contract. TPU-native: each worker also gets the
jax.distributed coordination variables, so dygraph prepare_context /
the collective fleet initialize over the coordination service instead
of a NCCL TCP id broadcast.

Supervision: the launcher no longer just propagates the first nonzero
exit. A worker that dies (crash, OOM-kill, SIGKILL) is relaunched in
place — same rank, same env, plus ``PADDLE_RESTART_COUNT`` — up to
``--max_restarts`` times per rank (env
``PADDLE_LAUNCH_MAX_RESTARTS``, default 3). Workers are expected to
resume from their newest valid checkpoint on restart
(``paddle_tpu.checkpoint.CheckpointManager.load_latest``); surviving
PS trainers keep making progress meanwhile via server-side heartbeat
eviction (``distributed/ps_rpc.py``). Only when a rank exhausts its
restart budget does the supervisor tear the job down.

Multi-server supervision (ISSUE 4): ``--pserver_endpoints=ep0,ep1``
with ``--server_script=serve.py`` additionally spawns one supervised
parameter-server process per endpoint (env contract:
``PADDLE_ROLE=pserver``, ``PADDLE_PSERVER_ENDPOINTS`` = full list,
``PADDLE_PSERVER_INDEX``, ``PSERVER_ENDPOINT`` = own endpoint).
Index 0 starts as the replication primary, the rest as backups. A
server that dies is relaunched with ``PADDLE_PS_REJOIN=1`` so it
rejoins as a CATCHING-UP BACKUP (never as a primary — the trainers
have already failed over; ``distributed/ps_rpc.py`` owns that
protocol). The job completes when every TRAINER rank exits 0; the
servers are then torn down and their exit codes ignored.

Per-shard supervision (ISSUE 8): ``--pserver_shards=N`` slices the
endpoint list into N contiguous primary+backup GROUPS
(``distributed/ps_shard.py`` owns the slicing and the client-side key
routing). Each server process gets ``PADDLE_PSERVER_SHARDS`` (the
count), ``PADDLE_PSERVER_SHARD`` (its group index, which also labels
its ``ps.lease_expiries{shard=}`` counters), ``PADDLE_PSERVER_INDEX``
(its index WITHIN the group) and — crucially —
``PADDLE_PSERVER_ENDPOINTS`` narrowed to ITS GROUP's list, so the
whole ISSUE-4/8 replication + lease + rejoin machinery runs per group
unchanged. Trainers get the FULL list plus the shard count and route
via ``ps_shard.client_from_env``. Supervision (relaunch as rejoining
backup, restart budgets) is per process, so one shard's failures
never charge another shard's budget.

Serving-replica supervision (ISSUE 11): ``--serving_replicas=N`` with
``--serving_script=replica.py`` spawns N supervised SERVING replica
processes (env contract: ``PADDLE_ROLE=serving``,
``PADDLE_SERVING_REPLICAS`` = count, ``PADDLE_SERVING_REPLICA_INDEX``,
``PADDLE_SERVING_ENDPOINTS`` = the full ``host:port`` list —
``--serving_endpoints`` or ``--serving_started_port`` + N —
``PADDLE_SERVING_ENDPOINT`` = the replica's own). Replicas are
stateless: a replica that dies (the chaos drill SIGKILLs one
mid-flight) is relaunched in place with the same endpoint and simply
rejoins the fleet router's rotation once its ``/healthz`` answers
``serving`` again. Trainers see ``PADDLE_SERVING_ENDPOINTS`` too (the
traffic driver builds its ``serving.FleetRouter`` from it). Like
pservers, replicas serve until every trainer rank exits, then are torn
down.

Job-level observability (ISSUE 5): with ``PADDLE_TPU_METRICS_DIR``
set, the supervisor clears stale dumps at job start (a merge must
never mix job incarnations), records every spawn / exit / relaunch
decision in its own flight recorder, and — in a ``finally``, so it
happens even when children were SIGKILLed — merges every per-process
dump into one job-level ``metrics.json`` and one merged chrome-trace
``trace.json`` (``observability.distributed.merge_job_dir``). A killed
child contributes its last periodic dump; the supervisor's flight ring
contributes the kill itself (``launch.exit`` with the signal).

Whole-job crash consistency (ISSUE 19): ``--ps_durable_dir=ROOT``
makes every shard primary tee its applied rounds to
``ROOT/shard-<k>/round-<n>/`` (delta frames riding the replication
machinery) and the launcher keep ``ROOT/job.json`` (incarnation
counter + restore cut). A relaunch over a populated root — or an
explicit ``--restore`` — is a COLD RESTART: the launcher computes the
newest round present on *every* shard, exports
``PADDLE_PS_RESTORE=1`` / ``PADDLE_PS_RESTORE_ROUND`` so servers load
exactly that cut and re-arm their fencing epochs past the dead
incarnation, and ``PADDLE_PS_RESTORE_ROUND`` to trainers so their
checkpoint resume clamps to the job cut. ``PADDLE_INCARNATION``
stamps every telemetry dump; the dead incarnation's dumps are KEPT
(postmortem evidence), never mixed into the new merge.

Usage:  python -m paddle_tpu.distributed.launch --nproc_per_node=2 \
            [--max_restarts=3] \
            [--server_script=serve.py --pserver_endpoints=ep0,ep1] \
            train.py --your-args
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

from ..observability import distributed as _dobs
from ..observability import flight as _flight

__all__ = ["launch", "get_cluster_env"]


def _parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes on this node")
    p.add_argument("--ips", default="127.0.0.1",
                   help="comma-separated node IPs (this node must be "
                        "included)")
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--log_dir", default=None)
    p.add_argument("--max_restarts", type=int,
                   default=int(os.environ.get(
                       "PADDLE_LAUNCH_MAX_RESTARTS", "3")),
                   help="relaunches per rank after an abnormal exit "
                        "before the whole job is brought down "
                        "(0 = die on first worker death)")
    p.add_argument("--server_script", default=None,
                   help="script run once per --pserver_endpoints entry "
                        "as a supervised parameter-server process")
    p.add_argument("--pserver_endpoints", default="",
                   help="comma-separated primary+backup pserver "
                        "endpoints (requires --server_script)")
    p.add_argument("--pserver_shards", type=int,
                   default=int(os.environ.get("PADDLE_PSERVER_SHARDS",
                                              "1")),
                   help="slice --pserver_endpoints into this many "
                        "contiguous primary+backup groups (key-range "
                        "sharded PS; endpoint count must divide "
                        "evenly)")
    p.add_argument("--ps_durable_dir",
                   default=os.environ.get("PADDLE_PS_DURABLE_DIR", ""),
                   help="root directory for round-fenced durable PS "
                        "snapshots (ISSUE 19): every shard primary "
                        "tees its applied rounds here, and a cold "
                        "restart resumes from the newest round present "
                        "on EVERY shard")
    p.add_argument("--restore", action="store_true",
                   help="force cold-restart resume from "
                        "--ps_durable_dir (restore is AUTO-detected "
                        "when the durable dir holds round frames; this "
                        "flag additionally makes an empty/unrestorable "
                        "dir a hard error instead of a fresh start)")
    p.add_argument("--ps_witness_endpoints", default="",
                   help="comma-separated external quorum-witness "
                        "endpoints (ISSUE 13): one witness process "
                        "per endpoint is spawned from --server_script "
                        "with PADDLE_ROLE=witness, and every pserver "
                        "gets PADDLE_PS_WITNESSES so its elections "
                        "require a live witness grant")
    p.add_argument("--serving_script", default=None,
                   help="script run once per serving replica as a "
                        "supervised stateless serving process")
    p.add_argument("--serving_replicas", type=int, default=0,
                   help="number of supervised serving replicas "
                        "(requires --serving_script)")
    p.add_argument("--serving_endpoints", default="",
                   help="comma-separated host:port per replica "
                        "(default: 127.0.0.1:<serving_started_port>+i)")
    p.add_argument("--serving_started_port", type=int, default=8200)
    p.add_argument("--steering", action="store_true",
                   help="supervise a steering daemon (observability."
                        "steering_daemon) over the job's "
                        "PADDLE_TPU_METRICS_DIR: it watches the merged "
                        "sampled reports and emits PROPOSED plan "
                        "artifacts (never applies; see README "
                        "'Self-driving runtime')")
    p.add_argument("--steering_interval", type=float, default=5.0,
                   help="seconds between steering-daemon polls")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def get_cluster_env(node_ips, node_rank, nproc_per_node, started_port,
                    local_rank):
    """The PADDLE_* env contract for one worker (reference launch.py:175)."""
    nnodes = len(node_ips)
    nranks = nnodes * nproc_per_node
    rank = node_rank * nproc_per_node + local_rank
    endpoints = [
        "%s:%d" % (ip, started_port + i)
        for ip in node_ips for i in range(nproc_per_node)
    ]
    env = {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nranks),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "FLAGS_selected_tpus": str(local_rank),
        # jax.distributed contract: coordinator is rank 0's endpoint
        "JAX_COORDINATOR_ADDRESS": endpoints[0],
        "JAX_NUM_PROCESSES": str(nranks),
        "JAX_PROCESS_ID": str(rank),
    }
    return env


def _log(msg: str) -> None:
    print("[launch] %s" % msg, file=sys.stderr, flush=True)


def _refuse_shared_tpu(nproc_per_node: int) -> None:
    """A TPU host's chips belong to ONE process at a time: every
    ``--nproc_per_node`` child would open all of them (nothing here
    partitions chips per process), so the second child hangs or dies
    inside the runtime. Refuse up front with the reason. The multi-chip
    path on one host is the in-process mesh
    (``CompiledProgram.with_data_parallel`` over ``jax.devices()``).

    The launcher itself must never touch the chip, so what the children
    would get is read from ``JAX_PLATFORMS`` or, when that is unset,
    asked of a short-lived probe process that exits before any child
    starts."""
    if nproc_per_node <= 1:
        return
    platforms = [p for p in os.environ.get("JAX_PLATFORMS", "").split(",")
                 if p]
    if platforms:
        on_tpu = platforms[0] == "tpu"
    else:
        probe = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.default_backend())"],
            capture_output=True, text=True, timeout=300)
        if probe.returncode != 0:
            raise SystemExit(
                "launch: could not determine the children's JAX "
                "backend: %s" % probe.stderr[-1000:])
        on_tpu = probe.stdout.strip().splitlines()[-1] == "tpu"
    if on_tpu:
        raise SystemExit(
            "launch: --nproc_per_node=%d on a TPU host: a host's chips "
            "belong to one process at a time and every child would "
            "open all of them. Run ONE process per host and drive its "
            "chips through the in-process mesh "
            "(CompiledProgram.with_data_parallel), or pin the children "
            "off the chip with JAX_PLATFORMS=cpu." % nproc_per_node)


class _Worker:
    """One supervised rank: its env, restart budget, and log sink."""

    def __init__(self, local_rank: int, cmd, env, log_dir,
                 role: str = "trainer", metrics_dir=None,
                 global_rank=None):
        self.local_rank = local_rank
        # the rank the CHILD will dump under (process_identity reads
        # the global PADDLE_TRAINER_ID / PADDLE_PSERVER_GLOBAL_INDEX,
        # not the node-local slot) — clock records must carry the same
        # name or the merge can never match them to their dump
        self.global_rank = (local_rank if global_rank is None
                            else int(global_rank))
        self.cmd = list(cmd)
        self.env = dict(env)
        self.log_dir = log_dir
        self.role = role
        self.metrics_dir = metrics_dir
        self.restarts = 0
        self.proc: subprocess.Popen = None
        self._fp = None
        # clock handshake bookkeeping (observability.distributed):
        # the ping file this incarnation will write, its dump name,
        # the launcher-clock spawn time, and the newest poll that saw
        # NO ping yet (tightening the skew window to one poll period)
        self.clock_ping_path = None
        self.clock_proc = None
        self.spawned_at_us = None
        self.last_absent_poll_us = None

    def _proc_base(self) -> str:
        base = "%s-%d" % (self.role, self.global_rank)
        if self.restarts:
            base += ".r%d" % self.restarts
        return base

    def spawn(self) -> None:
        env = dict(self.env)
        env["PADDLE_RESTART_COUNT"] = str(self.restarts)
        if self.role == "pserver" and self.restarts > 0:
            # a relaunched server must come back as a catching-up
            # BACKUP: the trainers have already failed over, and a
            # fresh index-0 process claiming the primary role would
            # split the brain
            env["PADDLE_PS_REJOIN"] = "1"
        if self.metrics_dir:
            # clock handshake: this incarnation writes its wall clock
            # here when its telemetry arms; the supervision loop
            # records the launcher-relative skew for the merge
            self.clock_proc = self._proc_base()
            self.clock_ping_path = os.path.join(
                self.metrics_dir, self.clock_proc + ".clockping")
            env[_dobs.CLOCK_PING_ENV] = self.clock_ping_path
        stdout = stderr = None
        self.close_log()  # a relaunch must not leak the old handle
        if self.log_dir:
            # append across restarts: one workerlog per rank tells the
            # whole story, crash included
            name = {"pserver": "serverlog.%d",
                    "serving": "servinglog.%d",
                    "witness": "witnesslog.%d",
                    "steering": "steeringlog.%d"}.get(
                        self.role, "workerlog.%d") % self.local_rank
            self._fp = open(os.path.join(self.log_dir, name), "a")
            stdout = stderr = self._fp
        self.spawned_at_us = time.time() * 1e6
        self.last_absent_poll_us = None
        self.proc = subprocess.Popen(self.cmd, env=env, stdout=stdout,
                                     stderr=stderr)
        _flight.record("launch.spawn", role=self.role,
                       rank=self.local_rank, restart=self.restarts,
                       pid=self.proc.pid)

    def poll_clock_ping(self) -> None:
        """Complete the clock handshake if this worker's ping file
        appeared: record skew vs the launcher clock, consume the file.
        Cheap when there is nothing to do (one stat per poll)."""
        path = self.clock_ping_path
        if not path:
            return
        if not os.path.exists(path):
            # the ping wasn't there THIS poll: the eventual write must
            # happen after now, so the skew window shrinks from
            # "since spawn" (which includes seconds of interpreter +
            # jax import) to one poll period
            self.last_absent_poll_us = time.time() * 1e6
            return
        try:
            import json as _json

            with open(path, "r", encoding="utf-8") as f:
                doc = _json.load(f)
            child_wall = float(doc.get("wall_us") or 0.0)
        except (OSError, ValueError):
            return   # torn write: next poll sees the finished file
        self.clock_ping_path = None
        try:
            os.unlink(path)
        except OSError:
            pass
        if child_wall and self.spawned_at_us:
            t0 = max(self.spawned_at_us,
                     self.last_absent_poll_us or self.spawned_at_us)
            skew, unc = _dobs.record_clock_offset(
                self.metrics_dir, self.clock_proc, child_wall,
                t0, time.time() * 1e6)
            _flight.record("launch.clock_sync", role=self.role,
                           rank=self.local_rank,
                           skew_us=round(skew), uncertainty_us=round(unc))

    def close_log(self) -> None:
        if self._fp is not None:
            self._fp.close()
            self._fp = None


def launch(args=None):
    args = args if args is not None else _parse_args()
    node_ips = [ip for ip in args.ips.split(",") if ip]
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    pserver_eps = [e.strip() for e in args.pserver_endpoints.split(",")
                   if e.strip()]
    nshards = max(1, int(getattr(args, "pserver_shards", 1)))
    # -- whole-job crash consistency (ISSUE 19) ---------------------------
    # With a durable root armed, decide BEFORE anything spawns whether
    # this launch is a fresh start or a cold restart: compute the
    # restore cut (the newest round restorable on EVERY shard — never
    # a mixed one), bump the incarnation counter in job.json, and pin
    # both into the children's env. PADDLE_INCARNATION also stamps
    # every telemetry dump, so a restored job's metrics never mix with
    # the dead incarnation's.
    durable_root = (getattr(args, "ps_durable_dir", "") or "").strip()
    incarnation = 0
    restore_round = None
    if durable_root and pserver_eps:
        from .. import checkpoint as _ckpt

        prev = _ckpt.read_job_manifest(durable_root)
        if getattr(args, "restore", False) \
                or _ckpt.job_has_durable_state(durable_root):
            # raises the typed RestoreMissingShard when a shard group
            # has no usable rounds — a partial restore must be loud
            restore_round = _ckpt.job_restore_round(durable_root,
                                                    nshards)
        incarnation = int(prev.get("incarnation", -1)) + 1
        _ckpt.write_job_manifest(durable_root, {
            "incarnation": incarnation,
            "restore_round": restore_round,
            "shards": nshards,
            "endpoints": pserver_eps})
        # inherited by every child env (dict(os.environ) below) and by
        # the launcher's own telemetry identity
        os.environ["PADDLE_INCARNATION"] = str(incarnation)
        if restore_round is not None:
            _log("cold restart: incarnation %d resumes from durable "
                 "round %d (%s)"
                 % (incarnation, restore_round, durable_root))
    metrics_dir = _dobs.metrics_dir()
    if metrics_dir:
        # the supervisor is a dumping process too (role "launcher"),
        # and the job's dump dir must start empty: a merge that read a
        # previous incarnation's dumps would "see" processes that were
        # never part of this job
        _dobs.set_identity("launcher", args.node_rank)
        if restore_round is None:
            removed = _dobs.clear_stale_dumps(metrics_dir)
            if removed:
                _log("cleared %d stale dump(s) from %s"
                     % (removed, metrics_dir))
        else:
            # a cold restart KEEPS the dead incarnation's dumps: they
            # are the postmortem evidence of the kill, and this
            # incarnation's dumps carry a .i<n> suffix + incarnation
            # stamp so the merge never mixes the two
            _log("restore: keeping the dead incarnation's telemetry "
                 "dumps in %s" % metrics_dir)
        _dobs.arm(metrics_dir)
        if restore_round is not None:
            _flight.record("launch.cold_start", incarnation=incarnation,
                           restore_round=restore_round,
                           shards=nshards)
        # one job trace id, minted before the worker envs are copied
        # from os.environ: every rank derives identical per-round span
        # context from it (distributed.fleet_round_args), so a dp sync
        # round is one timeline in the merged trace.json
        os.environ.setdefault(_dobs.JOB_TRACE_ENV, os.urandom(8).hex())
    # workers must import paddle_tpu even when it runs from a source
    # checkout (script-dir sys.path[0] replaces the launcher's cwd)
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if pserver_eps and not args.server_script:
        raise SystemExit("--pserver_endpoints requires --server_script")
    witness_eps = [e.strip() for e in
                   (getattr(args, "ps_witness_endpoints", "") or "")
                   .split(",") if e.strip()]
    if witness_eps and not args.server_script:
        raise SystemExit("--ps_witness_endpoints requires "
                         "--server_script")
    n_serving = max(0, int(getattr(args, "serving_replicas", 0) or 0))
    serving_eps = [e.strip() for e in
                   (getattr(args, "serving_endpoints", "") or "")
                   .split(",") if e.strip()]
    if serving_eps and not n_serving:
        n_serving = len(serving_eps)
    if n_serving and not args.serving_script:
        raise SystemExit("--serving_replicas/--serving_endpoints "
                         "require --serving_script")
    if n_serving and not serving_eps:
        serving_eps = ["127.0.0.1:%d" % (args.serving_started_port + i)
                       for i in range(n_serving)]
    if n_serving and len(serving_eps) != n_serving:
        raise SystemExit("--serving_endpoints names %d endpoint(s) for "
                         "%d replicas" % (len(serving_eps), n_serving))
    shard_groups = [pserver_eps]
    if pserver_eps and nshards > 1:
        from .ps_shard import split_endpoint_groups

        try:
            shard_groups = split_endpoint_groups(pserver_eps, nshards)
        except ValueError as e:
            raise SystemExit(str(e))
    nranks = len(node_ips) * args.nproc_per_node
    _refuse_shared_tpu(args.nproc_per_node)

    workers = []
    for local_rank in range(args.nproc_per_node):
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.update(get_cluster_env(node_ips, args.node_rank,
                                   args.nproc_per_node,
                                   args.started_port, local_rank))
        env["PADDLE_ROLE"] = "trainer"
        if pserver_eps:
            env["PADDLE_PSERVER_ENDPOINTS"] = ",".join(pserver_eps)
            env["PADDLE_PSERVER_SHARDS"] = str(nshards)
        if restore_round is not None:
            # trainers clamp their checkpoint resume to the job cut
            # (CheckpointManager.load_at_or_before): a trainer ckpt
            # can be AHEAD of the cut after a corrupt-newest fallback
            env["PADDLE_PS_RESTORE_ROUND"] = str(restore_round)
        if serving_eps:
            # the traffic driver builds its FleetRouter from this
            env["PADDLE_SERVING_ENDPOINTS"] = ",".join(serving_eps)
        cmd = [sys.executable, "-u", args.training_script] + \
            list(args.training_script_args)
        workers.append(_Worker(
            local_rank, cmd, env, args.log_dir,
            metrics_dir=metrics_dir,
            # the child dumps under its GLOBAL rank (PADDLE_TRAINER_ID)
            global_rank=args.node_rank * args.nproc_per_node
            + local_rank))

    servers = []
    for shard, group in enumerate(shard_groups if pserver_eps else []):
        for i, ep in enumerate(group):
            env = dict(os.environ)
            env["PYTHONPATH"] = pkg_root + os.pathsep + \
                env.get("PYTHONPATH", "")
            env.update({
                "PADDLE_ROLE": "pserver",
                # each server sees only ITS group: the ISSUE-4/8
                # replication/lease/rejoin machinery runs per shard
                # (witnesses are shared across shards — per-shard
                # state lives in the witness, keyed by the renewal's
                # shard label)
                "PADDLE_PS_WITNESSES": ",".join(witness_eps),
                "PADDLE_PSERVER_ENDPOINTS": ",".join(group),
                "PADDLE_PSERVER_SHARDS": str(nshards),
                "PADDLE_PSERVER_SHARD": str(shard),
                "PADDLE_PSERVER_INDEX": str(i),
                # telemetry identity: unique across the WHOLE job
                # (per-group indexes repeat across shards)
                "PADDLE_PSERVER_GLOBAL_INDEX":
                    str(pserver_eps.index(ep)),
                "PSERVER_ENDPOINT": ep,
                "PADDLE_TRAINERS_NUM": str(nranks),
            })
            if durable_root:
                # round-fenced durable snapshots (ISSUE 19): every
                # group member knows the root; the active primary
                # tees its applied rounds there
                env["PADDLE_PS_DURABLE_DIR"] = durable_root
            if restore_round is not None:
                # cold restart: every member restores the JOB cut
                # (never its own newest round) and re-arms its fence
                env["PADDLE_PS_RESTORE"] = "1"
                env["PADDLE_PS_RESTORE_ROUND"] = str(restore_round)
            servers.append(_Worker(
                pserver_eps.index(ep),
                [sys.executable, "-u", args.server_script], env,
                args.log_dir, role="pserver",
                metrics_dir=metrics_dir))

    for i, ep in enumerate(witness_eps):
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.update({
            "PADDLE_ROLE": "witness",
            "PSERVER_ENDPOINT": ep,
            "PADDLE_PS_WITNESSES": ",".join(witness_eps),
            # dump identity: process_identity's fallback rank (two
            # witnesses must not clobber each other's telemetry)
            "PADDLE_TRAINER_ID": str(i),
        })
        # witnesses hold no parameter state: supervised like servers
        # (bounded relaunch, torn down after the trainers), no rejoin
        # protocol needed
        # local_rank offsets past the pserver slots (distinct log
        # files); the DUMP rank is the witness index (global_rank —
        # process_identity falls back to PADDLE_TRAINER_ID-less 0-base)
        servers.append(_Worker(
            len(pserver_eps) + i,
            [sys.executable, "-u", args.server_script], env,
            args.log_dir, role="witness", metrics_dir=metrics_dir,
            global_rank=i))

    for i, ep in enumerate(serving_eps):
        env = dict(os.environ)
        env["PYTHONPATH"] = pkg_root + os.pathsep + \
            env.get("PYTHONPATH", "")
        env.update({
            "PADDLE_ROLE": "serving",
            "PADDLE_SERVING_REPLICAS": str(n_serving),
            "PADDLE_SERVING_REPLICA_INDEX": str(i),
            "PADDLE_SERVING_ENDPOINTS": ",".join(serving_eps),
            "PADDLE_SERVING_ENDPOINT": ep,
        })
        # serving replicas are supervised exactly like pservers (spawn,
        # bounded relaunch, teardown after the trainers finish) — they
        # are stateless, so a relaunch needs no rejoin protocol: the
        # fleet router re-admits the endpoint once /healthz answers
        servers.append(_Worker(
            i, [sys.executable, "-u", args.serving_script], env,
            args.log_dir, role="serving", metrics_dir=metrics_dir))

    if getattr(args, "steering", False):
        if not metrics_dir:
            _log("--steering ignored: PADDLE_TPU_METRICS_DIR is unset "
                 "(the daemon watches the merged job dump dir)")
        else:
            # the steering daemon is supervised exactly like a server
            # (bounded relaunch, torn down after the trainers): it
            # only READS the merged telemetry and WRITES proposal
            # artifacts — a crashed daemon costs proposals, never
            # training state, so relaunch is always safe
            env = dict(os.environ)
            env["PYTHONPATH"] = pkg_root + os.pathsep + \
                env.get("PYTHONPATH", "")
            env.update({
                "PADDLE_ROLE": "steering",
                "PADDLE_TRAINER_ID": "0",
                "PADDLE_TPU_METRICS_DIR": metrics_dir,
            })
            servers.append(_Worker(
                0, [sys.executable, "-u", "-m",
                    "paddle_tpu.observability.steering_daemon",
                    "--interval", str(args.steering_interval)],
                env, args.log_dir, role="steering",
                metrics_dir=metrics_dir))

    def _terminate_all(sig=signal.SIGTERM):
        for w in workers + servers:
            if w.proc is not None and w.proc.poll() is None:
                try:
                    w.proc.send_signal(sig)
                except OSError:
                    pass
        for w in workers + servers:
            if w.proc is not None:
                try:
                    w.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    w.proc.kill()
                    w.proc.wait()

    live = set(range(args.nproc_per_node))
    rc = 0
    try:
        for s in servers:
            s.spawn()
        for w in workers:
            w.spawn()
        # supervision loop: poll, relaunch the dead (bounded), finish
        # when every TRAINER rank has exited cleanly (servers serve
        # until torn down below)
        while live:
            time.sleep(0.2)
            for w in workers + servers:
                # clock handshake: record each child's launcher-
                # relative skew as soon as its ping lands (the merge
                # rebases multi-node dumps with it)
                w.poll_clock_ping()
            for s in servers:
                code = s.proc.poll()
                if code is None or code == 0:
                    continue  # running, or deliberately shut down
                sig_note = (" (signal %d)" % -code) if code < 0 else ""
                _flight.record("launch.exit", role=s.role,
                               rank=s.local_rank, code=code,
                               signal=(-code if code < 0 else None))
                if s.restarts >= args.max_restarts:
                    _log("%s %d exited %d%s; restart budget (%d) "
                         "exhausted — bringing the job down"
                         % (s.role, s.local_rank, code, sig_note,
                            args.max_restarts))
                    rc = code if code > 0 else 1
                    _terminate_all()
                    live = set()
                    break
                s.restarts += 1
                _log("%s %d exited %d%s; relaunching%s (restart %d/%d)"
                     % (s.role, s.local_rank, code, sig_note,
                        " as a catching-up backup"
                        if s.role == "pserver" else "",
                        s.restarts, args.max_restarts))
                s.spawn()
            for w in workers:
                if w.local_rank not in live:
                    continue
                code = w.proc.poll()
                if code is None:
                    continue
                if code == 0:
                    live.discard(w.local_rank)
                    continue
                sig_note = (" (signal %d)" % -code) if code < 0 else ""
                _flight.record("launch.exit", role="trainer",
                               rank=w.local_rank, code=code,
                               signal=(-code if code < 0 else None))
                if w.restarts >= args.max_restarts:
                    _log("rank %d exited %d%s; restart budget (%d) "
                         "exhausted — bringing the job down"
                         % (w.local_rank, code, sig_note,
                            args.max_restarts))
                    rc = code if code > 0 else 1
                    live.discard(w.local_rank)
                    _terminate_all()
                    live = set()
                    break
                w.restarts += 1
                _log("rank %d exited %d%s; relaunching (restart %d/%d)"
                     " — worker resumes from its newest valid "
                     "checkpoint"
                     % (w.local_rank, code, sig_note, w.restarts,
                        args.max_restarts))
                w.spawn()
        return rc
    except KeyboardInterrupt:
        rc = 1  # the finally's launch.done event must not read as a
        # clean exit in the merged postmortem
        _terminate_all()
        return 1
    finally:
        # trainers are done (or the job is down): the servers' work is
        # over — tear them down and ignore their exit codes
        for s in servers:
            if s.proc is not None and s.proc.poll() is None:
                try:
                    s.proc.terminate()
                except OSError:
                    pass
        for s in servers:
            if s.proc is not None:
                try:
                    s.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    s.proc.kill()
                    s.proc.wait()
        for w in workers + servers:
            w.close_log()
        if metrics_dir:
            # even a job whose children were SIGKILLed leaves ONE
            # merged picture: each child contributed its periodic /
            # at-exit dumps, the supervisor contributes the kills it
            # observed, and the merge rebases everything onto the
            # shared wall clock
            # an unexpected exception unwinding through here must not
            # stamp the postmortem with a success marker
            done_rc = rc if sys.exc_info()[0] is None else 1
            _flight.record("launch.done", rc=done_rc)
            try:
                for w in workers + servers:
                    # a short job can finish before the supervision
                    # loop saw the ping — collect stragglers so the
                    # merge below still gets its skew records
                    w.poll_clock_ping()
                _dobs.dump_process()
                mpath, tpath = _dobs.merge_job_dir(metrics_dir)
                if mpath:
                    _log("merged job telemetry: %s + %s"
                         % (mpath, tpath))
            except Exception as e:  # noqa: BLE001 — telemetry must
                # never turn a green job red
                _log("job telemetry merge failed: %s: %s"
                     % (type(e).__name__, e))


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
