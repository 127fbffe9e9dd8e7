"""Chaos drill: seeded randomized fault schedules against the
replicated (and sharded) PS job, gated on the bit-for-bit dedup
invariant.

Each drill derives, from one seed, a randomized schedule:

- a random ``PADDLE_TPU_FAULTS`` plan (``fault.random_plan`` — the
  recoverable drop/dup/delay menu),
- a random SIGKILL of one trainer at a random round (supervised
  relaunch + checkpoint resume), and
- a random SIGKILL of a PRIMARY pserver at a random round
  (lease expiry -> quorum election on the backup + client failover +
  replay + server rejoin).

It then runs the sync job under the launch supervisor and asserts the
final params match the CLEAN single-server computation bit-for-bit:
retry + ``(cid, round, seq)`` dedup + replication watermark must make
every gradient count exactly once, no matter which frames the
injector ate and which processes died.

ISSUE 8 modes:

- ``--shards 2`` — 2 key-range shard groups x (primary+backup); the
  schedule picks WHICH shard's primary dies. The two-phase round
  barrier must keep the sister shard's rounds intact (bit-for-bit per
  shard var), and the merged telemetry must show DELTA replication
  actually ran with ``ps.replication_bytes{mode=delta}`` strictly
  below the full-anchor bytes for the same workload.
- ``--partition`` (requires ``--shards 2``) — additionally severs the
  OTHER shard's primary<->backup pair with the ``partition`` fault
  primitive for the whole run. That shard's backup must see its lease
  expire and LOSE its elections (no quorum through a partition —
  ``ps.lease_expiries`` without a promotion), its primary must keep
  applying every round, and the job still exits 0 bit-for-bit:
  exactly one writable primary per shard, no split brain, no lost
  rounds — while the killed shard next door still promotes. This is
  the ISSUE 8 acceptance drill (SIGKILL + partition in one run).

ISSUE 13 modes:

- ``--migrate`` (requires ``--shards 2``) — a LIVE KEY-RANGE
  MIGRATION under fire: trainer 0 asks the schedule's shard to move
  its var to the sister shard at a seeded round; the donor primary is
  SIGKILLed in the WORST spot (range installed on the recipient,
  nothing committed or replicated — ``PADDLE_PS_CHAOS_DIE_AFTER_
  INSTALL``), so the first attempt must ROLL BACK (begin without
  commit on the killed incarnation); the promoted donor backup then
  completes the re-triggered migration. Gated on exit 0, params
  bit-for-bit vs the clean run (zero lost or double-applied rounds),
  the kill -> promotion -> migration-commit causal chain in the
  merged trace, the shard-map version bump visible to every trainer,
  and — the drill runs with one external quorum WITNESS and a
  ``clock_jitter`` rule armed — witness votes in the merged counters.
- ``--evict`` (requires ``--shards 2``) — per-shard effective fanin
  DISAGREEING mid-round: the dying trainer's phase-1 barrier reaches
  shard 0 only, eviction is armed on shard 1 alone, and the relaunch
  is delayed past the eviction window. The two-phase barrier plus the
  stale-round guard must reconcile DETERMINISTICALLY: shard 0's var
  bit-for-bit with the full 2-trainer oracle, shard 1's var
  bit-for-bit with the oracle MINUS exactly the dead trainer's grad
  for the one round eviction sailed without it, both trainers
  agreeing, ``ps.stale_rounds`` > 0 and eviction + readmission in the
  merged counters.

ISSUE 18 mode:

- ``--migrate-range`` (requires ``--shards 2``) — the SELF-STEERED
  row-range rebalance under fire: trainers hammer the hot quarter of
  one shard's slice of a sparse table; trainer 0's SteeringDaemon
  watches the job's own merged ``ps.row_heat`` census, proposes a
  ``migrate_range`` plan at the skew breach, and the canary applies
  it through the LIVE protocol — during which the donor primary is
  SIGKILLed in the worst spot (rows staged on the recipient, nothing
  committed — ``PADDLE_PS_CHAOS_DIE_AFTER_INSTALL``), so attempt 1
  dies with the donor and the re-trigger completes on its promoted
  backup. Gated on exit 0; the sparse table bit-for-bit vs the pure
  push-schedule oracle on BOTH trainers; the plan carving a tail of
  the hot quarter; install < kill < promotion < replicated range-commit
  in the merged trace; ``ps.migration_bytes{kind=range}`` > 0; every
  trainer routing the moved rows to the recipient; and the full
  audit chain (proposal artifact, audit trail, active-plan pointer,
  ``steering.proposed`` < ``canary.promoted`` flight order) with
  bit-equal plan digests end to end. No trainer kill rides this mode
  (the fire is the donor kill + live steering); witness + clock
  jitter ride as in ``--migrate``.

ISSUE 19 mode:

- ``--total-loss`` — whole-job crash consistency: the sync job runs
  with a durable round store armed (``PADDLE_PS_DURABLE_DIR``), and
  once the seeded round is durable on EVERY shard the drill SIGKILLs
  every process at once — supervisor, servers, trainers, one
  ``killpg`` on the session, no survivors, no warning. It then
  relaunches the IDENTICAL command: the new supervisor must
  auto-detect the durable state, compute the newest globally-complete
  round across all shard groups (never a mixed cut), restore every
  server to that ONE round with fencing epochs re-armed from disk,
  clamp the trainers' checkpoint resume to the cut, and finish the
  job with final params BIT-FOR-BIT equal to an uninterrupted run —
  exactly-once across a total power loss. Gated on the dead
  incarnation's black boxes surviving the relaunch and the
  cold-start -> restore -> first-applied-round causal chain reading
  in order in the merged timeline. ``--corrupt-newest`` additionally
  tears the newest durable round's frame on every shard between the
  kill and the relaunch: the restore must fall back EXACTLY one round
  (the previous globally-complete cut) and still end bit-for-bit.

The schedule is a pure function of the seed (``make_schedule``), so a
failing drill replays exactly: rerun with the printed seed.

Each drill also runs with ``PADDLE_TPU_METRICS_DIR`` armed and gates
on the job's merged telemetry: metrics.json + trace.json must exist,
the injected faults and the promotion must be visible, and the kill ->
failover -> promotion -> first-applied-round chain must read in causal
order across >= 3 processes (``check_telemetry``; the human-readable
version is printed via ``tools/ft_timeline.py``).

Usage: python tools/chaos_drill.py [--rounds 1] [--sync-rounds 6]
       [--seed 1234] [--shards N] [--partition] [--total-loss
       [--corrupt-newest]]
"""
from __future__ import annotations

import argparse
import json
import os
import random
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

import ft_timeline  # noqa: E402 — the cross-process postmortem
from ft_smoke import oracle_w  # noqa: E402 — ONE bit-for-bit oracle


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def make_schedule(seed: int, sync_rounds: int = 6, shards: int = 1,
                  partition: bool = False, migrate: bool = False,
                  evict: bool = False,
                  migrate_range: bool = False,
                  total_loss: bool = False,
                  corrupt_newest: bool = False) -> dict:
    """The randomized fault schedule as a pure function of the seed —
    two calls with the same args MUST return the same dict (asserted
    by tests/test_fault_tolerance.py and test_survivable_ps.py). The
    legacy draws keep their order, so legacy schedules replay
    identically; shard draws come after, migrate draws after those."""
    from paddle_tpu.distributed import fault

    rng = random.Random(int(seed))
    hi = max(1, int(sync_rounds) - 1)
    sched = {
        "seed": int(seed),
        "sync_rounds": int(sync_rounds),
        "plan": fault.random_plan(rng),
        "trainer_kill_rank": rng.randint(0, 1),
        "trainer_kill_round": rng.randint(1, hi),
        "server_kill_round": rng.randint(1, hi),
        "shards": max(1, int(shards)),
        "partition": bool(partition),
        "migrate": bool(migrate),
        "evict": bool(evict),
    }
    sched["die_shard"] = (rng.randrange(sched["shards"])
                          if sched["shards"] > 1 else 0)
    # the partitioned pair must belong to a SURVIVING shard: the drill
    # separates "promotion must happen" (killed shard) from "promotion
    # must be quorum-denied" (partitioned shard)
    sched["partition_shard"] = (
        (sched["die_shard"] + 1) % sched["shards"]
        if sched["partition"] and sched["shards"] > 1 else None)
    if sched["migrate"]:
        # trigger at m -> executes (and the donor dies) at m+1 ->
        # re-trigger at m+2 -> completes by m+4: keep m small enough
        # that the completed migration still serves rounds
        sched["migrate_round"] = rng.randint(
            1, max(1, int(sync_rounds) - 4))
        sched["migrate_from"] = sched["die_shard"]
        sched["migrate_to"] = ((sched["die_shard"] + 1)
                               % sched["shards"])
    else:
        sched["migrate_round"] = None
    if sched["evict"]:
        # the dying trainer's partial barrier reaches shard 0 only;
        # the death round leaves room for post-reconciliation rounds
        sched["trainer_kill_round"] = min(
            sched["trainer_kill_round"],
            max(1, int(sync_rounds) - 2))
        sched["evict_shard"] = 1
    sched["migrate_range"] = bool(migrate_range)
    if sched["migrate_range"]:
        # draws appended AFTER every legacy draw: old schedules replay
        # identically. The donor is the die_shard draw (its primary is
        # the one CHAOS_DIE_AFTER_INSTALL kills); the steerer must
        # independently re-derive it from the row-heat census.
        sched["mr_base_round"] = rng.randint(2, 3)
        sched["mr_hot_shard"] = sched["die_shard"]
        sched["mr_to_shard"] = ((sched["die_shard"] + 1)
                                % sched["shards"])
    sched["total_loss"] = bool(total_loss)
    sched["corrupt_newest"] = bool(corrupt_newest)
    if sched["total_loss"]:
        # drawn AFTER every legacy draw: old schedules replay
        # identically. The whole job dies the moment this round is
        # durable on every shard — never on the last round, so the
        # restored incarnation must still train THROUGH the cut
        sched["total_kill_round"] = rng.randint(
            2, max(2, int(sync_rounds) - 2))
    else:
        sched["total_kill_round"] = None
    return sched


def _groups(sched: dict, eps: list) -> list:
    """The shard -> endpoint-group mapping, from the ONE slicing
    implementation launch.py hands the servers — the drill's partition
    pair and telemetry gates must name exactly the processes the
    launcher built."""
    from paddle_tpu.distributed.ps_shard import split_endpoint_groups

    return split_endpoint_groups(eps, sched["shards"])


def _env(sched: dict, tmp: str, eps: list) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("PADDLE_PS_HEARTBEAT_MS", None)
    plan = sched["plan"]
    if sched["partition_shard"] is not None:
        pg = _groups(sched, eps)[sched["partition_shard"]]
        # hard both-ways partition between that shard's primary and
        # backup for the WHOLE run: the backup must never win quorum
        plan = "%s,partition:1:%s|%s" % (plan, pg[0], pg[1])
    if sched.get("migrate") or sched.get("migrate_range"):
        # jittered clocks ride the migration drills: the lease/quorum
        # machinery must keep exactly one writable primary per shard
        # while every participant's timers wander
        plan = "%s,clock_jitter:0.3:300" % plan
    if sched.get("evict"):
        # the eviction-reconciliation oracle is timing-sensitive (the
        # delayed relaunch pins WHICH round sails without the dead
        # trainer): no frame faults in this mode
        plan = ""
    env.update({
        "FT_ROLE": "trainer",
        "PSERVER_ENDPOINT": ",".join(eps),
        "FT_ROUNDS": str(sched["sync_rounds"]),
        "FT_DIE_AT_ROUND": str(sched["trainer_kill_round"]),
        "FT_DIE_RANK": str(sched["trainer_kill_rank"]),
        "FT_SERVER_DIE_AT_ROUND": str(sched["server_kill_round"]),
        "FT_DIE_SHARD": str(sched["die_shard"]),
        "FT_OUT": os.path.join(tmp, "out"),
        "FT_CKPT_ROOT": os.path.join(tmp, "ckpt"),
        "PADDLE_TPU_FAULTS": plan,
        "PADDLE_TPU_FAULT_SEED": str(sched["seed"]),
        # the drill is gated on BIT-FOR-BIT parity with the clean run:
        # eviction deliberately trades exactness for availability
        # (survivor-only rounds diverge from the 2-trainer oracle), so
        # it is OFF here — the supervisor guarantees every death is
        # followed by a relaunch, and the sync barrier simply waits
        # for the relaunched rank to re-send its round (the dedup
        # keyed pending buffer makes the re-send idempotent)
        "PADDLE_PS_EVICT_AFTER": "0",
        # faults must be absorbed by RETRY, never converted into a
        # spurious failover off a healthy primary: a deep per-endpoint
        # retry budget keeps P(exhaustion by injected drops) ~ 0 while
        # a genuinely dead server still fails fast (conn refused)
        "PADDLE_PS_RPC_RETRIES": "12",
        "PADDLE_PS_RPC_BACKOFF_MS": "30",
        # short per-attempt deadline: a server-side recv.drop eats the
        # request frame, and only this deadline converts that silence
        # into a retry — at the default (round timeout + 30s) one
        # dropped frame would stall the whole round into eviction
        # territory. Retried barriers are safe: the dedup cache parks
        # the duplicate on the in-flight original. 12 x 8s also covers
        # every LEGITIMATE block (a barrier waiting out a ~3s relaunch)
        "PADDLE_PS_RPC_DEADLINE": "8",
        "PADDLE_PS_CONNECT_TIMEOUT": "4",
        "PADDLE_PS_FAILOVER_CONNECT_TIMEOUT": "3",
        "PADDLE_PS_REPL_DEADLINE": "5",
        # a short lease keeps the SIGKILLed shard's failover inside
        # the drill budget while still being >> one renewal period;
        # the partitioned shard's backup gets plenty of failed
        # elections to prove quorum denial
        "PADDLE_PS_LEASE_MS": "1200",
        # job-level telemetry: every process dumps registry + spans +
        # flight ring here (dir implies metrics armed); a short cadence
        # so even a SIGKILLed process leaves a fresh black box, and the
        # launch supervisor merges the lot into metrics.json +
        # trace.json at job end
        "PADDLE_TPU_METRICS_DIR": os.path.join(tmp, "metrics"),
        "PADDLE_TPU_DUMP_PERIOD": "0.5",
    })
    if sched.get("migrate"):
        groups = _groups(sched, eps)
        env.update({
            # the server kill is the migration hook's, not the
            # round-counted suicide
            "FT_SERVER_DIE_AT_ROUND": "0",
            "FT_MIGRATE_AT_ROUND": str(sched["migrate_round"]),
            "FT_MIGRATE_FROM_SHARD": str(sched["migrate_from"]),
            "FT_MIGRATE_TO_SHARD": str(sched["migrate_to"]),
            # the donor's INITIAL primary dies between installing the
            # range on the recipient and committing anything — the
            # worst spot; its relaunched incarnation rejoins as a
            # backup and never matches again
            "PADDLE_PS_CHAOS_DIE_AFTER_INSTALL":
                groups[sched["migrate_from"]][0],
        })
    if sched.get("migrate_range"):
        groups = _groups(sched, eps)
        env.update({
            # no round-counted server suicide and NO trainer kill:
            # this drill's fire is the donor-primary kill mid-install
            # plus the live steering chain (sparse-push exactly-once
            # across a TRAINER relaunch is a separate, future drill)
            "FT_SERVER_DIE_AT_ROUND": "0",
            "FT_DIE_AT_ROUND": "0",
            "FT_MIGRATE_RANGE": "1",
            "FT_STEER_RANGE": "1",
            "FT_MR_BASE_ROUND": str(sched["mr_base_round"]),
            "FT_MR_HOT_SHARD": str(sched["mr_hot_shard"]),
            # the donor's INITIAL primary dies between staging the
            # rows on the recipient and committing anything — the
            # worst spot; the canary's re-trigger completes on its
            # promoted backup
            "PADDLE_PS_CHAOS_DIE_AFTER_INSTALL":
                groups[sched["mr_hot_shard"]][0],
        })
    if sched.get("total_loss"):
        env.update({
            # the fire is the whole-job SIGKILL, not the round-counted
            # suicides — and the launcher reads the durable root from
            # the env exactly like a real deployment would
            "FT_DIE_AT_ROUND": "0",
            "FT_SERVER_DIE_AT_ROUND": "0",
            "PADDLE_PS_DURABLE_DIR": os.path.join(tmp, "durable"),
        })
    if sched.get("evict"):
        env.update({
            "FT_SERVER_DIE_AT_ROUND": "0",
            "FT_DIE_MODE": "partial_barrier",
            # eviction armed on shard 1 ONLY — shard 0 (which got the
            # dying trainer's partial barrier) keeps full fanin
            "FT_EVICT_SHARD": str(sched["evict_shard"]),
            "FT_EVICT_AFTER": "1.0",
            # the relaunch must come back AFTER shard 1's monitor
            # fired, pinning exactly one survivor-only round there
            "FT_RESTART_DELAY": "3.0",
        })
    return env


def _rerun_hint(sched: dict) -> str:
    return ("tools/chaos_drill.py --seed %d --sync-rounds %d"
            "%s%s%s%s%s%s%s"
            % (sched["seed"], sched["sync_rounds"],
               " --shards %d" % sched["shards"]
               if sched["shards"] > 1 else "",
               " --partition" if sched["partition"] else "",
               " --migrate" if sched.get("migrate") else "",
               " --evict" if sched.get("evict") else "",
               " --migrate-range"
               if sched.get("migrate_range") else "",
               " --total-loss" if sched.get("total_loss") else "",
               " --corrupt-newest"
               if sched.get("corrupt_newest") else ""))


def oracle_w_skipping(rounds: int, var: int, skip_tid: int,
                      skip_round: int) -> np.ndarray:
    """The eviction-reconciliation oracle: the clean computation MINUS
    one trainer's contribution to one round (the round the evicting
    shard applied while that trainer was dead) — same float32 ops in
    the same order the PS applies them."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from dist_worker_ft import grad_for

    w = np.zeros(4, dtype=np.float32)
    for rnd in range(1, rounds + 1):
        total = None
        for t in (0, 1):
            if t == skip_tid and rnd == skip_round:
                continue
            g = grad_for(t, rnd, var)
            total = g if total is None else total + g
        if total is not None:
            w = w - np.float32(0.1) * total
    return w


def run_drill(sched: dict) -> int:
    tmp = tempfile.mkdtemp(prefix="chaos_drill_")
    eps = ["127.0.0.1:%d" % _free_port()
           for _ in range(2 * sched["shards"])]
    print("[chaos] schedule %s" % json.dumps(sched, sort_keys=True))
    launch_args = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node=2", "--max_restarts=3",
        "--started_port=%d" % _free_port(),
        "--server_script=%s" % WORKER,
        "--pserver_shards=%d" % sched["shards"],
        "--pserver_endpoints=%s" % ",".join(eps)]
    witness_ep = None
    if sched.get("migrate") or sched.get("migrate_range"):
        # the migration drills run with an external quorum witness:
        # the donor-kill election must gather a real witness grant
        witness_ep = "127.0.0.1:%d" % _free_port()
        launch_args.append("--ps_witness_endpoints=%s" % witness_ep)
    launch_args.append(WORKER)
    sup = subprocess.run(launch_args, env=_env(sched, tmp, eps),
                         timeout=420, cwd=REPO)
    if sup.returncode != 0:
        print("[chaos] FAIL: job exited %d under schedule seed=%d "
              "(rerun: %s)" % (sup.returncode, sched["seed"],
                               _rerun_hint(sched)))
        return 1
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from dist_worker_ft import var_names

    names = var_names(sched["shards"])
    ok = True
    outs = {}
    for tid in (0, 1):
        r = json.load(open(os.path.join(tmp, "out.t%d.json" % tid)))
        outs[tid] = r
        for vi, name in enumerate(names):
            expected = [oracle_w(sched["sync_rounds"], var=vi)]
            note = "the clean run"
            if sched.get("evict") \
                    and vi == sched.get("evict_shard"):
                # the evicting shard may have applied EXACTLY ONE
                # round without the dead trainer (the round its
                # monitor fired in, kill_round + 1) — or none, when
                # the relaunch won the race anyway. Both are exact.
                expected.append(oracle_w_skipping(
                    sched["sync_rounds"], vi,
                    sched["trainer_kill_rank"],
                    sched["trainer_kill_round"] + 1))
                note = "a reconciliation oracle"
            got = np.asarray(r["vars"][name], dtype=np.float32)
            bitwise = any(got.tobytes() == e.tobytes()
                          for e in expected)
            print("[chaos] %s: trainer %d var %s %s %s "
                  "(failovers=%s, evictions=%s)"
                  % ("PASS" if bitwise else "FAIL", tid, name,
                     "matches" if bitwise else "DIVERGES FROM", note,
                     r.get("failovers"), r.get("evictions")))
            ok = ok and bitwise
    if sched.get("evict"):
        # both trainers must agree var-for-var — the barrier
        # reconciled to ONE state, whichever oracle it was
        agree = all(
            outs[0]["vars"][n] == outs[1]["vars"][n] for n in names)
        print("[chaos] %s: trainers agree bit-for-bit post-eviction"
              % ("PASS" if agree else "FAIL"))
        ok = ok and agree
    if sched.get("migrate_range"):
        # the sparse table, pulled through the (now range-split)
        # router, must match the pure push-schedule oracle on BOTH
        # trainers — exactly-once across the donor kill, the staged
        # install that died with it, and every wrong_shard redirect
        from dist_worker_ft import emb_oracle

        exp = emb_oracle(sched["sync_rounds"],
                         sched["mr_base_round"], 16, 4,
                         sched["shards"], sched["mr_hot_shard"])
        for tid in (0, 1):
            got = np.asarray(outs[tid].get("emb"), dtype=np.float32)
            bitwise = got.tobytes() == exp.tobytes()
            print("[chaos] %s: trainer %d sparse table emb %s the "
                  "push-schedule oracle" % (
                      "PASS" if bitwise else "FAIL", tid,
                      "matches" if bitwise else "DIVERGES FROM"))
            ok = ok and bitwise
    mdir = os.path.join(tmp, "metrics")
    if sched.get("migrate_range"):
        ok = check_migrate_range_telemetry(sched, mdir, eps,
                                           outs) and ok
    elif sched.get("migrate"):
        ok = check_migrate_telemetry(sched, mdir, eps, outs) and ok
    elif sched.get("evict"):
        ok = check_evict_telemetry(sched, mdir) and ok
    else:
        ok = check_telemetry(sched, mdir, eps) and ok
    if not ok:
        print("[chaos] reproduce with: %s" % _rerun_hint(sched))
    return 0 if ok else 1


def check_telemetry(sched: dict, mdir: str, eps: list) -> bool:
    """The drill's second gate: the job must leave ONE merged picture
    in which the killed primary's SIGKILL, the trainers' failover, and
    the promoted backup's first applied round are visible in causal
    order across >= 3 processes; the injected faults must show up; and
    (ISSUE 8) delta replication must have carried the job with its
    bytes strictly below the full anchors', while a partitioned
    shard's backup shows lease expiries but NO promotion — at most one
    writable primary per shard."""
    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    # the postmortem itself (also re-merges metrics.json + trace.json)
    ft_timeline.print_postmortem(mdir, limit=40)
    mpath = os.path.join(mdir, "metrics.json")
    tpath = os.path.join(mdir, "trace.json")
    chk("job-level metrics.json + trace.json merged",
        os.path.exists(mpath) and os.path.exists(tpath))
    if not ok:
        return False
    merged = json.load(open(mpath))
    totals = merged["counters_total"]
    chk("merged metrics preserve per-rank sections (%d processes)"
        % len(merged["processes"]), len(merged["processes"]) >= 4)
    n_faults = sum(v for k, v in totals.items()
                   if k.startswith("fault.injected"))
    chk("injected faults visible in merged counters (%d)" % n_faults,
        n_faults > 0)
    trace = json.load(open(tpath))
    names = {}
    for ev in trace.get("traceEvents", []):
        names.setdefault(ev.get("name"), []).append(ev)
    chk("merged timeline has injected-fault events",
        bool(names.get("fault.injected")))
    chk("merged timeline has the promotion event",
        bool(names.get("ps.promotion")))

    # -- delta replication actually carried the job (ISSUE 8) ----------
    delta_b = totals.get("ps.replication_bytes{mode=delta}", 0)
    full_b = totals.get("ps.replication_bytes{mode=full}", 0)
    chk("delta rounds ran (ps.delta_rounds=%s)"
        % totals.get("ps.delta_rounds"),
        totals.get("ps.delta_rounds", 0) > 0)
    chk("delta bytes (%d) strictly below full-anchor bytes (%d)"
        % (delta_b, full_b), 0 < delta_b < full_b)

    # causal chain: kill -> failover -> promotion -> first applied
    # round on the promoted backup, across >= 3 distinct processes
    events = ft_timeline.load_events(mdir)

    def first(pred):
        for e in events:
            if pred(e):
                return e
        return None

    groups = _groups(sched, eps)
    died = set(groups[sched["die_shard"]])
    kill = first(lambda e: e["kind"] == "launch.exit"
                 and e["fields"].get("role") == "pserver"
                 and e["fields"].get("signal") == 9)
    fo = first(lambda e: e["kind"] == "rpc.failover.begin"
               and e["proc"].startswith("trainer"))
    promo = first(lambda e: e["kind"] == "ps.promotion"
                  and e["fields"].get("endpoint") in died)
    chk("supervisor observed the primary's SIGKILL", kill is not None)
    chk("a trainer failed over", fo is not None)
    chk("the killed shard's backup was promoted", promo is not None)
    if not ok:
        return False
    applied = first(lambda e: e["kind"] == "ps.round_applied"
                    and e["proc"] == promo["proc"]
                    and e["fields"].get("round")
                    == sched["server_kill_round"]
                    and e["t_us"] > promo["t_us"])
    chk("promoted backup (%s) applied the killed round %d"
        % (promo["proc"], sched["server_kill_round"]),
        applied is not None)
    if applied is not None:
        # lease-based promotion is PROACTIVE: the backup may win its
        # election (kill + ~one lease) before any trainer reaches it,
        # so failover and promotion are not ordered — but both must
        # precede the promoted backup re-applying the killed round
        chk("causal order: kill < promotion < first applied round",
            kill["t_us"] < promo["t_us"] < applied["t_us"])
        chk("trainers failed over before the round was rebuilt",
            fo["t_us"] < applied["t_us"])
        procs = {fo["proc"], promo["proc"], applied["proc"],
                 kill["proc"]}
        chk("chain spans >= 3 processes (%s)" % sorted(procs),
            len(procs) >= 3)

    # -- partition: quorum denied, exactly one writable primary --------
    if sched["partition_shard"] is not None:
        part = set(groups[sched["partition_shard"]])
        part_promos = [e for e in events if e["kind"] == "ps.promotion"
                       and e["fields"].get("endpoint") in part]
        lost = [e for e in events if e["kind"] == "ps.election"
                and e["fields"].get("endpoint") in part
                and not e["fields"].get("won")]
        expired = [e for e in events if e["kind"] == "ps.lease_expired"
                   and e["fields"].get("endpoint") in part]
        n_part = sum(v for k, v in totals.items()
                     if k.startswith("fault.injected{")
                     and "kind=partition" in k)
        chk("partition frames were actually eaten (%d)" % n_part,
            n_part > 0)
        chk("partitioned backup's lease expired (%d events)"
            % len(expired), len(expired) >= 1)
        chk("partitioned backup lost every election (%d lost, 0 won)"
            % len(lost), len(lost) >= 1)
        chk("NO promotion in the partitioned shard (split brain)",
            not part_promos)
        # no lost rounds: the partitioned shard's PRIMARY kept
        # applying to the end (its backup simply fell off the stream)
        part_applied = [e for e in events
                        if e["kind"] == "ps.round_applied"
                        and e["fields"].get("round")
                        == sched["sync_rounds"]]
        chk("final round %d applied on every shard (%d appliers)"
            % (sched["sync_rounds"], len(part_applied)),
            len(part_applied) >= sched["shards"])
    return ok


def _load_merged(mdir: str):
    ft_timeline.print_postmortem(mdir, limit=40)
    mpath = os.path.join(mdir, "metrics.json")
    tpath = os.path.join(mdir, "trace.json")
    if not (os.path.exists(mpath) and os.path.exists(tpath)):
        return None, None
    return (json.load(open(mpath)),
            ft_timeline.load_events(mdir))


def check_migrate_telemetry(sched: dict, mdir: str, eps: list,
                            outs: dict) -> bool:
    """The --migrate gate: donor-primary SIGKILL mid-migration ->
    rollback of attempt 1 (begin on the killed incarnation, no commit
    before the kill) -> promotion -> the re-triggered migration
    COMPLETES (kill < promotion < migration-commit causal chain) ->
    every trainer adopted the bumped shard map; witness votes and
    injected clock jitter visible in the merged counters."""
    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    merged, events = _load_merged(mdir)
    chk("job-level metrics.json + trace.json merged",
        merged is not None)
    if not ok:
        return False
    totals = merged["counters_total"]
    groups = _groups(sched, eps)
    donor = set(groups[sched["migrate_from"]])
    donor_primary = groups[sched["migrate_from"]][0]

    kill = next((e for e in events if e["kind"] == "launch.exit"
                 and e["fields"].get("role") == "pserver"
                 and e["fields"].get("signal") == 9), None)
    begins = [e for e in events if e["kind"] == "ps.migration_begin"]
    installs = [e for e in events
                if e["kind"] == "ps.migration_install"]
    commits = [e for e in events
               if e["kind"] == "ps.migration_commit"]
    promo = next((e for e in events if e["kind"] == "ps.promotion"
                  and e["fields"].get("endpoint") in donor), None)
    chk("supervisor observed the donor primary's SIGKILL",
        kill is not None)
    chk("migration began on the (to-be-killed) donor primary "
        "(%d begin events)" % len(begins), len(begins) >= 1)
    chk("range installed on the recipient (%d installs)"
        % len(installs), len(installs) >= 1)
    chk("the donor shard's backup was promoted", promo is not None)
    chk("the re-triggered migration COMMITTED (%d commits)"
        % len(commits), len(commits) >= 1)
    if not ok:
        return False
    first_install = min(installs, key=lambda e: e["t_us"])
    commit = min(commits, key=lambda e: e["t_us"])
    # attempt 1 rolled back: nothing committed before the kill. (The
    # killed donor's own `begin` flight line usually dies with it —
    # SIGKILL eats its last ring flush — so the SURVIVING recipient's
    # first install is the pre-kill evidence.)
    chk("attempt 1 rolled back (no commit precedes the kill)",
        commit["t_us"] > kill["t_us"])
    chk("causal chain: kill < promotion < migration commit",
        kill["t_us"] < promo["t_us"] < commit["t_us"])
    chk("attempt 1's install reached the recipient before the kill "
        "(install < kill)", first_install["t_us"] < kill["t_us"])
    procs = {kill["proc"], promo["proc"], commit["proc"]}
    chk("chain spans >= 2 processes (%s)" % sorted(procs),
        len(procs) >= 2)
    # every trainer adopted the bumped map, pointing the var at the
    # recipient shard
    for tid, r in outs.items():
        mo = r.get("map_overrides") or {}
        chk("trainer %d adopted shard map v%s with the var routed to "
            "shard %d (%s)" % (tid, r.get("map_version"),
                               sched["migrate_to"], mo),
            int(r.get("map_version") or 0) >= 1
            and sched["migrate_to"] in set(mo.values()))
    n_votes = sum(v for k, v in totals.items()
                  if k.startswith("ps.witness_votes"))
    chk("witness voted in the election (%d votes)" % n_votes,
        n_votes >= 1)
    n_jit = sum(v for k, v in totals.items()
                if k.startswith("fault.injected")
                and "clock_jitter" in k)
    chk("clock jitter was injected (%d events)" % n_jit, n_jit >= 1)
    chk("delta replication still carried the job "
        "(ps.delta_rounds=%s)" % totals.get("ps.delta_rounds"),
        totals.get("ps.delta_rounds", 0) > 0)
    # the final round applied on every shard — zero lost rounds
    final = [e for e in events if e["kind"] == "ps.round_applied"
             and e["fields"].get("round") == sched["sync_rounds"]]
    chk("final round %d applied on every shard (%d appliers)"
        % (sched["sync_rounds"], len(final)),
        len(final) >= sched["shards"])
    print("[chaos] (donor primary pinned by the schedule: %s)"
          % donor_primary)
    return ok


def check_migrate_range_telemetry(sched: dict, mdir: str, eps: list,
                                  outs: dict) -> bool:
    """The --migrate-range gate: the steering chain (skew breach ->
    proposal carving the hot quarter's tail -> canary -> promotion)
    must be AUDITED end to end with bit-equal plan digests, and the
    protocol chain (install staged on the recipient < donor-primary
    SIGKILL < promotion < replicated range commit) must read in
    causal order in the merged trace, with range bytes on the range
    counter and every trainer routing the moved rows to the
    recipient."""
    from paddle_tpu.distributed.ps_shard import row_range
    from paddle_tpu.observability import ps_steering
    from paddle_tpu.observability.canary import AuditTrail, PlanStore

    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    merged, events = _load_merged(mdir)
    chk("job-level metrics.json + trace.json merged",
        merged is not None)
    if not ok:
        return False
    totals = merged["counters_total"]
    groups = _groups(sched, eps)
    donor = set(groups[sched["mr_hot_shard"]])

    # -- the steering chain, audited end to end ------------------------
    steer = outs[0].get("steer") or {}
    chk("trainer 0's steering driver reported no error (%s)"
        % steer.get("error"), steer.get("error") is None)
    chk("the daemon proposed off the row-heat skew (digest %s)"
        % steer.get("proposed"), bool(steer.get("proposed")))
    chk("the canary PROMOTED the plan (decision=%s)"
        % steer.get("decision"), steer.get("promoted") is True)
    plan = steer.get("plan") or {}
    span_lo, span_hi = row_range(sched["mr_hot_shard"], 16,
                                 sched["shards"])
    hot_lo = span_lo + 3 * (span_hi - span_lo) // 4
    # the plan must carve a non-empty TAIL of the hot quarter off the
    # hot shard. It is NOT required to be the whole quarter: with the
    # fanin-2 barrier, the run-ahead trainer lands its next round's
    # hot pushes before blocking, so at poll time its parity's hot row
    # can carry one extra round of heat and the steerer honestly
    # isolates the hottest suffix ([15,16) instead of [14,16))
    chk("the plan moves a tail of the hot quarter [%d, %d) of shard "
        "%d -> shard %d (got %s)" % (hot_lo, span_hi,
                                     sched["mr_hot_shard"],
                                     sched["mr_to_shard"],
                                     {k: plan.get(k) for k in
                                      ("lo", "hi", "from_shard",
                                       "to_shard", "by")}),
        plan.get("hi") == span_hi
        and hot_lo <= (plan.get("lo") if plan.get("lo") is not None
                       else -1) < span_hi
        and plan.get("from_shard") == sched["mr_hot_shard"]
        and plan.get("to_shard") == sched["mr_to_shard"]
        and plan.get("by") == "row_heat")
    if not ok:
        return False
    steer_dir = os.path.join(mdir, "steering")
    prop_path = os.path.join(
        steer_dir, "proposed-%s.json" % ps_steering.STEERER_NAME)
    art = (json.load(open(prop_path))
           if os.path.exists(prop_path) else {})
    chk("proposal artifact on disk with the SAME digest",
        art.get("plan_digest") == steer.get("proposed"))
    trail = AuditTrail(steer_dir).entries()
    promoted_entries = [e for e in trail
                        if e.get("decision") == "promoted"]
    chk("audit trail records the promotion (%d entries)" % len(trail),
        len(promoted_entries) == 1
        and promoted_entries[-1].get("plan_digest")
        == steer.get("proposed"))
    active = PlanStore(steer_dir,
                       ps_steering.STEERER_NAME).active_digest()
    chk("active-plan pointer bit-matches the promoted digest",
        active == steer.get("proposed"))
    proposed_ev = [e for e in events
                   if e["kind"] == "steering.proposed"]
    promoted_ev = [e for e in events
                   if e["kind"] == "canary.promoted"]
    chk("steering.proposed and canary.promoted flights in the merged "
        "timeline, in order",
        bool(proposed_ev) and bool(promoted_ev)
        and min(e["t_us"] for e in proposed_ev)
        < min(e["t_us"] for e in promoted_ev))
    digests = {e["fields"].get("plan_digest") for e in promoted_ev}
    chk("promotion flight carries the same plan digest",
        digests == {steer.get("proposed")})

    # -- the protocol chain under the kill -----------------------------
    kill = next((e for e in events if e["kind"] == "launch.exit"
                 and e["fields"].get("role") == "pserver"
                 and e["fields"].get("signal") == 9), None)
    installs = [e for e in events
                if e["kind"] == "ps.range_migration_install"]
    commits = [e for e in events
               if e["kind"] == "ps.range_migration_committed"]
    promo = next((e for e in events if e["kind"] == "ps.promotion"
                  and e["fields"].get("endpoint") in donor), None)
    chk("supervisor observed the donor primary's SIGKILL",
        kill is not None)
    chk("rows staged on the recipient (%d install events)"
        % len(installs), len(installs) >= 1)
    chk("the donor shard's backup was promoted", promo is not None)
    chk("the re-triggered range migration COMMITTED (%d commits)"
        % len(commits), len(commits) >= 1)
    if not ok:
        return False
    first_install = min(installs, key=lambda e: e["t_us"])
    commit = min(commits, key=lambda e: e["t_us"])
    chk("attempt 1's rows reached the recipient before the kill "
        "(install < kill)", first_install["t_us"] < kill["t_us"])
    chk("attempt 1 never committed (kill < first commit)",
        kill["t_us"] < commit["t_us"])
    chk("causal chain: kill < promotion < range commit",
        kill["t_us"] < promo["t_us"] < commit["t_us"])
    range_bytes = sum(
        v for k, v in totals.items()
        if k.startswith("ps.migration_bytes") and "kind=range" in k)
    chk("range bytes on the range counter (%d)" % range_bytes,
        range_bytes > 0)

    # -- every trainer routes the moved rows to the recipient ----------
    for tid, r in outs.items():
        ranges = (r.get("map_ranges") or {}).get("emb") or []
        chk("trainer %d adopted map v%s with emb rows [%d, %d) on "
            "shard %d (%s)" % (tid, r.get("map_version"),
                               plan.get("lo"), plan.get("hi"),
                               sched["mr_to_shard"], ranges),
            int(r.get("map_version") or 0) >= 1
            and any(rr[0] == plan.get("lo") and rr[1] == plan.get("hi")
                    and rr[2] == sched["mr_to_shard"]
                    for rr in ranges))

    # -- the riders: witness, jitter, no lost rounds -------------------
    n_votes = sum(v for k, v in totals.items()
                  if k.startswith("ps.witness_votes"))
    chk("witness voted in the election (%d votes)" % n_votes,
        n_votes >= 1)
    n_jit = sum(v for k, v in totals.items()
                if k.startswith("fault.injected")
                and "clock_jitter" in k)
    chk("clock jitter was injected (%d events)" % n_jit, n_jit >= 1)
    final = [e for e in events if e["kind"] == "ps.round_applied"
             and e["fields"].get("round") == sched["sync_rounds"]]
    chk("final round %d applied on every shard (%d appliers)"
        % (sched["sync_rounds"], len(final)),
        len(final) >= sched["shards"])
    return ok


def check_evict_telemetry(sched: dict, mdir: str) -> bool:
    """The --evict gate: the disagreeing-fanin round must show an
    eviction AND a readmission AND stale-round drops (the guard that
    keeps a relaunched trainer's re-run from contaminating later
    rounds), with the final round applied on every shard."""
    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    merged, events = _load_merged(mdir)
    chk("job-level metrics.json + trace.json merged",
        merged is not None)
    if not ok:
        return False
    totals = merged["counters_total"]
    chk("a shard evicted the dead trainer (ps.evictions=%s)"
        % totals.get("ps.evictions"),
        totals.get("ps.evictions", 0) >= 1)
    chk("the relaunched trainer was re-admitted "
        "(ps.readmissions=%s)" % totals.get("ps.readmissions"),
        totals.get("ps.readmissions", 0) >= 1)
    chk("stale-round re-sends were dropped, not re-applied "
        "(ps.stale_rounds=%s)" % totals.get("ps.stale_rounds"),
        totals.get("ps.stale_rounds", 0) >= 1)
    final = [e for e in events if e["kind"] == "ps.round_applied"
             and e["fields"].get("round") == sched["sync_rounds"]]
    chk("final round %d applied on every shard (%d appliers)"
        % (sched["sync_rounds"], len(final)),
        len(final) >= sched["shards"])
    return ok


def _tear_newest_rounds(durable: str, shards: int) -> dict:
    """Simulate a torn write: truncate the newest restorable round's
    frame blob on EVERY shard. Tearing every shard's newest (rather
    than one shard's) makes the fallback deterministic — whichever
    shard held the pre-kill minimum loses exactly its top round, so
    the new globally-complete cut is exactly one round earlier."""
    from paddle_tpu import checkpoint as ckpt

    torn = {}
    for k in range(int(shards)):
        store = ckpt.RoundStore(durable, shard=k)
        newest = store.restorable_rounds()[-1]
        blob = os.path.join(store.round_dir(newest), "blob.bin")
        with open(blob, "r+b") as f:
            f.truncate(os.path.getsize(blob) // 2)
        torn["shard-%d" % k] = newest
    return torn


def run_total_loss_drill(sched: dict) -> int:
    """The --total-loss drill (ISSUE 19): run with the durable round
    store armed, SIGKILL the ENTIRE job (one killpg: supervisor,
    servers, trainers) once the seeded round is durable on every
    shard, optionally tear the newest durable round, then relaunch the
    identical command and gate on auto-detected restore to the newest
    globally-complete cut, bit-for-bit final params vs the
    uninterrupted oracle, and the cold-start -> restore -> first-
    applied-round causal chain in the merged telemetry."""
    import signal
    import time

    from paddle_tpu import checkpoint as ckpt

    tmp = tempfile.mkdtemp(prefix="chaos_total_loss_")
    durable = os.path.join(tmp, "durable")
    eps = ["127.0.0.1:%d" % _free_port()
           for _ in range(2 * sched["shards"])]
    print("[chaos] schedule %s" % json.dumps(sched, sort_keys=True))
    launch_args = [
        sys.executable, "-m", "paddle_tpu.distributed.launch",
        "--nproc_per_node=2", "--max_restarts=3",
        "--started_port=%d" % _free_port(),
        "--server_script=%s" % WORKER,
        "--pserver_shards=%d" % sched["shards"],
        "--pserver_endpoints=%s" % ",".join(eps),
        WORKER]
    env = _env(sched, tmp, eps)

    def common_cut():
        try:
            return ckpt.job_restore_round(durable, sched["shards"])
        except (ckpt.RestoreMissingShard, ckpt.CheckpointCorrupt,
                OSError, ValueError):
            return None

    # incarnation 0: run until the seeded round is durable on every
    # shard, then kill the whole session — no survivors, no warning
    proc = subprocess.Popen(launch_args, env=env, cwd=REPO,
                            start_new_session=True)
    kill_round = sched["total_kill_round"]
    deadline = time.time() + 300
    cut = None
    try:
        while time.time() < deadline:
            if proc.poll() is not None:
                print("[chaos] FAIL: job exited %s before the "
                      "whole-job kill (durable cut %s, wanted >= %d) "
                      "(rerun: %s)" % (proc.returncode, cut,
                                       kill_round,
                                       _rerun_hint(sched)))
                return 1
            cut = common_cut()
            if cut is not None and cut >= kill_round:
                break
            time.sleep(0.02)
        else:
            print("[chaos] FAIL: round %d never became durable on "
                  "every shard (last common cut %s) (rerun: %s)"
                  % (kill_round, cut, _rerun_hint(sched)))
            return 1
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
    # the true cut: rounds kept committing between the poll that
    # tripped the kill and the SIGKILL landing
    cut_pre = common_cut()
    print("[chaos] whole job SIGKILLed with round %s durable on "
          "every shard" % cut_pre)
    if cut_pre is None or cut_pre < kill_round:
        print("[chaos] FAIL: durable state unreadable after the kill "
              "(cut %s) (rerun: %s)" % (cut_pre, _rerun_hint(sched)))
        return 1
    expected_cut = cut_pre
    if sched.get("corrupt_newest"):
        torn = _tear_newest_rounds(durable, sched["shards"])
        expected_cut = common_cut()
        print("[chaos] tore newest durable round(s) %s: common cut "
              "%d -> %s" % (json.dumps(torn, sort_keys=True), cut_pre,
                            expected_cut))
        if expected_cut != cut_pre - 1:
            print("[chaos] FAIL: torn newest round must fall back "
                  "EXACTLY one round (wanted %d, got %s) (rerun: %s)"
                  % (cut_pre - 1, expected_cut, _rerun_hint(sched)))
            return 1

    # incarnation 1: the IDENTICAL command — restore is auto-detected
    # from the durable root, exactly like a real operator's relaunch
    sup = subprocess.run(launch_args, env=env, timeout=420, cwd=REPO)
    if sup.returncode != 0:
        print("[chaos] FAIL: relaunched job exited %d (rerun: %s)"
              % (sup.returncode, _rerun_hint(sched)))
        return 1

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from dist_worker_ft import var_names

    ok = True
    for tid in (0, 1):
        r = json.load(open(os.path.join(tmp, "out.t%d.json" % tid)))
        for vi, name in enumerate(var_names(sched["shards"])):
            expected = oracle_w(sched["sync_rounds"], var=vi)
            got = np.asarray(r["vars"][name], dtype=np.float32)
            bitwise = got.tobytes() == expected.tobytes()
            print("[chaos] %s: trainer %d var %s %s the uninterrupted "
                  "oracle (resumed_from=%s)"
                  % ("PASS" if bitwise else "FAIL", tid, name,
                     "matches" if bitwise else "DIVERGES FROM",
                     r.get("resumed_from")))
            ok = ok and bitwise
    ok = check_total_loss_telemetry(sched, os.path.join(tmp,
                                                        "metrics"),
                                    expected_cut) and ok
    if not ok:
        print("[chaos] reproduce with: %s" % _rerun_hint(sched))
    return 0 if ok else 1


def check_total_loss_telemetry(sched: dict, mdir: str,
                               expected_cut: int) -> bool:
    """The --total-loss gate: the dead incarnation's black boxes must
    survive the relaunch; the restored supervisor's cold start must
    name the newest globally-complete round; every server must restore
    that ONE cut (never a mixed one); and the chain dead-incarnation <
    cold start < restore < first-applied-round (= cut + 1: the
    restored servers drop the resumed trainers' stale re-sends, never
    re-apply them) must read in causal order in the merged timeline."""
    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    ft_timeline.print_postmortem(mdir, limit=40)
    mpath = os.path.join(mdir, "metrics.json")
    tpath = os.path.join(mdir, "trace.json")
    chk("job-level metrics.json + trace.json merged",
        os.path.exists(mpath) and os.path.exists(tpath))
    if not ok:
        return False
    totals = json.load(open(mpath))["counters_total"]
    events = ft_timeline.load_events(mdir)
    incs = sorted({e.get("incarnation", 0) for e in events})
    chk("dead incarnation's black boxes survived the relaunch "
        "(incarnations %s)" % incs, 0 in incs and 1 in incs)
    cold = [e for e in events if e["kind"] == "launch.cold_start"]
    chk("the relaunched supervisor cold-started from durable state "
        "(%d events)" % len(cold), len(cold) == 1)
    restores = [e for e in events if e["kind"] == "ps.restore"]
    chk("servers restored from disk (%d ps.restore events)"
        % len(restores), len(restores) >= 1)
    if not ok:
        return False
    cold = cold[0]
    chk("cold start computed the newest globally-complete round "
        "(restore_round=%s, want %d, incarnation=%s)"
        % (cold["fields"].get("restore_round"), expected_cut,
           cold["fields"].get("incarnation")),
        cold["fields"].get("restore_round") == expected_cut
        and cold["fields"].get("incarnation") == 1)
    rshards = sorted({e["fields"].get("shard") for e in restores})
    chk("every shard group restored (%s)" % rshards,
        rshards == list(range(sched["shards"])))
    rounds = sorted({e["fields"].get("round") for e in restores})
    chk("every restore loaded the ONE cut r%d, never a mixed one "
        "(got %s)" % (expected_cut, rounds),
        rounds == [expected_cut])
    inc1_applied = [e for e in events
                    if e["kind"] == "ps.round_applied"
                    and e.get("incarnation") == 1]
    chk("the restored incarnation applied rounds (%d events)"
        % len(inc1_applied), len(inc1_applied) >= 1)
    if not ok:
        return False
    first_ap = min(inc1_applied, key=lambda e: e["t_us"])
    chk("first post-restore applied round is the cut's successor "
        "r%d (got r%s: stale re-sends dropped, not re-applied)"
        % (expected_cut + 1, first_ap["fields"].get("round")),
        first_ap["fields"].get("round") == expected_cut + 1)
    last_dead = max((e["t_us"] for e in events
                     if e.get("incarnation") == 0), default=None)
    chk("causal chain: dead incarnation < cold start < restore < "
        "first applied round",
        last_dead is not None
        and last_dead < cold["t_us"]
        < min(e["t_us"] for e in restores) < first_ap["t_us"])
    durs = [e for e in events if e["kind"] == "ps.round_durable"]
    chk("round frames were persisted at commit time "
        "(%d ps.round_durable events)" % len(durs), len(durs) >= 1)
    n_faults = sum(v for k, v in totals.items()
                   if k.startswith("fault.injected"))
    chk("injected faults visible in the restored incarnation's "
        "merged counters (%d)" % n_faults, n_faults > 0)
    final = [e for e in events if e["kind"] == "ps.round_applied"
             and e["fields"].get("round") == sched["sync_rounds"]]
    chk("final round %d applied on every shard (%d appliers)"
        % (sched["sync_rounds"], len(final)),
        len(final) >= sched["shards"])
    trace_names = {ev.get("name") for ev in
                   json.load(open(tpath)).get("traceEvents", [])}
    chk("merged trace.json carries the restore chain",
        {"launch.cold_start", "ps.restore"} <= trace_names)
    return ok


def main() -> int:
    ap = argparse.ArgumentParser("chaos_drill")
    ap.add_argument("--rounds", type=int, default=1,
                    help="number of randomized drills to run")
    ap.add_argument("--sync-rounds", type=int, default=6,
                    help="training rounds per drill")
    ap.add_argument("--shards", type=int, default=1,
                    help="key-range PS shard groups (each "
                         "primary+backup)")
    ap.add_argument("--partition", action="store_true",
                    help="also sever a surviving shard's "
                         "primary<->backup pair for the whole run "
                         "(requires --shards >= 2)")
    ap.add_argument("--migrate", action="store_true",
                    help="live key-range migration drill: the donor "
                         "primary is SIGKILLed mid-migration; gated "
                         "on rollback-then-completion bit-for-bit "
                         "(requires --shards >= 2)")
    ap.add_argument("--evict", action="store_true",
                    help="sharded eviction drill: per-shard effective "
                         "fanin disagrees mid-round; gated on "
                         "deterministic reconciliation (requires "
                         "--shards >= 2)")
    ap.add_argument("--migrate-range", action="store_true",
                    dest="migrate_range",
                    help="self-steered row-range rebalance drill: the "
                         "job's own SteeringDaemon proposes the move "
                         "off the row-heat census and the canary "
                         "applies it live while the donor primary is "
                         "SIGKILLed mid-install (requires --shards 2 "
                         "and --sync-rounds >= 18)")
    ap.add_argument("--total-loss", action="store_true",
                    dest="total_loss",
                    help="whole-job crash drill: SIGKILL every "
                         "process at a seeded durable round, relaunch "
                         "from disk, gate bit-for-bit vs an "
                         "uninterrupted run (ISSUE 19)")
    ap.add_argument("--corrupt-newest", action="store_true",
                    dest="corrupt_newest",
                    help="with --total-loss: tear the newest durable "
                         "round between kill and relaunch — restore "
                         "must fall back exactly one round")
    ap.add_argument("--seed", type=int,
                    default=int(os.environ.get("PADDLE_TPU_FAULT_SEED",
                                               "1234")),
                    help="base seed (drill i uses seed + i)")
    args = ap.parse_args()
    if args.corrupt_newest and not args.total_loss:
        ap.error("--corrupt-newest rides --total-loss (it tears the "
                 "durable store the kill left behind)")
    if args.total_loss and (args.migrate or args.evict
                            or args.migrate_range or args.partition):
        ap.error("--total-loss is its own drill (the whole job dies; "
                 "there is no surviving shard to partition or "
                 "migrate)")
    if args.partition and args.shards < 2:
        ap.error("--partition needs --shards >= 2 (the partitioned "
                 "pair must belong to a shard that keeps training)")
    if (args.migrate or args.evict or args.migrate_range) \
            and args.shards < 2:
        ap.error("--migrate/--evict/--migrate-range need --shards >= "
                 "2 (the range moves — or the fanin disagrees — "
                 "between groups)")
    if args.migrate and args.partition:
        ap.error("--migrate and --partition are separate drills")
    if args.migrate_range and (args.migrate or args.evict
                               or args.partition):
        ap.error("--migrate-range is its own drill (the steering "
                 "chain owns the fault injection points)")
    if args.migrate_range and args.sync_rounds < 18:
        ap.error("--migrate-range needs --sync-rounds >= 18 (worst "
                 "case: 3 balanced + 3 hot + 3 incumbent + 6 apply + "
                 "3 measure rounds)")
    rc = 0
    for i in range(args.rounds):
        sched = make_schedule(args.seed + i, args.sync_rounds,
                              shards=args.shards,
                              partition=args.partition,
                              migrate=args.migrate,
                              evict=args.evict,
                              migrate_range=args.migrate_range,
                              total_loss=args.total_loss,
                              corrupt_newest=args.corrupt_newest)
        rc |= (run_total_loss_drill(sched) if sched["total_loss"]
               else run_drill(sched))
    if rc == 0:
        print("[chaos] ALL %d DRILL(S) PASS" % args.rounds)
    return rc


if __name__ == "__main__":
    sys.exit(main())
