#!/usr/bin/env python
"""CI multichip smoke (gate 7): prove the fast collective path on a
dp=8 CPU host mesh in a few minutes.

Runs the mlp multichip config in fresh processes — on the fast path
(bucketed allreduce + sharded weight update, the defaults
``bench.py --mc-config`` applies), forced onto the per-grad baseline
(``PADDLE_TPU_BUCKET_MB=0``, ``PADDLE_TPU_SHARDED_UPDATE=0``), and
through one profile-guided replan cycle (plan → measure → replan) —
and asserts:

  a. bucketing/sharding STRICTLY reduces per-step
     ``parallel.collective_ops`` vs the per-grad run, and the fast
     run's recorded per-grad-baseline figure agrees with the baseline
     run's counters (both come from the same static program estimator
     — this pins the two call sites to each other, it is not an
     independent traffic measurement);
  b. both runs converge to the same finite loss trajectory class
     (loss finite; the bit-for-bit claim is gate-kept by
     tests/test_collectives.py's parity tests, run here via pytest —
     including the profile-plan parity test);
  c. the REPLAN cycle closes the loop the ROADMAP asks for: a
     size-planned bucketed run's measured profile report is fed back
     via ``PADDLE_TPU_BUCKET_PLAN=profile``, the replanned run must
     demonstrably CHANGE the bucket plan (the measurement steered the
     schedule) and its measured ``overlap_frac`` must not decrease
     (or the measured hideable budget must already be saturated);
  d. ``tools/bench_diff.py`` answers ``--help`` and passes its
     built-in ``--self-test``.

``--out PATH`` additionally writes the two measured records as a
bench_diff-compatible artifact (``{"configs": {"mlp": ...,
"mlp_pergrad": ...}, "counters_total": ...}``) — ci/check.sh keeps the
previous run's copy under ``ci/baseline/`` and diffs against it
automatically (gate 7b), the ROADMAP's "CI keeps an artifact around"
item.
"""
from __future__ import annotations

import json
import math
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_config(extra_env):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "").strip()
                      + " --xla_force_host_platform_device_count=8").strip(),
    })
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--mc-config=mlp", "--mc-iters=2"],
        capture_output=True, text=True, timeout=240, env=env)
    if proc.returncode != 0:
        raise SystemExit("mc_smoke: mlp config failed (%s): %s"
                         % (extra_env, proc.stderr[-2000:]))
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    out_path = None
    args = list(sys.argv[1:])
    while args:
        a = args.pop(0)
        if a == "--out" and args:
            out_path = args.pop(0)
        elif a.startswith("--out="):
            out_path = a.split("=", 1)[1]
        else:
            raise SystemExit("mc_smoke: unknown arg %r" % a)
    t0 = time.time()
    fast = _run_config({})
    base = _run_config({"PADDLE_TPU_BUCKET_MB": "0",
                        "PADDLE_TPU_SHARDED_UPDATE": "0"})

    f_ops = fast["collective"]["per_step"]["parallel.collective_ops"]
    b_ops = base["collective"]["per_step"]["parallel.collective_ops"]
    est = fast["collective"]["pergrad_baseline_ops"]
    print("mc_smoke: fast path %d collective ops/step, per-grad "
          "baseline %d (estimator said %d)" % (f_ops, b_ops, est))
    assert f_ops < b_ops, (
        "bucketed/sharded path must STRICTLY reduce collective ops: "
        "fast=%d baseline=%d" % (f_ops, b_ops))
    assert b_ops == est, (
        "fast run's recorded per-grad baseline estimate (%d) disagrees "
        "with the estimate of the actually-executed per-grad program "
        "(%d)" % (est, b_ops))
    for rec in (fast, base):
        assert math.isfinite(rec["loss"]), rec["loss"]

    # ISSUE 12: every executed program's static collective-consistency
    # verdict must be clean (no conditional collectives, no
    # double-reduce), and the two runs of the SAME plan class must
    # carry a schedule digest at all (the cross-process comparison
    # handle)
    for tag, rec in (("fast", fast), ("pergrad", base)):
        sched = rec["collective"].get("schedule") or {}
        assert sched.get("ok") is True, (
            "%s run's collective schedule failed static verification: "
            "%r" % (tag, sched))
        assert sched.get("digest"), sched

    # profile-guided replan cycle (plan -> measure -> replan): the
    # size-planned bucketed run IS the measurement (its profile block
    # carries per-bucket cost + backward timing); feed it back and the
    # planner must change the schedule and not lose measured overlap
    buck = _run_config({"PADDLE_TPU_SHARDED_UPDATE": "0"})
    report = buck.get("profile") or {}
    assert report.get("per_bucket") and \
        report.get("backward_segments"), (
        "bucketed run carried no profile report: %r" % sorted(report))
    rpt_path = os.path.join(tempfile.mkdtemp(prefix="mc_smoke_rpt_"),
                            "profile_report.json")
    with open(rpt_path, "w") as f:
        json.dump(report, f)
    replan = _run_config({"PADDLE_TPU_SHARDED_UPDATE": "0",
                          "PADDLE_TPU_BUCKET_PLAN": "profile",
                          "PADDLE_TPU_BUCKET_PROFILE": rpt_path})
    plan0 = buck["collective"]["bucket_plan"]
    plan1 = replan["collective"]["bucket_plan"]
    print("mc_smoke: replan cycle: size plan %s -> profile plan %s"
          % (plan0, plan1))
    assert plan1 and plan1["mode"] == "profile", (
        "replan run fell back to the size plan: %r" % (plan1,))
    assert (plan1["n_buckets"], plan1["bucket_bytes"],
            plan1["anchors"]) != (plan0["n_buckets"],
                                  plan0["bucket_bytes"],
                                  plan0["anchors"]), (
        "profile-guided replan did not change the bucket plan: %r"
        % (plan1,))
    assert math.isfinite(replan["loss"]), replan["loss"]
    # structural, noise-robust: the replanned schedule must CREATE
    # hideable budget — buckets anchored before end-of-backward, where
    # the size plan's single late bucket had none. Anchors are
    # deterministic given the report, so timing noise can't move this.
    def _hideable_buckets(rec):
        return sum(1 for b in rec["profile"]["per_bucket"]
                   if b["max_hideable_frac"] > 0)

    assert _hideable_buckets(replan) > _hideable_buckets(buck), (
        "replanned schedule created no hideable budget: %r vs %r"
        % (replan["profile"]["per_bucket"],
           buck["profile"]["per_bucket"]))

    # measured: replanning must not LOSE overlap. A single CPU-box
    # overlap measurement is noisy (exposed = t_full - t_nocoll, each
    # min-of-2 on a shared machine), so a failed check earns ONE fresh
    # re-measurement before it fails the gate; "achieved most of its
    # own measured hideable budget" is the honest saturation escape.
    ov0 = buck["profile"].get("overlap_frac")
    assert ov0 is not None, buck["profile"]
    for attempt in (1, 2):
        ov1 = replan["profile"].get("overlap_frac")
        assert ov1 is not None, replan["profile"]
        pb = replan["profile"]["per_bucket"]
        tot = sum(b["collective_ms"] for b in pb) or 1.0
        hideable1 = sum(b["max_hideable_frac"] * b["collective_ms"]
                        for b in pb) / tot
        print("mc_smoke: measured overlap %.3f -> %.3f "
              "(replan's hideable budget %.3f, attempt %d)"
              % (ov0, ov1, hideable1, attempt))
        if ov1 >= ov0 - 0.10 or ov1 >= 0.75 * hideable1:
            break
        assert attempt == 1, (
            "profile-guided replan LOST measured overlap twice: "
            "%.3f -> %.3f (replan hideable %.3f)"
            % (ov0, ov1, hideable1))
        replan = _run_config({"PADDLE_TPU_SHARDED_UPDATE": "0",
                              "PADDLE_TPU_BUCKET_PLAN": "profile",
                              "PADDLE_TPU_BUCKET_PROFILE": rpt_path})

    # the dp=8 record must carry BOTH phase breakdowns + agreement
    # (device capture defaults ON for multichip configs; an empty
    # capture would silently fall back — fail loudly here instead)
    for rec in (fast, buck):
        p = rec["profile"]
        assert p.get("phase_ms") and p.get("device_phase_ms"), (
            "record lacks host+device phase breakdowns: %r"
            % sorted(p))
        assert p.get("host_device_agreement") is not None, sorted(p)

    # sharded-update + profile-plan parity is bit-for-bit (incl.
    # uneven shards) — the numerics gate for the paths this smoke
    # just exercised
    subprocess.run(
        [sys.executable, "-m", "pytest", "-q",
         "tests/test_collectives.py", "-k",
         "sharded_update_bit_for_bit or uneven_shards or "
         "profile_plan_bit_for_bit"],
        check=True, cwd=ROOT, timeout=240)

    bd = os.path.join(ROOT, "tools", "bench_diff.py")
    out = subprocess.run([sys.executable, bd, "--help"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0 and "--threshold" in out.stdout, out.stderr
    subprocess.run([sys.executable, bd, "--self-test"], check=True,
                   timeout=60)

    if out_path:
        # bench_diff-compatible artifact of THIS run: the "configs"
        # records carry step_ms/throughput/collective/profile, and the
        # fast path's per-step collective counters double as the
        # deterministic counters_total gate
        doc = {
            "schema": "mc_smoke_v1",
            "wrote_at": time.time(),
            # the replan pair rides along so gate 7b also watches the
            # profile-guided plan's overlap/agreement run-over-run
            "configs": {"mlp": fast, "mlp_pergrad": base,
                        "mlp_bucketed": buck, "mlp_replan": replan},
            "counters_total": dict(fast["collective"]["per_step"]),
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        print("mc_smoke: wrote %s" % out_path)

    print("mc_smoke: OK in %.1fs" % (time.time() - t0))


if __name__ == "__main__":
    main()
