#!/usr/bin/env python
"""CI placement-synthesis smoke (gate 7e, ~60s): close the ISSUE-15
loop on the dp=8 mlp smoke — measure, search, verify, apply, beat the
baseline.

Steps and assertions:

  a. run the mlp multichip config on the SIZE-plan configuration
     (sharded update off) — the baseline, whose profile block is the
     measured report the search fits its cost model to;
  b. run ``tools/placement_search.py`` on that report: the audit must
     show EVERY enumerated candidate passed the static verifier
     (zero rejected, zero traced-before-verify — candidates are gated
     through verify_program + check_cross_rank BEFORE anything could
     trace them), the cost model must be FITTED (not the analytic
     fallback), and a second search from the same report + seed must
     emit the SAME winning plan digest (search determinism);
  c. the emitted artifact must round-trip: load verifies the digest,
     and a re-save is byte-identical (canonical form);
  d. run the mlp config again under ``PADDLE_TPU_PLACEMENT_PLAN``:
     the bench record must carry a ``placement`` block with the
     matching plan digest and a predicted-vs-measured agreement
     figure, and the winner's measured step_ms must BEAT (<=) the
     size-plan baseline — with one fresh re-measurement of both runs
     before failing, because single CPU-box step timings jitter.
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

_WORK = tempfile.mkdtemp(prefix="placement_smoke_")


# knobs the measured comparison depends on: the baseline must be the
# DEFAULT size-plan configuration even when the operator's shell has
# plan/strategy/quant experiments exported
_PINNED_KNOBS = ("PADDLE_TPU_PLACEMENT_PLAN", "PADDLE_TPU_BUCKET_MB",
                 "PADDLE_TPU_BUCKET_PLAN", "PADDLE_TPU_BUCKET_PROFILE",
                 "PADDLE_TPU_QUANT_ALLREDUCE",
                 "PADDLE_TPU_QUANT_ERROR_FEEDBACK",
                 "PADDLE_TPU_REDUCE_STRATEGY",
                 "PADDLE_TPU_ASYNC_COLLECTIVES")


def _run_config(extra_env, tag):
    env = dict(os.environ)
    for k in _PINNED_KNOBS:
        env.pop(k, None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "").strip()
                      + " --xla_force_host_platform_device_count=8"
                      ).strip(),
        "PADDLE_TPU_SHARDED_UPDATE": "0",
    })
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"),
         "--mc-config=mlp", "--mc-iters=2"],
        capture_output=True, text=True, timeout=240, env=env)
    if proc.returncode != 0:
        raise SystemExit("placement_smoke: %s run failed: %s"
                         % (tag, proc.stderr[-2000:]))
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _run_search(report_path, out_path, audit_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "placement_search.py"),
         "--model", "mlp", "--report", report_path, "--out", out_path,
         "--audit", audit_path, "--devices", "8", "--beam", "4",
         "--seed", "0"],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise SystemExit("placement_smoke: search failed: %s\n%s"
                         % (proc.stdout[-1000:], proc.stderr[-2000:]))
    sys.stdout.write(proc.stdout)
    with open(audit_path) as f:
        audit = json.load(f)
    with open(out_path) as f:
        plan_doc = json.load(f)
    return plan_doc, audit


def main():
    t0 = time.time()
    # a. measured baseline = the size-plan bucketed run
    base = _run_config({}, "baseline")
    assert math.isfinite(base["loss"]), base["loss"]
    report = base.get("profile") or {}
    assert report.get("per_bucket") and report.get(
        "backward_segments"), (
        "baseline run carried no usable profile report: %r"
        % sorted(report))
    rpt_path = os.path.join(_WORK, "report.json")
    with open(rpt_path, "w") as f:
        json.dump(report, f)

    # b. search, twice — verifier-gated and deterministic
    plan_path = os.path.join(_WORK, "plan.json")
    audit_path = os.path.join(_WORK, "audit.json")
    plan_doc, audit = _run_search(rpt_path, plan_path, audit_path)
    rows = audit["candidates"]
    assert rows, "search enumerated nothing"
    bad = [r for r in rows if not r["verified"]]
    assert not bad, (
        "candidate(s) failed the static verifier on the mlp space: %r"
        % bad[:3])
    assert audit["rejected"] == 0, audit
    assert audit["traced_before_verify"] == 0, (
        "a candidate was traced before verification — the gate "
        "ordering is broken")
    assert not any(r["traced"] for r in rows), (
        "the symbolic search traced a candidate")
    assert audit["cost_provenance"] == "fitted", (
        "cost model fell back to analytic despite a measured report: "
        "%r" % audit["cost_provenance"])
    assert audit["unsupported"], (
        "mesh enumeration lost the unsupported hybrid factorizations "
        "(mp/pp/sp/ep rows should be recorded, not dropped)")
    print("placement_smoke: %d candidates, all verifier-clean "
          "(%d deduped, %d pruned, %d unsupported meshes recorded)"
          % (len(rows), audit["deduped"], audit["pruned"],
             len(audit["unsupported"])))

    plan2_path = os.path.join(_WORK, "plan2.json")
    plan2_doc, _audit2 = _run_search(rpt_path, plan2_path,
                                     os.path.join(_WORK, "audit2.json"))
    assert plan_doc["digest"] == plan2_doc["digest"], (
        "search is nondeterministic: %s != %s"
        % (plan_doc["digest"], plan2_doc["digest"]))

    # c. artifact round-trip through the loader (digest verification)
    sys.path.insert(0, ROOT)
    from paddle_tpu.placement import load_plan, save_plan

    plan = load_plan(plan_path)
    assert plan.digest == plan_doc["digest"]
    resaved = os.path.join(_WORK, "resaved.json")
    save_plan(plan, resaved)
    with open(plan_path, "rb") as f1, open(resaved, "rb") as f2:
        assert f1.read() == f2.read(), (
            "plan artifact is not canonical: re-save changed bytes")
    print("placement_smoke: plan %s round-trips (predicted %.1f ms, "
          "%s)" % (plan.digest[:12], plan.predicted_step_ms or 0.0,
                   plan.cost_provenance))

    # d. apply the plan end-to-end and beat the size-plan baseline
    base_ms = base["step_ms"]
    for attempt in (1, 2):
        planned = _run_config(
            {"PADDLE_TPU_PLACEMENT_PLAN": plan_path}, "planned")
        assert math.isfinite(planned["loss"]), planned["loss"]
        pb = planned.get("placement")
        assert pb, ("planned run carries no placement block: %r"
                    % sorted(planned))
        assert pb["plan_digest"] == plan.digest, (
            "placement block digest %r != plan %r"
            % (pb.get("plan_digest"), plan.digest))
        assert pb.get("placement_agreement") is not None, pb
        sched = planned["collective"].get("schedule") or {}
        assert sched.get("ok") is True, (
            "planned run's executed schedule failed the static "
            "check: %r" % sched)
        # the ENGINE must execute the exact collective schedule the
        # search verified and priced — the search re-implements the
        # engine's pass stack, and this digest equality is the drift
        # detector for that duplication ("verified before traced"
        # must hold for the executed program, not a lookalike)
        assert sched.get("digest") == plan.schedule_digest, (
            "executed schedule digest %r != the digest the search "
            "verified %r — engine and search rewrite stacks diverged"
            % (sched.get("digest"), plan.schedule_digest))
        plan_ms = planned["step_ms"]
        print("placement_smoke: step_ms baseline %.1f -> planned %.1f "
              "(predicted %.1f, agreement %.2f, attempt %d)"
              % (base_ms, plan_ms, plan.predicted_step_ms or 0.0,
                 pb["placement_agreement"], attempt))
        if plan_ms <= base_ms:
            break
        assert attempt == 1, (
            "winning plan is measurably SLOWER than the size-plan "
            "baseline twice: %.1f ms vs %.1f ms" % (plan_ms, base_ms))
        # one honest retry: re-measure BOTH runs fresh (shared-box
        # noise moves either side)
        base = _run_config({}, "baseline-remeasure")
        base_ms = base["step_ms"]

    print("placement_smoke: OK in %.1fs" % (time.time() - t0))


if __name__ == "__main__":
    main()
