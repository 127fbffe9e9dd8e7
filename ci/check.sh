#!/usr/bin/env bash
# CI invariant gate (reference: paddle/scripts/paddle_build.sh +
# tools/check_op_register_type.py + tools/print_signatures.py +
# tools/check_api_approvals.sh — the reference wires these into CI; this
# script is the equivalent single entry point).
#
# Usage:
#   ci/check.sh            # run all gates
#   ci/check.sh --update   # refresh the committed API fingerprint
#   SKIP_TESTS=1 ci/check.sh   # invariants only (fast)
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# ISSUE 12: force the static IR verifier ON for every CI gate (it
# defaults OFF in prod). Gate 4 measures the DEFAULT-off path and
# un-sets it explicitly.
export PADDLE_TPU_VERIFY_IR=1

if [[ "${1:-}" == "--update" ]]; then
    python -m paddle_tpu.tools.print_signatures > ci/api_fingerprint.txt
    echo "ci/api_fingerprint.txt refreshed ($(wc -l < ci/api_fingerprint.txt) entries)"
    exit 0
fi

echo "== gate 0: repo lint =="
# bare/silent excepts, metric-naming convention, unlocked module state
# in serving//distributed/ — new violations fail; grandfathered ones
# live in tools/lint_allowlist.txt
python tools/lint.py

echo "== gate 1: op-registry parity (diff must be 0 vs allowlist) =="
python -m paddle_tpu.tools.check_op_registry --parity

echo "== gate 2: public API signature freeze =="
FP_TMP="$(mktemp)"
trap 'rm -f "$FP_TMP"' EXIT
python -m paddle_tpu.tools.print_signatures > "$FP_TMP"
if ! diff -u ci/api_fingerprint.txt "$FP_TMP"; then
    echo "API surface changed. If intentional: ci/check.sh --update" >&2
    exit 1
fi
echo "API surface unchanged ($(wc -l < ci/api_fingerprint.txt) entries)"

echo "== gate 2b: IR-verifier mutation self-test =="
# ISSUE 12 acceptance: >= 12 seeded IR corruption kinds (dangling
# refs, use-before-def, dtype/shape flips, rank-divergent collective
# schedules, broken rewrite contracts, ...) must each be rejected by
# paddle_tpu/analysis with a structured finding; a clean transpiled
# program must verify clean. This is the verifier's own regression
# suite.
python tools/ir_mutate.py

echo "== gate 3: native artifacts build =="
if command -v g++ >/dev/null; then
    (cd csrc && ./build.sh >/dev/null)
    echo "csrc build OK"
else
    echo "g++ unavailable, skipped"
fi

echo "== gate 4: observability =="
# 4a: the observability layer's own tests (registry, spans, exporters,
# executor/lazy counters, profiler shim). Skipped when the full suite
# runs below — gate 6 collects the same file; running it twice buys
# nothing
if [[ "${SKIP_TESTS:-0}" == "1" ]]; then
    python -m pytest tests/test_observability.py -q
fi
# 4b: PADDLE_TPU_METRICS unset (default-off) must add no measurable
# overhead to a tiny executor microbench, and the ISSUE-5 additions —
# trace-context propagation (header stamp / child spans) and the
# flight-recorder ring — must stay sub-microsecond on their disabled /
# always-on paths (guard threshold, not exact timing — see
# tools/obs_overhead.py)
#    ... and (ISSUE 12) the default-off IR-verify hook must stay <1us
#    per program run — PADDLE_TPU_VERIFY_IR is un-set here because
#    this gate measures the DEFAULT path
#    ... and (ISSUE 16) sampled in-production capture must default
#    off with its per-step hook under the same <1us budget —
#    PADDLE_TPU_SAMPLE_EVERY is un-set for the same reason
#    ... and (ISSUE 20) the windowed time-series sampler must default
#    off (it arms off PADDLE_TPU_METRICS_DIR) with its hooks under
#    the same budget — PADDLE_TPU_TIMESERIES is un-set likewise
env -u PADDLE_TPU_METRICS -u FLAGS_tpu_metrics \
    -u PADDLE_TPU_METRICS_DIR -u PADDLE_TPU_DEVICE_TRACE \
    -u PADDLE_TPU_VERIFY_IR -u PADDLE_TPU_SAMPLE_EVERY \
    -u PADDLE_TPU_TIMESERIES -u PADDLE_TPU_TIMESERIES_WINDOWS \
    python -m paddle_tpu.tools.obs_overhead

echo "== gate 5: serving =="
# 5a: serving tests (batcher/engine/http contracts). Same dedup as
# gate 4a — the full suite below collects the same file
if [[ "${SKIP_TESTS:-0}" == "1" ]]; then
    python -m pytest tests/test_serving.py -q
fi
# 5b: end-to-end smoke — ServingEngine on a tiny MLP, 64 concurrent
# ragged requests: zero errors, jit compiles == warmed bucket count
# (NOT the number of distinct observed batch sizes), and an
# undersized queue must actually reject (backpressure engages).
# --out also writes the bench_diff-compatible serving record
SRV_OUT="$(mktemp)"
DEC_OUT="$(mktemp)"
trap 'rm -f "$FP_TMP" "$SRV_OUT" "$DEC_OUT"' EXIT
python tools/serving_bench.py --smoke --out "$SRV_OUT"
# 5b-decode: continuous-batching decode smoke — mixed-length token
# streams through the DecodeEngine, every stream exactly-once, zero
# stream errors, and tokens/s must beat the static wait-for-all
# baseline measured in the same record (ISSUE 17 acceptance)
python tools/serving_bench.py --decode --out "$DEC_OUT"

echo "== gate 5c: serving perf regression vs previous run =="
# same machine-local run-over-run scheme as gate 7b: queue-wait /
# batch-size / padding-waste / compile-count regressions (and any
# serving.errors growth) fail CI exactly like training regressions.
# Timing gates loose (CI jitter); the counters are the strict half.
SRV_BASELINE="ci/baseline/serving_smoke.json"
mkdir -p ci/baseline
if [[ -f "$SRV_BASELINE" ]]; then
    srv_rc=0
    python tools/bench_diff.py "$SRV_BASELINE" "$SRV_OUT" \
        --threshold 0.5 --counters-threshold 0.5 || srv_rc=$?
    if [[ "$srv_rc" == "0" ]]; then
        echo "serving perf gate: no regression vs previous run"
    elif [[ "$srv_rc" == "2" ]]; then
        echo "serving perf gate: baseline unreadable (rc=2) — reseeding $SRV_BASELINE"
    elif [[ "${PERF_BASELINE_ACCEPT:-0}" == "1" ]]; then
        echo "serving perf gate: regression ACCEPTED (PERF_BASELINE_ACCEPT=1)"
    else
        echo "serving perf gate: regression vs $SRV_BASELINE — intentional? re-run with PERF_BASELINE_ACCEPT=1" >&2
        exit 1
    fi
else
    echo "serving perf gate: no previous run on this machine — seeding $SRV_BASELINE"
fi
cp "$SRV_OUT" "$SRV_BASELINE"
# decode record: TTFT/ITL percentiles, the continuous-vs-static
# speedup margin, KV occupancy and preemptions, run-over-run
DEC_BASELINE="ci/baseline/decode_smoke.json"
if [[ -f "$DEC_BASELINE" ]]; then
    dec_rc=0
    python tools/bench_diff.py "$DEC_BASELINE" "$DEC_OUT" \
        --threshold 0.5 --counters-threshold 0.5 || dec_rc=$?
    if [[ "$dec_rc" == "0" ]]; then
        echo "decode perf gate: no regression vs previous run"
    elif [[ "$dec_rc" == "2" ]]; then
        echo "decode perf gate: baseline unreadable (rc=2) — reseeding $DEC_BASELINE"
    elif [[ "${PERF_BASELINE_ACCEPT:-0}" == "1" ]]; then
        echo "decode perf gate: regression ACCEPTED (PERF_BASELINE_ACCEPT=1)"
    else
        echo "decode perf gate: regression vs $DEC_BASELINE — intentional? re-run with PERF_BASELINE_ACCEPT=1" >&2
        exit 1
    fi
else
    echo "decode perf gate: no previous run on this machine — seeding $DEC_BASELINE"
fi
cp "$DEC_OUT" "$DEC_BASELINE"

echo "== gate 6: fault tolerance =="
# 6a: the fault-tolerance suite (injection grammar/determinism, retry
# + dedup exactly-once, eviction, atomic checkpoints, port hygiene,
# /healthz drain). Same dedup as gates 4a/5a — the full suite below
# collects the same file
if [[ "${SKIP_TESTS:-0}" == "1" ]]; then
    python -m pytest tests/test_fault_tolerance.py -q
fi
# 6b: multiprocess recovery drill — 2-trainer sync PS under the launch
# supervisor, one trainer SIGKILLed at round 3: the job must complete
# (eviction unblocks the survivor, the relaunch resumes from the
# newest manifest-verified checkpoint) and the final checkpoint must
# re-verify
python tools/ft_smoke.py
# 6c: SERVER-death drill — 2 trainers, 2 replicated pservers, the
# PRIMARY SIGKILLs itself while applying round 3: the job must exit 0
# with every trainer failed over to the promoted backup AND the final
# params matching the clean single-server run bit-for-bit (failover
# replay + replicated dedup watermark); the killed server must rejoin
# as a catching-up backup under the supervisor, and the merged
# telemetry must show DELTA replication actually carried the job
# (ps.delta_rounds > 0 — a silent regression to full-blob shipping
# fails here)
python tools/ft_smoke.py --server-kill
# 6d: bounded chaos drill — one seeded randomized schedule (random
# fault plan + random trainer kill + random primary-pserver kill),
# gated on bit-for-bit parity with the clean run PLUS the merged-
# telemetry invariants (job-level metrics.json + trace.json exist;
# injected faults, the quorum promotion, and the promoted backup's
# first applied round are visible in causal order across >= 3
# processes; delta replication ran with its bytes strictly below the
# full anchors'); a failure prints the seed that replays it
python tools/chaos_drill.py --rounds 1
# 6e: ISSUE-19 acceptance drill (~2x2min) — WHOLE-JOB CRASH
# consistency: two seeded schedules each SIGKILL every process
# (launcher, trainers, every pserver — the process group dies) at a
# seeded durable round, relaunch the IDENTICAL command from the
# durable store, and gate on final params bit-for-bit vs the
# uninterrupted oracle PLUS the kill -> cold-start (restore_round at
# the newest globally-complete cut) -> per-shard restore-at-the-cut
# -> first-applied-round == cut+1 causal chain in the merged
# cross-incarnation trace.json (stale re-sends from the dead
# incarnation dropped, never re-applied)
python tools/chaos_drill.py --rounds 2 --total-loss --shards 2
# ... and the torn-tail variant: the newest durable round is torn on
# disk between kill and relaunch — restore must fall back exactly one
# globally-complete round and still land bit-for-bit
python tools/chaos_drill.py --rounds 1 --total-loss --corrupt-newest --shards 2
# 6f: ISSUE-8 acceptance drill — 2 key-range shards x (primary +
# backup), the schedule's shard loses its primary to SIGKILL (lease
# expiry -> tombstone-quorum election -> promotion) while the OTHER
# shard's primary<->backup pair is network-partitioned for the whole
# run (the backup's lease expires but every election is quorum-DENIED
# — exactly one writable primary per shard, no split brain, no lost
# rounds). Exit 0, per-shard params bit-for-bit, and
# ps.replication_bytes{mode=delta} strictly below the full-anchor
# bytes in the merged job metrics.json
python tools/chaos_drill.py --rounds 1 --shards 2 --partition
# 6g: ISSUE-13 acceptance drill (~45s) — LIVE KEY-RANGE MIGRATION
# under fire: a seeded schedule migrates one shard's var to the
# sister shard mid-training, the donor primary is SIGKILLed in the
# worst spot (range installed on the recipient, nothing committed or
# replicated), and the drill gates on exit 0, params bit-for-bit vs
# the clean run (zero lost or double-applied rounds), the rollback of
# attempt 1 + kill -> promotion -> migration-commit causal chain in
# the merged trace.json, every trainer adopting the bumped shard map,
# external-witness votes in the election, and clock-jitter chaos
# armed throughout
python tools/chaos_drill.py --rounds 1 --shards 2 --migrate
# 6h: ISSUE-18 acceptance drill (~90s) — SELF-STEERED row-range
# rebalance under fire: trainers hammer the hot quarter of one
# shard's slice of a sparse row-partitioned table, trainer 0's
# SteeringDaemon watches the job's own merged ps.row_heat census,
# proposes a migrate_range plan at the sustained skew breach, and
# the canary applies it through the LIVE protocol — with the donor
# primary SIGKILLed mid-apply (rows staged on the recipient, nothing
# committed) so the re-trigger completes on its promoted backup.
# Gated on exit 0; the sparse table bit-for-bit vs the pure
# push-schedule oracle on BOTH trainers (exactly-once across the
# kill, the abandoned install, and the wrong_shard redirects); the
# plan carving a tail of the hot quarter; install < kill < promotion
# < replicated range-commit in causal order; range bytes on
# ps.migration_bytes{kind=range}; every trainer routing the moved
# rows to the recipient; and the full audit chain (proposal
# artifact, audit trail, active-plan pointer, flight order) with
# bit-equal plan digests end to end
python tools/chaos_drill.py --rounds 1 --shards 2 --migrate-range --sync-rounds 18
# 6i: sharded eviction drill (~30s) — per-shard effective fanin
# disagreeing mid-round (the dying trainer's phase-1 barrier reaches
# shard 0 only; eviction armed on shard 1 alone): the two-phase
# barrier + the stale-round guard must reconcile DETERMINISTICALLY
# (per-shard bit-for-bit oracles, trainers agreeing, stale re-sends
# dropped not re-applied)
python tools/chaos_drill.py --rounds 1 --shards 2 --evict

echo "== gate 7: multichip fast-path smoke =="
# dp=8 CPU host mesh, mlp config, ~2 min: the bucketed/sharded
# collective path must STRICTLY reduce per-step collective ops vs a
# forced per-grad run; ONE profile-guided replan cycle must close the
# measurement loop (plan -> measure -> feed the profile report back
# via PADDLE_TPU_BUCKET_PLAN=profile -> the bucket plan demonstrably
# changes and measured overlap_frac does not decrease, with parity
# bit-for-bit via pytest); every dp=8 record must carry BOTH the
# host- and device-measured phase breakdowns plus their agreement
# ratio; and tools/bench_diff.py must answer --help and pass its
# --self-test (the mechanical perf gate bench artifacts diff through)
MC_OUT="$(mktemp)"
trap 'rm -f "$FP_TMP" "$SRV_OUT" "$MC_OUT"' EXIT
python tools/mc_smoke.py --out "$MC_OUT"

echo "== gate 7b: perf regression vs previous run =="
# ci/baseline/ keeps the PREVIOUS run's smoke artifact on this machine
# (gitignored: step_ms across different hosts is meaningless, so the
# comparison is same-host run-over-run). First run seeds the baseline;
# later runs diff automatically — the per-step collective counters are
# deterministic (static program rewrite), so they gate at 1%; timing
# metrics gate at a loose 50% (CI-box jitter is real; the counters are
# the strict half). Intentional perf-profile changes:
# PERF_BASELINE_ACCEPT=1 ci/check.sh records the new numbers as the
# next baseline instead of failing.
BASELINE="ci/baseline/mc_smoke.json"
mkdir -p ci/baseline
if [[ -f "$BASELINE" ]]; then
    diff_rc=0
    python tools/bench_diff.py "$BASELINE" "$MC_OUT" \
        --threshold 0.5 --counters-threshold 0.01 || diff_rc=$?
    if [[ "$diff_rc" == "0" ]]; then
        echo "perf gate: no regression vs previous run"
    elif [[ "$diff_rc" == "2" ]]; then
        # load error (torn/corrupt baseline, schema drift) is NOT a
        # regression — reseed rather than fail or silently "accept"
        echo "perf gate: baseline unreadable/incomparable (rc=2) — reseeding $BASELINE"
    elif [[ "${PERF_BASELINE_ACCEPT:-0}" == "1" ]]; then
        echo "perf gate: regression ACCEPTED (PERF_BASELINE_ACCEPT=1) — new baseline recorded"
    else
        echo "perf gate: regression vs $BASELINE — intentional? re-run with PERF_BASELINE_ACCEPT=1" >&2
        exit 1
    fi
else
    echo "perf gate: no previous run on this machine — seeding $BASELINE"
fi
cp "$MC_OUT" "$BASELINE"

echo "== gate 7e: placement-synthesis smoke =="
# ISSUE-15 acceptance (~60s): the dp=8 mlp placement search must emit
# a verifier-clean plan artifact — every enumerated candidate gated
# through verify_program + check_cross_rank BEFORE anything could
# trace it (zero rejected, zero traced-before-verify), deterministic
# winner digest from the same report+seed, canonical round-trip
# through PADDLE_TPU_PLACEMENT_PLAN — and the winner's measured
# step_ms must beat (<=) the size-plan baseline, with the bench
# record carrying the plan digest + predicted-vs-measured agreement
# that bench_diff watches for drift.
python tools/placement_smoke.py

echo "== gate 8: serving-fleet chaos drill =="
# the ISSUE-11 acceptance drill (~45s): 2 supervised serving replicas
# + a closed-loop FleetRouter driver under an RPC fault plan
# (drop/delay/close on the fleet dispatch path); replica 0 SIGKILLs
# itself mid-dispatch. Gated on the DRIVER's accounting (zero lost
# accepted requests, every response value-verified, shed strictly by
# cost class under the synthetic overload burst, the relaunched
# replica demonstrably serving again) AND on the merged job telemetry
# (p99 serving.queue_ms within budget, serving.hedges > 0,
# serving.replica_ejections >= 1, the kill -> ejection -> relaunch ->
# rejoin chain in causal order, per-replica serving spans joining ONE
# job trace) — not on logs.
python tools/serving_chaos.py --smoke

echo "== gate 8-decode: streaming-decode chaos drill =="
# the ISSUE-17 acceptance drill (~10s): 2 supervised DecodeEngine
# replicas, 8 concurrent token streams through FleetRouter.generate();
# replica 0 SIGKILLs itself mid-stream. Zero lost accepted streams,
# zero duplicated token indices, every delivered token value-verified
# against local regeneration (exactly-once resume after the kill),
# serving.stream_resumes >= 1 / stream_errors == 0 in merged
# counters, and the kill -> eject -> resume -> relaunch -> rejoin
# chain in causal order from the merged timeline.
python tools/serving_chaos.py --decode

echo "== gate 8b: steering drill =="
# the ISSUE-16 acceptance drill (seeded, in-process, ~10s): sampled
# capture fires on exactly every Nth executor step and surfaces in
# the merged metrics.json; the steering daemon proposes exactly ONCE
# for a sustained breach (hysteresis resets on a clean poll, the
# cooldown prevents a replan storm); a planted serving-ladder
# regression ROLLS BACK and a planted improvement PROMOTES under the
# shared comparator; and the audit closes — plan digests bit-match
# across steering_audit.json, the flight ring, the proposal artifact
# and the active-plan pointer, with installs == promoted entries
# (zero un-audited plan switches, the PlanStore refuses structurally).
env -u PADDLE_TPU_METRICS_DIR -u PADDLE_TPU_SAMPLE_EVERY \
    -u PADDLE_TPU_TIMESERIES \
    python tools/steering_drill.py

echo "== gate 8c: drifting-load A/B objective drill =="
# the ISSUE-20 acceptance drill (seeded, in-process, ~5s): under
# injected monotone load drift (+4%/window), the LEGACY flat
# comparator run against a stale incumbent record PROMOTES an
# objectively-worse serving ladder (drift masquerades as a +40%
# throughput win, every true regression hides under the flat noise
# floors) while the interleaved A/B canary — adjacent incumbent/
# candidate windows scored pairwise under a weighted objective —
# ROLLS BACK the same plan 0/3 AND PROMOTES a genuinely-better plan
# 3/3 in the same run; every window, pairwise verdict and objective
# term is asserted present in steering_audit.json, and ft_timeline
# renders the A/B window timeline from that trail.
env -u PADDLE_TPU_METRICS_DIR -u PADDLE_TPU_SAMPLE_EVERY \
    -u PADDLE_TPU_TIMESERIES -u PADDLE_TPU_AB_PAIRS \
    python tools/steering_drill.py --drift

if [[ "${SKIP_TESTS:-0}" != "1" ]]; then
    echo "== gate 9: test suite =="
    python -m pytest tests/ -q
fi
echo "ALL CI GATES PASS"
