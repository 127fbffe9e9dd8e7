"""Serving-fleet chaos drill: SIGKILL a replica mid-flight under load,
inject RPC faults on the fleet dispatch path, and assert the SLO held.

The drill the replica-fleet tier exists to pass (ISSUE 11). It runs a
supervised job through ``paddle_tpu.distributed.launch``:

- 2 (``--replicas N``) serving replica processes
  (``tests/dist_worker_serving.py`` — real save/load inference path,
  deterministic weights) supervised with relaunch budgets;
- 1 "trainer" process: THIS script in ``--driver`` mode — a
  closed-loop traffic generator over a ``serving.FleetRouter``, mixed
  cost classes, per-request deadlines, response VALUES verified
  against a locally-built reference model;
- a ``PADDLE_TPU_FAULTS`` plan (drop/delay/close) eating fleet RPC
  frames in the driver for the whole run;
- replica 0 SIGKILLs itself mid-dispatch after a fixed number of
  predictor runs (in-flight requests + co-batched peers die with it).

What must hold (asserted from the DRIVER's accounting and from the
MERGED job telemetry — metrics.json + trace.json — not from logs):

- **zero lost accepted requests**: every admitted request resolves
  with the CORRECT outputs (hedges/retries absorb the kill and the
  injected faults); admission failures are only typed sheds from the
  deliberate overload phase;
- **p99 serving.queue_ms within the drill budget** (read back from the
  merged metrics.json histogram);
- **shedding is by cost class**: under the synthetic overload burst
  the low-priority shed rate is strictly above the high-priority one;
- **hedges fired and stayed exactly-once**: ``serving.hedges > 0``,
  every request's result surfaced exactly once (value-checked), no
  duplicate surfaced to any client;
- **the causal chain reads from telemetry**: SIGKILL observed by the
  supervisor (``launch.exit`` signal=9) -> fleet ejection
  (``serving.replica_ejected``) -> supervised relaunch
  (``launch.spawn`` restart>=1) -> fleet rejoin
  (``serving.replica_rejoined``) -> the relaunched replica serves
  traffic again (driver-observed served count);
- per-replica ``serving.request`` spans from BOTH replicas join ONE
  job trace in the merged trace.json.

The ``--decode`` scenario (ISSUE 17) runs the same supervised-job
shape against STREAMING replicas (``tests/dist_worker_decode.py``:
``DecodeEngine`` + chunked ``/generate``): replica 0 SIGKILLs itself
mid-stream after emitting a fixed number of decode tokens, and the
driver's ``FleetRouter.generate()`` streams must fail over with
token-level ``(request_id, token_index)`` resume:

- **zero lost accepted streams**: every admitted stream finishes with
  ``max_tokens`` tokens;
- **zero duplicated token indices**: each stream's delivered indices
  are exactly ``0..n-1``, once each — the resume dedup holds;
- **exactly-once BY VALUE**: every delivered token equals the local
  reference engine's regeneration (replicas are deterministic, so a
  resumed suffix that re-prefilled wrongly cannot hide);
- the kill -> eject -> relaunch -> rejoin chain reads from merged
  telemetry, ``serving.stream_resumes >= 1`` and
  ``serving.stream_errors == 0`` in merged counters, and the
  relaunched replica serves STREAMS again.

Usage:
    python tools/serving_chaos.py --smoke      # the CI gate-8 drill
    python tools/serving_chaos.py --decode --smoke  # streaming drill
    python tools/serving_chaos.py [--requests N] [--burst N] ...
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "dist_worker_serving.py")
DECODE_WORKER = os.path.join(REPO, "tests", "dist_worker_decode.py")
if REPO not in sys.path:
    sys.path.insert(0, REPO)
_TOOLS = os.path.dirname(os.path.abspath(__file__))
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)
_TESTS = os.path.join(REPO, "tests")
if _TESTS not in sys.path:  # the driver imports the replica's model
    sys.path.insert(0, _TESTS)

DIM = 16  # must match dist_worker_serving.DIM
CLASSES = ("high", "normal", "low")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# driver mode: runs INSIDE the launch job as the "trainer"
# ---------------------------------------------------------------------------

def driver() -> int:
    """Closed-loop traffic + overload burst + rejoin watch. Writes its
    verdict to $SERVING_CHAOS_OUT and always exits 0 — the OUTER
    process asserts on the verdict (a nonzero trainer exit would be
    relaunched by the supervisor and re-run the whole drill)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import numpy as np

    from dist_worker_serving import build_model_dir, make_predictor
    from paddle_tpu import serving
    from paddle_tpu import observability as obs
    from paddle_tpu.observability.registry import reservoir_quantile

    out_path = os.environ["SERVING_CHAOS_OUT"]
    endpoints = [e for e in os.environ["PADDLE_SERVING_ENDPOINTS"]
                 .split(",") if e]
    n_requests = int(os.environ.get("SC_REQUESTS", "120"))
    n_clients = int(os.environ.get("SC_CLIENTS", "6"))
    burst = int(os.environ.get("SC_BURST", "180"))
    deadline_ms = float(os.environ.get("SC_DEADLINE_MS", "15000"))
    die_endpoint = endpoints[int(os.environ.get("SERVING_DIE_REPLICA",
                                                "0") or 0)]
    failures = []
    result = {"failures": failures, "accepted": 0, "ok": 0,
              "shed": {}, "rejoined": False}

    def fail(msg):
        print("[driver] FAIL: %s" % msg, flush=True)
        failures.append(msg)

    # the reference copy of the replicas' deterministic model: fleet
    # responses are verified VALUE-FOR-VALUE, so a duplicate, a stale
    # hedge loser, or a cross-request mixup cannot hide
    with tempfile.TemporaryDirectory(prefix="serving_ref_") as d:
        build_model_dir(d)
        ref_predictor = make_predictor(d)

        router = serving.FleetRouter(
            endpoints,
            serving.FleetConfig(
                max_queue=int(os.environ.get("SC_MAX_QUEUE", "48")),
                num_dispatchers=max(8, n_clients + 2),
                hedge_after_ms=float(os.environ.get(
                    "SC_HEDGE_AFTER_MS", "250")),
                max_hedges=1, max_attempts=5,
                health_interval_ms=100.0, eject_after=3,
                request_timeout_s=30.0)).start()
        try:
            rc = _drive(router, ref_predictor, np, serving, obs,
                        reservoir_quantile, endpoints, die_endpoint,
                        n_requests, n_clients, burst, deadline_ms,
                        result, fail)
        finally:
            router.stop()
            with open(out_path + ".tmp", "w") as f:
                json.dump(result, f, indent=2)
            os.replace(out_path + ".tmp", out_path)
            print("[driver] wrote %s (%d failure(s))"
                  % (out_path, len(failures)), flush=True)
    return rc


def _drive(router, ref_predictor, np, serving, obs, reservoir_quantile,
           endpoints, die_endpoint, n_requests, n_clients, burst,
           deadline_ms, result, fail) -> int:
    # -- wait for the fleet to come up (replicas import jax + build) --
    t0 = time.monotonic()
    while router.healthy_count() < len(endpoints):
        if time.monotonic() - t0 > 120:
            fail("fleet never became healthy (%d/%d)"
                 % (router.healthy_count(), len(endpoints)))
            return 0
        time.sleep(0.25)
    print("[driver] fleet healthy (%d replicas) after %.1fs"
          % (len(endpoints), time.monotonic() - t0), flush=True)

    def expected(x):
        return np.asarray(ref_predictor.run(
            {"x": np.asarray(x, "float32")})[0].data)

    # -- phase 1: closed-loop load; replica 0 SIGKILLs itself mid-way --
    lock = threading.Lock()
    stats = {"accepted": 0, "ok": 0, "wrong": [], "errors": []}

    def client(cid):
        rng = np.random.RandomState(1000 + cid)
        for i in range(n_requests // n_clients):
            rows = 1 + (i % 3)
            x = rng.uniform(-1, 1, size=(rows, DIM)).astype("float32")
            cls = CLASSES[(cid + i) % len(CLASSES)]
            try:
                f = router.submit({"x": x}, deadline_ms=deadline_ms,
                                  cost_class=cls)
            except serving.ServerOverloaded as e:
                # closed-loop load must stay under the watermarks: an
                # admission failure here IS a drill failure
                with lock:
                    stats["errors"].append("admission: %r" % e)
                continue
            with lock:
                stats["accepted"] += 1
            try:
                out = f.result(60)
            except Exception as e:  # noqa: BLE001
                with lock:
                    stats["errors"].append("lost: %r" % e)
                continue
            y = np.asarray(list(out.values())[0])
            if y.shape != (rows, 4) or not np.allclose(
                    y, expected(x), rtol=1e-4, atol=1e-5):
                with lock:
                    stats["wrong"].append(cid)
            else:
                with lock:
                    stats["ok"] += 1

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    result["accepted"] = stats["accepted"]
    result["ok"] = stats["ok"]
    if stats["errors"]:
        fail("phase1: %d accepted request(s) lost/failed: %s"
             % (len(stats["errors"]), stats["errors"][:4]))
    if stats["wrong"]:
        fail("phase1: %d response(s) with WRONG values (duplicate or "
             "cross-request mixup)" % len(stats["wrong"]))
    if stats["ok"] != stats["accepted"]:
        fail("phase1: ok=%d != accepted=%d (zero lost accepted "
             "requests is the drill's first SLO)"
             % (stats["ok"], stats["accepted"]))
    print("[driver] phase1: %d/%d accepted requests served correctly"
          % (stats["ok"], stats["accepted"]), flush=True)

    # -- the kill must have happened: wait for ejection + relaunch +
    # rejoin, then PROVE the relaunched replica takes traffic ---------
    def rep_state(ep):
        for r in router.stats()["replicas"]:
            if r["endpoint"] == ep:
                return r
        return None

    t0 = time.monotonic()
    while time.monotonic() - t0 < 90:
        r = rep_state(die_endpoint)
        if r and r["state"] == "serving" and r["ejections"] >= 1:
            break
        time.sleep(0.25)
    r = rep_state(die_endpoint)
    if not (r and r["ejections"] >= 1):
        fail("killed replica %s was never ejected (state=%s)"
             % (die_endpoint, r and r["state"]))
    if not (r and r["state"] == "serving"):
        fail("killed replica %s never rejoined (state=%s)"
             % (die_endpoint, r and r["state"]))
    else:
        served0 = r["served"]
        x = np.ones((1, DIM), "float32")
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            out = router.predict({"x": x}, deadline_ms=deadline_ms,
                                 cost_class="high", timeout=60)
            if not np.allclose(np.asarray(list(out.values())[0]),
                               expected(x), rtol=1e-4, atol=1e-5):
                fail("post-rejoin response has wrong values")
                break
            r = rep_state(die_endpoint)
            if r["served"] > served0:
                result["rejoined"] = True
                print("[driver] relaunched replica %s serving again "
                      "(served %d)" % (die_endpoint, r["served"]),
                      flush=True)
                break
            time.sleep(0.05)
        if not result["rejoined"]:
            fail("relaunched replica %s never served a request"
                 % die_endpoint)

    # -- phase 2: synthetic overload — shed must be by cost class -----
    # slam the queue open-loop; per-class sheds counted from the typed
    # exceptions (and cross-checked from merged counters by the outer)
    shed = {c: 0 for c in CLASSES}
    admitted = {c: 0 for c in CLASSES}
    futures = []
    rng = np.random.RandomState(7)
    for i in range(burst):
        cls = CLASSES[i % len(CLASSES)]
        x = rng.uniform(-1, 1, size=(1, DIM)).astype("float32")
        try:
            futures.append(router.submit(
                {"x": x}, deadline_ms=30000, cost_class=cls))
            admitted[cls] += 1
        except serving.RequestShed:
            shed[cls] += 1
        except serving.ServerOverloaded:
            shed[cls] += 1  # hard bound: still a shed for rate math
    lost = 0
    for f in futures:
        try:
            f.result(120)
        except Exception:  # noqa: BLE001
            lost += 1
    result["shed"] = shed
    result["admitted"] = admitted
    if lost:
        fail("overload: %d ADMITTED burst request(s) lost" % lost)
    if not (shed["low"] > shed["high"]):
        fail("overload: shed(low)=%d not strictly above shed(high)=%d"
             % (shed["low"], shed["high"]))
    if admitted["high"] <= admitted["low"]:
        fail("overload: high-priority admits (%d) not above "
             "low-priority (%d)" % (admitted["high"], admitted["low"]))
    print("[driver] overload: shed=%s admitted=%s" % (shed, admitted),
          flush=True)

    # -- fleet-side counters the outer will cross-check ---------------
    result["hedges"] = obs.counter_value("serving.hedges")
    result["hedge_wasted"] = obs.counter_value("serving.hedge_wasted")
    result["fleet_retries"] = obs.counter_value("serving.fleet_retries")
    q = obs.histogram("serving.queue_ms").snapshot()
    result["queue_ms_p99"] = q.get("p99")
    result["replicas"] = router.stats()["replicas"]
    if result["hedges"] < 1:
        fail("serving.hedges=%d — the kill window must hedge"
             % result["hedges"])
    return 0


# ---------------------------------------------------------------------------
# decode driver mode: streaming traffic inside the launch job
# ---------------------------------------------------------------------------

def _decode_specs(n_streams, victim_tokens):
    """Deterministic stream workload: one long 'victim' stream that is
    guaranteed to span the replica kill, plus mixed-length peers."""
    import numpy as np

    rng = np.random.RandomState(0xFA110)
    specs = []
    for i in range(n_streams):
        prompt = [int(t) for t in rng.randint(1, 90, size=3 + i % 4)]
        n = victim_tokens if i == 0 else (24 + 8 * (i % 5))
        specs.append((prompt, n))
    return specs


def decode_driver() -> int:
    """Streaming chaos driver: run mixed-length decode streams through
    ``FleetRouter.generate()`` while replica 0 SIGKILLs itself
    mid-stream; verify exactly-once token delivery BY VALUE against a
    local reference engine, then prove the relaunched replica streams
    again. Verdict goes to $SERVING_CHAOS_OUT; exits 0 (the outer
    process asserts — see ``driver()``)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from dist_worker_decode import build_engine
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.serving import metrics as sm

    out_path = os.environ["SERVING_CHAOS_OUT"]
    endpoints = [e for e in os.environ["PADDLE_SERVING_ENDPOINTS"]
                 .split(",") if e]
    die_endpoint = endpoints[int(os.environ.get("SERVING_DIE_REPLICA",
                                                "0") or 0)]
    n_streams = int(os.environ.get("SC_DECODE_STREAMS", "8"))
    victim_tokens = int(os.environ.get("SC_DECODE_VICTIM_TOKENS", "240"))
    failures = []
    result = {"failures": failures, "accepted": 0, "completed": 0,
              "duplicate_indices": 0, "resumes": 0, "rejoined": False}

    def fail(msg):
        print("[decode driver] FAIL: %s" % msg, flush=True)
        failures.append(msg)

    specs = _decode_specs(n_streams, victim_tokens)

    # local reference regeneration: the replicas serve the identical
    # deterministic function, so every delivered token — including the
    # failed-over suffix re-prefixed on the OTHER replica — must equal
    # this run value-for-value
    ref = build_engine().start()
    expected = []
    try:
        for i, (prompt, n) in enumerate(specs):
            evs = list(ref.submit(prompt, max_tokens=n,
                                  request_id="ref%d" % i))
            expected.append([e["token"] for e in evs
                             if e["type"] == "token"])
    finally:
        ref.stop()

    router = serving.FleetRouter(
        endpoints,
        serving.FleetConfig(
            max_queue=128, num_dispatchers=4,
            health_interval_ms=100.0, eject_after=3,
            max_attempts=8, request_timeout_s=300.0,
            stream_stall_s=2.0)).start()
    try:
        rc = _drive_decode(router, serving, obs, sm, endpoints,
                           die_endpoint, specs, expected, result, fail)
    finally:
        router.stop()
        with open(out_path + ".tmp", "w") as f:
            json.dump(result, f, indent=2)
        os.replace(out_path + ".tmp", out_path)
        print("[decode driver] wrote %s (%d failure(s))"
              % (out_path, len(failures)), flush=True)
    return rc


def _drive_decode(router, serving, obs, sm, endpoints, die_endpoint,
                  specs, expected, result, fail) -> int:
    t0 = time.monotonic()
    while router.healthy_count() < len(endpoints):
        if time.monotonic() - t0 > 120:
            fail("fleet never became healthy (%d/%d)"
                 % (router.healthy_count(), len(endpoints)))
            return 0
        time.sleep(0.25)
    print("[decode driver] fleet healthy (%d replicas) after %.1fs"
          % (len(endpoints), time.monotonic() - t0), flush=True)

    # -- phase 1: concurrent streams; replica 0 dies mid-stream -------
    lock = threading.Lock()
    per_stream = [None] * len(specs)

    def consume(i, prompt, n):
        events = []
        try:
            for ev in router.generate(prompt, max_tokens=n,
                                      request_id="chaos-s%d" % i,
                                      cost_class="high",
                                      deadline_s=240.0):
                events.append(ev)
        except Exception as e:  # noqa: BLE001 — any escape is a loss
            with lock:
                fail("stream %d raised %r (streams must end with an "
                     "in-band finish event)" % (i, e))
        per_stream[i] = events

    threads = [threading.Thread(target=consume, args=(i, p, n))
               for i, (p, n) in enumerate(specs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    result["accepted"] = len(specs)

    dup_total = 0
    for i, ((_p, n), events) in enumerate(zip(specs, per_stream)):
        events = events or []
        toks = [e for e in events if e["type"] == "token"]
        fin = [e for e in events if e["type"] == "finish"]
        idxs = [t["index"] for t in toks]
        dups = len(idxs) - len(set(idxs))
        dup_total += dups
        if dups:
            fail("stream %d delivered %d DUPLICATE token index(es)"
                 % (i, dups))
        if not (fin and fin[-1].get("reason") == "max_tokens"):
            fail("stream %d lost: finished %r, want max_tokens"
                 % (i, fin[-1].get("reason") if fin else None))
            continue
        if idxs != list(range(n)):
            fail("stream %d indices not exactly-once 0..%d (got %d "
                 "tokens, head=%s)" % (i, n - 1, len(idxs), idxs[:6]))
            continue
        got = [t["token"] for t in toks]
        if got != expected[i]:
            div = next(k for k in range(n) if got[k] != expected[i][k])
            fail("stream %d DIVERGED from reference at token %d "
                 "(resume re-prefill broke determinism)" % (i, div))
            continue
        result["completed"] += 1
    result["duplicate_indices"] = dup_total
    result["resumes"] = obs.counter_value(sm.STREAM_RESUMES)
    result["stream_errors"] = obs.counter_value(sm.STREAM_ERRORS)
    if result["completed"] != result["accepted"]:
        fail("lost streams: completed=%d != accepted=%d"
             % (result["completed"], result["accepted"]))
    if result["resumes"] < 1:
        fail("serving.stream_resumes=%d — the mid-stream kill must "
             "force at least one token-level resume"
             % result["resumes"])
    if result["stream_errors"]:
        fail("serving.stream_errors=%d (want 0)"
             % result["stream_errors"])
    print("[decode driver] phase1: %d/%d streams exactly-once "
          "(resumes=%d)" % (result["completed"], result["accepted"],
                            result["resumes"]), flush=True)

    # -- the relaunched replica must STREAM again ---------------------
    def rep_state(ep):
        for r in router.stats()["replicas"]:
            if r["endpoint"] == ep:
                return r
        return None

    t0 = time.monotonic()
    while time.monotonic() - t0 < 90:
        r = rep_state(die_endpoint)
        if r and r["state"] == "serving" and r["ejections"] >= 1:
            break
        time.sleep(0.25)
    r = rep_state(die_endpoint)
    if not (r and r["ejections"] >= 1):
        fail("killed replica %s was never ejected (state=%s)"
             % (die_endpoint, r and r["state"]))
    if not (r and r["state"] == "serving"):
        fail("killed replica %s never rejoined (state=%s)"
             % (die_endpoint, r and r["state"]))
    else:
        served0 = r["served"]
        t0 = time.monotonic()
        probe_i = 0
        while time.monotonic() - t0 < 60:
            evs = list(router.generate(
                [1, 2, 3], max_tokens=4, cost_class="high",
                request_id="rejoin-%d" % probe_i, deadline_s=30.0))
            probe_i += 1
            if not (evs and evs[-1].get("reason") == "max_tokens"):
                fail("post-rejoin probe stream finished %r"
                     % (evs and evs[-1].get("reason")))
                break
            r = rep_state(die_endpoint)
            if r["served"] > served0:
                result["rejoined"] = True
                print("[decode driver] relaunched replica %s streaming "
                      "again (served %d)" % (die_endpoint, r["served"]),
                      flush=True)
                break
            time.sleep(0.05)
        if not result["rejoined"]:
            fail("relaunched replica %s never served a stream"
                 % die_endpoint)
    result["replicas"] = router.stats()["replicas"]
    return 0


# ---------------------------------------------------------------------------
# outer mode: orchestrate the supervised job + assert on telemetry
# ---------------------------------------------------------------------------

def _env(tmp, endpoints, args) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({
        "SERVING_CHAOS_OUT": os.path.join(tmp, "driver.json"),
        "SC_REQUESTS": str(args.requests),
        "SC_CLIENTS": str(args.clients),
        "SC_BURST": str(args.burst),
        # replica 0 dies after this many predictor dispatches (warmup
        # compiles its 4 ladder buckets first): mid phase-1 traffic
        "SERVING_DIE_REPLICA": "0",
        "SERVING_DIE_AFTER": str(args.die_after),
        # per-dispatch replica latency: keeps batches forming and the
        # overload burst actually overloading on fast hosts
        "SERVING_REPLICA_DELAY_MS": "10",
        # the RPC fault plan on the fleet dispatch path (driver side):
        # drop + delay + an occasional severed connection, all absorbed
        # by the retry/hedge budget
        "PADDLE_TPU_FAULTS":
            "send.drop:0.02,any.delay:0.05:5,send.close:0.01",
        "PADDLE_TPU_FAULT_SEED": str(args.seed),
        "PADDLE_TPU_METRICS_DIR": os.path.join(tmp, "metrics"),
        "PADDLE_TPU_DUMP_PERIOD": "0.5",
    })
    return env


def run_drill(args) -> int:
    tmp = tempfile.mkdtemp(prefix="serving_chaos_")
    endpoints = ["127.0.0.1:%d" % _free_port()
                 for _ in range(args.replicas)]
    env = _env(tmp, endpoints, args)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node=1", "--max_restarts=3",
           "--started_port=%d" % _free_port(),
           "--serving_script=%s" % WORKER,
           "--serving_endpoints=%s" % ",".join(endpoints),
           os.path.abspath(__file__), "--driver"]
    print("[chaos] fleet drill: %d replicas, kill replica 0 after %d "
          "dispatches, faults=%s"
          % (args.replicas, args.die_after, env["PADDLE_TPU_FAULTS"]))
    sup = subprocess.run(cmd, env=env, timeout=600, cwd=REPO)
    if sup.returncode != 0:
        print("[chaos] FAIL: job exited %d" % sup.returncode)
        return 1
    ok = check_results(os.path.join(tmp, "driver.json"),
                       os.path.join(tmp, "metrics"), endpoints, args)
    return 0 if ok else 1


def run_decode_drill(args) -> int:
    tmp = tempfile.mkdtemp(prefix="serving_chaos_decode_")
    endpoints = ["127.0.0.1:%d" % _free_port()
                 for _ in range(args.replicas)]
    env = _env(tmp, endpoints, args)
    # streaming-path chaos: lighter RPC faults (every drop on the
    # chunked stream already forces a full token-level resume), the
    # kill armed on emitted decode tokens instead of dispatches
    env.update({
        "DECODE_DIE_AFTER_TOKENS": str(args.die_after_tokens),
        "SC_DECODE_STREAMS": str(args.streams),
        "SC_DECODE_VICTIM_TOKENS": str(args.victim_tokens),
        "PADDLE_TPU_FAULTS": "send.drop:0.01,any.delay:0.05:5",
    })
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nproc_per_node=1", "--max_restarts=3",
           "--started_port=%d" % _free_port(),
           "--serving_script=%s" % DECODE_WORKER,
           "--serving_endpoints=%s" % ",".join(endpoints),
           os.path.abspath(__file__), "--driver", "--decode"]
    print("[chaos] decode drill: %d streaming replicas, kill replica 0 "
          "after %d emitted tokens, faults=%s"
          % (args.replicas, args.die_after_tokens,
             env["PADDLE_TPU_FAULTS"]))
    sup = subprocess.run(cmd, env=env, timeout=600, cwd=REPO)
    if sup.returncode != 0:
        print("[chaos] FAIL: job exited %d" % sup.returncode)
        return 1
    ok = check_decode_results(os.path.join(tmp, "driver.json"),
                              os.path.join(tmp, "metrics"), endpoints)
    return 0 if ok else 1


def check_decode_results(driver_json, mdir, endpoints) -> bool:
    """Outer gate for the streaming drill: driver verdict (exactly-once
    by value) + the kill->resume causal chain from merged telemetry."""
    import ft_timeline

    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    try:
        res = json.load(open(driver_json))
    except (OSError, ValueError) as e:
        print("[chaos] FAIL: no driver verdict (%s)" % e)
        return False
    for f in res.get("failures", []):
        chk("driver: %s" % f, False)
    chk("zero lost accepted streams (%d/%d finished max_tokens)"
        % (res.get("completed", 0), res.get("accepted", 0)),
        res.get("accepted", 0) > 0
        and res.get("completed") == res.get("accepted"))
    chk("zero duplicated token indices",
        res.get("duplicate_indices", -1) == 0)
    chk("token-level resume fired (driver resumes=%d)"
        % res.get("resumes", 0), res.get("resumes", 0) >= 1)
    chk("relaunched replica streamed again", bool(res.get("rejoined")))

    ft_timeline.print_postmortem(mdir, limit=30)
    mpath = os.path.join(mdir, "metrics.json")
    chk("job-level metrics.json merged", os.path.exists(mpath))
    if not ok:
        return False
    merged = json.load(open(mpath))
    totals = merged["counters_total"]
    chk("serving.stream_resumes >= 1 in merged counters (%d)"
        % totals.get("serving.stream_resumes", 0),
        totals.get("serving.stream_resumes", 0) >= 1)
    chk("serving.stream_errors == 0 in merged counters (%d)"
        % totals.get("serving.stream_errors", 0),
        totals.get("serving.stream_errors", 0) == 0)
    eject = sum(v for k, v in totals.items()
                if k.startswith("serving.replica_ejections"))
    chk("serving.replica_ejections >= 1 (%d)" % eject, eject >= 1)

    # causal chain: SIGKILL -> ejection -> token-level stream resume ->
    # relaunch -> rejoin, all from the merged event timeline
    events = ft_timeline.load_events(mdir)

    def first(pred):
        for e in events:
            if pred(e):
                return e
        return None

    die_ep = endpoints[0]
    kill = first(lambda e: e["kind"] == "launch.exit"
                 and e["fields"].get("role") == "serving"
                 and e["fields"].get("signal") == 9)
    chk("supervisor observed the replica SIGKILL", kill is not None)
    if kill is None:
        return False
    t_kill = kill["t_us"]
    eject_ev = first(lambda e: e["kind"] == "serving.replica_ejected"
                     and e["fields"].get("endpoint") == die_ep
                     and e["t_us"] > t_kill - 1e6)
    resume_ev = first(lambda e: e["kind"] == "serving.stream_resume"
                      and e["t_us"] > t_kill - 1e6)
    relaunch = first(lambda e: e["kind"] == "launch.spawn"
                     and e["fields"].get("role") == "serving"
                     and e["fields"].get("restart", 0) >= 1
                     and e["t_us"] > t_kill)
    rejoin = first(lambda e: e["kind"] == "serving.replica_rejoined"
                   and e["fields"].get("endpoint") == die_ep
                   and relaunch is not None
                   and e["t_us"] > relaunch["t_us"])
    chk("fleet ejected the killed replica in the kill window",
        eject_ev is not None)
    chk("a stream resumed from a mid-stream token index after the "
        "kill (from_index=%s)"
        % (resume_ev and resume_ev["fields"].get("from_index")),
        resume_ev is not None
        and resume_ev["fields"].get("from_index", 0) > 0)
    chk("supervisor relaunched the replica after the kill",
        relaunch is not None)
    chk("fleet re-admitted the replica after the relaunch",
        rejoin is not None)
    if ok and eject_ev and relaunch and rejoin:
        chk("causal order: kill < relaunch < rejoin, ejection < rejoin",
            t_kill < relaunch["t_us"] < rejoin["t_us"]
            and eject_ev["t_us"] < rejoin["t_us"])
    return ok


def check_results(driver_json, mdir, endpoints, args) -> bool:
    """The outer gate: driver verdict + merged-telemetry invariants."""
    import ft_timeline

    ok = True

    def chk(what, passed):
        nonlocal ok
        print("[chaos] %s: %s" % ("PASS" if passed else "FAIL", what))
        ok = ok and passed

    try:
        res = json.load(open(driver_json))
    except (OSError, ValueError) as e:
        print("[chaos] FAIL: no driver verdict (%s)" % e)
        return False
    for f in res.get("failures", []):
        chk("driver: %s" % f, False)
    chk("driver verdict clean (%d accepted, %d ok, rejoined=%s)"
        % (res.get("accepted", 0), res.get("ok", 0),
           res.get("rejoined")), not res.get("failures"))
    chk("zero lost accepted requests (%d/%d)"
        % (res.get("ok", 0), res.get("accepted", 0)),
        res.get("accepted", 0) > 0
        and res.get("ok") == res.get("accepted"))
    chk("relaunched replica took traffic again",
        bool(res.get("rejoined")))

    # -- merged job telemetry, not logs -------------------------------
    ft_timeline.print_postmortem(mdir, limit=30)
    mpath = os.path.join(mdir, "metrics.json")
    tpath = os.path.join(mdir, "trace.json")
    chk("job-level metrics.json + trace.json merged",
        os.path.exists(mpath) and os.path.exists(tpath))
    if not ok:
        return False
    merged = json.load(open(mpath))
    totals = merged["counters_total"]
    chk("processes merged (driver + %d replicas + launcher >= 4: %d)"
        % (args.replicas, len(merged["processes"])),
        len(merged["processes"]) >= args.replicas + 2)

    # SLO: p99 queue wait within budget, from the MERGED metrics
    driver_proc = merged["processes"].get("trainer-0") or {}
    q = (driver_proc.get("metrics") or {}).get("histograms", {}).get(
        "serving.queue_ms") or {}
    chk("p99 serving.queue_ms %.1fms within %.0fms budget (merged "
        "metrics)" % (q.get("p99") or -1, args.slo_p99_ms),
        q.get("p99") is not None and q["p99"] <= args.slo_p99_ms)

    hedges = totals.get("serving.hedges", 0)
    chk("serving.hedges > 0 in merged counters (%d)" % hedges,
        hedges > 0)
    eject = sum(v for k, v in totals.items()
                if k.startswith("serving.replica_ejections"))
    chk("serving.replica_ejections >= 1 (%d)" % eject, eject >= 1)
    shed_low = totals.get("serving.shed{class=low}", 0)
    shed_high = totals.get("serving.shed{class=high}", 0)
    chk("shed by cost class: low (%d) strictly above high (%d)"
        % (shed_low, shed_high), shed_low > shed_high)
    n_faults = sum(v for k, v in totals.items()
                   if k.startswith("fault.injected"))
    chk("injected RPC faults visible in merged counters (%d)"
        % n_faults, n_faults > 0)
    # exactly-once cross-check: every replica-side admitted request
    # came from the driver's attempts; the driver's value checks
    # already proved no duplicate was SURFACED — here the dedup
    # counter shows duplicate deliveries were JOINED, not re-run
    served = sum(
        (p.get("metrics") or {}).get("counters", {}).get(
            "serving.requests", 0)
        for name, p in merged["processes"].items()
        if name.startswith("serving-"))
    chk("replica-side serving.requests recorded (%d)" % served,
        served > 0)

    # -- the causal chain: kill -> ejection -> relaunch -> rejoin -----
    events = ft_timeline.load_events(mdir)

    def first(pred):
        for e in events:
            if pred(e):
                return e
        return None

    die_ep = endpoints[0]
    kill = first(lambda e: e["kind"] == "launch.exit"
                 and e["fields"].get("role") == "serving"
                 and e["fields"].get("signal") == 9)
    chk("supervisor observed the replica SIGKILL", kill is not None)
    if kill is None:
        return False
    # window the chain AT the kill: a slow-starting replica is
    # (correctly) ejected+rejoined once at STARTUP too — the chain the
    # drill gates is the one the SIGKILL caused. The ejection may land
    # up to ~1s before the launcher's 0.2s poll records the corpse
    # (dispatch failures eject faster than the supervisor observes),
    # hence the small backward margin.
    t_kill = kill["t_us"]
    eject_ev = first(lambda e: e["kind"] == "serving.replica_ejected"
                     and e["fields"].get("endpoint") == die_ep
                     and e["t_us"] > t_kill - 1e6)
    relaunch = first(lambda e: e["kind"] == "launch.spawn"
                     and e["fields"].get("role") == "serving"
                     and e["fields"].get("restart", 0) >= 1
                     and e["t_us"] > t_kill)
    rejoin = first(lambda e: e["kind"] == "serving.replica_rejoined"
                   and e["fields"].get("endpoint") == die_ep
                   and relaunch is not None
                   and e["t_us"] > relaunch["t_us"])
    chk("fleet ejected the killed replica in the kill window",
        eject_ev is not None)
    chk("supervisor relaunched the replica after the kill",
        relaunch is not None)
    chk("fleet re-admitted the replica after the relaunch",
        rejoin is not None)
    if ok and eject_ev and relaunch and rejoin:
        chk("causal order: kill < relaunch < rejoin, ejection < rejoin",
            t_kill < relaunch["t_us"] < rejoin["t_us"]
            and eject_ev["t_us"] < rejoin["t_us"])
        procs = {kill["proc"], eject_ev["proc"], relaunch["proc"],
                 rejoin["proc"]}
        chk("chain spans supervisor + driver (%s)" % sorted(procs),
            len(procs) >= 2)

    # -- per-replica serving spans join ONE job trace -----------------
    trace = json.load(open(tpath))
    by_trace = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("name") == "serving.request" and ev.get("args"):
            tid = ev["args"].get("trace_id")
            if tid:
                by_trace.setdefault(tid, set()).add(ev.get("pid"))
    multi = [t for t, pids in by_trace.items() if len(pids) >= 2]
    chk("serving.request spans from >= 2 replica processes share one "
        "job trace (%d shared trace ids)" % len(multi), bool(multi))
    return ok


def main() -> int:
    ap = argparse.ArgumentParser("serving_chaos")
    ap.add_argument("--driver", action="store_true",
                    help="(internal) run as the in-job traffic driver")
    ap.add_argument("--decode", action="store_true",
                    help="streaming-decode scenario: SIGKILL a replica "
                         "mid-stream, assert token-level exactly-once "
                         "failover")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized drill (the gate-8 configuration)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=240)
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--burst", type=int, default=180)
    ap.add_argument("--die-after", type=int, default=24,
                    help="replica-0 predictor dispatches before its "
                         "self-SIGKILL (warmup compiles count)")
    ap.add_argument("--slo-p99-ms", type=float, default=3000.0,
                    help="drill budget for p99 serving.queue_ms")
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--streams", type=int, default=8,
                    help="(--decode) concurrent streams in phase 1")
    ap.add_argument("--victim-tokens", type=int, default=240,
                    help="(--decode) length of the long stream the "
                         "kill must land inside")
    ap.add_argument("--die-after-tokens", type=int, default=60,
                    help="(--decode) replica-0 emitted decode tokens "
                         "before its self-SIGKILL")
    args = ap.parse_args()
    if args.driver:
        return decode_driver() if args.decode else driver()
    if args.decode:
        return run_decode_drill(args)
    if args.smoke:
        args.requests = 120
        args.burst = 150
        args.die_after = 18
    return run_drill(args)


if __name__ == "__main__":
    sys.exit(main())
