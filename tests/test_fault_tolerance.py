"""Fault-tolerant distributed training (ISSUES 3 + 4).

Covers: the deterministic fault-injection shim at the RPC frame
boundary; client retry + server dedup keeping gradient application
exactly-once under injected drops/dups (bit-for-bit parity with the
clean run); heartbeat eviction unblocking survivors after a SIGKILL;
supervised relaunch resuming from the newest valid checkpoint; atomic
checkpoint dirs (manifest, rotation, corrupt-shard fallback); typed
load errors; PS server port hygiene on stop(); serving /healthz
draining.

ISSUE 4 additions: PS state replication + client failover (primary
killed mid-round, trainers fail over to the backup and the final
params match the clean run bit-for-bit); backup promotion rules
(fresh clients redirected, only failed-over clients promote); server
rejoin catch-up from a manifest-verified snapshot; chaos-drill
schedule determinism; scope-snapshot load integrity; serving typed
batch errors; per-method rpc counter labels."""
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FT_WORKER = os.path.join(REPO, "tests", "dist_worker_ft.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class MiniScope(dict):
    def local_var_names(self):
        return list(self)


class MiniExec:
    def _read_var(self, scope, name):
        return scope.get(name)

    def _write_var(self, scope, name, val):
        scope[name] = np.asarray(val)

    def run_block(self, block, scope):
        block(scope)


def _sgd_block(scope, lr=0.1):
    scope["w"] = scope["w"] - lr * scope["w@GRAD"]


def _grad(tid, rnd, dim=4):
    return np.full(dim, (tid + 1) * 0.01 * rnd, dtype=np.float32)


# -- fault injector ---------------------------------------------------------


def test_fault_plan_grammar():
    from paddle_tpu.distributed.fault import FaultRule, parse_plan

    rules = parse_plan("send.drop:0.05, recv.delay:0.1:30 ,any.dup:1")
    assert [(r.side, r.kind, r.prob) for r in rules] == [
        ("send", "drop", 0.05), ("recv", "delay", 0.1),
        ("any", "dup", 1.0)]
    assert rules[1].param == 30
    with pytest.raises(ValueError, match="side"):
        parse_plan("up.drop:0.1")
    with pytest.raises(ValueError, match="kind"):
        parse_plan("send.explode:0.1")
    with pytest.raises(ValueError, match="recv-side"):
        FaultRule("recv", "dup", 0.5)
    with pytest.raises(ValueError, match="probability"):
        parse_plan("send.drop:1.5")
    with pytest.raises(ValueError, match="bad PADDLE_TPU_FAULTS"):
        parse_plan("send.drop:abc")


class _FakeSock:
    def __init__(self):
        self.sent = []
        self.closed = False

    def sendall(self, b):
        self.sent.append(bytes(b))

    def shutdown(self, how):
        pass

    def close(self):
        self.closed = True


def test_fault_injector_seeded_determinism():
    from paddle_tpu.distributed.fault import (FaultInjected,
                                              FaultInjector, parse_plan)

    def run(seed):
        inj = FaultInjector(parse_plan("send.drop:0.3,send.dup:0.3"),
                            seed=seed)
        events = []
        for i in range(50):
            s = _FakeSock()
            try:
                sent = inj.on_send(s, b"frame%d" % i)
                events.append("dup" if len(s.sent) == 2
                              else ("sent" if sent else "drop"))
            except FaultInjected:
                events.append("sever")
        return events

    a, b = run(7), run(7)
    assert a == b, "same seed must replay the same fault pattern"
    assert set(a) & {"drop", "dup"}, "plan at 30% must actually fire"
    assert run(8) != a, "different seed should diverge"


def test_fault_injector_env_armed(monkeypatch):
    from paddle_tpu.distributed import fault

    monkeypatch.setenv("PADDLE_TPU_FAULTS", "send.drop:1.0")
    fault.reset_injector()
    try:
        inj = fault.get_injector()
        s = _FakeSock()
        assert inj.on_send(s, b"x") is False and s.sent == []
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        fault.reset_injector()
        assert fault.get_injector() is None
    finally:
        fault.reset_injector()


# -- exactly-once under injected drop/dup ----------------------------------


def test_ps_training_bitwise_parity_under_drop_dup(monkeypatch):
    """5% drops + 5% dups on every RPC frame: 2-trainer sync training
    completes via retry + (cid, round, seq) dedup, and the final param
    matches the fault-free computation BIT-FOR-BIT — each grad summed
    exactly once, by token, not by luck."""
    from paddle_tpu.distributed import fault
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    rounds, dim = 4, 4
    # fault-free oracle: same float32 ops the server applies
    w_clean = np.zeros(dim, dtype=np.float32)
    for rnd in range(1, rounds + 1):
        scope = {"w": w_clean, "w@GRAD": _grad(0, rnd, dim)
                 + _grad(1, rnd, dim)}
        _sgd_block(scope)
        w_clean = scope["w"]

    monkeypatch.setenv("PADDLE_TPU_FAULTS", "send.drop:0.05,send.dup:0.05")
    monkeypatch.setenv("PADDLE_TPU_FAULT_SEED", "42")
    monkeypatch.setenv("PADDLE_PS_RPC_DEADLINE", "1.0")
    monkeypatch.setenv("PADDLE_PS_RPC_RETRIES", "12")
    monkeypatch.setenv("PADDLE_PS_RPC_BACKOFF_MS", "20")
    fault.reset_injector()
    scope = MiniScope()
    scope["w"] = np.zeros(dim, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope,
                      {"w@GRAD": _sgd_block}, fanin=2)
    server.start_background()
    errors = []

    def trainer(tid):
        try:
            c = PSClient(endpoint, trainer_id=tid)
            for rnd in range(1, rounds + 1):
                c.send_grad("w@GRAD", _grad(tid, rnd, dim))
                c.send_barrier()
                c.get_param("w")
                c.fetch_barrier()
            c.close()
        except Exception as e:  # pragma: no cover
            errors.append((tid, e))

    try:
        ts = [threading.Thread(target=trainer, args=(t,))
              for t in (0, 1)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in ts), \
            "training deadlocked under fault injection"
        assert not errors, errors
        np.testing.assert_array_equal(np.asarray(scope["w"]), w_clean)
    finally:
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        fault.reset_injector()
        server.stop()


# -- eviction + re-admission (in-process) ----------------------------------


def test_heartbeat_eviction_and_readmission():
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope, {}, fanin=2,
                      evict_after=0.6)
    server.start_background()
    ev0 = obs.counter("ps.evictions").value
    re0 = obs.counter("ps.readmissions").value
    try:
        c0 = PSClient(endpoint, trainer_id=0)
        c1 = PSClient(endpoint, trainer_id=1)
        c0.send_grad("w@GRAD", np.ones(4, "f4"))
        c1.send_grad("w@GRAD", np.ones(4, "f4"))
        c1.close()  # trainer 1 goes silent (simulated death)
        deadline = time.time() + 8
        resp = {}
        while time.time() < deadline:
            resp = c0.heartbeat_full()  # c0 keeps itself alive
            if 1 in resp.get("evicted", []):
                break
            time.sleep(0.15)
        assert 1 in resp.get("evicted", []), resp
        assert resp["effective_fanin"] == 1
        assert obs.counter("ps.evictions").value - ev0 == 1
        # the relaunched trainer TRAINING again is re-admitted
        c1b = PSClient(endpoint, trainer_id=1)
        c1b.send_grad("w@GRAD", np.ones(4, "f4"))
        resp = c0.heartbeat_full()
        assert 1 not in resp.get("evicted", [])
        assert resp["effective_fanin"] == 2
        assert obs.counter("ps.readmissions").value - re0 == 1
        c0.close()
        c1b.close()
    finally:
        server.stop()


def test_barrier_completes_via_eviction():
    """fanin=2 but only ONE live trainer: its barrier must complete in
    ~evict_after, not hang until the round timeout."""
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope,
                      {"w@GRAD": _sgd_block}, fanin=2, evict_after=0.8)
    server.start_background()
    try:
        # trainer 1 shows up once, then dies before its barrier
        c1 = PSClient(endpoint, trainer_id=1)
        c1.send_grad("w@GRAD", _grad(1, 1))
        c1.close()
        c0 = PSClient(endpoint, trainer_id=0)
        c0.start_heartbeat(0.2)  # keeps t0 fresh while blocked
        c0.send_grad("w@GRAD", _grad(0, 1))
        t0 = time.time()
        c0.send_barrier()  # blocks until t1 is evicted
        elapsed = time.time() - t0
        assert elapsed < 10, "eviction must beat the round timeout"
        w = c0.get_param("w")
        c0.fetch_barrier()
        # the dead trainer's grad was already in: both count
        exp = {"w": np.zeros(4, "f4"),
               "w@GRAD": _grad(0, 1) + _grad(1, 1)}
        _sgd_block(exp)
        np.testing.assert_array_equal(w, exp["w"])
        assert 1 in c0.evicted_peers or 1 in \
            c0.heartbeat_full().get("evicted", [])
        c0.close()
    finally:
        server.stop()


def test_healthy_straggler_not_evicted_auto_heartbeat():
    """A slow-but-alive trainer must NOT be evicted even when its step
    takes far longer than evict_after and the operator never set
    PADDLE_PS_HEARTBEAT_MS: the server advertises its eviction
    deadline in every response and the client auto-arms a background
    heartbeater off it — a partial round is never applied for a mere
    straggler."""
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    assert "PADDLE_PS_HEARTBEAT_MS" not in os.environ
    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope,
                      {"w@GRAD": _sgd_block}, fanin=2, evict_after=0.8)
    server.start_background()
    errors = []

    def trainer(tid, straggle):
        try:
            c = PSClient(endpoint, trainer_id=tid)
            c.send_grad("w@GRAD", np.ones(4, "f4"))  # auto-arms hb
            time.sleep(straggle)  # slow step: main socket silent
            c.send_barrier()
            c.get_param("w")
            c.fetch_barrier()
            c.close()
        except Exception as e:  # pragma: no cover
            errors.append((tid, e))

    try:
        ts = [threading.Thread(target=trainer, args=(0, 0.0)),
              threading.Thread(target=trainer, args=(1, 2.5))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in ts), "round hung"
        assert not errors, errors
        assert not server._evicted, \
            "healthy straggler evicted: %s" % server._evicted
        np.testing.assert_array_equal(
            np.asarray(scope["w"]), np.full(4, -0.2, "f4"))
    finally:
        server.stop()


def test_eviction_covers_never_connected_rank():
    """A rank that dies BEFORE its first rpc must still be evicted:
    the first live trainer's ping arms the staleness clock for every
    expected rank, so the survivor's barrier completes without the
    dead rank ever having been heard from."""
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope,
                      {"w@GRAD": _sgd_block}, fanin=2, evict_after=0.8)
    server.start_background()
    try:
        c0 = PSClient(endpoint, trainer_id=0)  # rank 1 never connects
        c0.start_heartbeat(0.2)
        c0.send_grad("w@GRAD", _grad(0, 1))
        t0 = time.time()
        c0.send_barrier()
        assert time.time() - t0 < 10
        assert 1 in c0.heartbeat_full().get("evicted", [])
        c0.get_param("w")
        c0.fetch_barrier()
        c0.close()
    finally:
        server.stop()


# -- multiprocess: SIGKILL + supervised relaunch ---------------------------


def _ft_env(**over):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PADDLE_PS_EVICT_AFTER"] = "2.0"
    env["PADDLE_PS_HEARTBEAT_MS"] = "200"
    env.update({k: str(v) for k, v in over.items()})
    return env


def test_sigkill_mid_round_survivors_finish(tmp_path):
    """Trainer 1 SIGKILLs itself mid-round (grad sent, barrier never
    sent). Trainer 0 must finish every round via heartbeat eviction —
    well under the round timeout — and the server must report exactly
    one eviction."""
    endpoint = "127.0.0.1:%d" % _free_port()
    ps = subprocess.Popen(
        [sys.executable, FT_WORKER],
        env=_ft_env(FT_ROLE="pserver", PSERVER_ENDPOINT=endpoint,
                    PADDLE_TRAINERS_NUM=2),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    procs = []
    try:
        for tid in (0, 1):
            over = dict(FT_ROLE="trainer", PSERVER_ENDPOINT=endpoint,
                        PADDLE_TRAINERS_NUM=2, PADDLE_TRAINER_ID=tid,
                        FT_ROUNDS=5, FT_OUT=str(tmp_path / "out"),
                        FT_CKPT_ROOT=str(tmp_path / "ckpt"))
            if tid == 1:
                over.update(FT_DIE_AT_ROUND=2, FT_DIE_RANK=1)
            procs.append(subprocess.Popen(
                [sys.executable, FT_WORKER], env=_ft_env(**over),
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        t0, t1 = procs
        out1 = t1.communicate(timeout=120)
        assert t1.returncode == -signal.SIGKILL, out1
        out0 = t0.communicate(timeout=120)
        assert t0.returncode == 0, out0[1][-3000:]
        result = json.loads((tmp_path / "out.t0.json").read_text())
        assert result["rounds_done"] == 5
        assert result["evictions"] == 1, result
        assert 1 in result["evicted_peers"], result
    finally:
        for p in procs + [ps]:
            if p.poll() is None:
                p.kill()
        ps.communicate(timeout=10)


def test_supervised_relaunch_resumes_from_checkpoint(tmp_path):
    """launch.py as supervisor: rank 1 SIGKILLs itself at round 3; the
    supervisor relaunches it, it resumes from its newest valid
    checkpoint (round 2) and finishes; the job exits 0."""
    endpoint = "127.0.0.1:%d" % _free_port()
    ps = subprocess.Popen(
        [sys.executable, FT_WORKER],
        env=_ft_env(FT_ROLE="pserver", PSERVER_ENDPOINT=endpoint,
                    PADDLE_TRAINERS_NUM=2),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        sup = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node=2", "--max_restarts=2",
             "--started_port=%d" % _free_port(), FT_WORKER],
            env=_ft_env(FT_ROLE="trainer", PSERVER_ENDPOINT=endpoint,
                        FT_ROUNDS=6, FT_DIE_AT_ROUND=3, FT_DIE_RANK=1,
                        FT_OUT=str(tmp_path / "out"),
                        FT_CKPT_ROOT=str(tmp_path / "ckpt")),
            capture_output=True, text=True, timeout=240, cwd=REPO)
        assert sup.returncode == 0, sup.stderr[-4000:]
        assert "relaunching" in sup.stderr
        r0 = json.loads((tmp_path / "out.t0.json").read_text())
        r1 = json.loads((tmp_path / "out.t1.json").read_text())
        assert r0["rounds_done"] == 6 and r0["restart"] == 0
        assert r1["restart"] == 1, r1
        assert r1["resumed_from"] == 2, r1
        assert r1["rounds_done"] == 4  # rounds 3..6 after resume
        # recovery takes one of two valid paths depending on machine
        # load: a slow relaunch means rank 0 was unblocked by EVICTION
        # and the relaunch was re-admitted; a fast relaunch rejoins
        # the round before the eviction deadline and no eviction is
        # needed. (The no-supervisor SIGKILL test above asserts the
        # eviction path deterministically.)
        assert r1["evictions"] >= r1["readmissions"] >= 0, r1
        # the relaunched rank's final checkpoint is complete + verified
        from paddle_tpu.checkpoint import CheckpointManager

        mgr = CheckpointManager(str(tmp_path / "ckpt" / "t1"))
        state = {}

        def _load(d):
            state["w"] = np.load(os.path.join(d, "state.npz"))["w"]

        assert mgr.load_latest(_load) == 6
        assert state["w"].shape == (4,)
    finally:
        if ps.poll() is None:
            ps.kill()
        ps.communicate(timeout=10)


# -- replication + failover (ISSUE 4) ---------------------------------------


def _fast_failover_env(monkeypatch):
    """Client knobs that make an in-process failover take ~1s instead
    of the boot-tolerant defaults (read at PSClient construction)."""
    monkeypatch.setenv("PADDLE_PS_CONNECT_TIMEOUT", "1")
    monkeypatch.setenv("PADDLE_PS_FAILOVER_CONNECT_TIMEOUT", "1")
    monkeypatch.setenv("PADDLE_PS_RPC_RETRIES", "2")
    monkeypatch.setenv("PADDLE_PS_RPC_BACKOFF_MS", "10")
    monkeypatch.setenv("PADDLE_PS_RPC_DEADLINE", "20")


def _mk_ps(eps, i, rejoin=False, fanin=2):
    from paddle_tpu.distributed.ps_rpc import PSServer

    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    server = PSServer(eps[i], MiniExec(), scope,
                      {"w@GRAD": _sgd_block}, fanin=fanin,
                      endpoints=eps, rejoin=rejoin)
    server.start_background()
    return server, scope


def _clean_w(rounds, dim=4):
    w = np.zeros(dim, dtype=np.float32)
    for rnd in range(1, rounds + 1):
        scope = {"w": w, "w@GRAD": _grad(0, rnd, dim)
                 + _grad(1, rnd, dim)}
        _sgd_block(scope)
        w = scope["w"]
    return w


def test_replicated_ps_failover_bitwise(monkeypatch):
    """Primary killed mid-round 3 (both grads in, round never applied
    or replicated): both trainers must fail over to the backup, replay
    their round logs exactly once (replicated dedup watermark), and
    finish with params matching the clean single-server run
    BIT-FOR-BIT. The backup must have been promoted by a genuinely
    failed-over client."""
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.ps_rpc import PSClient

    _fast_failover_env(monkeypatch)
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    s0, _sc0 = _mk_ps(eps, 0)
    s1, sc1 = _mk_ps(eps, 1)
    rounds, kill_at = 6, 3
    gate = threading.Barrier(3)
    errors, ws = [], {}
    fo0 = obs.counter_value("ps.failovers", cause="transport") or 0

    def trainer(tid):
        try:
            c = PSClient(",".join(eps), trainer_id=tid)
            w = None
            for rnd in range(1, rounds + 1):
                c.send_grad("w@GRAD", _grad(tid, rnd))
                if rnd == kill_at:
                    gate.wait(timeout=30)  # round-3 grads are in
                    gate.wait(timeout=30)  # main thread killed s0
                c.send_barrier()
                w = c.get_param("w")
                c.fetch_barrier()
            ws[tid] = w
            c.close()
        except Exception as e:  # pragma: no cover
            errors.append((tid, e))

    try:
        ts = [threading.Thread(target=trainer, args=(t,))
              for t in (0, 1)]
        for t in ts:
            t.start()
        gate.wait(timeout=30)
        s0.stop()  # sever mid-round: the round dies with the primary
        gate.wait(timeout=30)
        for t in ts:
            t.join(timeout=90)
        assert not any(t.is_alive() for t in ts), "failover deadlocked"
        assert not errors, errors
        expected = _clean_w(rounds)
        assert ws[0].tobytes() == expected.tobytes()
        assert ws[1].tobytes() == expected.tobytes()
        assert s1._promoted, "backup was never promoted"
        np.testing.assert_array_equal(np.asarray(sc1["w"]), expected)
        assert (obs.counter_value("ps.failovers", cause="transport")
                or 0) >= fo0 + 2
    finally:
        s0.stop()
        s1.stop()


def test_backup_redirects_fresh_clients_no_promotion(monkeypatch):
    """A FRESH client whose endpoint list starts at a backup must be
    redirected to the live primary WITHOUT promoting the backup — the
    split-brain guard (only a client that watched its endpoint die,
    fo >= 1, may promote)."""
    from paddle_tpu.distributed.ps_rpc import PSClient

    _fast_failover_env(monkeypatch)
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    s0, sc0 = _mk_ps(eps, 0, fanin=1)
    s1, _sc1 = _mk_ps(eps, 1, fanin=1)
    try:
        # list order reversed: the client walks INTO the backup first
        c = PSClient("%s,%s" % (eps[1], eps[0]), trainer_id=0)
        c.send_grad("w@GRAD", _grad(0, 1))
        c.send_barrier()
        w = c.get_param("w")
        c.fetch_barrier()
        assert c.endpoint == eps[0], "client not redirected to primary"
        assert not s1._promoted, "redirect must not promote the backup"
        exp = {"w": np.zeros(4, "f4"), "w@GRAD": _grad(0, 1)}
        _sgd_block(exp)
        np.testing.assert_array_equal(w, exp["w"])
        # and the round reached the primary, not the backup
        np.testing.assert_array_equal(np.asarray(sc0["w"]), exp["w"])
        c.close()
    finally:
        s0.stop()
        s1.stop()


def test_rejoined_server_catches_up_and_survives_second_kill(
        monkeypatch):
    """Full availability cycle: primary dies (failover #1), relaunched
    server rejoins as a backup via the manifest-verified snapshot
    catch-up, then the CURRENT primary dies and the rejoined server is
    promoted (failover #2, wrapping the endpoint list) — final params
    still bit-for-bit."""
    from paddle_tpu.distributed.ps_rpc import PSClient

    _fast_failover_env(monkeypatch)
    eps = ["127.0.0.1:%d" % _free_port(), "127.0.0.1:%d" % _free_port()]
    s0, _ = _mk_ps(eps, 0)
    s1, _ = _mk_ps(eps, 1)
    rounds = 8
    gate1, gate2 = threading.Barrier(3), threading.Barrier(3)
    errors, ws = [], {}

    def trainer(tid):
        try:
            c = PSClient(",".join(eps), trainer_id=tid)
            w = None
            for rnd in range(1, rounds + 1):
                if rnd == 3:
                    gate1.wait(timeout=60)  # s0 is killed
                if rnd == 6:
                    gate2.wait(timeout=60)  # s0 rejoined; s1 killed
                c.send_grad("w@GRAD", _grad(tid, rnd))
                c.send_barrier()
                w = c.get_param("w")
                c.fetch_barrier()
            ws[tid] = w
            c.close()
        except Exception as e:  # pragma: no cover
            errors.append((tid, e))

    s0b = None
    try:
        ts = [threading.Thread(target=trainer, args=(t,))
              for t in (0, 1)]
        for t in ts:
            t.start()
        gate1.wait(timeout=60)
        s0.stop()
        s0b, _ = _mk_ps(eps, 0, rejoin=True)
        deadline = time.time() + 30
        while not s0b._caught_up and time.time() < deadline:
            time.sleep(0.1)
        assert s0b._caught_up, "rejoined server never caught up"
        time.sleep(0.3)  # let at least one replicated round stream
        gate2.wait(timeout=60)
        s1.stop()
        for t in ts:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in ts), "deadlocked"
        assert not errors, errors
        expected = _clean_w(rounds)
        assert ws[0].tobytes() == expected.tobytes()
        assert ws[1].tobytes() == expected.tobytes()
        assert s0b._promoted, "rejoined server never promoted"
    finally:
        s0.stop()
        s1.stop()
        if s0b is not None:
            s0b.stop()


def test_scope_snapshot_roundtrip_and_corruption(tmp_path):
    """The rejoin catch-up primitive: snapshot_scope_to_dir with the
    names map restores exact var names and bytes; a flipped byte is a
    typed CheckpointCorrupt, never garbage params."""
    from paddle_tpu.checkpoint import (CheckpointCorrupt,
                                       load_scope_snapshot)
    from paddle_tpu.distributed.ps_rpc import snapshot_scope_to_dir

    exe = MiniExec()
    scope = MiniScope()
    scope["w"] = np.arange(4, dtype=np.float32)
    scope["emb/table"] = np.ones((3, 2), dtype=np.float32)
    d = str(tmp_path / "snap")
    snapshot_scope_to_dir(exe, scope, d, names_map=True)

    restored = MiniScope()
    assert load_scope_snapshot(exe, restored, d) == 2
    assert set(restored) == {"w", "emb/table"}  # exact names, un-munged
    np.testing.assert_array_equal(restored["w"], scope["w"])
    np.testing.assert_array_equal(restored["emb/table"],
                                  scope["emb/table"])

    with open(os.path.join(d, "w"), "r+b") as f:
        f.seek(8)
        b = f.read(1)
        f.seek(8)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CheckpointCorrupt, match="sha256"):
        load_scope_snapshot(exe, MiniScope(), d)


# -- chaos drill determinism -------------------------------------------------


def test_random_plan_seeded_and_parses():
    import random as _random

    from paddle_tpu.distributed.fault import parse_plan, random_plan

    plans = {random_plan(_random.Random(5)) for _ in range(3)}
    assert len(plans) == 1, "same rng seed must yield one plan"
    plan = plans.pop()
    assert parse_plan(plan), plan
    assert random_plan(_random.Random(6)) != plan


def test_chaos_schedule_deterministic():
    """Same PADDLE_TPU_FAULT_SEED -> identical fault schedule (the CI
    acceptance knob: a failing drill replays from its printed seed)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import chaos_drill

    a = chaos_drill.make_schedule(4242, sync_rounds=6)
    b = chaos_drill.make_schedule(4242, sync_rounds=6)
    assert a == b
    assert chaos_drill.make_schedule(4243, sync_rounds=6) != a
    from paddle_tpu.distributed.fault import parse_plan

    assert parse_plan(a["plan"])
    assert 1 <= a["trainer_kill_round"] <= 5
    assert 1 <= a["server_kill_round"] <= 5
    assert a["trainer_kill_rank"] in (0, 1)


def test_chaos_inprocess_same_seed_same_params(monkeypatch):
    """Fast tier-1 chaos variant (in-process servers): seeded frame
    faults + a primary kill mid-run, twice with the same seed — both
    runs must land on the SAME final params, equal to the clean run
    (the bit-for-bit dedup invariant, which is exactly what makes the
    schedule reproducible end to end)."""
    from paddle_tpu.distributed import fault
    from paddle_tpu.distributed.ps_rpc import PSClient

    _fast_failover_env(monkeypatch)
    monkeypatch.setenv("PADDLE_PS_RPC_DEADLINE", "1.0")
    monkeypatch.setenv("PADDLE_PS_RPC_RETRIES", "12")
    monkeypatch.setenv("PADDLE_TPU_FAULTS",
                       "send.drop:0.04,send.dup:0.04")
    monkeypatch.setenv("PADDLE_TPU_FAULT_SEED", "99")
    rounds, kill_at = 5, 2

    def one_run():
        fault.reset_injector()
        eps = ["127.0.0.1:%d" % _free_port(),
               "127.0.0.1:%d" % _free_port()]
        s0, _ = _mk_ps(eps, 0)
        s1, _ = _mk_ps(eps, 1)
        gate = threading.Barrier(3)
        errors, ws = [], {}

        def trainer(tid):
            try:
                c = PSClient(",".join(eps), trainer_id=tid)
                w = None
                for rnd in range(1, rounds + 1):
                    c.send_grad("w@GRAD", _grad(tid, rnd))
                    if rnd == kill_at:
                        gate.wait(timeout=60)
                        gate.wait(timeout=60)
                    c.send_barrier()
                    w = c.get_param("w")
                    c.fetch_barrier()
                ws[tid] = w
                c.close()
            except Exception as e:  # pragma: no cover
                errors.append((tid, e))

        try:
            ts = [threading.Thread(target=trainer, args=(t,))
                  for t in (0, 1)]
            for t in ts:
                t.start()
            gate.wait(timeout=60)
            s0.stop()
            gate.wait(timeout=60)
            for t in ts:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in ts), "deadlocked"
            assert not errors, errors
            return ws[0].tobytes(), ws[1].tobytes()
        finally:
            s0.stop()
            s1.stop()

    try:
        first = one_run()
        second = one_run()
    finally:
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        fault.reset_injector()
    expected = _clean_w(rounds).tobytes()
    assert first == (expected, expected)
    assert second == first


# -- per-method rpc counter labels -------------------------------------------


def test_rpc_counters_labeled_by_method(monkeypatch):
    """rpc.timeouts / rpc.retries carry a method= label so a mis-set
    per-attempt deadline shows up against the call shape that trips
    it (ROADMAP retry-tuning item)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed import fault
    from paddle_tpu.distributed.ps_rpc import PSClient, PSServer

    scope = MiniScope()
    scope["w"] = np.zeros(4, dtype=np.float32)
    endpoint = "127.0.0.1:%d" % _free_port()
    server = PSServer(endpoint, MiniExec(), scope, {}, fanin=1)
    server.start_background()
    t0 = obs.counter_value("rpc.timeouts", method="get_param") or 0
    r0 = obs.counter_value("rpc.retries", method="get_param") or 0
    try:
        c = PSClient(endpoint, trainer_id=0, rpc_deadline=0.3,
                     max_retries=1)
        monkeypatch.setenv("PADDLE_TPU_FAULTS", "send.drop:1.0")
        monkeypatch.setenv("PADDLE_TPU_FAULT_SEED", "1")
        fault.reset_injector()
        with pytest.raises(RuntimeError):
            c.get_param("w")
        monkeypatch.delenv("PADDLE_TPU_FAULTS")
        fault.reset_injector()
        assert (obs.counter_value("rpc.timeouts", method="get_param")
                - t0) >= 1
        assert (obs.counter_value("rpc.retries", method="get_param")
                - r0) >= 1
        # the unlabeled aggregate is NOT silently double-counted
        c.close()
    finally:
        fault.reset_injector()
        server.stop()


# -- serving: typed batch errors ---------------------------------------------


def test_serving_batch_error_typed_and_engine_stays_healthy():
    """A predictor exception inside a batch dispatch fails exactly that
    batch's futures with the typed BatchExecutionError (HTTP 500),
    increments serving.batch_errors once per failed batch, and leaves
    the engine serving the next request."""
    import urllib.request

    from paddle_tpu import observability as obs
    from paddle_tpu.serving.engine import (BatchExecutionError,
                                           ServingConfig, ServingEngine)
    from paddle_tpu.serving.http import start_http_server

    class FlakyPredictor:
        def get_input_names(self):
            return ["x"]

        def run(self, feed):
            x = np.asarray(feed["x"])
            if float(x.max()) > 100.0:
                raise RuntimeError("NaN in layer 3")

            class T:
                name = "y"
                data = x * 2.0

            return [T()]

    be0 = obs.counter_value("serving.batch_errors") or 0
    eng = ServingEngine(
        FlakyPredictor(),
        ServingConfig(max_batch_size=2, num_workers=1, warmup=False),
        sample_feed={"x": np.zeros((1, 3), "f4")}).start()
    server, _thread = start_http_server(eng)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    try:
        f = eng.submit({"x": np.full((1, 3), 999.0, "f4")})
        with pytest.raises(BatchExecutionError, match="NaN in layer 3"):
            f.result(10)
        assert (obs.counter_value("serving.batch_errors") - be0) == 1
        # the engine survived: next request dispatches normally
        assert eng.health() == "serving"
        out = eng.predict({"x": np.ones((1, 3), "f4")}, timeout=10)
        np.testing.assert_array_equal(out["y"], np.full((1, 3), 2.0))
        # and over HTTP the model failure is a 500 with the typed name
        req = urllib.request.Request(
            base + "/predict",
            data=json.dumps({"inputs": {"x": [[999.0, 0.0, 0.0]]}}
                            ).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 500
        body = json.loads(ei.value.read())
        assert body["type"] == "BatchExecutionError"
        assert (obs.counter_value("serving.batch_errors") - be0) == 2
    finally:
        eng.stop()
        server.shutdown()
        server.server_close()


# -- atomic checkpoints -----------------------------------------------------


def test_checkpoint_rotation_latest_and_corrupt_fallback(tmp_path):
    from paddle_tpu.checkpoint import (CheckpointCorrupt,
                                       CheckpointManager)

    root = str(tmp_path / "ckpts")
    mgr = CheckpointManager(root, keep=3)

    def writer_for(step):
        def w(d):
            np.savez(os.path.join(d, "state.npz"),
                     w=np.full(4, step, "f4"))
        return w

    for step in range(1, 6):
        mgr.save(step, writer_for(step))
    assert mgr.steps() == [3, 4, 5], "keep-last-3 rotation"
    assert mgr.latest_step() == 5
    assert (tmp_path / "ckpts" / "latest").read_text() == "ckpt-5"

    loaded = {}

    def loader(d):
        loaded["w"] = np.load(os.path.join(d, "state.npz"))["w"]

    assert mgr.load_latest(loader) == 5
    # corrupt the newest shard: load falls back to the previous one
    shard = tmp_path / "ckpts" / "ckpt-5" / "state.npz"
    shard.write_bytes(b"garbage" + shard.read_bytes()[7:])
    assert mgr.load_latest(loader) == 4
    assert loaded["w"][0] == 4.0
    # corrupt everything: typed failure, not garbage params
    for step in (3, 4):
        p = tmp_path / "ckpts" / ("ckpt-%d" % step) / "state.npz"
        p.write_bytes(b"garbage" + p.read_bytes()[7:])
    with pytest.raises(CheckpointCorrupt, match="sha256"):
        mgr.load_latest(loader)


def test_checkpoint_crash_before_rename_invisible(tmp_path):
    """A writer that dies before the rename (simulated by raising)
    leaves NO visible checkpoint — and a handmade leftover tmp dir is
    ignored by the rotation scan."""
    from paddle_tpu.checkpoint import (CheckpointManager,
                                       atomic_checkpoint_dir)

    root = str(tmp_path / "ckpts")
    mgr = CheckpointManager(root)
    with pytest.raises(RuntimeError, match="died mid-save"):
        with atomic_checkpoint_dir(mgr.dir_for(7)) as tmp:
            np.savez(os.path.join(tmp, "state.npz"), w=np.ones(4))
            raise RuntimeError("died mid-save")
    assert mgr.steps() == [] and mgr.latest_step() is None
    # a stranded tmp dir from a SIGKILLed save is equally invisible
    leftover = os.path.join(root, "ckpt-9.tmp-123-456")
    os.makedirs(leftover)
    with open(os.path.join(leftover, "state.npz"), "wb") as f:
        f.write(b"partial")
    assert mgr.steps() == []
    assert mgr.load_latest(lambda d: None) is None


def test_checkpoint_manifest_detects_missing_and_resized(tmp_path):
    from paddle_tpu.checkpoint import (CheckpointCorrupt,
                                       atomic_checkpoint_dir,
                                       verify_manifest)

    final = str(tmp_path / "snap")
    with atomic_checkpoint_dir(final) as tmp:
        with open(os.path.join(tmp, "a.bin"), "wb") as f:
            f.write(b"aaaa")
        with open(os.path.join(tmp, "b.bin"), "wb") as f:
            f.write(b"bbbb")
    verify_manifest(final)  # intact
    os.remove(os.path.join(final, "b.bin"))
    with pytest.raises(CheckpointCorrupt, match="missing file"):
        verify_manifest(final)
    with open(os.path.join(final, "b.bin"), "wb") as f:
        f.write(b"bbbbbb")
    with pytest.raises(CheckpointCorrupt, match="bytes"):
        verify_manifest(final)


def test_io_save_persistables_manifest_roundtrip(tmp_path):
    """Static-graph persistables: atomic save writes a manifest;
    load verifies it; a flipped byte raises CheckpointCorrupt."""
    import paddle_tpu as fluid
    from paddle_tpu.checkpoint import MANIFEST_NAME
    from paddle_tpu.io import CheckpointCorrupt

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[2, 3], dtype="float32")
        fluid.layers.fc(x, 4, param_attr=fluid.ParamAttr(name="wfc"))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    d = str(tmp_path / "model")
    fluid.io.save_persistables(exe, d, main)
    assert os.path.exists(os.path.join(d, MANIFEST_NAME))
    fluid.io.load_persistables(exe, d, main)  # verifies + loads
    p = os.path.join(d, "__params__.npz")
    with open(p, "r+b") as f:
        f.seek(30)
        b = f.read(1)
        f.seek(30)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(CheckpointCorrupt, match="sha256"):
        fluid.io.load_persistables(exe, d, main)


def test_io_load_missing_names_file_and_dir(tmp_path):
    import paddle_tpu as fluid

    empty = tmp_path / "empty"
    empty.mkdir()
    exe = fluid.Executor(fluid.CPUPlace())
    with pytest.raises(FileNotFoundError) as ei:
        fluid.io.load_persistables(exe, str(empty))
    assert "__params__.npz" in str(ei.value)
    assert str(empty) in str(ei.value)
    with pytest.raises(FileNotFoundError, match="does not exist"):
        fluid.io.load_inference_model(str(tmp_path / "nope"), exe)
    with pytest.raises(FileNotFoundError, match="__model__"):
        fluid.io.load_inference_model(str(empty), exe)


# -- PS server socket hygiene ----------------------------------------------


def test_server_stop_releases_port_mid_frame():
    """stop() must close the listening socket and sever live
    connections even while a client is mid-frame, so the port is
    immediately rebindable (no leaks between test runs)."""
    from paddle_tpu.distributed.ps_rpc import PSServer

    port = _free_port()
    endpoint = "127.0.0.1:%d" % port
    server = PSServer(endpoint, MiniExec(), MiniScope(), {}, fanin=1)
    server.start_background()
    conn = socket.create_connection(("127.0.0.1", port), timeout=5)
    conn.sendall(b"\x20\x00\x00")  # partial frame header: the conn
    # thread is now blocked mid-_recv_exact
    time.sleep(0.2)
    server.stop()
    for t in server._threads:
        assert not t.is_alive(), "server thread leaked past stop()"
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", port))  # would raise EADDRINUSE on a leak
    s.close()
    conn.close()


# -- serving drain signal ---------------------------------------------------


class _SlowPredictor:
    def __init__(self, delay=1.0):
        self.delay = delay

    def get_input_names(self):
        return ["x"]

    def run(self, feed):
        time.sleep(self.delay)

        class T:
            name = "y"
            data = np.asarray(feed["x"])

        return [T()]


def test_serving_healthz_draining_during_stop():
    from paddle_tpu.serving.engine import ServingConfig, ServingEngine
    from paddle_tpu.serving.http import start_http_server
    import urllib.request

    eng = ServingEngine(_SlowPredictor(delay=1.0),
                        ServingConfig(max_batch_size=2, num_workers=1,
                                      warmup=False),
                        sample_feed={"x": np.zeros((1, 2), "f4")})
    eng.start()
    server, thread = start_http_server(eng)
    base = "http://127.0.0.1:%d" % server.server_address[1]
    try:
        assert eng.health() == "serving"
        fut = eng.submit({"x": np.zeros((1, 2), "f4")})
        stopper = threading.Thread(target=eng.stop)
        stopper.start()
        statuses = set()
        deadline = time.time() + 10
        while stopper.is_alive() and time.time() < deadline:
            statuses.add(eng.health())
            try:
                urllib.request.urlopen(base + "/healthz", timeout=5)
                statuses.add("http-200")
            except urllib.error.HTTPError as e:
                statuses.add(json.loads(e.read())["status"])
            time.sleep(0.05)
        stopper.join(timeout=30)
        assert "draining" in statuses, statuses
        assert eng.health() == "stopped"
        fut.result(timeout=5)  # the in-flight request still finished
    finally:
        server.shutdown()
        server.server_close()
