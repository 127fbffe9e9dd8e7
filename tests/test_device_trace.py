"""Device-truth profiling (ISSUE 10): XPlane parsing, phase folding,
host-vs-device cross-check, and the profile-guided bucket planner's
report plumbing.

The parser/folding tests run on CANNED trace fixtures built with the
module's own encoder — no device, no jax.profiler, so they hold in
tier-1 anywhere. The one real end-to-end capture test is slow-marked
(full CI runs it): it proves the jax.profiler -> xplane.pb -> fold
pipeline against a live program.

Contracts under test:
- wire roundtrip: encode_xspace -> parse_xspace preserves planes /
  lines / events / stats / HLO op_name maps;
- phase folding: device op intervals land in their named_scope phase,
  per-phase time is the interval UNION (concurrent thunks counted
  once), collective-vs-compute overlap matches analyze_timeline;
- unknown-scope tolerance: an op resolving to no known phase is
  accounted (unattributed_ms), never dropped silently, never fatal;
- empty-trace fallback: no phase-attributed events => fold returns
  None and callers keep host numbers;
- cross_check: min/max per-phase agreement, duration-weighted overall.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import device_trace as dtr

MS = 1_000_000_000   # ps per ms


def _plane(events, name="/host:CPU", hlo=None, ts_ns=0):
    return {"name": name, "hlo_op_names": hlo or {},
            "lines": [{"name": "thread0", "timestamp_ns": ts_ns,
                       "events": events}]}


def _ev(name, ts_ms, dur_ms, stats=None):
    return {"name": name, "ts_ps": int(ts_ms * MS),
            "dur_ps": int(dur_ms * MS), "stats": stats or {}}


# -- wire roundtrip ---------------------------------------------------------


def test_encode_parse_roundtrip():
    space = {"planes": [{
        "name": "/host:CPU",
        "hlo_op_names": {"fusion.1": "jit(f)/jit(main)/forward/mul/dot",
                         "reduce.9": "jit(f)/jit(main)/backward/sum"},
        "lines": [{"name": "t0", "timestamp_ns": 1000, "events": [
            {"name": "fusion.1", "ts_ps": 1_500_000, "dur_ps": 250,
             "stats": {"hlo_op": "fusion.1"}},
            {"name": "reduce.9", "ts_ps": 2_000_000, "dur_ps": 40,
             "stats": {}},
        ]}],
    }]}
    got = dtr.parse_xspace(dtr.encode_xspace(space))
    assert len(got["planes"]) == 1
    pl = got["planes"][0]
    assert pl["name"] == "/host:CPU"
    assert pl["hlo_op_names"] == space["planes"][0]["hlo_op_names"]
    (line,) = pl["lines"]
    assert line["timestamp_ns"] == 1000
    evs = line["events"]
    assert [e["name"] for e in evs] == ["fusion.1", "reduce.9"]
    assert evs[0]["ts_ps"] == 1_500_000
    assert evs[0]["dur_ps"] == 250
    assert evs[0]["stats"] == {"hlo_op": "fusion.1"}


def test_parse_rejects_garbage_tolerates_unknown_fields():
    with pytest.raises((ValueError, IndexError)):
        dtr.parse_xspace(b"\x99\x99not a proto")
    # unknown fields inside a plane are skipped, known ones survive
    plane = dtr._enc_len(2, b"p") + dtr._enc_int(9, 7) \
        + dtr._enc_len(15, b"future-field")
    space = dtr._enc_len(1, plane)
    got = dtr.parse_xspace(space)
    assert got["planes"][0]["name"] == "p"


# -- phase resolution -------------------------------------------------------


def test_phase_of_op_name():
    assert dtr.phase_of_op_name(
        "jit(step)/jit(main)/backward/mul_grad/dot_general") == "backward"
    assert dtr.phase_of_op_name(
        "jit(s)/jit(main)/jit(shmap_body)/collective/c_bucket_allreduce"
        "/psum") == "collective"
    assert dtr.phase_of_op_name("forward/mul") == "forward"
    assert dtr.phase_of_op_name("jit(f)/jit(main)/reduce_sum") is None
    assert dtr.phase_of_op_name("") is None
    assert dtr.phase_of_op_name(None) is None


# -- folding on canned fixtures ---------------------------------------------


def test_fold_phases_from_hlo_map_and_direct_names():
    hlo = {"fusion.1": "jit(f)/jit(main)/forward/mul/dot",
           "fusion.2": "jit(f)/jit(main)/backward/mul_grad/dot",
           "ar.1": "jit(f)/jit(main)/collective/c_bucket_allreduce/psum"}
    space = {"planes": [_plane([
        _ev("fusion.1", 0.0, 2.0),            # forward, via name->hlo
        _ev("thunk", 2.0, 3.0,                # backward, via hlo_op stat
            stats={"hlo_op": "fusion.2"}),
        _ev("ar.1", 3.0, 2.0),                # collective, overlaps bwd
        _ev("optimizer/sgd", 5.0, 1.0),       # direct phase-named event
    ], hlo=hlo)]}
    rep = dtr.fold_device_phases(space)
    assert rep is not None
    assert rep["n_attributed"] == 4
    pm = rep["device_phase_ms"]
    assert pm["forward"] == pytest.approx(2.0)
    assert pm["backward"] == pytest.approx(3.0)
    assert pm["collective"] == pytest.approx(2.0)
    assert pm["optimizer"] == pytest.approx(1.0)
    # collective [3,5] vs compute union [0,5]+[5,6]: fully overlapped
    assert rep["overlap_frac"] == pytest.approx(1.0)
    assert rep["exposed_collective_ms"] == pytest.approx(0.0)
    assert rep["critical_path_ms"] == pytest.approx(6.0)


def test_fold_union_not_sum_across_lines():
    # the same 2ms window busy on TWO lines (concurrent thunks) must
    # count once in the phase's device time
    hlo = {"f.1": "jit(f)/forward/mul"}
    space = {"planes": [{
        "name": "/host:CPU", "hlo_op_names": hlo,
        "lines": [
            {"name": "t0", "timestamp_ns": 0,
             "events": [_ev("f.1", 0.0, 2.0)]},
            {"name": "t1", "timestamp_ns": 0,
             "events": [_ev("f.1", 1.0, 2.0)]},
        ]}]}
    rep = dtr.fold_device_phases(space)
    assert rep["device_phase_ms"]["forward"] == pytest.approx(3.0)


def test_fold_unknown_scope_tolerated_and_accounted():
    hlo = {"f.1": "jit(f)/forward/mul",
           "mystery.1": "jit(f)/jit(main)/some_new_scope/op"}
    space = {"planes": [_plane([
        _ev("f.1", 0.0, 1.0),
        _ev("mystery.1", 1.0, 5.0),       # known op, unknown scope
        _ev("ThunkExecutor::Execute", 0.0, 9.0),   # host machinery
    ], hlo=hlo)]}
    rep = dtr.fold_device_phases(space)
    assert rep["n_attributed"] == 1
    assert rep["device_phase_ms"] == {"forward": pytest.approx(1.0)}
    # the unknown-scope op is accounted; the unresolvable host event
    # is ignored (it is not a device op)
    assert rep["unattributed_ms"] == pytest.approx(5.0)


def test_fold_empty_trace_falls_back_to_none():
    assert dtr.fold_device_phases({"planes": []}) is None
    # events exist but none resolve to a phase -> still None
    space = {"planes": [_plane([_ev("PjitFunction(f)", 0.0, 1.0)])]}
    assert dtr.fold_device_phases(space) is None


def test_fold_divides_by_steps():
    hlo = {"f.1": "jit(f)/forward/mul"}
    space = {"planes": [_plane(
        [_ev("f.1", 0.0, 2.0), _ev("f.1", 10.0, 2.0)], hlo=hlo)]}
    rep = dtr.fold_device_phases(space, steps=2)
    assert rep["device_phase_ms"]["forward"] == pytest.approx(2.0)
    assert rep["steps"] == 2


def test_fixture_file_roundtrip_via_trace_dir(tmp_path):
    # the on-disk layout jax.profiler writes: the fold must find the
    # newest run dir's xplane.pb
    run = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    hlo = {"f.1": "jit(f)/forward/mul"}
    space = {"planes": [_plane([_ev("f.1", 0.0, 4.0)], hlo=hlo)]}
    (run / "host.xplane.pb").write_bytes(dtr.encode_xspace(space))
    (run / "garbage.xplane.pb").write_bytes(b"\xff\xff torn capture")
    loaded = dtr.load_trace_dir(str(tmp_path))
    rep = dtr.fold_device_phases(loaded)
    assert rep["device_phase_ms"]["forward"] == pytest.approx(4.0)


# -- cross-check ------------------------------------------------------------


def test_cross_check_agreement_math():
    cc = dtr.cross_check({"forward": 2.0, "backward": 4.0},
                         {"forward": 2.0, "backward": 4.0})
    assert cc["agreement"] == pytest.approx(1.0)
    assert all(v["agreement"] == pytest.approx(1.0)
               for v in cc["per_phase"].values())
    # device half of host on one phase: ratio 0.5, weighted by the
    # larger side (4ms) against the perfectly-agreeing 2ms phase
    cc = dtr.cross_check({"forward": 2.0, "backward": 4.0},
                         {"forward": 2.0, "backward": 2.0})
    assert cc["per_phase"]["backward"]["agreement"] == pytest.approx(0.5)
    assert cc["agreement"] == pytest.approx((1.0 * 2 + 0.5 * 4) / 6)
    # a phase missing on one side scores 0 for that phase
    cc = dtr.cross_check({"optimizer": 3.0}, {})
    assert cc["per_phase"]["optimizer"]["agreement"] == 0.0
    assert cc["agreement"] == pytest.approx(0.0)
    assert dtr.cross_check({}, {})["agreement"] is None


def test_capture_enabled_env(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_DEVICE_TRACE", raising=False)
    assert not dtr.capture_enabled()
    assert dtr.capture_enabled(default=True)
    monkeypatch.setenv("PADDLE_TPU_DEVICE_TRACE", "1")
    assert dtr.capture_enabled()
    monkeypatch.setenv("PADDLE_TPU_DEVICE_TRACE", "0")
    assert not dtr.capture_enabled(default=True)


# -- end-to-end capture (real jax.profiler) ---------------------------------


def _small_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="dx", shape=[16, 8], dtype="float32")
        lbl = fluid.data(name="dlbl", shape=[16, 1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, lbl))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    return main, startup, loss


@pytest.mark.slow
def test_device_profile_step_end_to_end(tmp_path):
    main, startup, loss = _small_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"dx": rng.rand(16, 8).astype("float32"),
                "dlbl": rng.randint(0, 10, (16, 1)).astype("int64")}
        exe.run(main, feed=feed, fetch_list=[loss])
        dev = dtr.device_profile_step(main, scope, feed, steps=2,
                                      trace_dir=str(tmp_path))
    assert dev is not None, "real capture folded to empty"
    assert dev["n_attributed"] > 0
    assert set(dev["device_phase_ms"]) <= set(dtr.PHASES)
    assert all(ms >= 0 for ms in dev["device_phase_ms"].values())
    assert dev["critical_path_ms"] > 0
    # the raw capture really is on disk where TensorBoard would read it
    assert dtr.find_xplane_files(str(tmp_path))


@pytest.mark.slow
def test_bench_profile_record_carries_device_block(monkeypatch,
                                                   tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    main, startup, loss = _small_program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        rng = np.random.RandomState(0)
        feed = {"dx": rng.rand(16, 8).astype("float32"),
                "dlbl": rng.randint(0, 10, (16, 1)).astype("int64")}
        exe.run(main, feed=feed, fetch_list=[loss])
        monkeypatch.setenv("PADDLE_TPU_PROFILE_BENCH", "1")
        monkeypatch.setenv("PADDLE_TPU_DEVICE_TRACE", "1")
        rec = bench._profile_record(0.01, 1e9, program=main,
                                    scope=scope, feed=feed)
    assert "phase_ms" in rec, rec.get("phase_error")
    assert "device_trace_error" not in rec, rec["device_trace_error"]
    # both breakdowns + the agreement ratio ride one record
    assert rec.get("device_phase_ms")
    assert rec.get("host_device_agreement") is not None
    assert rec.get("agreement_per_phase")
    assert json.dumps(rec)   # the whole block is json-serializable
