"""Unified runtime observability (paddle_tpu/observability): registry
semantics, span tracing, exporters, and the counters threaded through
every execution path — static executor (compiled + interpreter), lazy
dygraph engine, mesh data-parallel engine — plus the profiler
compatibility shim and the default-off no-op contract.

Reference contract being generalized: platform/profiler.cc RecordEvent
+ device_tracer + tools/timeline.py chrome-trace export."""
import json
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability.registry import MetricsRegistry


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts from an armed, empty registry and leaves the
    layer disabled (other test files assume default-off)."""
    obs.reset()
    obs.enable()
    yield
    obs.reset()
    obs.disable()


# -- registry semantics ----------------------------------------------------

def test_counter_inc_and_labels():
    r = MetricsRegistry()
    c = r.counter("steps", path="compiled")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) -> same metric; different labels -> distinct
    assert r.counter("steps", path="compiled") is c
    assert r.counter("steps", path="interp").value == 0
    assert r.counter_value("steps", path="compiled") == 5
    assert r.counter_value("never_touched") == 0
    with pytest.raises(ValueError):
        c.inc(-1)


def test_kind_mismatch_raises():
    r = MetricsRegistry()
    r.counter("m")
    with pytest.raises(TypeError):
        r.gauge("m")


def test_gauge_set_inc_dec():
    r = MetricsRegistry()
    g = r.gauge("live_bytes")
    g.set(100)
    g.inc(50)
    g.dec(25)
    assert g.value == 125


def test_histogram_stats_and_reservoir_bound():
    r = MetricsRegistry()
    h = r.histogram("lat_ms")
    for v in range(1, 101):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 100 and s["sum"] == 5050.0
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert abs(s["mean"] - 50.5) < 1e-9
    assert 30 <= s["p50"] <= 70    # reservoir estimate
    # bounded memory no matter how many observations
    for v in range(10000):
        h.observe(v)
    assert len(h._reservoir) <= h.RESERVOIR


def test_registry_thread_safety_smoke():
    r = MetricsRegistry()
    c = r.counter("hits")

    def worker():
        for _ in range(1000):
            c.inc()
            r.histogram("h").observe(1.0)
            r.counter("per_thread", t=threading.get_ident()).inc()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert r.histogram("h").count == 8000


def test_snapshot_and_prometheus_format():
    r = MetricsRegistry()
    r.counter("steps", path="compiled").inc(3)
    r.gauge("bubble").set(0.25)
    r.histogram("lat_ms").observe(2.0)
    snap = r.snapshot()
    assert snap["counters"]["steps{path=compiled}"] == 3
    assert snap["gauges"]["bubble"] == 0.25
    assert snap["histograms"]["lat_ms"]["count"] == 1
    text = r.to_prometheus()
    assert "# TYPE paddle_tpu_steps counter" in text
    assert 'paddle_tpu_steps{path="compiled"} 3' in text
    assert "# TYPE paddle_tpu_lat_ms summary" in text
    assert "paddle_tpu_lat_ms_count 1" in text
    assert "paddle_tpu_bubble 0.25" in text


def test_prometheus_label_value_escaping():
    """Exposition format 0.0.4: backslash, double quote, and newline in
    label VALUES must be escaped — an unescaped newline would split the
    sample line and corrupt the whole scrape."""
    r = MetricsRegistry()
    r.counter("evil", path='say "hi"\\there\nbye').inc()
    text = r.to_prometheus()
    line = [ln for ln in text.splitlines()
            if ln.startswith("paddle_tpu_evil")][0]
    assert line == ('paddle_tpu_evil{path="say \\"hi\\"\\\\there'
                    '\\nbye"} 1')
    # one sample line, not two: the newline never reached the wire raw
    assert sum(ln.startswith("paddle_tpu_evil")
               for ln in text.splitlines()) == 1


def test_prometheus_summary_series_shape():
    """A histogram exports as a summary: one quantile series per
    (labels, quantile) plus _sum and _count — the shape Prometheus
    clients parse, including labeled families like
    rpc.latency_ms{method=}."""
    r = MetricsRegistry()
    for v in range(1, 11):
        r.histogram("rpc.latency_ms", method="send_grad").observe(v)
    r.histogram("rpc.latency_ms", method="get_param").observe(7.0)
    text = r.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE paddle_tpu_rpc_latency_ms summary" in lines
    for q in ("0.5", "0.9", "0.99"):
        assert any(ln.startswith(
            'paddle_tpu_rpc_latency_ms{method="send_grad",'
            'quantile="%s"}' % q) for ln in lines), q
    assert 'paddle_tpu_rpc_latency_ms_sum{method="send_grad"} 55.0' \
        in lines
    assert 'paddle_tpu_rpc_latency_ms_count{method="send_grad"} 10' \
        in lines
    assert 'paddle_tpu_rpc_latency_ms_count{method="get_param"} 1' \
        in lines
    # exactly one TYPE header for the family, not one per label set
    assert sum("TYPE paddle_tpu_rpc_latency_ms" in ln
               for ln in lines) == 1


# -- span tracing ----------------------------------------------------------

def test_span_nesting_records_contained_intervals():
    with obs.span("outer", cat="step"):
        time.sleep(0.002)
        with obs.span("inner"):
            time.sleep(0.001)
    evs = {e[0]: e for e in obs.tracing.trace_events()}
    assert "outer" in evs and "inner" in evs
    (_, o_ts, o_dur, o_tid, o_cat, _) = evs["outer"]
    (_, i_ts, i_dur, i_tid, _, _) = evs["inner"]
    assert o_cat == "step"
    assert o_tid == i_tid == threading.get_ident()
    # containment: inner starts after outer and ends before it
    assert i_ts >= o_ts
    assert i_ts + i_dur <= o_ts + o_dur + 1.0  # 1us slack
    assert o_dur >= i_dur


def test_span_disabled_is_noop_singleton():
    obs.disable()
    s1 = obs.tracing.span("a")
    s2 = obs.tracing.span("b", cat="step", foo=1)
    assert s1 is s2            # shared null object: no allocation
    with s1:
        pass
    assert obs.tracing.trace_events() == []


def test_chrome_trace_export_roundtrip(tmp_path):
    with obs.span("step_one", cat="step", idx=7):
        pass
    path = str(tmp_path / "trace.json")
    obs.write_chrome_trace(path)
    with open(path) as f:
        doc = json.load(f)   # valid JSON == loads in Perfetto/chrome
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    ev = [e for e in doc["traceEvents"] if e["name"] == "step_one"][0]
    assert ev["ph"] == "X" and ev["cat"] == "step"
    assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
    assert ev["args"] == {"idx": 7}
    # ts-sorted, required for sane timeline rendering
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


def test_chrome_trace_merges_legacy_profiler_timeline():
    from paddle_tpu import profiler

    with profiler.profiler():
        with profiler.RecordEvent("legacy_op"):
            pass
    # session is OVER (snapshot only) — the unified export must still
    # carry it
    assert any(e["name"] == "legacy_op"
               for e in obs.chrome_trace()["traceEvents"])
    # and reset() clears the snapshot too: a post-reset export is empty
    obs.reset()
    assert obs.chrome_trace()["traceEvents"] == []


# -- executor counters on a real 2-op program ------------------------------

def _two_op_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[4, 8], dtype="float32")
        y = fluid.layers.scale(x, scale=2.0)
        out = fluid.layers.mean(y)
    return main, startup, out


def test_compiled_executor_counters_and_dump():
    main, startup, out = _two_op_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    # the startup program counts as a step too — measure the delta
    base = obs.counter_value("executor.steps", path="compiled")
    feed = {"x": np.ones((4, 8), "float32")}
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[out])
    d = obs.dump()
    steps = obs.counter_value("executor.steps", path="compiled")
    assert steps - base == 3
    assert d["counters"]["executor.compiles"] >= 1
    assert d["histograms"]["executor.step_ms{path=compiled}"]["count"] \
        == steps
    # memory gauges ride every dump
    assert "memory.allocated_bytes" in d["gauges"]
    assert "memory.peak_bytes" in d["gauges"]
    # prometheus export of the same state
    text = obs.dump(fmt="prometheus")
    assert 'paddle_tpu_executor_steps{path="compiled"} %d' % steps in text


def test_interpreter_executor_per_op_counters():
    main, startup, out = _two_op_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((4, 8), "float32")}
    # FLAGS_check_nan_inf forces the op-by-op interpreter
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        exe.run(main, feed=feed, fetch_list=[out])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    d = obs.dump()
    assert d["counters"]["executor.steps{path=interpreter}"] == 1
    assert d["counters"]["executor.ops{type=scale}"] == 1
    assert d["counters"]["executor.ops{type=mean}"] == 1


def test_interpreter_step_emits_spans_under_metrics_mode():
    main, startup, out = _two_op_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                fetch_list=[out])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    names = [e[0] for e in obs.tracing.trace_events()]
    assert "executor/step" in names
    assert "scale" in names and "mean" in names


# -- lazy dygraph engine counters ------------------------------------------

def test_lazy_engine_flush_and_recompile_counters():
    from paddle_tpu.dygraph import Linear, to_variable

    with fluid.dygraph.guard(lazy=True):
        lin = Linear(8, 4)
        x = np.ones((2, 8), "float32")

        def step():
            loss = fluid.layers.mean(lin(to_variable(x)))
            loss.backward()
            return float(np.asarray(loss.numpy()).ravel()[0])

        step()
        d1 = obs.dump()["counters"]
        assert d1["lazy.flushes"] == 1
        assert d1["lazy.recompiles"] == 1     # first structure: a miss
        assert d1["dygraph.ops{dispatch=lazy}"] >= 2
        # steps 2 and 3: param-init nodes are gone after step 1, so at
        # most one more structure compiles — then the cache must hit
        step()
        step()
        d2 = obs.dump()["counters"]
        assert d2["lazy.flushes"] == 3
        assert d2["lazy.recompiles"] <= 2
        assert d2.get("lazy.cache_hits", 0) >= 1
    h = obs.dump()["histograms"]["lazy.graph_nodes"]
    assert h["count"] == 3 and h["min"] >= 1


def test_force_pins_value_held_only_by_locals():
    """Satellite dygraph/lazy.py:119 — forcing a PendingValue whose
    only reference is a local variable (no VarBase owner) must
    materialize it instead of raising 'dead at flush time'."""
    import jax
    import jax.numpy as jnp

    with fluid.dygraph.guard(lazy=True):
        from paddle_tpu.dygraph.tracer import current_tracer

        eng = current_tracer().lazy_engine
        p = eng.constant_node(
            lambda: jnp.full((3,), 7.0, jnp.float32),
            jax.ShapeDtypeStruct((3,), jnp.float32),
            ("t_const", (3,), "float32"))
        assert not p._resolved and not p.is_needed()
        np.testing.assert_allclose(np.asarray(p.force()),
                                   np.full((3,), 7.0))


def test_attrs_sig_hashes_array_content():
    """Satellite dygraph/tracer.py:435 — array-valued attrs must be
    cache-keyed by content, not repr (repr elides interior elements of
    large arrays, aliasing distinct ops onto one compiled graph)."""
    from paddle_tpu.dygraph.tracer import attrs_signature

    a = np.zeros(2000, dtype=np.float32)
    b = a.copy()
    b[1000] = 5.0   # elided by repr's summarization
    assert repr(a) == repr(b)   # the old key COULD NOT tell them apart
    assert attrs_signature({"v": a}) != attrs_signature({"v": b})
    assert attrs_signature({"v": a}) == attrs_signature({"v": a.copy()})
    # nested containers canonicalize too
    assert attrs_signature({"v": [a, 1]}) != attrs_signature({"v": [b, 1]})


# -- parallel engine counters ----------------------------------------------

def test_parallel_engine_counters():
    from paddle_tpu.parallel.mesh_utils import make_mesh

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[8, 4], dtype="float32")
        y = fluid.layers.fc(x, size=2)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    mesh = make_mesh([2], ["dp"])
    cp = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=mesh)
    feed = {"x": np.ones((8, 4), "float32")}
    exe.run(cp, feed=feed, fetch_list=[loss])
    exe.run(cp, feed=feed, fetch_list=[loss])
    d = obs.dump()["counters"]
    assert d["parallel.steps"] == 2
    assert d["parallel.compiles"] == 1
    # grad allreduces moved bytes both steps
    assert d["parallel.collective_ops"] >= 2
    assert d["parallel.collective_bytes"] > 0
    assert obs.dump()["histograms"]["parallel.step_ms"]["count"] == 2


# -- lod lowering decline surface ------------------------------------------

def test_lowering_decline_returned_and_counted():
    """Satellite core/lod_lowering.py:68 — the decline reason is a
    return value (no mutable module global), and the executor surfaces
    it as a labeled counter."""
    from paddle_tpu.core.lod_lowering import Decline, plan_lowering
    from paddle_tpu.core.tensor import LoDTensor

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.data(name="ids", shape=[-1, 1], dtype="int64",
                         lod_level=1)
        emb = fluid.layers.embedding(ids, size=[10, 4])
        fluid.layers.fc(emb, size=2)      # fc over ragged: unsupported
        pooled = fluid.layers.sequence_pool(emb, pool_type="SUM")
        loss = fluid.layers.mean(pooled)

    plan = plan_lowering(main, ["ids"])
    assert isinstance(plan, Decline) and not plan   # falsy
    assert plan.op_type == "mul"
    assert "unsupported" in plan.reason
    # module has no mutable decline global anymore
    from paddle_tpu.core import lod_lowering

    assert not hasattr(lod_lowering, "LAST_DECLINE")

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    t = LoDTensor(np.array([[1], [2], [3]], dtype="int64"))
    t.set_lod([[0, 1, 3]])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        exe.run(main, feed={"ids": t}, fetch_list=[loss])
    d = obs.dump()["counters"]
    key = [k for k in d if k.startswith("lod_lowering.declines")]
    assert key and "op_type=mul" in key[0]


# -- profiler shim backward compatibility ----------------------------------

def test_profiler_shim_session_contract(capsys):
    from paddle_tpu import profiler

    main, startup, out = _two_op_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    fluid.set_flags({"FLAGS_check_nan_inf": True})  # per-op events
    try:
        assert not profiler.is_profiler_enabled()
        with profiler.profiler():
            assert profiler.is_profiler_enabled()
            exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                    fetch_list=[out])
            live = profiler.get_trace_events()
            assert any(n == "scale" for (n, _, _) in live)
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    # stop printed the host summary table
    assert "Event" in capsys.readouterr().out
    # snapshot survives after stop; live state drained
    assert not profiler.is_profiler_enabled()
    snap = profiler.get_trace_events()
    assert any(n == "scale" for (n, _, _) in snap)
    assert all(len(ev) == 3 for ev in snap)
    # timeline converter keeps working on the shim
    from paddle_tpu.tools.timeline import chrome_trace_events

    evs = chrome_trace_events()
    assert any(e["name"] == "scale" and e["ph"] == "X" for e in evs)


def test_profiler_sessions_do_not_bleed(capsys):
    from paddle_tpu import profiler

    with profiler.profiler():
        with profiler.RecordEvent("first_session_op"):
            pass
    capsys.readouterr()
    with profiler.profiler():
        pass
    # second (empty) session replaced the snapshot
    assert profiler.get_trace_events() == []


def test_reset_profiler_scoped_to_session():
    """reset_profiler drops only the live session's events — spans
    recorded by the metrics layer before the session are not the
    legacy API's to destroy."""
    from paddle_tpu import profiler

    with obs.span("metrics_mode_span"):
        pass
    profiler.start_profiler()
    with profiler.RecordEvent("sess_op"):
        pass
    profiler.reset_profiler()
    assert profiler.get_trace_events() == []   # session emptied
    profiler.stop_profiler()
    names = [e[0] for e in obs.tracing.trace_events()]
    assert "metrics_mode_span" in names        # survived the reset


def test_profiler_summary_exact_under_buffer_pressure(capsys):
    """The session summary table aggregates exactly even when buffer
    pressure drops old span tuples mid-session."""
    from paddle_tpu import profiler
    from paddle_tpu.observability import tracing

    old_cap, tracing._MAX_EVENTS = tracing._MAX_EVENTS, 64
    try:
        with profiler.profiler():
            for _ in range(200):   # >> capped buffer
                with profiler.RecordEvent("hot_op"):
                    pass
    finally:
        tracing._MAX_EVENTS = old_cap
    out = capsys.readouterr().out
    row = [ln for ln in out.splitlines() if ln.startswith("hot_op")]
    assert row and row[0].split()[1] == "200"   # exact Calls column


# -- default-off contract --------------------------------------------------

def test_disabled_records_nothing_and_is_cheap():
    obs.disable()
    main, startup, out = _two_op_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed={"x": np.ones((4, 8), "float32")},
            fetch_list=[out])
    d = obs.dump()
    assert d["enabled"] is False
    # a disabled dump is a pure observation: it creates NOTHING (not
    # even the dump-time memory gauges)
    assert d["counters"] == {}
    assert d["gauges"] == {}
    assert d["histograms"] == {}
    assert d["spans"]["recorded"] == 0
    # disabled primitives are sub-microsecond-ish (generous CI bound)
    t0 = time.perf_counter()
    for _ in range(100000):
        obs.tracing.span("x")
        obs.inc("y")
    per_call_us = (time.perf_counter() - t0) / 200000 * 1e6
    assert per_call_us < 5.0, per_call_us


def test_flag_arms_the_layer():
    obs.disable()
    fluid.set_flags({"FLAGS_tpu_metrics": True})
    try:
        assert obs.enabled()
    finally:
        fluid.set_flags({"FLAGS_tpu_metrics": False})
    assert not obs.enabled()
    # and the sync is two-way: direct enable() keeps get_flags truthful
    obs.enable()
    assert fluid.get_flags("FLAGS_tpu_metrics")["FLAGS_tpu_metrics"]
    obs.disable()
    assert not fluid.get_flags("FLAGS_tpu_metrics")["FLAGS_tpu_metrics"]


def test_stop_profiler_without_start_keeps_metrics_spans(capsys):
    from paddle_tpu import profiler

    with obs.span("precious_metrics_span"):
        pass
    profiler.stop_profiler()   # no session live: harmless no-op
    capsys.readouterr()
    names = [e[0] for e in obs.tracing.trace_events()]
    assert "precious_metrics_span" in names
