"""ISSUE 24: the spans inside ``Executor.run``. One primitive
(``observability.tracing.span``) with two sinks: the in-memory buffer,
and a ``jax.profiler.TraceAnnotation`` named ``"pt:" + name`` that
lands in the XPlane's host plane under a live profiler trace."""
import glob
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing

CHILDREN = ["executor/prepare", "executor/stage", "executor/launch",
            "executor/writeback", "executor/fetch"]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.reset()
    obs.disable()


@pytest.fixture
def trained():
    """(executor, program, feed, loss) of a small forward + backward +
    optimizer program whose step is already compiled."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data(name="sx", shape=[8, 32], dtype="float32")
        y = fluid.data(name="sy", shape=[8, 1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"sx": rng.rand(8, 32).astype("float32"),
            "sy": rng.randint(0, 10, (8, 1)).astype("int64")}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        yield exe, main, feed, loss


def test_armed_step_records_run_and_its_five_children(trained):
    exe, main, feed, loss = trained
    obs.enable()
    for _ in range(2):
        exe.run(main, feed=feed, fetch_list=[loss])
    tree = tracing.nest(tracing.trace_events())
    runs = [i for i, e in enumerate(tree) if e["name"] == "executor/run"]
    assert len(runs) == 2
    steps = []
    for r in runs:
        run = tree[r]
        assert run["parent"] is None and run["depth"] == 0
        kids = [e for e in tree if e["parent"] == r]
        assert [k["name"] for k in kids] == CHILDREN   # in order of start
        # one step value for the spans of one Executor.run
        assert {k["args"]["step"] for k in kids} == {run["args"]["step"]}
        steps.append(run["args"]["step"])
        end = run["ts_us"] + run["dur_us"]
        for k in kids:
            assert run["ts_us"] <= k["ts_us"]
            assert k["ts_us"] + k["dur_us"] <= end
        # self time is duration less children
        assert run["self_us"] == pytest.approx(
            run["dur_us"] - sum(k["dur_us"] for k in kids))
        assert 0 <= run["self_us"] < run["dur_us"]
    # the executor's count of runs: consecutive
    assert steps[1] == steps[0] + 1
    # executor.step_ms{path=compiled} is the whole call, fetch included
    hist = obs.dump()["histograms"]["executor.step_ms{path=compiled}"]
    assert hist["count"] == 2
    slowest_run_ms = max(tree[r]["dur_us"] for r in runs) / 1e3
    assert 0 < hist["max"] <= slowest_run_ms


def test_first_run_of_a_shape_records_the_trace_under_launch():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data(name="tx", shape=[4, 8], dtype="float32")
        out = fluid.layers.mean(fluid.layers.scale(x, scale=2.0))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    obs.enable()
    for _ in range(2):
        exe.run(main, feed={"tx": np.ones((4, 8), "float32")},
                fetch_list=[out])
    tree = tracing.nest(tracing.trace_events())
    traces = [e for e in tree if e["name"] == "executor/trace"]
    assert len(traces) == 1            # trace time only, once per shape
    assert tree[traces[0]["parent"]]["name"] == "executor/launch"
    assert obs.counter_value("executor.trace_s") == pytest.approx(
        traces[0]["dur_us"] / 1e6, rel=0.2, abs=1e-3)
    assert obs.counter_value("executor.jit_traces") == 1


def test_interpreter_spans_inherit_the_run_and_its_step():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data(name="ix", shape=[4, 8], dtype="float32")
        out = fluid.layers.mean(fluid.layers.scale(x, scale=2.0))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    obs.enable()
    fluid.set_flags({"FLAGS_check_nan_inf": True})
    try:
        exe.run(main, feed={"ix": np.ones((4, 8), "float32")},
                fetch_list=[out])
    finally:
        fluid.set_flags({"FLAGS_check_nan_inf": False})
    tree = tracing.nest(tracing.trace_events())
    by_name = {e["name"]: e for e in tree}
    step = by_name["executor/step"]
    assert tree[step["parent"]]["name"] == "executor/run"
    assert tree[by_name["scale"]["parent"]]["name"] == "executor/step"
    assert {e["args"]["step"] for e in tree} == {
        by_name["executor/run"]["args"]["step"]}


def test_off_the_buffer_stays_empty_and_span_is_the_shared_null(trained):
    exe, main, feed, loss = trained
    assert not tracing.active()
    assert tracing.span("executor/run") is tracing._NULL
    assert tracing.span("x", cat="step", step=3) is tracing._NULL
    exe.run(main, feed=feed, fetch_list=[loss])
    assert tracing.trace_events() == []
    assert obs.dump()["counters"] == {}


def test_under_a_live_profiler_trace_the_xplane_holds_pt_events(
        trained, tmp_path):
    import jax
    from jax.profiler import ProfileData

    exe, main, feed, loss = trained
    obs.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                exe.run(main, feed=feed, fetch_list=[loss])
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((tracing.ANNOTATION_PREFIX, "bench.")):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    for name in ["executor/run"] + CHILDREN:
        assert len(spans["pt:" + name]) == 3, name
    # one clock: each pt:executor/run lies inside its bench.step, and
    # its children inside it
    for (s0, s1), (r0, r1), (f0, f1) in zip(sorted(spans["bench.step"]),
                                            sorted(spans["pt:executor/run"]),
                                            sorted(spans["pt:executor/fetch"])):
        assert s0 <= r0 <= f0 <= f1 <= r1 <= s1
    # and the buffer recorded the same spans
    names = [e[0] for e in tracing.trace_events()]
    assert names.count("executor/run") == 3
