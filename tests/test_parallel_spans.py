"""ISSUE 39: the spans of a mesh step. ``Executor.run`` opens one
``executor/run`` whichever engine the call ends in; inside it
``run_data_parallel`` tiles its own part with ``parallel/prepare``,
``stage``, ``step``, ``writeback``, ``fetch`` and ``release``; the
Python trace of a compiled step is ``parallel/trace`` (counter
``parallel.trace_s``), and the lowering of a program's own steps is
counted as ``<family>.lower_s`` by one ``jax.monitoring`` listener. The
twin of ``tests/test_executor_spans.py``, on the CPU host mesh the
suite forces.
"""
import glob
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu.observability import tracing
from paddle_tpu.parallel.mesh_utils import make_mesh

CHILDREN = ["parallel/prepare", "parallel/stage", "parallel/step",
            "parallel/writeback", "parallel/fetch", "parallel/release"]


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.reset()
    obs.disable()


def _program(prefix):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        x = fluid.data(name=prefix + "x", shape=[16, 32], dtype="float32")
        y = fluid.data(name=prefix + "y", shape=[16, 1], dtype="int64")
        h = fluid.layers.fc(x, 16, act="relu")
        pred = fluid.layers.fc(h, 10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, y))
        fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {prefix + "x": rng.rand(16, 32).astype("float32"),
            prefix + "y": rng.randint(0, 10, (16, 1)).astype("int64")}
    return main, startup, loss, feed


@pytest.fixture
def meshed():
    """(executor, data-parallel program, feed, loss) over four of the
    host's devices, its step already compiled."""
    main, startup, loss, feed = _program("p")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=make_mesh([4], ["dp"]))
        exe.run(cp, feed=feed, fetch_list=[loss])
        yield exe, cp, feed, loss


def _roots(tree):
    return [i for i, e in enumerate(tree) if e["name"] == "executor/run"]


def test_armed_mesh_step_records_one_root_and_its_six_children(meshed):
    exe, cp, feed, loss = meshed
    obs.enable()
    for _ in range(2):
        exe.run(cp, feed=feed, fetch_list=[loss])
    tree = tracing.nest(tracing.trace_events())
    runs = _roots(tree)
    assert len(runs) == 2
    steps = []
    for r in runs:
        run = tree[r]
        assert run["parent"] is None and run["depth"] == 0
        kids = [e for e in tree if e["parent"] == r]
        assert [k["name"] for k in kids] == CHILDREN   # in order of start
        assert {k["args"]["step"] for k in kids} == {run["args"]["step"]}
        steps.append(run["args"]["step"])
        # siblings: each ends before the next starts, all inside the root
        edges = [run["ts_us"]]
        for k in kids:
            edges += [k["ts_us"], k["ts_us"] + k["dur_us"]]
        edges.append(run["ts_us"] + run["dur_us"])
        assert edges == sorted(edges)
        # they tile the call to within its self time
        assert run["self_us"] == pytest.approx(
            run["dur_us"] - sum(k["dur_us"] for k in kids))
        assert 0 <= run["self_us"] < run["dur_us"]
    assert steps[1] == steps[0] + 1
    # parallel.step_ms is the whole call, fetch included
    hist = obs.dump()["histograms"]["parallel.step_ms"]
    assert hist["count"] == 2
    launches = [e["dur_us"] / 1e3 for e in tree
                if e["name"] == "parallel/step"]
    slowest_run_ms = max(tree[r]["dur_us"] for r in runs) / 1e3
    assert min(launches) < hist["min"] and hist["max"] <= slowest_run_ms


def test_first_run_of_a_shape_records_the_trace_under_the_step():
    main, startup, loss, feed = _program("t")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=make_mesh([2], ["dp"]))
        obs.enable()
        exe.run(cp, feed=feed, fetch_list=[loss])
        first = obs.counter_value("parallel.trace_s")
        exe.run(cp, feed=feed, fetch_list=[loss])
    tree = tracing.nest(tracing.trace_events())
    traces = [e for e in tree if e["name"] == "parallel/trace"]
    assert len(traces) == 1            # trace time only, once a step
    assert tree[traces[0]["parent"]]["name"] == "parallel/step"
    assert first == pytest.approx(traces[0]["dur_us"] / 1e6, rel=0.2,
                                  abs=1e-3)
    assert obs.counter_value("parallel.trace_s") == first   # not again
    assert obs.counter_value("parallel.compiles") == 1


def test_compiled_program_without_data_parallel_has_one_root():
    main, startup, loss, feed = _program("n")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        obs.enable()
        before = exe._runs
        exe.run(fluid.CompiledProgram(main), feed=feed, fetch_list=[loss])
    tree = tracing.nest(tracing.trace_events())
    (root,) = _roots(tree)
    assert exe._runs == before + 1     # one run counted, not two
    kids = [e["name"] for e in tree if e["parent"] == root]
    assert kids[0] == "executor/prepare" and "executor/launch" in kids
    assert {e["args"]["step"] for e in tree} == {before + 1}


def test_parallel_step_keeps_ranks_and_round(meshed):
    exe, cp, feed, loss = meshed
    obs.enable()
    for _ in range(2):
        exe.run(cp, feed=feed, fetch_list=[loss])
    args = [e[5] for e in tracing.trace_events() if e[0] == "parallel/step"]
    assert [a["ranks"] for a in args] == [4, 4]
    assert args[1]["round"] == args[0]["round"] + 1


def test_off_the_buffer_stays_empty_and_span_is_the_shared_null(meshed):
    exe, cp, feed, loss = meshed
    assert not tracing.active()
    assert tracing.span("parallel/stage", cat="step") is tracing._NULL
    exe.run(cp, feed=feed, fetch_list=[loss])
    assert tracing.trace_events() == []
    assert obs.dump()["counters"] == {}
    assert obs.dump()["histograms"] == {}


def test_under_a_live_profiler_trace_the_xplane_holds_pt_parallel_events(
        meshed, tmp_path):
    import jax
    from jax.profiler import ProfileData

    exe, cp, feed, loss = meshed
    obs.enable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                exe.run(cp, feed=feed, fetch_list=[loss])
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
    # one clock: the root inside its bench.step, the children inside it
    for (s0, s1), (r0, r1), (p0, _), (f0, f1) in zip(
            sorted(spans["bench.step"]), sorted(spans["pt:executor/run"]),
            sorted(spans["pt:parallel/prepare"]),
            sorted(spans["pt:parallel/fetch"])):
        assert s0 <= r0 <= p0 <= f0 <= f1 <= r1 <= s1


def test_lowering_is_counted_for_a_programs_first_step_only(meshed):
    exe, cp, feed, loss = meshed
    main, startup, loss1, feed1 = _program("l")
    obs.enable()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe1 = fluid.Executor(fluid.CPUPlace())
        exe1.run(startup)
        exe1.run(main, feed=feed1, fetch_list=[loss1])
    lowered = obs.counter_value("executor.lower_s")
    assert lowered > 0
    tree = tracing.nest(tracing.trace_events())
    lowers = [e for e in tree if e["name"] == "executor/lower"]
    assert sum(e["dur_us"] for e in lowers) / 1e6 == pytest.approx(lowered)
    # the step's own lowering stands under its launch, with its step
    last = lowers[-1]
    assert tree[last["parent"]]["name"] == "executor/launch"
    assert last["args"]["step"] == exe1._runs
    # a step that is compiled lowers nothing, on either path
    with fluid.scope_guard(scope):
        exe1.run(main, feed=feed1, fetch_list=[loss1])
    exe.run(cp, feed=feed, fetch_list=[loss])
    assert obs.counter_value("executor.lower_s") == lowered
    assert not obs.counter_value("parallel.lower_s")


def test_lowering_of_a_mesh_step_is_parallel_lower_s():
    main, startup, loss, feed = _program("m")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name, places=make_mesh([2], ["dp"]))
        obs.enable()
        exe.run(cp, feed=feed, fetch_list=[loss])
    lowered = obs.counter_value("parallel.lower_s")
    assert lowered > 0
    tree = tracing.nest(tracing.trace_events())
    (step,) = [i for i, e in enumerate(tree) if e["name"] == "parallel/step"]
    inside = [e for e in tree if e["name"] == "parallel/lower"]
    assert inside and inside[-1]["parent"] == step
    # after the trace it lowers: the trace ends before the lowering does
    (trace,) = [e for e in tree if e["name"] == "parallel/trace"]
    assert trace["ts_us"] + trace["dur_us"] <= (
        inside[-1]["ts_us"] + inside[-1]["dur_us"])


def test_a_bare_jit_lowered_outside_any_program_span_counts_nothing():
    import jax
    import jax.numpy as jnp

    obs.enable()
    # the benchmark's plain reference, make_params, a user's own jit
    jax.jit(lambda a: jnp.tanh(a) * 3.0 + 1.0)(jnp.ones((7, 5)))
    counters = obs.dump()["counters"]
    assert not [k for k in counters if k.endswith(".lower_s")]
    assert tracing.trace_events() == []
    # the same lowering under a program span is that family's
    with tracing.span("executor/launch", cat="step"):
        jax.jit(lambda a: jnp.tanh(a) * 5.0 + 2.0)(jnp.ones((7, 5)))
    assert obs.counter_value("executor.lower_s") > 0
    # and under the interpreter's per-op span, named by the op type
    # alone, it is the enclosing family's and no family of its own
    with tracing.span("executor/step"), tracing.span("scale"):
        jax.jit(lambda a: jnp.tanh(a) * 7.0 + 3.0)(jnp.ones((7, 5)))
    assert not [k for k in obs.dump()["counters"]
                if k.startswith("scale")]


def test_enable_registers_one_listener_and_none_when_never_enabled():
    import subprocess
    import sys

    code = (
        "import jax\n"
        "from jax._src import monitoring as m\n"
        "import paddle_tpu\n"
        "from paddle_tpu import observability as obs\n"
        "n0 = len(m._event_duration_secs_listeners)\n"
        "assert not obs._listening\n"
        "obs.enable(); obs.disable(); obs.enable()\n"
        "assert len(m._event_duration_secs_listeners) == n0 + 1\n"
        "print('ok')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PADDLE_TPU_METRICS", None)
    env.pop("FLAGS_tpu_metrics", None)
    env.pop("PADDLE_TPU_METRICS_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
