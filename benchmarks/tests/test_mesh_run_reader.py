"""The readers ``layer_metrics/mesh_run.py`` and ``layer_metrics/startup.py``
(PR 39): on a trace written by hand, whose answers are known exactly; on
traces without the mesh engine's new spans (a one-chip run, and the parent's
mesh step, which has ``parallel/step`` alone); on the host plane that the CPU
run of the preset's data-parallel cell writes; and the twelve entries of
``BENCHMARK.json`` against what the readers give (ISSUE 39's eleven and
``mesh_run.idle_release_ms``, which the span put where ``other`` read over
2 ms brought)."""
import json
import os

import pytest

from benchmarks.layer_metrics import mesh_run, startup
from benchmarks.lib import program_spans as P

from .conftest import REPO

MS = 1_000_000
NEW = ["mesh_run.prepare_ms.tokens", "mesh_run.stage_ms.tokens",
       "mesh_run.launch_ms.tokens", "mesh_run.writeback_ms.tokens",
       "mesh_run.idle_prepare_ms.tokens", "mesh_run.idle_stage_ms.tokens",
       "mesh_run.idle_launch_ms.tokens", "mesh_run.idle_fetch_ms.tokens",
       "mesh_run.idle_release_ms.tokens", "mesh_run.idle_other_ms.tokens",
       "mesh_run.trace_s",
       "startup.lower_s"]


@pytest.fixture(autouse=True)
def _clean_counters():
    from paddle_tpu import observability as obs

    obs.reset()
    yield
    obs.reset()


def hand_trace(names=None):
    """Two steps. Step 1's span is 0-100 ms and step 2's 104-200 ms, so the
    tiles are 0-104 and 104-200. The host, inside executor/run: prepare 2 ms,
    stage 3, the jitted call 4 (the chips start 3 ms into it), 1 ms of the
    root's own, writeback 1, fetch to 3 ms before the step's end, release
    2 ms. The chips idle until their first operation, for 2 ms between two
    operations (under fetch), and after the last one."""
    def step(t0, end):
        spans = [("executor/run", t0 + 1 * MS, end - 1 * MS),
                 ("parallel/prepare", t0 + 1 * MS, t0 + 3 * MS),
                 ("parallel/stage", t0 + 3 * MS, t0 + 6 * MS),
                 ("parallel/step", t0 + 6 * MS, t0 + 10 * MS),
                 ("parallel/writeback", t0 + 11 * MS, t0 + 12 * MS),
                 ("parallel/fetch", t0 + 12 * MS, end - 3 * MS),
                 ("parallel/release", end - 3 * MS, end - 1 * MS)]
        ops = [("forward/mul", t0 + 9 * MS, t0 + 40 * MS),
               ("backward/mul_grad", t0 + 40 * MS, t0 + 74 * MS),
               ("optimizer/adam", t0 + 76 * MS, end - 4 * MS)]
        return [s for s in spans if names is None or s[0] in names], ops
    s1, o1 = step(0, 100 * MS)
    s2, o2 = step(104 * MS, 200 * MS)
    return P.ProgramTrace([(0, 100 * MS), (104 * MS, 200 * MS)],
                          s1 + s2, o1 + o2)


def read(monkeypatch, trace, reduction=None):
    monkeypatch.setattr(P, "newest_xplane", lambda: "hand.xplane.pb")
    monkeypatch.setattr(P, "load", lambda path: trace)
    return mesh_run.read({"suffix": "tokens", "trace": reduction})


def test_idle_parts_of_the_hand_trace_sum_to_the_windows_idle_time():
    idle = mesh_run.idle_parts(hand_trace())
    # step 1 idles 0-9 (the root's own 0-1, prepare 1-3, stage 3-6, the
    # call 6-9), 74-76 and 96-97 under fetch, 97-99 under release, 99-104
    # under nothing
    assert idle["prepare"] == [2 * MS, 2 * MS]
    assert idle["stage"] == [3 * MS, 3 * MS]
    assert idle["launch"] == [3 * MS, 3 * MS]
    assert idle["fetch"] == [3 * MS, 3 * MS]
    assert idle["release"] == [2 * MS, 2 * MS]
    assert idle["other"] == [6 * MS, 2 * MS]   # step 2 has no tail to a next
    assert idle["interior"] == [2 * MS, 2 * MS]
    # executor.dispatch_ms: span start to first op, last op to span end
    assert idle["dispatch"] == [13 * MS, 13 * MS]
    parts = sum(sum(idle[k]) for k in mesh_run.IDLE_PARTS + ("other",))
    # busy: 9-74 and 76-96 in step 1; 113-178 and 180-196 in step 2
    assert parts == 200 * MS - (65 + 20 + 65 + 16) * MS


def test_the_ten_per_step_numbers_and_the_lines(monkeypatch, capsys):
    out = read(monkeypatch, hand_trace(),
               reduction={"window_s": 0.2, "busy_s_chip0": 0.166,
                          "dispatch_s": [0.013, 0.013]})
    assert out == {
        "mesh_run.prepare_ms.tokens": pytest.approx(2.0),
        "mesh_run.stage_ms.tokens": pytest.approx(3.0),
        "mesh_run.launch_ms.tokens": pytest.approx(4.0),
        "mesh_run.writeback_ms.tokens": pytest.approx(1.0),
        "mesh_run.idle_prepare_ms.tokens": pytest.approx(2.0),
        "mesh_run.idle_stage_ms.tokens": pytest.approx(3.0),
        "mesh_run.idle_launch_ms.tokens": pytest.approx(3.0),
        "mesh_run.idle_fetch_ms.tokens": pytest.approx(3.0),
        "mesh_run.idle_release_ms.tokens": pytest.approx(2.0),
        "mesh_run.idle_other_ms.tokens": pytest.approx(4.0),
    }
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("# mesh_run:")]
    assert "2 steps, 14 program spans, 6 operations" in lines[0]
    assert "summed over the window 0.034000 s" in lines[1]
    assert "ratio 1.0000" in lines[1]
    assert "median 13.0000 ms; executor.dispatch_ms 13.0000" in lines[2]


@pytest.mark.parametrize("names", [
    pytest.param({"executor/run"}, id="one_chip_run"),
    pytest.param({"executor/run", "parallel/step"}, id="the_parents_step"),
    pytest.param(set(), id="no_program_span"),
])
def test_a_trace_without_the_new_spans_reads_nothing(monkeypatch, names):
    assert read(monkeypatch, hand_trace(names)) == {}


def test_no_trace_reads_the_counter_alone(monkeypatch):
    from paddle_tpu import observability as obs

    monkeypatch.setattr(P, "newest_xplane", lambda: None)
    assert mesh_run.read({"suffix": "tokens", "trace": None}) == {}
    obs.counter("parallel.trace_s").inc(12.5)
    assert mesh_run.read({"suffix": "tokens", "trace": None}) == {
        "mesh_run.trace_s": 12.5}


def test_startup_sums_the_two_counters_and_reads_nothing_without_them():
    from paddle_tpu import observability as obs

    assert startup.read({}) == {}
    obs.counter("executor.lower_s").inc(1.5)
    assert startup.read({}) == {"startup.lower_s": 1.5}
    obs.counter("parallel.lower_s").inc(2.25)
    assert startup.read({}) == {"startup.lower_s": 3.75}
    obs.reset()
    obs.counter("parallel.lower_s").inc(2.25)
    assert startup.read({}) == {"startup.lower_s": 2.25}


def test_the_manifests_twelve_entries_are_what_the_readers_give(monkeypatch):
    from paddle_tpu import observability as obs

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if m["name"].split(".")[0] in ("mesh_run", "startup")]
    assert [m["name"] for m in mine] == NEW
    assert manifest["per_layer"][-12:] == mine     # appended, nothing moved
    cells = [c["name"] for c in manifest["workloads"]]
    for m in mine:
        if m["name"] == "startup.lower_s":
            assert (m["workloads"], m["layer"], m["moves"]) == (
                cells, "executor", "setup_s")
        else:
            assert m["workloads"] == ["bert_base.dp4_s128"]
            assert m["layer"] == "mesh engines"
            assert m["moves"] == ("setup_s" if m["name"].endswith("_s")
                                  else "tokens_per_s")
    obs.counter("parallel.trace_s").inc(1.0)
    obs.counter("parallel.lower_s").inc(1.0)
    given = read(monkeypatch, hand_trace())
    given.update(startup.read({}))
    assert sorted(given) == sorted(NEW)


def test_the_preset_mesh_cells_cpu_trace_holds_the_spans(preset_run):
    """The real spans, on the thread that ran the steps, in the host plane of
    a CPU run: durations only, a CPU trace has no chip to idle."""
    result, _ = preset_run("tiny_bert.dp4", 5, 0.5, 1)
    assert result["correct"]
    path = P.newest_xplane()
    assert os.sep + "tiny_bert.dp4" + os.sep in path
    P.load.cache_clear()
    trace = P.load(path)
    names = {n for n, _, _ in trace.spans}
    assert {"executor/run"} | set(mesh_run.SPANS.values()) <= names
    out = mesh_run.read({"suffix": "tokens", "trace": None})
    assert sorted(out) == ["mesh_run.launch_ms.tokens",
                           "mesh_run.prepare_ms.tokens",
                           "mesh_run.stage_ms.tokens",
                           "mesh_run.trace_s",
                           "mesh_run.writeback_ms.tokens"]
    assert all(v > 0 for v in out.values())
    assert startup.read({})["startup.lower_s"] > 0
