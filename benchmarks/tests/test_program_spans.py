"""The readers of the program's spans and op roles: on a trace written by
hand, whose answers are known exactly; on a role-less one; on a trace that the
CPU run of a preset cell wrote (host plane only); and on the two steps of
``resnet50.static_b128`` recorded on the chip."""
import gzip
import json
import os

import pytest

from benchmarks.layer_metrics import exe_run, phases
from benchmarks.lib import program_spans as P

from .conftest import REPO

MS = 1_000_000
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")


def hand_trace(roles=True):
    """Two steps. Step 1's span is 0-100 ms and step 2's 104-200 ms, so the
    tiles are 0-104 and 104-200. The host: stage, launch, (device runs),
    writeback and fetch inside executor/run; the device idles while the host
    stages and launches, between two operations, and after its last one."""
    def step(t0, end):
        spans = [("executor/run", t0 + 1 * MS, end - 1 * MS),
                 ("executor/prepare", t0 + 1 * MS, t0 + 2 * MS),
                 ("executor/stage", t0 + 2 * MS, t0 + 5 * MS),
                 ("executor/launch", t0 + 5 * MS, t0 + 9 * MS),
                 ("executor/writeback", t0 + 9 * MS, t0 + 10 * MS),
                 ("executor/fetch", t0 + 10 * MS, end - 1 * MS)]
        scope = (lambda r: r) if roles else (lambda r: "")
        ops = [(scope("forward/mul"), t0 + 8 * MS, t0 + 30 * MS),
               (scope("backward/mul_grad"), t0 + 30 * MS, t0 + 70 * MS),
               (scope("backward/relu_grad"), t0 + 60 * MS, t0 + 72 * MS),
               ("", t0 + 72 * MS, t0 + 74 * MS),               # no role
               (scope("optimizer/adam"), t0 + 76 * MS, end - 4 * MS)]
        return spans, ops
    s1, o1 = step(0, 100 * MS)
    s2, o2 = step(104 * MS, 200 * MS)
    return P.ProgramTrace([(0, 100 * MS), (104 * MS, 200 * MS)],
                          s1 + s2, o1 + o2)


def test_interval_helpers():
    a = [(0, 10), (20, 30), (40, 50)]
    assert P.intersect(a, [(5, 25), (45, 60)]) == [(5, 10), (20, 25),
                                                   (45, 50)]
    assert P.intersect(a, []) == []
    assert P.per_tile(a, [(0, 25), (25, 45), (45, 100)]) == [15, 10, 5]
    assert P.per_tile([(0, 100)], [(10, 20), (20, 30)]) == [10, 10]
    assert P.step_tiles([(0, 5), (7, 9)]) == [(0, 7), (7, 9)]
    assert P.scope_of("jit(step)/jit(main)/backward/mul_grad/dot:") == \
        "backward/mul_grad"
    assert P.scope_of("jit(step)/optimizer") == "optimizer"
    assert P.scope_of("jit(step)/jvp(x)/forward/mul") == ""
    assert P.scope_of("state['conv2d.w_44']:") == ""
    assert P.scope_of("") == ""
    assert P.role_of("backward/mul_grad") == "backward"


def test_span_durations_of_the_hand_trace():
    tr = hand_trace()
    assert P.span_ms(tr, "executor/stage") == pytest.approx(3.0)
    assert P.span_ms(tr, "executor/launch") == pytest.approx(4.0)
    assert P.span_ms(tr, "executor/writeback") == pytest.approx(1.0)
    assert P.span_ms(tr, "executor/none") is None


def test_idle_parts_add_up_to_the_windows_idle_time():
    tr = hand_trace()
    idle = P.idle_parts(tr)
    # step 1: idle 0-8 (prepare 1-2 and run's own 0-1: other 2; stage 3;
    # launch 5-8: 3), 74-76 under fetch, 96-99 under fetch, 99-104 other
    assert idle["stage"] == [3 * MS, 3 * MS]
    assert idle["launch"] == [3 * MS, 3 * MS]
    assert idle["fetch"] == [5 * MS, 5 * MS]
    assert idle["other"] == [7 * MS, 3 * MS]   # step 2 has no tail to a next
    assert idle["interior"] == [2 * MS, 2 * MS]
    # executor.dispatch_ms: span start to first op, last op to span end
    assert idle["dispatch"] == [12 * MS, 12 * MS]
    total = sum(sum(idle[k]) for k in ("stage", "launch", "fetch", "other"))
    # busy: 8-74 and 76-96 in step 1; 112-178 and 180-196 in step 2
    assert total == 200 * MS - (66 + 20 + 66 + 16) * MS


def test_phase_times_of_the_hand_trace():
    t = P.phase_times(hand_trace())
    assert t["per_step"]["forward"] == [22 * MS, 22 * MS]
    assert t["per_step"]["backward"] == [42 * MS, 42 * MS]   # a union
    assert t["per_step"]["optimizer"] == [20 * MS, 16 * MS]
    # step 2's optimizer ends 4 ms before its span; step 1's runs to 96
    assert t["sum_ops"] == (22 + 40 + 12 + 2 + 20) * MS + \
        (22 + 40 + 12 + 2 + 16) * MS
    assert t["sum_attributed"] == t["sum_ops"] - 4 * MS
    assert t["busy"] == (86 + 82) * MS
    assert t["by_scope"]["backward/relu_grad"] == 24 * MS
    assert set(t["by_scope"]) == {"forward/mul", "backward/mul_grad",
                                  "backward/relu_grad", "optimizer/adam"}


def read_both(monkeypatch, trace, suffix="images", reduction=None):
    monkeypatch.setattr(P, "newest_xplane", lambda: "hand.xplane.pb")
    monkeypatch.setattr(P, "load", lambda path: trace)
    ctx = {"suffix": suffix, "trace": reduction}
    out = exe_run.read(ctx)
    out.update(phases.read(ctx))
    return out


def test_readers_on_the_hand_trace(monkeypatch, capsys):
    out = read_both(monkeypatch, hand_trace(),
                    reduction={"window_s": 0.2, "busy_s_chip0": 0.168,
                               "dispatch_s": [0.012, 0.012]})
    assert out["exe_run.stage_ms.images"] == pytest.approx(3.0)
    assert out["exe_run.idle_stage_ms.images"] == pytest.approx(3.0)
    assert out["exe_run.idle_launch_ms.images"] == pytest.approx(3.0)
    assert out["exe_run.idle_fetch_ms.images"] == pytest.approx(5.0)
    assert out["exe_run.idle_other_ms.images"] == pytest.approx(5.0)
    assert out["phases.forward_ms.images"] == pytest.approx(22.0)
    assert out["phases.backward_ms.images"] == pytest.approx(42.0)
    assert out["phases.optimizer_ms.images"] == pytest.approx(18.0)
    assert out["phases.attributed_pct.images"] == pytest.approx(
        100.0 * (188 - 4) / 188)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("#")]
    assert any("ratio 1.0000" in l for l in lines)
    assert any("median 12.0000 ms; executor.dispatch_ms 12.0000" in l
               for l in lines)


def test_a_role_less_trace_reads_zero_attributed_and_no_phase(monkeypatch):
    out = read_both(monkeypatch, hand_trace(roles=False))
    assert out["phases.attributed_pct.images"] == 0.0
    assert not [k for k in out if k.startswith("phases.") and "_ms" in k]
    assert out["exe_run.idle_stage_ms.images"] == pytest.approx(3.0)


def test_a_trace_without_program_spans_reads_nothing(monkeypatch):
    tr = hand_trace()
    parent = P.ProgramTrace(tr.steps, [], [("", s, e) for _, s, e in tr.ops])
    out = read_both(monkeypatch, parent)
    assert set(out) - {"exe_run.trace_s"} == {"phases.attributed_pct.images"}
    assert out["phases.attributed_pct.images"] == 0.0
    monkeypatch.setattr(P, "newest_xplane", lambda: None)
    assert set(exe_run.read({"suffix": "images"})) <= {"exe_run.trace_s"}
    assert phases.read({"suffix": "images"}) == {}


def test_cpu_run_of_a_preset_cell_holds_the_programs_spans(preset_run):
    """The host plane of a real trace, read with ``ProfileData``: the spans
    are there with the ``pt:`` prefix on the steps' thread; without a chip
    there is no operation, so no idle part and no phase."""
    preset_run("tiny_bert.static", 2 ** 31 + 5, 2, 1)
    path = P.newest_xplane()
    assert os.sep + "tiny_bert.static" + os.sep in path
    P.load.cache_clear()
    tr = P.load(path)
    assert len(tr.steps) >= 10 and not tr.ops
    names = {n for n, _, _ in tr.spans}
    assert {"executor/run", "executor/prepare", "executor/stage",
            "executor/launch", "executor/writeback",
            "executor/fetch"} <= names
    out = exe_run.read({"suffix": "tokens", "trace": None})
    assert out["exe_run.trace_s"] > 0
    for part in ("stage", "launch", "writeback"):
        assert 0 < out["exe_run.%s_ms.tokens" % part] < 1e3
    assert not [k for k in out if ".idle_" in k]
    assert phases.read({"suffix": "tokens"}) == {}


def test_the_recorded_trace_of_resnet50_on_the_chip():
    """Two steps of ``resnet50.static_b128`` on a v5e (this PR's first
    traced run, cold-compiled), reduced to steps, program spans and (scope,
    start, end) of chip 0's operations."""
    with gzip.open(os.path.join(FIXTURES, "resnet50_two_steps.json.gz"),
                   "rt") as f:
        tr = P.ProgramTrace.from_json(json.load(f))
    with open(os.path.join(FIXTURES,
                           "resnet50_two_steps.expected.json")) as f:
        want = json.load(f)
    idle = P.idle_parts(tr)
    times = P.phase_times(tr)
    got = {"stage_ms": P.span_ms(tr, "executor/stage"),
           "launch_ms": P.span_ms(tr, "executor/launch"),
           "writeback_ms": P.span_ms(tr, "executor/writeback"),
           "attributed_pct": 100.0 * times["sum_attributed"]
           / times["sum_ops"]}
    for part in P.IDLE_PARTS + ("other",):
        got["idle_%s_ms" % part] = P.median_ms(idle[part])
    for role in phases.PHASES:
        got[role + "_ms"] = P.median_ms(times["per_step"][role])
    assert set(got) == set(want["metrics"])   # all eleven
    for name, value in want["metrics"].items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    # the parts add up to the window's idle time exactly
    lo, hi = tr.steps[0][0], tr.steps[-1][1]
    parts = sum(sum(idle[k]) for k in P.IDLE_PARTS + ("other",))
    assert parts == (hi - lo) - times["busy"]
    assert parts == want["window_idle_ns"]
