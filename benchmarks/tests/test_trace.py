"""The trace reduction: interval arithmetic on a trace written by hand, whose
answers are known exactly, and on the trace recorded on the chip."""
import gzip
import json
import os

import pytest

from benchmarks.lib import trace as T

from .conftest import REPO

MS = 1_000_000


def hand_trace():
    """Two steps on two chips. Step 1 spans 0-100 ms, step 2 spans 110-200 ms
    with a fetch span inside at 180-200 ms."""
    chip0 = [
        ("matmul fusion:kOutput", 10 * MS, 40 * MS),
        ("all-reduce all-reduce", 30 * MS, 60 * MS),   # 10 ms hidden
        ("add fusion:kLoop", 60 * MS, 80 * MS),
        ("matmul fusion:kOutput", 120 * MS, 150 * MS),
        ("all-reduce all-reduce", 150 * MS, 170 * MS),   # all exposed
    ]
    chip1 = [("matmul fusion:kOutput", 10 * MS, 50 * MS)]
    spans = [("step", 0, 100 * MS), ("step", 110 * MS, 200 * MS),
             ("fetch", 180 * MS, 200 * MS)]
    return T.Trace({0: chip0, 1: chip1}, spans)


def test_interval_arithmetic():
    assert T.merge([(5, 7), (0, 3), (2, 4), (7, 7)]) == [(0, 4), (5, 7)]
    assert T.total([(0, 4), (5, 7)]) == 6
    assert T.gaps([(0, 4), (5, 7)], 0, 10) == [(4, 5), (7, 10)]
    assert T.gaps([], 2, 5) == [(2, 5)]
    assert T.uncovered([(0, 10)], [(2, 4), (8, 12)]) == 6
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_reduction_of_the_hand_trace():
    r = T.reduce(hand_trace())
    assert r["n_steps"] == 2
    assert r["window_s"] == pytest.approx(0.200)
    # chip 0 runs operations 10-80 and 120-170: 120 ms; chip 1 40
    assert r["busy_s_chip0"] == pytest.approx(0.120)
    assert r["busy_s"] == pytest.approx(0.080)
    assert r["collective_s"] == pytest.approx(0.050)
    assert r["exposed_collective_s"] == pytest.approx(0.040)
    # step 1 waits 10 ms for its first op and 20 ms after its last;
    # step 2 waits 10 ms and 30 ms
    assert r["dispatch_s"] == pytest.approx([0.030, 0.040])
    assert r["step_busy_s"] == pytest.approx([0.070, 0.050])
    assert r["step_wall_s"] == pytest.approx([0.100, 0.090])
    assert r["device_ops"][0] == ["matmul fusion:kOutput",
                                  pytest.approx(0.060)]
    # the longest gaps, each named by the span the host was in
    # 80-120 ms: its middle falls between the two step spans
    assert r["idle_gaps"][0] == ["between_steps", pytest.approx(0.040)]
    assert ["fetch", pytest.approx(0.030)] in r["idle_gaps"]     # 170-200 ms
    assert ["step", pytest.approx(0.010)] in r["idle_gaps"]      # 0-10 ms


def test_idle_gap_between_steps_is_named_so():
    tr = T.Trace({0: [("a", 0, 10), ("a", 90, 100)]},
                 [("step", 0, 20), ("step", 80, 100)])
    r = T.reduce(tr)
    assert r["idle_gaps"][0][0] == "between_steps"


def test_short_name_of_an_hlo_instruction():
    text = ("%convert_reduce_fusion.54 = (f32[32,512]{1,0:T(8,128)S(1)}, "
            "bf16[32,512,768]{2,1,0}) fusion(bf16[768]{0} %convert.1), "
            "kind=kOutput, calls=%fused_computation.1289")
    assert T.short_name(text) == "convert_reduce_fusion fusion:kOutput"
    assert T.short_name("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), "
                        "replica_groups={}") == "all-reduce all-reduce"
    assert T.is_collective("all-reduce all-reduce")
    assert not T.is_collective("convert_reduce_fusion fusion:kOutput")


def test_recorded_trace():
    """A data-parallel BERT step recorded on four v5e chips (two steps, chips
    0 and 1): the numbers are what the reduction read on the day, and the
    invariants hold for any trace."""
    path = os.path.join(REPO, "benchmarks", "fixtures", "dp4_two_steps.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with gzip.open(path, "rt") as f:
        tr = T.Trace.from_json(json.load(f))
    r = T.reduce(tr)
    assert r["n_steps"] == 2 and len(tr.chips) == 2
    assert 0 < r["busy_s_chip0"] <= r["window_s"]
    assert 0 < r["exposed_collective_s"] <= r["collective_s"] <= r["busy_s_chip0"]
    assert sum(d for _, d in r["device_ops"]) <= r["sum_ops_s"] + 1e-9
    assert all(b <= w for b, w in zip(r["step_busy_s"], r["step_wall_s"]))
    with open(os.path.join(REPO, "benchmarks", "fixtures",
                           "dp4_two_steps.expected.json")) as f:
        expected = json.load(f)
    for key, value in expected.items():
        assert r[key] == pytest.approx(value, rel=1e-9), key
