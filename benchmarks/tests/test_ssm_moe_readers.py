"""The readers of the scan's and the routed experts' time: which operations
each takes, the union per step and the roofline share, on events written by
hand and on a small trace directory that carries nothing but its name."""
import pytest

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import moe, ssm

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
RAGGED = ('%ragged-dot-none.4 = f32[6144,1856]{1,0} custom-call(%a, %b), '
          'custom_call_target="tpu_custom_call"')
NAMES = {
    "scan": JIT + "forward/ssd_chunk_scan/dot_general",
    "scan_bwd": JIT + "backward/ssd_chunk_scan_grad/transpose(jvp())/exp",
    "route": JIT + "forward/moe_topk/route/dot_general",
    "route_bwd": JIT + "backward/moe_topk_grad/transpose(jvp(route))/mul",
    "sort": JIT + "forward/moe_topk/experts/sort",
    "slow": JIT + "backward/moe_topk_grad/jvp(experts)/cond/branch_1_fun/x",
    "fc": JIT + "forward/mul/dot_general",
    "attn": JIT + "forward/flash_attention/pallas_call",
}


def test_which_operations_belong_to_which_reader():
    assert ssm.is_scan("%fusion.1", NAMES["scan"])
    assert ssm.is_scan("%fusion.2", NAMES["scan_bwd"])
    assert not ssm.is_scan("%fusion.3", NAMES["fc"])
    assert not ssm.is_scan(RAGGED, "")
    assert moe.part_of("%f", NAMES["route"]) == "route"
    assert moe.part_of("%f", NAMES["route_bwd"]) == "route"
    assert moe.part_of("%f", NAMES["sort"]) == "experts"
    assert moe.part_of("%f", NAMES["slow"]) == "experts"
    # the TPU compiler's grouped-matmul kernel, whatever its metadata says
    assert moe.part_of(RAGGED, "") == "experts"
    assert moe.part_of(RAGGED, "ragged-dot-none") == "experts"
    for other in ("fc", "attn", "scan"):
        assert moe.part_of("%f", NAMES[other]) is None
    # an operand called ragged-dot does not make an instruction one
    assert moe.part_of("%fusion.9 = f32[8] fusion(%ragged-dot-none.4)",
                       NAMES["fc"]) is None


def test_union_per_step_and_roofline():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("scan", 10 * MS, 20 * MS), ("scan_bwd", 15 * MS, 30 * MS),
              ("fc", 30 * MS, 60 * MS), ("route", 60 * MS, 62 * MS),
              ("sort", 62 * MS, 70 * MS), (RAGGED, 68 * MS, 80 * MS),
              ("scan", 100 * MS, 110 * MS), ("slow", 150 * MS, 153 * MS)]
    assert S.per_step_ns(events, NAMES, steps, ssm.is_scan) == [
        24 * MS, 6 * MS]
    assert S.per_step_ns(
        events, NAMES, steps,
        lambda e, o: moe.part_of(e, o) == "experts") == [18 * MS, 3 * MS]
    assert S.per_step_ns(
        events, NAMES, steps,
        lambda e, o: moe.part_of(e, o) == "route") == [2 * MS, 0]
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    # 1 TFLOP needs 10 ms, 1 GB needs 1 ms: bound by compute, 50 % of 20 ms
    assert S.roofline_pct(1e12, 1e9, peaks, 0.020) == (
        pytest.approx(50.0), "compute")
    assert S.roofline_pct(1e9, 5e9, peaks, 0.010) == (
        pytest.approx(50.0), "memory")


def test_a_reader_finds_its_cell_from_the_trace_directory(monkeypatch):
    from benchmarks.lib import harness

    from .test_tiny_nemotron import NEMOTRON_PRESET

    monkeypatch.setattr(harness, "MANIFEST", NEMOTRON_PRESET)
    cfg, traffic, flops = S.cell_of(
        "/x/.bench_trace/tiny_nemotron.static/plugins/profile/1/a.xplane.pb")
    assert cfg["name"] == "tiny_nemotron" and traffic["seq_len"] == 24
    ops, moved = flops.scan_ops_and_bytes(cfg, 48)
    assert ops == 2 * 48 * (8 * 2 * 16 + 8 * 8 * 8 + 2 * 8 * 8 * 16)
    assert flops.expected_slots(cfg, 48) == 36


def test_readers_return_nothing_without_a_trace(monkeypatch):
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    assert ssm.read({"suffix": "tokens"}) == {}
    assert moe.read({"suffix": "tokens"}) == {}


def test_both_readers_end_to_end_on_hand_written_events(monkeypatch, capsys):
    """``read`` as a traced run calls it: the newest trace is the toy
    cell's, the chip's peaks are given, and every metric the manifest lists
    for the readers comes back finite, no share over 100 %."""
    from benchmarks.lib import harness

    from .test_tiny_nemotron import NEMOTRON_PRESET

    monkeypatch.setattr(harness, "MANIFEST", NEMOTRON_PRESET)
    path = "/x/.bench_trace/tiny_nemotron.static/plugins/profile/1/a.xplane.pb"
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("scan", 10 * MS, 20 * MS), ("scan_bwd", 20 * MS, 30 * MS),
              ("route", 60 * MS, 62 * MS), ("sort", 62 * MS, 70 * MS),
              (RAGGED, 70 * MS, 80 * MS), ("scan", 110 * MS, 130 * MS),
              ("route_bwd", 140 * MS, 142 * MS), ("slow", 150 * MS, 168 * MS)]
    monkeypatch.setattr(S, "load", lambda: (path, steps, events, NAMES))
    ctx = {"suffix": "tokens",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = {**ssm.read(ctx), **moe.read(ctx)}
    assert set(got) == {
        "ssm.scan_ms.tokens", "ssm.scan_roofline_pct.tokens",
        "moe.experts_ms.tokens", "moe.route_ms.tokens",
        "moe.experts_roofline_pct.tokens"}
    assert got["ssm.scan_ms.tokens"] == pytest.approx(20.0)
    assert got["moe.experts_ms.tokens"] == pytest.approx(18.0)
    assert got["moe.route_ms.tokens"] == pytest.approx(2.0)
    for name in ("ssm.scan_roofline_pct.tokens",
                 "moe.experts_roofline_pct.tokens"):
        assert 0 < got[name] < 100
    # without the chip's peaks (the tests' stand-in for a chip) no share
    assert set(ssm.read({"suffix": "tokens", "peaks": None})) == {
        "ssm.scan_ms.tokens"}
    lines = capsys.readouterr().out
    assert "# ssm: read" in lines and "# moe: read" in lines
    assert "ragged-dot" in lines
