"""The readers of the short-convolution mixer's time and of grouped-query
attention's roofline share: which operations they take for the op and for
the mixer outside it (scoped and unscoped ``mul``s, the op and its gradient
op), the union per step and the shares, on events written by hand and on a
small trace directory that carries nothing but its name."""
import pytest

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import gqa, kda, shortconv

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
NAMES = {
    "gate": JIT + "forward/short_conv_gate/shortconv/mul",
    "gate_again": JIT + "backward/short_conv_gate/shortconv/mul",
    "gate_bwd": JIT + "backward/short_conv_gate_grad/shortconv/concatenate",
    "in_w": JIT + "forward/mul/shortconv/dot_general",
    "out_bwd": JIT + "backward/mul_grad/shortconv/transpose(jvp())/"
               "dot_general",
    "q_w": JIT + "forward/mul/dot_general_q",
    "q_norm": JIT + "forward/rms_norm/rsqrt",
    "attn": JIT + "forward/flash_attention/pallas_call",
    "attn_bwd": JIT + "backward/flash_attention_grad/pallas_call",
    "fc": JIT + "forward/mul/dot_general",
    "head": JIT + "forward/mul/dot_general_head",
    "route": JIT + "forward/moe_topk/route/dot_general",
    "adam": JIT + "optimizer/adam/shortconv_like_name",
}


def test_which_operations_belong_to_which_part():
    for name in ("gate", "gate_again", "gate_bwd"):
        assert shortconv.is_gate("%f", NAMES[name]), name
        assert not shortconv.is_projection("%f", NAMES[name]), name
    for name in ("in_w", "out_bwd"):
        assert shortconv.is_projection("%f", NAMES[name]), name
        assert not shortconv.is_gate("%f", NAMES[name]), name
        assert not kda.is_projection("%f", NAMES[name]), name
    for other in ("q_w", "q_norm", "attn", "attn_bwd", "fc", "head", "route",
                  "adam"):
        assert not shortconv.is_gate("%f", NAMES[other]), other
        assert not shortconv.is_projection("%f", NAMES[other]), other
    assert not shortconv.is_gate("%f", "")
    assert not shortconv.is_projection("%f", "")


def events_and_steps():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("in_w", 0, 6 * MS), ("gate", 6 * MS, 7 * MS),
              ("fc", 7 * MS, 30 * MS), ("q_w", 30 * MS, 33 * MS),
              ("q_norm", 33 * MS, 34 * MS), ("attn", 34 * MS, 40 * MS),
              ("route", 40 * MS, 42 * MS), ("head", 42 * MS, 50 * MS),
              ("attn_bwd", 50 * MS, 64 * MS),
              ("gate_again", 64 * MS, 65 * MS),
              ("gate_bwd", 65 * MS, 67 * MS), ("out_bwd", 67 * MS, 75 * MS),
              ("in_w", 104 * MS, 110 * MS), ("gate", 110 * MS, 111 * MS),
              ("attn", 120 * MS, 126 * MS), ("attn_bwd", 150 * MS, 166 * MS),
              ("gate_again", 166 * MS, 167 * MS),
              ("gate_bwd", 167 * MS, 171 * MS),
              ("out_bwd", 171 * MS, 181 * MS), ("adam", 181 * MS, 190 * MS)]
    return steps, events


def test_union_per_step():
    steps, events = events_and_steps()
    assert S.per_step_ns(events, NAMES, steps, shortconv.is_gate) == [
        4 * MS, 6 * MS]
    assert S.per_step_ns(events, NAMES, steps, shortconv.is_projection) == [
        14 * MS, 16 * MS]


def test_the_readers_end_to_end_on_hand_written_events(monkeypatch, capsys):
    """``read`` as a traced run calls it: the newest trace is the toy cell's,
    the chip's peaks are given, every metric the manifest lists for the
    readers comes back finite and the shares are under 100 %."""
    from benchmarks.lib import harness

    from .test_tiny_lfm2 import LFM2_PRESET

    monkeypatch.setattr(harness, "MANIFEST", LFM2_PRESET)
    path = "/x/.bench_trace/tiny_lfm2.static/plugins/profile/1/a.xplane.pb"
    cfg, traffic, flops = S.cell_of(path)
    assert cfg["name"] == "tiny_lfm2" and traffic["seq_len"] == 36
    steps, events = events_and_steps()
    # hand-written times are far above what 72 toy tokens need: shrink them
    # so that the shares are numbers one can read
    steps = [(a // 10 ** 4, b // 10 ** 4) for a, b in steps]
    events = [(n, a // 10 ** 4, b // 10 ** 4) for n, a, b in events]
    monkeypatch.setattr(S, "load", lambda: (path, steps, events, NAMES))
    ctx = {"suffix": "tokens",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = shortconv.read(ctx)
    assert set(got) == {"shortconv.gate_ms.tokens",
                        "shortconv.gate_roofline_pct.tokens",
                        "shortconv.project_ms.tokens"}
    assert got["shortconv.gate_ms.tokens"] == pytest.approx(5e-4)
    assert got["shortconv.project_ms.tokens"] == pytest.approx(15e-4)
    # the share by hand: the toy cell's two convolution sublayers, 72 tokens
    # a step, a step three forwards, bound by the bytes
    tokens = traffic["batch"] * traffic["seq_len"]
    ops, moved = flops.gate_ops_and_bytes(cfg, tokens)
    assert moved / 819e9 > ops / 197e12
    assert got["shortconv.gate_roofline_pct.tokens"] == pytest.approx(
        100 * 3 * 2 * moved / 819e9 / 5e-7)
    assert 0 < got["shortconv.gate_roofline_pct.tokens"] < 100
    # attention's share: one ``*`` sublayer over the 21 ms of its kernels
    ops, moved = flops.attend_ops_and_bytes(cfg, tokens)
    share = gqa.read(ctx)
    assert set(share) == {"gqa.attend_roofline_pct.tokens"}
    assert share["gqa.attend_roofline_pct.tokens"] == pytest.approx(
        100 * max(3 * ops / 197e12, 3 * moved / 819e9) / 21e-7)
    assert 0 < share["gqa.attend_roofline_pct.tokens"] < 100
    # without the chip's peaks (the tests' stand-in for a chip) no share
    assert set(shortconv.read({"suffix": "tokens", "peaks": None})) == {
        "shortconv.gate_ms.tokens", "shortconv.project_ms.tokens"}
    assert gqa.read({"suffix": "tokens", "peaks": None}) == {}
    out = capsys.readouterr().out
    assert "# shortconv: read" in out and "# gqa:" in out


def test_a_program_without_the_op_reads_nothing(monkeypatch):
    """The parent commit's program has no such op: no metric, no error."""
    steps, events = events_and_steps()
    others = [e for e in events if e[0] in ("fc", "route", "adam")]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, others, NAMES))
    assert shortconv.read({"suffix": "tokens", "peaks": None}) == {}
    assert gqa.read({"suffix": "tokens", "peaks": None}) == {}
    monkeypatch.undo()
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    assert shortconv.read({"suffix": "tokens"}) == {}
    assert gqa.read({"suffix": "tokens"}) == {}
