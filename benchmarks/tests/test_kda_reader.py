"""The reader of the delta-rule mixer's time: which operations it takes for
the op and for the mixer outside it, the union per step and the roofline
share, on events written by hand and on a small trace directory that carries
nothing but its name."""
import pytest

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import kda, mla

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
NAMES = {
    "scan": JIT + "forward/kda_chunk/while/body/dot_general",
    "scan_gram": JIT + "backward/kda_chunk/checkpoint/reduce_sum",
    "scan_bwd": JIT + "backward/kda_chunk_grad/transpose(jvp())/dot_general",
    "q_w": JIT + "forward/mul/kda/dot_general",
    "conv": JIT + "backward/causal_conv1d/kda/mul",
    "gate_bias": JIT + "forward/elementwise_add/kda/add",
    "o_norm": JIT + "forward/rms_norm/kda/rsqrt",
    "o_bwd": JIT + "backward/mul_grad/kda/transpose(jvp())/dot_general",
    "cast": JIT + "forward/cast/kda/convert_element_type",
    "latent_q": JIT + "forward/mul/latent/dot_general",
    "attn": JIT + "forward/flash_attention/latent/pallas_call",
    "fc": JIT + "forward/mul/dot_general",
    "norm": JIT + "forward/rms_norm/rsqrt",
    "route": JIT + "forward/moe_topk/route/dot_general",
    "adam": JIT + "optimizer/adam/kda_like_name",
}


def test_which_operations_belong_to_which_part():
    for name in ("scan", "scan_gram", "scan_bwd"):
        assert kda.is_scan("%f", NAMES[name]), name
        assert not kda.is_projection("%f", NAMES[name]), name
    for name in ("q_w", "conv", "gate_bias", "o_norm", "o_bwd", "cast"):
        assert kda.is_projection("%f", NAMES[name]), name
        assert not kda.is_scan("%f", NAMES[name]), name
        assert not mla.is_projection("%f", NAMES[name]), name
    for other in ("latent_q", "attn", "fc", "norm", "route", "adam"):
        assert not kda.is_scan("%f", NAMES[other]), other
        assert not kda.is_projection("%f", NAMES[other]), other
    assert not kda.is_scan("%f", "") and not kda.is_projection("%f", "")


def events_and_steps():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("q_w", 0, 6 * MS), ("conv", 6 * MS, 7 * MS),
              ("scan", 7 * MS, 17 * MS), ("o_norm", 17 * MS, 18 * MS),
              ("fc", 18 * MS, 40 * MS), ("latent_q", 40 * MS, 44 * MS),
              ("attn", 44 * MS, 54 * MS), ("scan_gram", 54 * MS, 60 * MS),
              ("scan_bwd", 60 * MS, 84 * MS), ("o_bwd", 84 * MS, 90 * MS),
              ("q_w", 104 * MS, 110 * MS), ("scan", 110 * MS, 122 * MS),
              ("gate_bias", 122 * MS, 123 * MS), ("fc", 123 * MS, 140 * MS),
              ("scan_gram", 150 * MS, 156 * MS),
              ("scan_bwd", 156 * MS, 182 * MS), ("o_bwd", 182 * MS, 190 * MS),
              ("cast", 190 * MS, 191 * MS)]
    return steps, events


def test_union_per_step():
    steps, events = events_and_steps()
    assert S.per_step_ns(events, NAMES, steps, kda.is_scan) == [
        40 * MS, 44 * MS]
    assert S.per_step_ns(events, NAMES, steps, kda.is_projection) == [
        14 * MS, 16 * MS]


def test_the_reader_end_to_end_on_hand_written_events(monkeypatch, capsys):
    """``read`` as a traced run calls it: the newest trace is the toy cell's,
    the chip's peaks are given, every metric the manifest lists for the
    reader comes back finite and the share is under 100 %."""
    from benchmarks.lib import harness

    from .test_tiny_kimi_linear import KIMI_PRESET

    monkeypatch.setattr(harness, "MANIFEST", KIMI_PRESET)
    path = ("/x/.bench_trace/tiny_kimi_linear.static/plugins/profile/1/"
            "a.xplane.pb")
    cfg, traffic, flops = S.cell_of(path)
    assert cfg["name"] == "tiny_kimi_linear" and traffic["seq_len"] == 36
    steps, events = events_and_steps()
    monkeypatch.setattr(S, "load", lambda: (path, steps, events, NAMES))
    ctx = {"suffix": "tokens",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = kda.read(ctx)
    assert set(got) == {"kda.scan_ms.tokens", "kda.scan_roofline_pct.tokens",
                        "kda.project_ms.tokens"}
    assert got["kda.scan_ms.tokens"] == pytest.approx(42.0)
    assert got["kda.project_ms.tokens"] == pytest.approx(15.0)
    # the share by hand: the toy cell's two delta-rule sublayers, 72 tokens
    # a step, a step three forwards
    tokens = traffic["batch"] * traffic["seq_len"]
    ops, moved = flops.kda_ops_and_bytes(cfg, tokens)
    least = max(3 * 2 * ops / 197e12, 3 * 2 * moved / 819e9)
    assert got["kda.scan_roofline_pct.tokens"] == pytest.approx(
        100 * least / 0.042)
    assert 0 < got["kda.scan_roofline_pct.tokens"] < 100
    # the latent reader still finds its own mixer in such a trace
    assert mla.read(ctx)["mla.project_ms.tokens"] == pytest.approx(2.0)
    # without the chip's peaks (the tests' stand-in for a chip) no share
    assert set(kda.read({"suffix": "tokens", "peaks": None})) == {
        "kda.scan_ms.tokens", "kda.project_ms.tokens"}
    assert "# kda: read" in capsys.readouterr().out


def test_a_program_without_the_op_reads_nothing(monkeypatch):
    """The parent commit's program has no such op: no metric, no error."""
    steps, events = events_and_steps()
    others = [e for e in events if e[0] in ("attn", "latent_q", "fc")]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, others, NAMES))
    assert kda.read({"suffix": "tokens", "peaks": None}) == {}
    monkeypatch.undo()
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    assert kda.read({"suffix": "tokens"}) == {}
