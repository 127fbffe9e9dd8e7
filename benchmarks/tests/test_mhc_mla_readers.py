"""The readers of the hyper-connected residual path's and latent attention's
time: which operations each takes, the union per step and the roofline
shares, on events written by hand and on a small trace directory that carries
nothing but its name."""
import pytest

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import mhc, mla

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
NAMES = {
    "maps": JIT + "forward/mhc_pre/maps/dot_general",
    "rounds": JIT + "backward/mhc_pre/maps/div",
    "mix_in": JIT + "forward/mhc_pre/mix/reduce_sum",
    "maps_bwd": JIT + "backward/mhc_pre_grad/transpose(jvp(maps))/dot_general",
    "mix_in_bwd": JIT + "backward/mhc_pre_grad/transpose(jvp(mix))/mul",
    "cast": JIT + "backward/mhc_pre_grad/convert_element_type",
    "mix_out": JIT + "forward/mhc_post/mix/add",
    "mix_out_bwd": JIT + "backward/mhc_post_grad/transpose(jvp(mix))/pad",
    "q_a": JIT + "forward/mul/latent/dot_general",
    "q_norm": JIT + "backward/rms_norm/latent/rsqrt",
    "rotary": JIT + "forward/rotary_embedding/latent/cos",
    "o_bwd": JIT + "backward/mul_grad/latent/transpose(jvp())/dot_general",
    "attn": JIT + "forward/flash_attention/latent/pallas_call",
    "attn_bwd": JIT + "backward/flash_attention_grad/latent/pallas_call",
    "fc": JIT + "forward/mul/dot_general",
    "norm": JIT + "forward/rms_norm/rsqrt",
    "route": JIT + "forward/moe_topk/route/dot_general",
    "adam": JIT + "optimizer/adam/latent_like_name",
}


def test_which_operations_belong_to_which_part():
    for name in ("maps", "rounds", "maps_bwd"):
        assert mhc.part_of("%f", NAMES[name]) == "maps", name
    for name in ("mix_in", "mix_in_bwd", "mix_out", "mix_out_bwd", "cast"):
        assert mhc.part_of("%f", NAMES[name]) == "mix", name
    for name in ("q_a", "q_norm", "rotary", "o_bwd"):
        assert mla.is_projection("%f", NAMES[name]), name
        assert mhc.part_of("%f", NAMES[name]) is None, name
    for other in ("attn", "attn_bwd", "fc", "norm", "route", "adam", "maps",
                  "mix_out"):
        assert not mla.is_projection("%f", NAMES[other]), other
    for other in ("attn", "fc", "norm", "route", "adam"):
        assert mhc.part_of("%f", NAMES[other]) is None, other
    assert mhc.part_of("%f", "") is None and not mla.is_projection("%f", "")


def events_and_steps():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("maps", 0, 4 * MS), ("mix_in", 4 * MS, 6 * MS),
              ("norm", 6 * MS, 7 * MS), ("q_a", 7 * MS, 12 * MS),
              ("rotary", 12 * MS, 13 * MS), ("attn", 13 * MS, 33 * MS),
              ("mix_out", 33 * MS, 38 * MS), ("fc", 38 * MS, 50 * MS),
              ("rounds", 50 * MS, 52 * MS), ("q_norm", 52 * MS, 53 * MS),
              ("attn_bwd", 53 * MS, 83 * MS), ("o_bwd", 83 * MS, 90 * MS),
              ("mix_out_bwd", 90 * MS, 94 * MS), ("cast", 94 * MS, 95 * MS),
              ("maps_bwd", 95 * MS, 99 * MS),
              ("maps", 104 * MS, 110 * MS), ("mix_in", 110 * MS, 112 * MS),
              ("q_a", 112 * MS, 118 * MS), ("attn", 118 * MS, 138 * MS),
              ("mix_out", 138 * MS, 144 * MS),
              ("attn_bwd", 150 * MS, 178 * MS), ("o_bwd", 178 * MS, 186 * MS),
              ("mix_out_bwd", 186 * MS, 192 * MS),
              ("maps_bwd", 192 * MS, 198 * MS)]
    return steps, events


def test_union_per_step():
    steps, events = events_and_steps()

    def ns(part):
        return S.per_step_ns(events, NAMES, steps,
                             lambda e, o: mhc.part_of(e, o) == part)

    assert ns("maps") == [10 * MS, 12 * MS]
    assert ns("mix") == [12 * MS, 14 * MS]
    assert S.per_step_ns(events, NAMES, steps, mla.is_projection) == [
        14 * MS, 14 * MS]


def test_the_readers_end_to_end_on_hand_written_events(monkeypatch, capsys):
    """``read`` as a traced run calls it: the newest trace is the toy cell's,
    the chip's peaks are given, every metric the manifest lists for the two
    readers comes back finite and no share is over 100 %."""
    from benchmarks.lib import harness

    from .test_tiny_xing4 import XING4_PRESET

    monkeypatch.setattr(harness, "MANIFEST", XING4_PRESET)
    path = "/x/.bench_trace/tiny_xing4.static/plugins/profile/1/a.xplane.pb"
    cfg, traffic, flops = S.cell_of(path)
    assert cfg["name"] == "tiny_xing4" and traffic["seq_len"] == 32
    steps, events = events_and_steps()
    monkeypatch.setattr(S, "load", lambda: (path, steps, events, NAMES))
    ctx = {"suffix": "tokens",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = dict(mhc.read(ctx), **mla.read(ctx))
    assert set(got) == {
        "mhc.maps_ms.tokens", "mhc.mix_ms.tokens", "mhc.roofline_pct.tokens",
        "mla.project_ms.tokens", "mla.attend_roofline_pct.tokens"}
    assert got["mhc.maps_ms.tokens"] == pytest.approx(11.0)
    assert got["mhc.mix_ms.tokens"] == pytest.approx(13.0)
    assert got["mla.project_ms.tokens"] == pytest.approx(14.0)
    # the shares by hand: the toy cell's four sublayers, two of them latent
    # attention, 64 tokens a step
    tokens = traffic["batch"] * traffic["seq_len"]
    ops, moved = flops.mhc_ops_and_bytes(cfg, tokens)
    least = max(3 * 4 * ops / 197e12, 3 * 4 * moved / 819e9)
    assert got["mhc.roofline_pct.tokens"] == pytest.approx(
        100 * least / 0.024)
    ops, moved = flops.attend_ops_and_bytes(cfg, tokens)
    least = max(3 * 2 * ops / 197e12, 3 * 2 * moved / 819e9)
    # attention's kernels: 50 ms and 48 ms in the two steps
    assert got["mla.attend_roofline_pct.tokens"] == pytest.approx(
        100 * least / 0.049)
    for name in ("mhc.roofline_pct.tokens", "mla.attend_roofline_pct.tokens"):
        assert 0 < got[name] < 100
    # without the chip's peaks (the tests' stand-in for a chip) no share
    none = {"suffix": "tokens", "peaks": None}
    assert set(dict(mhc.read(none), **mla.read(none))) == {
        "mhc.maps_ms.tokens", "mhc.mix_ms.tokens", "mla.project_ms.tokens"}
    out = capsys.readouterr().out
    assert "# mhc: read" in out and "# mla: read" in out


def test_a_program_without_the_ops_reads_nothing(monkeypatch):
    """The parent commit's program has neither: no metric, no error."""
    steps, events = events_and_steps()
    others = [e for e in events if e[0] in ("attn", "attn_bwd", "fc", "norm")]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, others, NAMES))
    assert mhc.read({"suffix": "tokens", "peaks": None}) == {}
    assert mla.read({"suffix": "tokens", "peaks": None}) == {}
    monkeypatch.undo()
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    assert mhc.read({"suffix": "tokens"}) == {}
    assert mla.read({"suffix": "tokens"}) == {}
