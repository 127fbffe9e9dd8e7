"""The reader of learned sparse attention's time: which operations it takes
for the index scores, the selection and the indexer's loss, the union per
step and the two roofline shares, on events written by hand and on a small
trace directory that carries nothing but its name."""
import pytest

from benchmarks.layer_metrics import _scoped as S
from benchmarks.layer_metrics import sparse_attn

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
NAMES = {
    "project": JIT + "forward/attn_index_project/score/dot_general",
    "project_bwd": JIT + "backward/attn_index_project_grad/"
                         "transpose(jvp(score))/dot_general",
    "score": JIT + "forward/attn_index_select/while/body/score/dot_general",
    "count": JIT + "backward/attn_index_select/while/body/select/while/"
                   "body/reduce_sum",
    "pad": JIT + "forward/attn_index_select/concatenate",
    "kl": JIT + "forward/attn_index_loss/while/body/loss/exp",
    "kl_score": JIT + "forward/attn_index_loss/while/body/score/dot_general",
    "grad_score": JIT + "backward/attn_index_loss_grad/while/body/"
                        "score/transpose/dot_general",
    "grad_kl": JIT + "backward/attn_index_loss_grad/while/body/loss/sub",
    "attn": JIT + "forward/flash_attention/pallas_call",
    "attn_bwd": JIT + "backward/flash_attention_grad/pallas_call",
    "route": JIT + "forward/moe_topk/route/dot_general",
    "fc": JIT + "forward/mul/dot_general",
    "rotary": JIT + "forward/rotary_embedding/cos",
}


def test_which_operations_belong_to_which_part():
    part = sparse_attn.part_of
    for name in ("project", "project_bwd", "score", "kl_score", "grad_score"):
        assert part("%f", NAMES[name]) == "score", name
    for name in ("count", "pad"):      # no inner scope: the op's own part
        assert part("%f", NAMES[name]) == "select", name
    for name in ("kl", "grad_kl"):
        assert part("%f", NAMES[name]) == "loss", name
    for other in ("attn", "attn_bwd", "route", "fc", "rotary"):
        assert part("%f", NAMES[other]) is None, other
    assert part("%f", "") is None


def events_and_steps():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    events = [("project", 0, 2 * MS), ("score", 2 * MS, 12 * MS),
              ("count", 12 * MS, 20 * MS), ("pad", 20 * MS, 21 * MS),
              ("attn", 21 * MS, 41 * MS), ("kl_score", 41 * MS, 51 * MS),
              ("kl", 50 * MS, 56 * MS), ("fc", 56 * MS, 60 * MS),
              ("grad_score", 60 * MS, 80 * MS), ("grad_kl", 80 * MS, 84 * MS),
              ("attn_bwd", 84 * MS, 99 * MS),
              ("score", 104 * MS, 114 * MS), ("count", 114 * MS, 124 * MS),
              ("attn", 124 * MS, 144 * MS), ("grad_score", 150 * MS, 182 * MS),
              ("kl", 182 * MS, 188 * MS), ("attn_bwd", 188 * MS, 199 * MS)]
    return steps, events


def test_union_per_step():
    steps, events = events_and_steps()

    def ns(part):
        return S.per_step_ns(events, NAMES, steps,
                             lambda e, o: sparse_attn.part_of(e, o) == part)

    assert ns("score") == [42 * MS, 42 * MS]
    assert ns("select") == [9 * MS, 10 * MS]
    assert ns("loss") == [10 * MS, 6 * MS]


def test_the_reader_end_to_end_on_hand_written_events(monkeypatch, capsys):
    """``read`` as a traced run calls it: the newest trace is the toy cell's,
    the chip's peaks are given, every metric the manifest lists for the
    reader comes back finite and no share is over 100 %."""
    from benchmarks.lib import harness

    from .test_tiny_keye import KEYE_PRESET

    monkeypatch.setattr(harness, "MANIFEST", KEYE_PRESET)
    path = "/x/.bench_trace/tiny_keye.static/plugins/profile/1/a.xplane.pb"
    cfg, traffic, flops = S.cell_of(path)
    assert cfg["name"] == "tiny_keye" and traffic["seq_len"] == 32
    steps, events = events_and_steps()
    monkeypatch.setattr(S, "load", lambda: (path, steps, events, NAMES))
    ctx = {"suffix": "tokens",
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    got = sparse_attn.read(ctx)
    assert set(got) == {
        "sparse_attn.index_ms.tokens", "sparse_attn.select_ms.tokens",
        "sparse_attn.index_loss_ms.tokens",
        "sparse_attn.index_roofline_pct.tokens",
        "sparse_attn.attend_roofline_pct.tokens"}
    assert got["sparse_attn.index_ms.tokens"] == pytest.approx(42.0)
    assert got["sparse_attn.select_ms.tokens"] == pytest.approx(9.5)
    assert got["sparse_attn.index_loss_ms.tokens"] == pytest.approx(8.0)
    # the shares by hand: two S layers of the toy cell, 64 tokens a step
    tokens = traffic["batch"] * traffic["seq_len"]
    ops, moved = flops.index_ops_and_bytes(cfg, tokens)
    least = max(2 * ops / 197e12, 2 * moved / 819e9)
    assert got["sparse_attn.index_roofline_pct.tokens"] == pytest.approx(
        100 * least / 0.042)
    ops, moved = flops.attend_ops_and_bytes(cfg, tokens)
    least = max(3 * 2 * ops / 197e12, 3 * 2 * moved / 819e9)
    # attention's kernels: 35 ms and 31 ms in the two steps
    assert got["sparse_attn.attend_roofline_pct.tokens"] == pytest.approx(
        100 * least / 0.033)
    for name in ("sparse_attn.index_roofline_pct.tokens",
                 "sparse_attn.attend_roofline_pct.tokens"):
        assert 0 < got[name] < 100
    # without the chip's peaks (the tests' stand-in for a chip) no share
    assert set(sparse_attn.read({"suffix": "tokens", "peaks": None})) == {
        "sparse_attn.index_ms.tokens", "sparse_attn.select_ms.tokens",
        "sparse_attn.index_loss_ms.tokens"}
    assert "# sparse_attn: read" in capsys.readouterr().out


def test_a_program_without_the_ops_reads_nothing(monkeypatch):
    """The parent commit's program has no indexer: no metric, no error."""
    steps, events = events_and_steps()
    others = [e for e in events if e[0] in ("attn", "attn_bwd", "fc")]
    monkeypatch.setattr(S, "load", lambda: ("/x", steps, others, NAMES))
    assert sparse_attn.read({"suffix": "tokens", "peaks": None}) == {}
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    monkeypatch.undo()
    monkeypatch.setattr(S.P, "newest_xplane", lambda: None)
    assert sparse_attn.read({"suffix": "tokens"}) == {}
