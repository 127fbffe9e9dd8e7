"""The reader of attention's core time: which operations it takes, and the
union per step, on events written by hand."""
from benchmarks.layer_metrics import attention as A

MS = 1_000_000
JIT = "jit(step_s1)/jit(main)/"
XLA_OWN = ('%custom-call.5 = f32[32,12,512,64]{3,2,1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call"')


def test_which_operations_are_attention():
    assert A.is_attention("%fusion.1 = ...", JIT + "forward/matmul/dot_general")
    assert A.is_attention("%fusion.2 = ...", JIT + "backward/softmax_grad/mul")
    assert A.is_attention("%flash_short_fwd.3 = ...",
                          JIT + "forward/flash_attention/flash_short_fwd")
    assert A.is_attention("%flash_short_bwd.3 = ...",
                          JIT + "backward/flash_attention_grad/pallas_call")
    # the FC layers, the head split, and operations with no name at all
    assert not A.is_attention("%fusion.3 = ...", JIT + "forward/mul/dot_general")
    assert not A.is_attention("%fusion.4 = ...", JIT + "forward/transpose2/t")
    assert not A.is_attention("%copy.7 = bf16[32,512,768] copy(%x)", "")
    # XLA's own attention rewrite: a Mosaic call without op_name
    assert A.is_attention(XLA_OWN, "")


def test_union_per_step_tile():
    steps = [(0, 100 * MS), (104 * MS, 200 * MS)]
    names = {"a": JIT + "forward/matmul/dot", "b": JIT + "forward/softmax/exp",
             "c": JIT + "forward/mul/dot", "d": JIT + "backward/matmul_grad/x"}
    events = [("a", 10 * MS, 20 * MS), ("b", 15 * MS, 30 * MS),   # overlap
              ("c", 30 * MS, 60 * MS),                            # not ours
              (XLA_OWN, 60 * MS, 61 * MS),
              ("d", 100 * MS, 110 * MS),                # spans the tile edge
              ("a", 150 * MS, 152 * MS)]
    assert A.per_step_ns(events, names, steps) == [
        (20 + 1 + 4) * MS, (6 + 2) * MS]
    assert A.per_step_ns([("c", 0, MS)], names, steps) == [0, 0]
