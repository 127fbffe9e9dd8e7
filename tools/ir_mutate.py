#!/usr/bin/env python
"""Seeded IR-mutation self-test for the static verifier (CI gate).

Applies N seeded corruptions to freshly-built (and collective-
transpiled) Programs — drop an input var, dangle a reference, reorder
one rank's collectives, flip a dtype, orphan an op, double-reduce a
grad, break a rewrite contract, ... — and asserts the
``paddle_tpu.analysis`` verifier flags EVERY one with a structured
finding naming the op and the violated invariant. A corruption the
verifier misses is a hole in the net; this gate is the verifier's own
regression suite.

Usage:
    python tools/ir_mutate.py          # run all mutations, exit != 0 on a miss
    python tools/ir_mutate.py --list   # print the mutation catalogue

Also importable (tests/test_ir_verifier.py parametrizes over
``MUTATIONS``).
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NRANKS = 8


def _build(bucket=True, optimizer="sgd", scope=None):
    """Fresh dp-transpiled MLP: insert_allreduce(+bucket) applied, so
    mutations operate on the same rewritten IR the engine verifies."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel.collectives import bucket_allreduce_ops
    from paddle_tpu.parallel.transpiler import insert_allreduce_ops

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.data(name="x", shape=[16, 8], dtype="float32")
        lbl = fluid.data(name="lbl", shape=[16, 1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        pred = fluid.layers.fc(h, size=10, act="softmax")
        loss = fluid.layers.mean(fluid.layers.cross_entropy(pred, lbl))
        if optimizer == "momentum":
            fluid.optimizer.MomentumOptimizer(0.1, 0.9).minimize(loss)
        else:
            fluid.optimizer.SGD(0.1).minimize(loss)
    insert_allreduce_ops(main, NRANKS)
    if bucket:
        bucket_allreduce_ops(main, bucket_bytes=4 << 20, scope=scope)
    return main, startup, loss


def _findings(main, loss, recheck=False):
    from paddle_tpu.analysis import verify_program

    return verify_program(main, fetch_names=[loss.name],
                          recheck_shapes=recheck, raise_on_error=False)


def _expect_invariant(main, loss, invariant, recheck=False):
    fs = [f for f in _findings(main, loss, recheck=recheck)
          if f.invariant == invariant]
    return bool(fs), "; ".join(str(f) for f in fs[:3])


def _op_of_type(block, t):
    for op in block.ops:
        if op.type == t:
            return op
    raise AssertionError("no %r op in block (%s)"
                         % (t, [o.type for o in block.ops]))


# -- mutation catalogue ------------------------------------------------------
# each entry: (kind, description, run() -> (flagged, detail))


def _m_clean_baseline():
    main, _, loss = _build()
    fs = [f for f in _findings(main, loss, recheck=True)
          if f.severity == "error"]
    return not fs, ("clean rewritten program has %d error findings: %s"
                    % (len(fs), [str(f) for f in fs[:3]]) if fs
                    else "clean program verifies clean")


def _m_drop_input():
    main, _, loss = _build()
    op = _op_of_type(main.global_block(), "mul")
    op.inputs.pop("X")
    return _expect_invariant(main, loss, "missing-slot")


def _m_dangling_input():
    main, _, loss = _build()
    op = _op_of_type(main.global_block(), "mul")
    op.inputs["X"] = ["__no_such_var__"]
    return _expect_invariant(main, loss, "dangling-input")


def _m_never_written_input():
    # a DECLARED var nobody writes: dangling-input can't fire (it
    # resolves) and use-before-def can't fire (no writer exists) — the
    # dedicated never-written-input net must
    main, _, loss = _build()
    block = main.global_block()
    block.create_var(name="__declared_garbage__", shape=(16, 8),
                     dtype="float32")
    _op_of_type(block, "mul").inputs["X"] = ["__declared_garbage__"]
    return _expect_invariant(main, loss, "never-written-input")


def _m_use_before_def():
    main, _, loss = _build()
    block = main.global_block()
    # move the first producer (reads only external feeds/params) to the
    # end: every consumer of its output now reads before any write
    block.ops.append(block.ops.pop(0))
    return _expect_invariant(main, loss, "use-before-def")


def _m_dtype_corrupt():
    main, _, loss = _build()
    block = main.global_block()
    op = _op_of_type(block, "mul")
    v = block.var(op.output("Out")[0])
    v.dtype = "float16"  # producer actually emits float32
    return _expect_invariant(main, loss, "dtype-mismatch", recheck=True)


def _m_shape_corrupt():
    main, _, loss = _build()
    block = main.global_block()
    op = _op_of_type(block, "mul")
    v = block.var(op.output("Out")[0])
    v.shape = tuple(v.shape[:-1]) + (v.shape[-1] + 3,)
    return _expect_invariant(main, loss, "shape-mismatch", recheck=True)


def _m_invalid_dtype():
    main, _, loss = _build()
    block = main.global_block()
    op = _op_of_type(block, "mul")
    block.var(op.output("Out")[0]).dtype = "float99"
    return _expect_invariant(main, loss, "invalid-dtype")


def _m_orphan_op():
    import paddle_tpu.framework as fw

    main, _, loss = _build()
    block = main.global_block()
    src = _op_of_type(block, "mul").output("Out")[0]
    v = block.create_var(name="__orphan_out__",
                         shape=block.var(src).shape, dtype="float32")
    op = fw.Operator(block, "scale", {"X": [src]}, {"Out": [v.name]},
                     {"scale": 2.0, "bias": 0.0})
    op._id = main._next_op_id()
    block.ops.append(op)
    return _expect_invariant(main, loss, "unreachable-op")


def _m_duplicate_write():
    main, _, loss = _build()
    block = main.global_block()
    for i, op in enumerate(block.ops):
        if op.type == "mul":
            import copy

            clone = copy.copy(op)
            clone.inputs = {k: list(v) for k, v in op.inputs.items()}
            clone.outputs = {k: list(v) for k, v in op.outputs.items()}
            block.ops.insert(i + 1, clone)
            break
    return _expect_invariant(main, loss, "overwritten-write")


def _m_drop_output():
    main, _, loss = _build()
    op = _op_of_type(main.global_block(), "mul")
    op.outputs = {}
    return _expect_invariant(main, loss, "missing-slot")


def _m_unknown_op():
    main, _, loss = _build()
    _op_of_type(main.global_block(), "mul").type = "bogus_op_xyz"
    return _expect_invariant(main, loss, "unknown-op")


def _m_attr_type():
    main, _, loss = _build()
    op = _op_of_type(main.global_block(), "c_bucket_allreduce")
    op.attrs["ring_id"] = "zero"
    return _expect_invariant(main, loss, "attr-type")


def _m_alias_write():
    main, _, loss = _build()
    op = _op_of_type(main.global_block(), "mul")
    out = op.output("Out")[0]
    op.outputs["Out"] = [out, out]
    return _expect_invariant(main, loss, "alias-write")


def _m_conditional_collective():
    import paddle_tpu.framework as fw
    from paddle_tpu.analysis import (CollectiveMismatchError,
                                     check_collective_schedule)

    main, _, loss = _build(bucket=False)
    block = main.global_block()
    ar = next(op for op in block.ops if op.type == "c_allreduce_sum")
    g = ar.input("X")[0]
    sub = main._create_block(parent_idx=0)
    main._rollback()
    inner = fw.Operator(sub, "c_allreduce_sum", {"X": [g]},
                        {"Out": [g]}, {"ring_id": 0})
    inner._id = main._next_op_id()
    sub.ops.append(inner)
    cond = fw.Operator(block, "conditional_block", {}, {},
                       {"sub_block": sub})
    cond._id = main._next_op_id()
    block.ops.append(cond)
    try:
        check_collective_schedule(main, nranks=NRANKS)
    except CollectiveMismatchError as e:
        return ("conditional-collective" in str(e)
                and e.kind == "would-deadlock", str(e)[:300])
    return False, "conditional collective not flagged"


def _per_rank_schedules(n=NRANKS, bucket=False):
    from paddle_tpu.analysis import extract_collective_schedule

    main, _, loss = _build(bucket=bucket)
    sigs, _f = extract_collective_schedule(main)
    assert len(sigs) >= 2, "need >=2 collectives to diverge"
    return [list(sigs) for _ in range(n)]


def _expect_cross_rank(scheds, kind, needles=()):
    from paddle_tpu.analysis import (CollectiveMismatchError,
                                     check_cross_rank)

    try:
        check_cross_rank(scheds, where="ir_mutate")
    except CollectiveMismatchError as e:
        ok = e.kind == kind and all(s in str(e) for s in needles)
        return ok, "%s: %s" % (e.kind, str(e)[:300])
    return False, "divergent schedules not flagged"


def _m_rank_reorder():
    # swapping two same-kind collectives pairs up DIFFERENT payloads in
    # the same execution slot: the ranks don't hang, they psum
    # misaligned buffers together — classified would-corrupt
    scheds = _per_rank_schedules()
    r = scheds[5] = list(scheds[5])
    r[0], r[1] = r[1], r[0]
    return _expect_cross_rank(scheds, "would-corrupt",
                              ("rank 5", "rank 0", "position 0"))


def _m_rank_dtype():
    import copy

    scheds = _per_rank_schedules()
    scheds[3] = list(scheds[3])
    s = scheds[3][1] = copy.copy(scheds[3][1])
    s.dtype = "bfloat16"
    return _expect_cross_rank(scheds, "would-corrupt",
                              ("rank 3", "position 1"))


def _m_rank_numel():
    import copy

    scheds = _per_rank_schedules()
    scheds[7] = list(scheds[7])
    s = scheds[7][0] = copy.copy(scheds[7][0])
    s.numel = (s.numel or 0) + 13
    return _expect_cross_rank(scheds, "would-corrupt", ("rank 7",))


def _m_rank_missing():
    scheds = _per_rank_schedules()
    scheds[2] = scheds[2][:-1]
    return _expect_cross_rank(scheds, "would-deadlock", ("rank 2",))


def _m_double_reduce():
    import copy

    from paddle_tpu.analysis import (CollectiveMismatchError,
                                     check_collective_schedule)

    main, _, loss = _build(bucket=False)
    block = main.global_block()
    for i, op in enumerate(block.ops):
        if op.type == "c_allreduce_sum":
            block.ops.insert(i + 1, copy.copy(op))
            break
    try:
        check_collective_schedule(main, nranks=NRANKS)
    except CollectiveMismatchError as e:
        return "double-reduce" in str(e), str(e)[:300]
    return False, "double reduce not flagged"


def _m_bucket_contract():
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.collectives import bucket_allreduce_ops

    import paddle_tpu as fluid
    main, _, loss = _build(bucket=False)
    contract = contract_for("bucket_allreduce")
    state = contract.pre(main)
    bucket_allreduce_ops(main, bucket_bytes=4 << 20)
    # sabotage the rewrite: silently drop one grad from the bucket
    op = _op_of_type(main.global_block(), "c_bucket_allreduce")
    op.inputs["X"] = op.input("X")[1:]
    op.outputs["Out"] = op.output("Out")[1:]
    try:
        contract.post(main, state)
    except ContractViolation as e:
        return "multiset" in str(e), str(e)[:300]
    return False, "dropped bucket member not flagged"


def _m_sharded_contract():
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.collectives import \
        apply_sharded_weight_update

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        main, startup, loss = _build(bucket=False, optimizer="momentum",
                                     scope=scope)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        contract = contract_for("sharded_update")
        state = contract.pre(main)
        n = apply_sharded_weight_update(main, scope, NRANKS)
        assert n >= 1, "sharded update pass did not fire"
        op = _op_of_type(main.global_block(), "c_sharded_update")
        # sabotage: drop the LAST param/grad pair from the group
        op.inputs["Param"] = op.input("Param")[:-1]
        op.inputs["Grad"] = op.input("Grad")[:-1]
        op.outputs["ParamOut"] = op.output("ParamOut")[:-1]
        try:
            contract.post(main, state)
        except ContractViolation as e:
            return "never be updated" in str(e), str(e)[:300]
    return False, "dropped sharded param not flagged"


def _build_async_input():
    """Per-grad buckets (tiny cap) so several have real slack before
    their first consumer — the shape the async split fires on."""
    from paddle_tpu.parallel.collectives import bucket_allreduce_ops

    main, _, loss = _build(bucket=False)
    bucket_allreduce_ops(main, bucket_bytes=1)
    return main, loss


def _m_async_drop_await():
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.scheduling import \
        schedule_async_collectives

    main, loss = _build_async_input()
    contract = contract_for("async_collective")
    state = contract.pre(main)
    n = schedule_async_collectives(main)
    assert n >= 1, "async pass split nothing"
    block = main.global_block()
    # sabotage: delete one await — its members would keep their
    # UNREDUCED values and the optimizer applies divergent grads
    block.ops = [op for op in block.ops
                 if op.type != "c_bucket_allreduce_await"]
    try:
        contract.post(main, state)
    except ContractViolation as e:
        return "no await" in str(e) or "lost" in str(e), str(e)[:300]
    return False, "dropped await not flagged"


def _m_async_reader_before_await():
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.scheduling import \
        schedule_async_collectives

    main, loss = _build_async_input()
    contract = contract_for("async_collective")
    state = contract.pre(main)
    n = schedule_async_collectives(main)
    assert n >= 1, "async pass split nothing"
    block = main.global_block()
    # sabotage: hoist a consumer of a reduced grad ABOVE its await —
    # it would read the unreduced value (the exact hazard the
    # consumer barrier exists to stop)
    for ai, op in enumerate(block.ops):
        if op.type != "c_bucket_allreduce_await":
            continue
        members = set(op.input("X"))
        for j in range(ai + 1, len(block.ops)):
            reader = block.ops[j]
            if reader.type.startswith("c_bucket_allreduce"):
                continue
            if members & set(reader.input_arg_names):
                block.ops.insert(ai, block.ops.pop(j))
                try:
                    contract.post(main, state)
                except ContractViolation as e:
                    return ("consumer-barrier" in str(e), str(e)[:300])
                return False, "hoisted reader not flagged"
    return False, "no reader found to hoist"


def _m_async_writer_between_pair():
    import paddle_tpu.framework as fw
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.scheduling import \
        schedule_async_collectives

    main, loss = _build_async_input()
    contract = contract_for("async_collective")
    state = contract.pre(main)
    n = schedule_async_collectives(main)
    assert n >= 1, "async pass split nothing"
    block = main.global_block()
    # sabotage: splice a WRITER of a member grad between a start and
    # its await — the await would clobber it with a reduction of the
    # stale pre-write value
    for si, op in enumerate(block.ops):
        if op.type != "c_bucket_allreduce_start":
            continue
        g = op.input("X")[0]
        w = fw.Operator(block, "scale", {"X": [g]}, {"Out": [g]},
                        {"scale": 2.0, "bias": 0.0})
        w._id = main._next_op_id()
        block.ops.insert(si + 1, w)
        break
    try:
        contract.post(main, state)
    except ContractViolation as e:
        return "clobber" in str(e), str(e)[:300]
    return False, "writer between start/await not flagged"


def _m_reduction_swap_bogus_strategy():
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.scheduling import swap_reduction_strategy

    main, _, loss = _build(bucket=True)
    contract = contract_for("reduction_swap")
    state = contract.pre(main)
    swap_reduction_strategy(main, "tree")
    # sabotage: corrupt the spelling to something no lowering knows —
    # it would raise mid-trace inside shard_map on every rank
    op = _op_of_type(main.global_block(), "c_bucket_allreduce")
    op.attrs["strategy"] = "quantum_leap"
    try:
        contract.post(main, state)
    except ContractViolation as e:
        return "unknown reduction strategy" in str(e), str(e)[:300]
    return False, "bogus strategy not flagged"


def _m_bucket_quant_residual_mismatch():
    import paddle_tpu as fluid
    from paddle_tpu.analysis import ContractViolation
    from paddle_tpu.analysis.contracts import contract_for
    from paddle_tpu.parallel.scheduling import configure_bucket_quant

    scope = fluid.Scope()
    main, _, loss = _build(bucket=True, scope=scope)
    contract = contract_for("bucket_quant")
    state = contract.pre(main)
    n = configure_bucket_quant(main, scope, NRANKS, "dp", modes="int8",
                               error_feedback=True)
    assert n >= 1, "bucket-quant pass wired nothing"
    # sabotage: drop the ResidualOut rebinding — the rounding error
    # would be read every step but never updated (frozen feedback,
    # silently compounding bias)
    op = _op_of_type(main.global_block(), "c_bucket_allreduce")
    assert op.input("Residual"), "residual was not wired"
    op.outputs.pop("ResidualOut")
    try:
        contract.post(main, state)
    except ContractViolation as e:
        return "ResidualOut" in str(e), str(e)[:300]
    return False, "dropped ResidualOut not flagged"


def _m_lazy_graph():
    from paddle_tpu.analysis import IRVerificationError, verify_lazy_graph

    # node 1 wires node 2's output — a replay use-before-def
    wiring = [(("e", 0),), (("n", 2, 0),), (("n", 1, 0),)]
    try:
        verify_lazy_graph(wiring, [1, 1, 1], 1, [(2, 0)])
    except IRVerificationError as e:
        return "not an earlier node" in str(e), str(e)[:200]
    return False, "mis-wired lazy graph not flagged"


MUTATIONS = [
    ("clean-baseline", "rewritten program verifies clean",
     _m_clean_baseline),
    ("drop-input-var", "required input slot unbound", _m_drop_input),
    ("dangling-input", "input renamed to an undeclared var",
     _m_dangling_input),
    ("never-written-input", "input repointed at a declared-but-"
     "never-written var", _m_never_written_input),
    ("use-before-def", "producer moved after its consumers",
     _m_use_before_def),
    ("dtype-change", "hidden var dtype flipped to float16",
     _m_dtype_corrupt),
    ("shape-change", "hidden var shape grown by 3", _m_shape_corrupt),
    ("invalid-dtype", "var dtype set to garbage", _m_invalid_dtype),
    ("orphan-op", "appended op nobody consumes", _m_orphan_op),
    ("duplicate-write", "producer duplicated (dead first write)",
     _m_duplicate_write),
    ("drop-output", "output slots cleared", _m_drop_output),
    ("unknown-op", "op type renamed off-registry", _m_unknown_op),
    ("attr-type", "ring_id set to a string", _m_attr_type),
    ("alias-write", "one op writes the same var twice", _m_alias_write),
    ("conditional-collective", "collective moved under a branch",
     _m_conditional_collective),
    ("rank-reorder-collectives", "one rank's collectives swapped",
     _m_rank_reorder),
    ("rank-dtype-divergence", "one rank's payload dtype differs",
     _m_rank_dtype),
    ("rank-numel-divergence", "one rank's payload size differs",
     _m_rank_numel),
    ("rank-missing-collective", "one rank issues one fewer collective",
     _m_rank_missing),
    ("double-reduce", "grad allreduced twice", _m_double_reduce),
    ("bucket-contract-drop-grad", "bucket pass silently drops a grad",
     _m_bucket_contract),
    ("sharded-contract-drop-param", "sharded update drops a param",
     _m_sharded_contract),
    ("async-drop-await", "async split loses an await (grads never "
     "written back)", _m_async_drop_await),
    ("async-reader-before-await", "consumer hoisted above its await",
     _m_async_reader_before_await),
    ("async-writer-between-pair", "member grad written between start "
     "and await (clobbered by the slice-back)",
     _m_async_writer_between_pair),
    ("reduction-swap-bogus-strategy", "strategy attr set off-registry",
     _m_reduction_swap_bogus_strategy),
    ("bucket-quant-residual-mismatch", "error-feedback ResidualOut "
     "dropped (frozen residual)", _m_bucket_quant_residual_mismatch),
    ("lazy-graph-miswire", "flush graph wires a later node",
     _m_lazy_graph),
]


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--list" in argv:
        for kind, desc, _fn in MUTATIONS:
            print("%-28s %s" % (kind, desc))
        return 0
    failed = []
    for kind, desc, fn in MUTATIONS:
        try:
            flagged, detail = fn()
        except Exception as e:  # a crash is NOT a structured finding
            flagged, detail = False, "checker crashed: %r" % e
        status = "CAUGHT" if flagged else "MISSED"
        print("%-28s %-6s %s" % (kind, status, detail[:160]))
        if not flagged:
            failed.append(kind)
    print("ir_mutate: %d/%d mutation kinds caught"
          % (len(MUTATIONS) - len(failed), len(MUTATIONS)))
    if failed:
        print("MISSED: %s" % ", ".join(failed), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
