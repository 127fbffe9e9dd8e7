"""Static-graph autodiff: append_backward / gradients.

Behavioral parity with /root/reference/python/paddle/fluid/backward.py
(:1145 append_backward, :366 _addup_repetitive_outputs_, :448
_remove_no_grad_branch_): walks the block in reverse, appends
``<type>_grad`` ops, inserts ``sum`` ops where a forward var fans out to
several consumers, and respects stop_gradient / no_grad_set.

The grad ops themselves are the auto-VJP ops from the registry (or
hand-registered customs), so unlike the reference there is no per-op C++
GradOpMaker protocol to mirror — the maker here only decides *wiring*
(which slots are bound), and shapes are copied from the forward vars
instead of re-inferred.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set

from . import framework
from .core.registry import GRAD_SUFFIX, OpInfoMap, ensure_grad_op
from .utils import unique_name


def _op_io(block, op):
    """Effective (inputs, outputs) of an op for dataflow analysis. A
    `while` op declares no tensors itself — its body reads/writes
    parent vars by name (while_op.cc semantics), so its effective IO is
    the sub-block's external read/write sets restricted to
    parent-visible vars."""
    if op.type == "while" and op.attrs.get("sub_block") is not None:
        from .core.compiler_engine import _block_rw

        written, read_first = _block_rw(op.attrs["sub_block"])
        ins = [n for n in read_first
               if block._find_var_recursive(n) is not None]
        outs = [n for n in written
                if block._find_var_recursive(n) is not None]
        return (list(op.input_arg_names) + ins,
                list(op.output_arg_names) + outs)
    return list(op.input_arg_names), list(op.output_arg_names)


def _find_op_path(block, loss_name: str, req: Set[str]) -> List[int]:
    """Indices of ops that both (a) depend on a grad-requiring var and
    (b) contribute to the loss."""
    # forward reachability of req
    contributes: Set[str] = set(req)
    fwd_ops: Set[int] = set()
    for i, op in enumerate(block.ops):
        ins, outs = _op_io(block, op)
        if any(n in contributes for n in ins):
            fwd_ops.add(i)
            contributes.update(outs)
    # backward reachability from loss
    needed: Set[str] = {loss_name}
    path: List[int] = []
    for i in reversed(range(len(block.ops))):
        op = block.ops[i]
        ins, outs = _op_io(block, op)
        if i in fwd_ops and any(n in needed for n in outs):
            path.append(i)
            needed.update(ins)
    return list(reversed(path))


def _requires_grad_set(block, parameter_list=None, no_grad_set=None) -> Set[str]:
    no_grad = set(no_grad_set or ())
    req: Set[str] = set()
    if parameter_list is not None:
        for p in parameter_list:
            name = p if isinstance(p, str) else p.name
            if name not in no_grad:
                req.add(name)
    else:
        for p in block.program.all_parameters():
            if getattr(p, "trainable", True) and not p.stop_gradient \
                    and p.name not in no_grad:
                req.add(p.name)
    # any non-stop-gradient var is a valid diff leaf too (matches
    # reference: stop_gradient=False inputs get gradients)
    for v in block.vars.values():
        if not v.stop_gradient and v.name not in no_grad:
            req.add(v.name)
    return req


def _ensure_grad_var(block, fwd_name: str, grad_name: str):
    fwd = block._find_var_recursive(fwd_name)
    if block.has_var_local(grad_name):
        return block.vars[grad_name]
    v = block.create_var(
        name=grad_name,
        shape=fwd.shape if fwd is not None else None,
        dtype=fwd.dtype if fwd is not None else "float32",
        persistable=False,
        # grad vars are differentiable quantities: a later
        # append_backward over this program (gradient penalty /
        # grad-of-grad) must be able to flow gradients through them —
        # stop_gradient=True here would put every @GRAD var in that
        # pass's no_grad set and silently sever the double-grad path
        stop_gradient=False,
    )
    return v


def append_backward(
    loss,
    parameter_list=None,
    no_grad_set=None,
    callbacks=None,
    checkpoints=None,
):
    """Append grad ops computing d(loss)/d(var); returns
    [(param, param_grad_var)] like the reference (backward.py:1145)."""
    block = loss.block
    program = block.program
    program._appending_grad_times += 1
    # pass-aware grad naming (reference backward.py _rename_grad_): a
    # second pass over a program already holding grad vars must not
    # clobber the first pass's canonical @GRAD names — its canonicals
    # get an @<pass> suffix when the base name predates this pass
    prev = _PASS_STATE.copy()
    _PASS_STATE["times"] = program._appending_grad_times
    _PASS_STATE["preexisting"] = frozenset(
        n for b in program.blocks for n in b.vars)
    try:
        with program._backward_role_guard():
            return _append_backward_impl(loss, block, program,
                                         parameter_list, no_grad_set,
                                         checkpoints)
    finally:
        _PASS_STATE.clear()
        _PASS_STATE.update(prev)


_PASS_STATE: Dict = {}


def grad_name_for(n: str) -> str:
    """Canonical grad-var name for ``n`` in the CURRENT backward pass:
    the plain ``n@GRAD`` unless an earlier pass already owns it."""
    base = framework.grad_var_name(n)
    if _PASS_STATE.get("times", 1) > 1 \
            and base in _PASS_STATE.get("preexisting", ()):
        return "%s@%d" % (base, _PASS_STATE["times"])
    return base


def _emit_recompute_ops(block, path, checkpoints) -> Dict[str, str]:
    """Append renamed copies of the forward path ops (externally-produced
    vars are read as-is). Returns the old->new name map the grad binding
    uses for forward-value references.

    A checkpoint value the copies read passes through a
    ``recompute_barrier`` op (``jax.lax.optimization_barrier``) first. The
    whole step is one XLA program: without it a copy is the same operations
    on the same inputs as its original, CSE folds the two, the original's
    intermediates live on until the backward reads them and recomputation
    saves nothing (the compiled step of a 9-layer model measured the same
    temporaries with and without checkpoints; PERF.md section 6, PR 27).

    Ops after the last checkpoint are not copied: the backward starts with
    them, so their values are still there when it reads them."""
    keep = {c.name if hasattr(c, "name") else str(c) for c in checkpoints}
    last = max((k for k, idx in enumerate(path)
                if keep.intersection(block.ops[idx].output_arg_names)),
               default=-1)
    rename: Dict[str, str] = {}
    behind_barrier: Dict[str, str] = {}

    def read(n):
        if n in rename:
            return rename[n]
        if n not in keep:
            return n
        if n not in behind_barrier:
            v = block._find_var_recursive(n)
            nv = block.create_var(
                name=n + "@RECOMPUTE@IN",
                shape=None if v is None else v.shape,
                dtype="float32" if v is None else v.dtype)
            nv.stop_gradient = True
            block.append_op("recompute_barrier", inputs={"X": [n]},
                            outputs={"Out": [nv.name]}, infer_shape=False)
            behind_barrier[n] = nv.name
        return behind_barrier[n]

    for idx in path[:last + 1]:
        op = block.ops[idx]
        outs_to_rename = [n for n in op.output_arg_names
                          if n and n not in keep]
        if not outs_to_rename:
            continue  # only checkpoint outputs: stored, not recomputed
        new_inputs = {slot: [read(n) for n in names]
                      for slot, names in op.inputs.items()}
        new_outputs = {}
        for slot, names in op.outputs.items():
            outs = []
            for n in names:
                if not n:
                    outs.append(n)
                    continue
                # NEVER rebind the original name: checkpoint values are
                # stored (reads go to the original), and persistable
                # outputs (BN running stats) must not update twice.
                nn = n + "@RECOMPUTE"
                if nn not in block.vars:
                    v = block._find_var_recursive(n)
                    nv = block.create_var(
                        name=nn,
                        shape=None if v is None else v.shape,
                        dtype="float32" if v is None else v.dtype)
                    nv.stop_gradient = True
                if n not in keep:
                    rename[n] = nn
                outs.append(nn)
            new_outputs[slot] = outs
        attrs = dict(op.attrs)
        attrs.setdefault("_fwd_op_id", op._id or 0)
        block.append_op(op.type, inputs=new_inputs, outputs=new_outputs,
                        attrs=attrs, infer_shape=False)
    return rename


def _append_backward_impl(loss, block, program, parameter_list=None,
                          no_grad_set=None, checkpoints=None):

    no_grad = set()
    for b in program.blocks:
        for v in b.vars.values():
            if v.stop_gradient:
                no_grad.add(v.name)
    user_no_grad = {n if isinstance(n, str) else n.name
                    for n in (no_grad_set or ())}
    no_grad |= user_no_grad

    # a float var REWRITTEN by a while body is no longer the
    # stop-gradient constant its initializer produced (fill_constant
    # marks outputs stop_gradient=True by default — the natural init for
    # a loop carry): severing it here would cut the grad chain through
    # the loop entirely. An EXPLICIT user no_grad_set entry still wins.
    for op in block.ops:
        sub = op.attrs.get("sub_block") if op.type == "while" else None
        if sub is None:
            continue
        from .core.compiler_engine import _block_rw

        written, _ = _block_rw(sub)
        for n in written:
            v = block._find_var_recursive(n)
            if v is not None and _is_float_var(v) \
                    and n not in user_no_grad:
                no_grad.discard(n)

    req = _requires_grad_set(block, parameter_list, no_grad)
    # propagate requires-grad forward through the op list
    diffable: Set[str] = set(req)
    for op in block.ops:
        if op.type == "while":
            ins, outs = _op_io(block, op)
            if any(n in diffable for n in ins):
                for n in outs:
                    if n not in no_grad:
                        diffable.add(n)
            continue
        info = _op_info(op.type)
        if info is None or info.grad is None and not _has_grad_op(op.type):
            continue
        if any(n in diffable for n in op.input_arg_names):
            for n in op.output_arg_names:
                if n not in no_grad:
                    diffable.add(n)

    path = _find_op_path(block, loss.name, req)

    # Recompute (reference backward.py:623
    # _append_backward_ops_with_checkpoints_): re-emit the forward ops of
    # each inter-checkpoint segment at the start of the backward region
    # with renamed outputs; grad ops then read the RECOMPUTED values, so
    # the original intermediates have no backward consumers and die
    # early. RNG ops re-emit with the original op's seed stream so
    # dropout masks match. An optimization barrier on the checkpoint
    # values the copies read keeps XLA from folding a copy back onto its
    # original (see _emit_recompute_ops).
    recompute_rename: Dict[str, str] = {}
    if checkpoints:
        recompute_rename = _emit_recompute_ops(block, path, checkpoints)

    # Seed d(loss)/d(loss) = 1
    loss_grad_name = grad_name_for(loss.name)
    _ensure_grad_var(block, loss.name, loss_grad_name)
    block.append_op(
        "fill_constant",
        inputs={},
        outputs={"Out": loss_grad_name},
        attrs={
            "shape": list(loss.shape or ()),
            "value": 1.0,
            "dtype": _dtype_enum(loss.dtype),
            "force_cpu": False,
        },
        infer_shape=False,
    )

    # pending grads per forward var (producers merge on arrival)
    pending: Dict[str, List[str]] = {loss.name: [loss_grad_name]}
    grad_to_var: Dict[str, str] = {loss_grad_name: loss.name}
    finalize = make_finalize(block, pending)

    _emit_grad_ops(block, [block.ops[i] for i in path], pending,
                   finalize, diffable, no_grad, recompute_rename,
                   grad_to_var)

    # finalize leaves (parameters & data): merge their partial grads
    params_and_grads = []
    target_params = (
        [p if isinstance(p, framework.Variable) else block.var(p)
         for p in parameter_list]
        if parameter_list is not None
        else block.program.all_parameters()
    )
    for p in target_params:
        g = finalize(p.name)
        if g is None:
            continue
        params_and_grads.append((p, block.var(g)))
    return params_and_grads


def make_finalize(block, pending: Dict[str, List[str]],
                  clear_on_merge: bool = False):
    """Finalize closure: merge a var's pending partial grads into its
    canonical @GRAD name (sum op emitted into ``block``).
    ``clear_on_merge`` empties the pending list after the merge — used
    inside while-grad sub-blocks, where the same NAME is both the loop
    carry's incoming grad (consumed by the write op's grad) and later
    the pre-value's partials; without clearing, the consumed canonical
    would be double-counted at the end-of-block merge."""

    def finalize(var_name: str) -> Optional[str]:
        glist = pending.get(var_name)
        if not glist:
            return None
        canonical = grad_name_for(var_name)
        if len(glist) == 1 and glist[0] == canonical:
            if clear_on_merge:
                pending[var_name] = []
            return canonical
        _ensure_grad_var(block, var_name, canonical)
        block.append_op(
            "sum",
            inputs={"X": list(glist)},
            outputs={"Out": canonical},
            infer_shape=False,
        )
        pending[var_name] = [] if clear_on_merge else [canonical]
        return canonical

    return finalize


def _emit_grad_ops(block, fwd_ops, pending, finalize, diffable, no_grad,
                   recompute_rename, grad_to_var):
    """Reverse-walk ``fwd_ops`` appending grad ops into ``block`` — the
    shared engine behind append_backward AND while-body grad blocks."""
    for op in reversed(fwd_ops):
        if op.type == "while":
            _emit_while_grad(block, op, pending, finalize, diffable,
                             no_grad, grad_to_var)
            continue
        info = _op_info(op.type)
        if info is None:
            continue
        grad_type = op.type + "_grad"
        # A callable grad maker owns its op's backward entirely (custom
        # output binding, e.g. data_norm's in-place stat rebind) — it wins
        # even when a <type>_grad op is also registered for it to emit.
        if callable(info.grad) and info.grad != "auto":
            info.grad(block, op, pending, finalize)
            continue
        if not _has_grad_op(op.type):
            # info.grad is None or "auto" with no grad op: grads don't flow
            continue
        ginfo = OpInfoMap.instance().get(grad_type)

        # which outputs have incoming grads?
        out_grads = {}
        has_grad = False
        for slot in info.outputs:
            names = op.output(slot.name)
            if not names:
                continue
            gnames = []
            for n in names:
                g = finalize(n)
                gnames.append(g if g is not None else "")
                if g is not None:
                    has_grad = True
            if any(gnames):
                out_grads[slot.name + GRAD_SUFFIX] = gnames
        if not has_grad:
            continue

        # bind inputs: forward ins + out grads. Forward VALUE references
        # go through the recompute rename (grad math reads recomputed
        # activations); grad accumulation stays on original names.
        g_inputs = {}
        for slot in info.inputs:
            names = op.input(slot.name)
            if names:
                g_inputs[slot.name] = [recompute_rename.get(n, n)
                                       for n in names]
        g_inputs.update(out_grads)
        # some custom grad ops consume forward outputs too (slot name match)
        for slot in ginfo.inputs:
            if slot.name in g_inputs or slot.name.endswith(GRAD_SUFFIX):
                continue
            if slot.name in op.outputs:
                g_inputs[slot.name] = [recompute_rename.get(n, n)
                                       for n in op.outputs[slot.name]]

        # outputs: a fresh partial-grad name per diffable input var.
        # no_grad forward slots (labels, masks) never get a grad binding —
        # the grad kernel won't write them, and binding one would leave an
        # uninitialized var feeding the downstream sum (ADVICE r1 #3).
        g_outputs = {}
        for slot in info.inputs:
            if slot.no_grad:
                continue
            names = op.input(slot.name)
            if not names:
                continue
            gnames = []
            bind = False
            for n in names:
                if n in diffable and n not in no_grad:
                    if n in pending and pending[n]:
                        gname = "%s@RENAME@%d" % (grad_name_for(n),
                                                  len(pending[n]))
                    else:
                        gname = grad_name_for(n)
                    _ensure_grad_var(block, n, gname)
                    pending.setdefault(n, []).append(gname)
                    grad_to_var[gname] = n
                    gnames.append(gname)
                    bind = True
                else:
                    gnames.append("")
            if bind:
                g_outputs[slot.name + GRAD_SUFFIX] = gnames

        if not g_outputs:
            continue

        g_attrs = dict(op.attrs)
        g_attrs["_fwd_op_id"] = op._id
        block.append_op(grad_type, g_inputs, g_outputs, g_attrs,
                       infer_shape=False)


def _is_float_var(v) -> bool:
    if v is None or v.dtype is None:
        return True  # unknown: let the runtime decide
    return str(v.dtype).startswith(("float", "bfloat"))


def _emit_while_grad(block, op, pending, finalize, diffable, no_grad,
                     grad_to_var):
    """Backward THROUGH a while loop (reference while_grad,
    controlflow/while_op.cc WhileGradOp): build a grad sub-block from
    the body's ops and append ONE while_grad host op that replays the
    body per saved step in reverse, threading carry grads and
    accumulating parameter grads.

    Supported body shape (the RNN pattern): each parent-written carry is
    written once per trip, with every body read of it happening before
    the write (reads see the previous trip's value)."""
    from .core.compiler_engine import _block_rw

    sub = op.attrs.get("sub_block")
    if sub is None:
        return
    program = block.program
    written_all, read_first = _block_rw(sub)
    parent_written = sorted(
        n for n in written_all
        if block._find_var_recursive(n) is not None)
    parent_read = sorted(
        n for n in read_first
        if block._find_var_recursive(n) is not None)
    carries = sorted(set(parent_written) & set(read_first))

    # incoming grads of the loop's outputs (the final written values)
    incoming = {}
    for w in parent_written:
        if not _is_float_var(block._find_var_recursive(w)):
            continue
        g = finalize(w)
        if g is not None:
            incoming[w] = g
            # fully consumed here: producers BEFORE the loop receive the
            # pre-loop grad from while_grad's outputs, not this one
            pending[w] = []
    if not incoming:
        return

    targets = [r for r in parent_read
               if r in diffable and r not in no_grad
               and _is_float_var(block._find_var_recursive(r))]
    # carries must be grad-THREADED through trips even when
    # stop_gradient (fill_constant's default!) excludes them from
    # user-visible grads: without a per-trip carry grad, every replayed
    # trip would be reseeded with the stale final-output gradient
    float_carries = [c for c in carries
                     if _is_float_var(block._find_var_recursive(c))]
    thread_targets = sorted(set(targets) | set(float_carries))
    if not thread_targets:
        return

    # diffable set inside the body: threaded vars + anything they reach.
    # Carries leave the no_grad set for the SUB-generation only (their
    # internal grads are loop plumbing, not user-visible outputs).
    no_grad2 = set(no_grad) - set(float_carries)
    diffable2 = set(diffable) | set(thread_targets)
    for bop in sub.ops:
        if any(n in diffable2 for n in bop.input_arg_names):
            for n in bop.output_arg_names:
                if n not in no_grad2:
                    diffable2.add(n)

    gblock = program._create_block()
    pending2: Dict[str, List[str]] = {}
    seed_names = {}
    # seed EVERY float carry, not only those with outer grads: a carry
    # without a user-visible consumer can still carry cross-trip
    # gradient between interacting carries (h1 <- f(h2) <- previous
    # trip's h1); the host zero-seeds entries with no value yet
    seeded = sorted(set(incoming) | set(float_carries))
    for w in seeded:
        gname = grad_name_for(w)
        _ensure_grad_var(gblock, w, gname)
        pending2[w] = [gname]
        seed_names[w] = gname
    finalize2 = make_finalize(gblock, pending2, clear_on_merge=True)
    from .ops.control_flow_ops import _IN_WHILE_GRAD_GEN

    _IN_WHILE_GRAD_GEN.append(True)
    try:
        _emit_grad_ops(gblock, list(sub.ops), pending2, finalize2,
                       diffable2, no_grad2, {}, {})
    finally:
        _IN_WHILE_GRAD_GEN.pop()
    inner_grads = {}
    for r in thread_targets:
        g = finalize2(r)
        if g is not None:
            inner_grads[r] = g
    program._rollback()
    if not inner_grads:
        return

    tgt_list = sorted(inner_grads)
    # user-visible outputs only for diffable targets; pure-plumbing
    # carry grads stay internal
    out_tgt_list = [r for r in tgt_list if r in targets]
    outer_out = []
    for r in out_tgt_list:
        if r in pending and pending[r]:
            gname = "%s@RENAME@%d" % (grad_name_for(r),
                                      len(pending[r]))
        else:
            gname = grad_name_for(r)
        _ensure_grad_var(block, r, gname)
        pending.setdefault(r, []).append(gname)
        grad_to_var[gname] = r
        outer_out.append(gname)

    gop = framework.Operator(
        block, "while_grad",
        {"OutGrads": [incoming.get(w, "@EMPTY@") for w in seeded]},
        {"InGrads": outer_out},
        {"sub_block": gblock, "fwd_block": sub,
         "snap_var": "@WHILE_SNAPS@%d" % (op._id or 0),
         "written": seeded,
         "seed_names": [seed_names[w] for w in seeded],
         "targets": tgt_list,
         "inner_grads": [inner_grads[r] for r in tgt_list],
         "out_targets": out_tgt_list,
         "carries": carries})
    gop._id = program._next_op_id()
    block.ops.append(gop)


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """fluid.gradients (reference backward.py:1678): d(targets)/d(inputs)."""
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]
    assert len(targets) == 1, "multi-target gradients arrive with a later wave"
    loss = targets[0]
    block = loss.block
    pre_names = {v.name for v in inputs}
    append_backward(loss, parameter_list=[v.name for v in inputs]
                    if all(isinstance(v, framework.Variable) for v in inputs)
                    else None,
                    no_grad_set=no_grad_set)
    outs = []
    for v in inputs:
        gname = framework.grad_var_name(v.name)
        outs.append(block.var(gname) if block.has_var(gname) else None)
    return outs


def _op_info(op_type):
    try:
        return OpInfoMap.instance().get(op_type)
    except KeyError:
        return None


def _has_grad_op(op_type):
    if OpInfoMap.instance().has(op_type + "_grad"):
        return True
    # grad programs are differentiable too: auto-VJP grad ops get their
    # own grad op registered on demand (static double-grad — reference
    # conv2d_grad_grad / elementwise_*_grad_grad)
    return ensure_grad_op(op_type)


def _dtype_enum(dtype):
    from .core import dtypes as _dt

    return _dt.dtype_to_enum(dtype)
