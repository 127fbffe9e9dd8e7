"""Program / Block / Operator / Variable — the static-graph IR.

Behavioral counterpart of the reference's ProgramDesc tree and its Python
mirror (/root/reference/paddle/fluid/framework.py:806,1706,2176,3602 and
paddle/fluid/framework/framework.proto). Differences by design:

- The IR is Python-native (dataclass-style objects) rather than protobuf
  descs shadowed by C++ wrappers; serialization goes through a compact
  JSON form (``Program.to_json``) used by save/load_inference_model.
- Shape/dtype inference runs at ``append_op`` time through the SAME jax
  ``eval_shape`` path the executor compiles, so there is no separate
  compile-time InferShape (reference shape_inference.h duality).
- Ops never mutate vars in place at the IR level; "in-place" outputs
  (e.g. optimizer ParamOut==Param) are expressed by binding the same
  variable name, and executors handle rebinding/donation.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from .core import dtypes as _dt
from .core.registry import OpInfoMap, GRAD_SUFFIX
from .utils import unique_name

_SENTINEL = 1223  # stands in for -1 (unknown batch) during eval_shape


class Variable:
    """Symbolic variable inside a Block (graph-build time).

    Mirrors python/paddle/fluid/framework.py:806. The runtime value lives
    in a Scope under the same name.
    """

    def __init__(
        self,
        block: "Block",
        name: Optional[str] = None,
        shape: Optional[Sequence[int]] = None,
        dtype="float32",
        lod_level: int = 0,
        persistable: bool = False,
        stop_gradient: bool = False,
        is_data: bool = False,
        type: str = "lod_tensor",
        initializer=None,
        **kwargs,
    ):
        self.block = block
        self.name = name if name is not None else unique_name.generate("_generated_var")
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = _dt.convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.type = type  # "lod_tensor" | "selected_rows" | "lod_tensor_array" | ...
        self.op: Optional[Operator] = None  # last writer

    # numpy-style helpers used by layers code
    @property
    def ndim(self):
        return len(self.shape) if self.shape is not None else None

    def astype(self, dtype):
        from .layers import tensor as _lt

        return _lt.cast(self, dtype)

    def __repr__(self):
        return "Variable(%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    __str__ = __repr__

    # Operator overloads are patched in by layers.math_op_patch (monkey
    # patch like the reference) to avoid import cycles here.


class Parameter(Variable):
    """A persistable, trainable variable (framework.py:4631)."""

    def __init__(self, block, shape, dtype, **kwargs):
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.is_distributed = kwargs.pop("is_distributed", False)
        kwargs.setdefault("persistable", True)
        kwargs.setdefault("stop_gradient", False)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)


class OpRole:
    """Op phase tags (reference framework.py op_role attr / OpProto roles).

    Bitmask: Loss may combine with Forward/Backward."""

    Forward = 0x0000
    Backward = 0x0001
    Optimize = 0x0002
    RPC = 0x0004
    Dist = 0x0008
    LRSched = 0x0010
    Loss = 0x0100


class Operator:
    """One op in a Block: (type, slot->var-names, attrs).

    Mirrors framework.py:1706 / OpDesc. Input/output maps store *names*;
    resolve via block.var().
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs: Dict[str, List[str]] = {}
        self.outputs: Dict[str, List[str]] = {}
        self.attrs: Dict[str, object] = dict(attrs or {})
        self._id = None  # set by Block.append_op
        # role of the phase appending this op (reference: the op_role attr
        # set by Program.op_role / _optimized_guard, framework.py:3602);
        # clone(for_test=True) prunes Backward/Optimize ops by it.
        prog = getattr(block, "program", None) if block is not None else None
        self._role = getattr(prog, "_current_role", 0)

        for slot, arg in (inputs or {}).items():
            self.inputs[slot] = _to_name_list(arg)
        for slot, arg in (outputs or {}).items():
            self.outputs[slot] = _to_name_list(arg)

    def input(self, slot) -> List[str]:
        return self.inputs.get(slot, [])

    def output(self, slot) -> List[str]:
        return self.outputs.get(slot, [])

    @property
    def input_arg_names(self):
        return [n for ns in self.inputs.values() for n in ns]

    @property
    def output_arg_names(self):
        return [n for ns in self.outputs.values() for n in ns]

    def attr(self, name):
        return self.attrs.get(name)

    def _set_attr(self, name, val):
        self.attrs[name] = val

    def has_attr(self, name):
        return name in self.attrs

    def __repr__(self):
        return "Op(%s: %s -> %s)" % (self.type, self.inputs, self.outputs)


def _to_name_list(arg) -> List[str]:
    if arg is None:
        return []
    if isinstance(arg, (list, tuple)):
        return [a.name if isinstance(a, Variable) else str(a) for a in arg]
    return [arg.name if isinstance(arg, Variable) else str(arg)]


class Block:
    def __init__(self, program: "Program", idx: int, parent_idx: int = -1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.parent_idx < 0:
            return None
        return self.program.block(self.parent_idx)

    # -- vars -------------------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        name = kwargs.get("name")
        if name and name in self.vars:
            return self.vars[name]
        v = Variable(self, **kwargs)
        self.vars[v.name] = v
        return v

    def create_parameter(self, **kwargs) -> Parameter:
        p = Parameter(self, **kwargs)
        # Parameters live in the top (global) block, like the reference.
        gb = self.program.global_block()
        gb.vars[p.name] = p
        if self is not gb:
            self.vars[p.name] = p
        return p

    def var(self, name: str) -> Variable:
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name: str) -> bool:
        return self._find_var_recursive(name) is not None

    def has_var_local(self, name: str) -> bool:
        return name in self.vars

    def _find_var_recursive(self, name: str) -> Optional[Variable]:
        b: Optional[Block] = self
        while b is not None:
            v = b.vars.get(name)
            if v is not None:
                return v
            b = b.parent_block
        return None

    @property
    def all_parameters(self) -> List[Parameter]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # -- ops --------------------------------------------------------------
    def append_op(self, type, inputs=None, outputs=None, attrs=None,
                  infer_shape=True) -> Operator:
        if _name_scopes and "op_namescope" not in (attrs or {}):
            attrs = dict(attrs or {},
                         op_namescope="/%s/" % "/".join(_name_scopes))
        op = Operator(self, type, inputs, outputs, attrs)
        op._id = self.program._next_op_id()
        self.ops.append(op)
        if infer_shape:
            try:
                infer_op_shapes(self, op)
            except Exception as e:
                if OpInfoMap.instance().has(type):
                    # roll the failed op back out so a caller that
                    # catches the build error isn't left with a
                    # poisoned block that re-raises at exe.run
                    self.ops.pop()
                    from .core.enforce import annotate_op_error

                    annotate_op_error(e, op, "shape inference")
                    raise
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None) -> Operator:
        op = Operator(self, type, inputs, outputs, attrs)
        op._id = self.program._next_op_id()
        self.ops.insert(0, op)
        try:
            infer_op_shapes(self, op)
        except Exception:
            pass
        return op

    def __repr__(self):
        return "Block(%d, %d ops, %d vars)" % (self.idx, len(self.ops), len(self.vars))


# ---------------------------------------------------------------------------
# Shape inference (shared compile-time path)
# ---------------------------------------------------------------------------


def infer_op_shapes(block: Block, op: Operator) -> None:
    """Set output var shapes/dtypes from input metadata via the op's fn.

    Unknown dims (-1) round-trip through a sentinel prime so eval_shape can
    run on concrete ints.
    """
    import jax

    info = OpInfoMap.instance().get(op.type)
    if info.fn is None and info.infer_shape is None:
        return  # host op with no declared shape semantics

    def meta_of(name):
        v = block.var(name)
        if v.shape is None:
            raise ValueError("input %r has no shape" % name)
        shape = tuple(_SENTINEL if d < 0 else d for d in v.shape)
        return jax.ShapeDtypeStruct(shape, _dt.to_numpy_dtype(v.dtype))

    ins = {}
    for slot in info.inputs:
        names = op.input(slot.name)
        if not names:
            ins[slot.name] = None
            continue
        metas = [meta_of(n) for n in names]
        ins[slot.name] = metas if slot.duplicable else metas[0]

    attrs = dict(op.attrs)
    if info.needs_lod and info.infer_shape is None:
        # LoD-dependent output shapes are runtime information (they vary
        # with the fed sequence lengths); running the kernel for
        # eval_shape would raise. Default every float output to
        # [-1, trailing dims of the first input].
        first = None
        for slot in info.inputs:
            names = op.input(slot.name)
            if names:
                first = block._find_var_recursive(names[0])
                break
        for slot in info.outputs:
            for n in op.output(slot.name):
                v = block._find_var_recursive(n)
                if v is None:
                    v = block.create_var(name=n)
                if v.shape is None and first is not None \
                        and first.shape is not None:
                    v.shape = (-1,) + tuple(first.shape[1:])
                    if v.dtype is None:
                        v.dtype = first.dtype
                v.op = op
        return
    from .core.registry import BOUND_OUTPUTS_ATTR, RNG_SEED_ATTR

    attrs[BOUND_OUTPUTS_ATTR] = tuple(
        s.name for s in info.outputs if op.output(s.name)
    )

    if info.infer_shape is not None:
        out_meta = info.infer_shape(ins, attrs)
    else:
        if info.needs_rng:
            ins[RNG_SEED_ATTR] = jax.ShapeDtypeStruct((), np.uint32)
        out_meta = jax.eval_shape(lambda kw: info.fn(kw, attrs), ins)

    for slot in info.outputs:
        names = op.output(slot.name)
        if not names:
            continue
        m = out_meta.get(slot.name)
        if m is None:
            continue
        metas = m if isinstance(m, (list, tuple)) else [m]
        for n, mm in zip(names, metas):
            v = block._find_var_recursive(n)
            if v is None:
                v = block.create_var(name=n)
            if mm is None:
                continue
            v.shape = tuple(-1 if d == _SENTINEL else int(d) for d in mm.shape)
            v.dtype = _dt.convert_dtype(mm.dtype)
            v.op = op


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


_program_uid_counter = [0]


class Program:
    def __init__(self):
        # process-unique id for compile caches: unlike id(), never reused
        # after GC, so a fresh Program can't alias a dead one's cache entry
        _program_uid_counter[0] += 1
        self._uid = _program_uid_counter[0]
        self.blocks: List[Block] = [Block(self, 0)]
        self._current_block_idx = 0
        self._op_id = 0
        self._seed = 0
        self.random_seed = 0
        # op-role bookkeeping used by backward/optimizer passes
        self._appending_grad_times = 0
        self._current_role = OpRole.Forward

    def _next_op_id(self):
        self._op_id += 1
        return self._op_id

    @contextlib.contextmanager
    def _role_guard(self, role):
        """Ops appended inside carry `role` (reference _optimized_guard /
        _backward_role_guard, framework.py:3602)."""
        prev = self._current_role
        self._current_role = role
        try:
            yield
        finally:
            self._current_role = prev

    def _optimized_guard(self, param_and_grads=None):
        return self._role_guard(OpRole.Optimize)

    def _backward_role_guard(self):
        return self._role_guard(OpRole.Backward)

    def global_block(self) -> Block:
        return self.blocks[0]

    def current_block(self) -> Block:
        return self.blocks[self._current_block_idx]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    @property
    def num_blocks(self):
        return len(self.blocks)

    def _create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self._current_block_idx if parent_idx is None else parent_idx
        b = Block(self, len(self.blocks), parent)
        self.blocks.append(b)
        self._current_block_idx = b.idx
        return b

    def _rollback(self):
        self._current_block_idx = self.current_block().parent_idx

    def all_parameters(self) -> List[Parameter]:
        return self.global_block().all_parameters

    def list_vars(self):
        for b in self.blocks:
            for v in b.vars.values():
                yield v

    # -- cloning / pruning ------------------------------------------------
    def clone(self, for_test: bool = False) -> "Program":
        import copy

        p = Program.__new__(Program)
        _program_uid_counter[0] += 1
        p._uid = _program_uid_counter[0]
        p.blocks = []
        p._current_block_idx = 0
        p._current_role = OpRole.Forward
        p._op_id = self._op_id
        p._seed = self._seed
        p.random_seed = self.random_seed
        p._appending_grad_times = self._appending_grad_times
        for b in self.blocks:
            nb = Block(p, b.idx, b.parent_idx)
            p.blocks.append(nb)
        for b, nb in zip(self.blocks, p.blocks):
            for name, v in b.vars.items():
                nv = copy.copy(v)
                nv.block = nb
                nb.vars[name] = nv
            for op in b.ops:
                if for_test and op.type in _TRAIN_ONLY_SKIP:
                    continue
                if for_test and op._role & (OpRole.Backward
                                            | OpRole.Optimize):
                    continue  # reference clone(for_test) prunes by op_role
                nop = Operator(nb, op.type, None, None, dict(op.attrs))
                nop.inputs = {k: list(v) for k, v in op.inputs.items()}
                nop.outputs = {k: list(v) for k, v in op.outputs.items()}
                nop._id = op._id
                nop._role = op._role
                if for_test and "is_test" in _op_attr_names(op.type):
                    nop.attrs["is_test"] = True
                nb.ops.append(nop)
        return p

    def __repr__(self):
        return "Program(%d blocks, %d ops)" % (
            len(self.blocks),
            sum(len(b.ops) for b in self.blocks),
        )


def _op_attr_names(op_type):
    try:
        return OpInfoMap.instance().get(op_type).attrs.keys()
    except KeyError:
        return ()


_TRAIN_ONLY_SKIP = set()  # op types dropped by clone(for_test=True)


# ---------------------------------------------------------------------------
# Default programs & guards (reference framework.py:4845,4879)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_main_program() -> Program:
    return _main_program_


def default_startup_program() -> Program:
    return _startup_program_


def switch_main_program(program: Program) -> Program:
    global _main_program_
    old = _main_program_
    _main_program_ = program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program_
    old = _startup_program_
    _startup_program_ = program
    return old


_name_scopes: List[str] = []


@contextlib.contextmanager
def name_scope(prefix: str):
    """Ops appended inside carry the attribute ``op_namescope``
    (``"/outer/inner/"``, reference framework.py name_scope); their gradient
    ops and recomputed copies inherit it with the other attributes. A compiled
    step traces such an op inside ``jax.named_scope("<role>/<op_type>/
    <outer/inner>")``, so a device trace tells one part of a model from
    another that is built from the same op types."""
    _name_scopes.append(str(prefix).strip("/"))
    try:
        yield
    finally:
        _name_scopes.pop()


@contextlib.contextmanager
def program_guard(main_program: Program, startup_program: Optional[Program] = None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


# ---------------------------------------------------------------------------
# dygraph mode switch (tracer set by dygraph.guard)
# ---------------------------------------------------------------------------

_dygraph_tracer_ = None
_dygraph_place_ = None


def in_dygraph_mode() -> bool:
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_


def _current_expected_place():
    from .core.place import _current_expected_place_default

    if _dygraph_place_ is not None:
        return _dygraph_place_
    return _current_expected_place_default()


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


def default_startup_seed():
    return _startup_program_.random_seed
