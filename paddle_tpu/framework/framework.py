"""Program/Block/Variable/Operator graph model.

TPU-native equivalent of the reference's Python front-end graph classes
(reference: python/paddle/fluid/framework.py:117 Variable, :361 Operator,
:644 Block, :940 Program, :1118 Parameter, :1176-1257 default program guards).
The user builds a Program whose desc is the serializable IR in `desc.py`;
execution compiles blocks to XLA (see executor.py) instead of interpreting
ops one-by-one.
"""

from __future__ import annotations

import contextlib
import copy
import os
import sys
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from . import unique_name
from .desc import (BlockDesc, BlockRef, BlocksRef, OpDesc, ProgramDesc,
                   VarDesc, VarType)

__all__ = [
    "Variable",
    "Operator",
    "Block",
    "Program",
    "Parameter",
    "default_main_program",
    "default_startup_program",
    "switch_main_program",
    "switch_startup_program",
    "program_guard",
    "name_scope",
    "grad_var_name",
    "GRAD_VAR_SUFFIX",
]

GRAD_VAR_SUFFIX = "@GRAD"


def op_external_reads(program, op) -> set:
    """Names an op reads, including everything its sub-blocks read from
    outside themselves (reference prune.cc:181 recurses into block attrs —
    a while/conditional_block depends on its upstream producers even when
    the root op desc only lists e.g. Cond)."""
    reads = set(op.input_arg_names)
    sub_idxs = []
    for a in op.desc.attrs.values():
        if isinstance(a, BlockRef):
            sub_idxs.append(a.idx)
        elif isinstance(a, BlocksRef):
            sub_idxs.extend(a.idxs)
    seen = set()
    while sub_idxs:
        si = sub_idxs.pop()
        if si in seen:
            continue
        seen.add(si)
        sub = program.block(si)
        produced = set()
        for sop in sub.ops:
            for name in sop.input_arg_names:
                if name not in produced and not sub.desc.has_var(name):
                    reads.add(name)
            produced.update(sop.output_arg_names)
            for a in sop.desc.attrs.values():
                if isinstance(a, BlockRef):
                    sub_idxs.append(a.idx)
                elif isinstance(a, BlocksRef):
                    sub_idxs.extend(a.idxs)
    return reads


_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _user_frame() -> Optional[str]:
    """file:line of the first stack frame outside paddle_tpu (cheap: walks
    frames, no traceback objects)."""
    try:
        f = sys._getframe(2)
    except ValueError:
        return None
    while f is not None:
        fn = f.f_code.co_filename
        if not fn.startswith(_PKG_DIR) or os.sep + "tests" + os.sep in fn:
            return f"{fn}:{f.f_lineno}"
        f = f.f_back
    return None


def grad_var_name(name: str) -> str:
    return name + GRAD_VAR_SUFFIX


_np_dtype_names = {
    "float16", "bfloat16", "float32", "float64",
    "int8", "int16", "int32", "int64", "uint8", "bool",
}


def convert_dtype(dtype) -> str:
    """Normalize a dtype spec (np.dtype, str, jnp dtype) to a canonical name."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        name = dtype
    else:
        name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    if name not in _np_dtype_names:
        # handles things like np.float32 type objects
        name = np.dtype(name).name
    assert name in _np_dtype_names, f"unsupported dtype {dtype!r}"
    return name


class Variable:
    """Compile-time variable handle inside a Block (reference framework.py:117).

    Holds no data; runtime values live in a Scope (executor.py). Math operator
    overloading is patched on by layers/math_op_patch.py.
    """

    def __init__(
        self,
        block: "Block",
        name: Optional[str] = None,
        shape: Optional[Iterable[int]] = None,
        dtype=None,
        lod_level: Optional[int] = None,
        type: VarType = VarType.LOD_TENSOR,
        persistable: Optional[bool] = None,
        stop_gradient: bool = False,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        if block.desc.has_var(name):
            # Re-opening an existing var (e.g. startup/main program share
            # parameter names): merge, verifying compatible metadata.
            d = block.desc.var(name)
            if shape is not None and d.shape is not None:
                assert list(shape) == list(d.shape), (
                    f"Variable {name} re-declared with shape {list(shape)} != {d.shape}")
            if shape is not None:
                d.shape = list(shape)
            if dtype is not None:
                d.dtype = convert_dtype(dtype)
            if lod_level is not None:
                d.lod_level = lod_level
            if persistable is not None:
                d.persistable = persistable
        else:
            d = VarDesc(
                name=name,
                type=type,
                dtype=convert_dtype(dtype),
                shape=list(shape) if shape is not None else None,
                lod_level=lod_level or 0,
                persistable=bool(persistable),
                stop_gradient=stop_gradient,
            )
            block.desc.vars[name] = d
        self.desc = d
        self.stop_gradient = stop_gradient
        block.vars[name] = self

    # --- metadata accessors -------------------------------------------------
    @property
    def name(self) -> str:
        return self.desc.name

    @property
    def shape(self):
        return tuple(self.desc.shape) if self.desc.shape is not None else None

    @property
    def dtype(self) -> str:
        return self.desc.dtype

    @property
    def lod_level(self) -> int:
        return self.desc.lod_level

    @property
    def type(self) -> VarType:
        return self.desc.type

    @property
    def persistable(self) -> bool:
        return self.desc.persistable

    @persistable.setter
    def persistable(self, p: bool):
        self.desc.persistable = p
        # invalidates the executor's cached program analysis (persistable
        # map) and jit cache — the run signature changes with this flag
        self.block.program._version += 1

    def __str__(self):
        return (f"Variable(name={self.name}, shape={self.shape}, "
                f"dtype={self.dtype}, type={self.type.value})")

    __repr__ = __str__


class Operator:
    """Compile-time operator inside a Block (reference framework.py:361).

    Appending an operator immediately runs compile-time shape inference via
    the op registry (the reference does this through C++ InferShape at desc
    build time).
    """

    def __init__(self, block: "Block", desc: OpDesc):
        self.block = block
        self.desc = desc
        # Python creation site (first frame outside paddle_tpu): the
        # CustomStackTrace analogue (reference utils/CustomStackTrace.h
        # dumps the layer stack on crash) — executor error messages point
        # at the user line that built the failing op.
        self.creation_site = _user_frame()

    @property
    def type(self) -> str:
        return self.desc.type

    @property
    def input_arg_names(self):
        return self.desc.input_arg_names()

    @property
    def output_arg_names(self):
        return self.desc.output_arg_names()

    def input(self, slot):
        return self.desc.input(slot)

    def output(self, slot):
        return self.desc.output(slot)

    def attr(self, name, default=None):
        return self.desc.attr(name, default)

    def set_attr(self, name, val):
        self.desc.attrs[name] = val

    def __str__(self):
        ins = {k: v for k, v in self.desc.inputs.items()}
        outs = {k: v for k, v in self.desc.outputs.items()}
        return f"Op(type={self.type}, inputs={ins}, outputs={outs})"

    __repr__ = __str__


class Block:
    """An ordered op list plus a var table (reference framework.py:644)."""

    def __init__(self, program: "Program", idx: int):
        self.program = program
        self.desc: BlockDesc = program.desc.block(idx)
        self.vars: Dict[str, Variable] = {}
        self.ops: List[Operator] = []

    @property
    def idx(self) -> int:
        return self.desc.idx

    @property
    def parent_idx(self) -> int:
        return self.desc.parent_idx

    @property
    def parent_block(self) -> Optional["Block"]:
        if self.desc.parent_idx < 0:
            return None
        return self.program.block(self.desc.parent_idx)

    # --- vars ---------------------------------------------------------------
    def create_var(self, **kwargs) -> Variable:
        return Variable(self, **kwargs)

    def create_parameter(self, **kwargs) -> "Parameter":
        # Parameters always live in the global (root) block, matching the
        # reference's global-block parameter placement.
        gblock = self.program.global_block()
        return Parameter(gblock, **kwargs)

    def has_var(self, name: str) -> bool:
        return name in self.vars or self.desc.has_var(name)

    def var(self, name: str) -> Variable:
        v = self.vars.get(name)
        if v is not None:
            return v
        if self.desc.has_var(name):
            # materialize a wrapper for a desc-only var (e.g. after clone)
            d = self.desc.vars[name]
            v = Variable.__new__(Variable)
            v.block = self
            v.desc = d
            v.stop_gradient = d.stop_gradient
            self.vars[name] = v
            return v
        raise ValueError(f"Variable {name} not found in block {self.idx}")

    def var_recursive(self, name: str) -> Variable:
        b: Optional[Block] = self
        while b is not None:
            if b.has_var(name):
                return b.var(name)
            b = b.parent_block
        raise ValueError(f"Variable {name} not found in block chain from {self.idx}")

    def has_var_recursive(self, name: str) -> bool:
        b: Optional[Block] = self
        while b is not None:
            if b.has_var(name):
                return True
            b = b.parent_block
        return False

    def all_parameters(self) -> List["Parameter"]:
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    # --- ops ----------------------------------------------------------------
    def _make_op(self, type: str, inputs=None, outputs=None, attrs=None) -> OpDesc:
        def norm(d):
            out = {}
            for k, v in (d or {}).items():
                if v is None:
                    continue
                if isinstance(v, (Variable, str)):
                    v = [v]
                out[k] = [x.name if isinstance(x, Variable) else x for x in v]
            return out

        attrs = dict(attrs or {})
        if _name_scopes:
            attrs.setdefault(NAME_SCOPE_ATTR, "/%s/" % "/".join(_name_scopes))
        return OpDesc(type=type, inputs=norm(inputs), outputs=norm(outputs),
                      attrs=attrs)

    def append_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        desc = self._make_op(type, inputs, outputs, attrs)
        op = Operator(self, desc)
        self.desc.ops.append(desc)
        self.ops.append(op)
        self.program._version += 1
        self._infer_shape(op)
        return op

    def prepend_op(self, type: str, inputs=None, outputs=None, attrs=None) -> Operator:
        desc = self._make_op(type, inputs, outputs, attrs)
        op = Operator(self, desc)
        self.desc.ops.insert(0, desc)
        self.ops.insert(0, op)
        self.program._version += 1
        self._infer_shape(op)
        return op

    def insert_op(self, index: int, type: str, inputs=None, outputs=None,
                  attrs=None) -> Operator:
        desc = self._make_op(type, inputs, outputs, attrs)
        op = Operator(self, desc)
        self.desc.ops.insert(index, desc)
        self.ops.insert(index, op)
        self.program._version += 1
        self._infer_shape(op)
        return op

    def remove_op(self, index: int):
        del self.desc.ops[index]
        del self.ops[index]
        self.program._version += 1

    def _infer_shape(self, op: Operator):
        from ..ops import registry  # local import to avoid cycle
        opdef = registry.try_get(op.type)
        if opdef is None:
            raise ValueError(f"Operator type '{op.type}' is not registered")
        if opdef.infer_shape is not None:
            opdef.infer_shape(op, self)

    def _sync_ops(self):
        """Rebuild Operator wrappers from desc (after clone/deserialize)."""
        self.ops = [Operator(self, d) for d in self.desc.ops]
        for name in list(self.desc.vars):
            self.var(name)


class Program:
    """A whole computation: list of blocks (reference framework.py:940)."""

    def __init__(self):
        self.desc = ProgramDesc()
        self.blocks: List[Block] = [Block(self, 0)]
        self.current_block_idx = 0
        self._seed = 0
        # bumped on every op append/remove so the executor's compile cache
        # never serves a stale trace for a mutated program
        self._version = 0
        # device mesh for SPMD execution (parallel/transpiler.py)
        self._mesh = None
        # populated by append_backward: grad var name <-> fwd var mapping
        self.grad_info_map: Dict[str, Any] = {}

    # --- seeds --------------------------------------------------------------
    @property
    def random_seed(self) -> int:
        return self._seed

    @random_seed.setter
    def random_seed(self, s: int):
        self._seed = int(s)

    # --- block management ---------------------------------------------------
    def global_block(self) -> Block:
        return self.blocks[0]

    def block(self, idx: int) -> Block:
        return self.blocks[idx]

    def current_block(self) -> Block:
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx: Optional[int] = None) -> Block:
        parent = self.current_block_idx if parent_idx is None else parent_idx
        self.desc.append_block(parent)
        b = Block(self, len(self.desc.blocks) - 1)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        return b

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    @property
    def num_blocks(self) -> int:
        return len(self.blocks)

    # --- whole-program ops --------------------------------------------------
    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def clone(self, for_test: bool = False) -> "Program":
        """Deep-copy the program (reference framework.py Program.clone).

        for_test=True flips training-only behavior off (e.g. dropout/batch_norm
        is_test attr), mirroring the reference's inference_optimize+clone use.
        """
        p = Program()
        p.desc = ProgramDesc.from_json(self.desc.to_json())
        p._seed = self._seed
        # dynamic execution attributes ride along (the reference keeps these
        # in the desc; here they are Python-side program state): mesh tag,
        # AMP policy, bound reader pipelines
        p._mesh = getattr(self, "_mesh", None)
        for attr in ("_amp_dtype", "_amp_level", "_pipeline_readers",
                     "_param_shardings", "_feed_shardings", "_sharded_tables",
                     # observability state: telemetry side-fetch marks, loss
                     # names recorded by append_backward, inspector probe
                     # sites / audit / internal-run marker — all describe the
                     # desc being copied, so they ride along (dict/list
                     # values shallow-copied so mutating the clone's map
                     # never leaks back)
                     "_telemetry_fetch_extra", "_loss_names", "_probe_sites",
                     "_probe_parent", "_grad_audit", "_inspector_internal"):
            if hasattr(self, attr):
                val = getattr(self, attr)
                if isinstance(val, (dict, list)):
                    val = copy.copy(val)
                setattr(p, attr, val)
        if self.grad_info_map:
            p.grad_info_map = dict(self.grad_info_map)
        p.blocks = [Block(p, i) for i in range(len(p.desc.blocks))]
        for b in p.blocks:
            b._sync_ops()
            # preserve Parameter-ness
            src = self.blocks[b.idx]
            for name, v in src.vars.items():
                if isinstance(v, Parameter) and name in b.vars:
                    pv = b.vars[name]
                    param = Parameter.__new__(Parameter)
                    param.__dict__.update(pv.__dict__)
                    param.trainable = v.trainable
                    param.optimize_attr = copy.copy(v.optimize_attr)
                    param.regularizer = v.regularizer
                    param.gradient_clip_attr = v.gradient_clip_attr
                    param.do_model_average = v.do_model_average
                    b.vars[name] = param
        if for_test:
            for b in p.blocks:
                for op in b.ops:
                    if "is_test" in op.desc.attrs:
                        op.set_attr("is_test", True)
        p.current_block_idx = 0
        return p

    def prune(self, feeds: List[str], fetches: List[str]) -> "Program":
        """Dead-op elimination from fetch targets (reference prune.cc:181).

        Keeps, in the root block, only ops on a path to `fetches` given that
        `feeds` are externally provided.

        Backward/optimize-role ops reached only through an in-place
        persistable update are dropped: an optimizer op writes ParamOut
        aliasing the parameter, so a fetch built after minimize() sees it
        as the parameter's producer and the reverse walk would drag the
        whole training tail — gradients, moments, beta pows — into the
        inference slice, leaving dead opt-state persistables the dead-var
        pass then flags. The pre-update value is what an inference slice
        wants; the parameter stays a state leaf. A training-role op that
        is the sole producer of a needed NON-persistable (an explicitly
        fetched gradient) is still kept.
        """
        pruned = self.clone()
        block = pruned.global_block()

        def op_reads(op):
            return op_external_reads(pruned, op)

        def _persistable(name):
            return block.desc.has_var(name) and \
                block.desc.var(name).persistable

        needed = set(fetches)
        keep: List[int] = []
        for i in range(len(block.ops) - 1, -1, -1):
            op = block.ops[i]
            hit = needed & set(op.output_arg_names)
            if hit:
                if op.desc.attrs.get("op_role") in ("backward", "optimize") \
                        and all(_persistable(n) for n in hit):
                    continue
                keep.append(i)
                for name in op_reads(op):
                    if name not in feeds:
                        needed.add(name)
        keep.reverse()
        block.desc.ops = [block.desc.ops[i] for i in keep]
        block._sync_ops()
        # drop root vars no longer referenced (sub-block vars stay put)
        used = set(feeds) | set(fetches)
        for op in block.ops:
            used |= op_reads(op) | set(op.output_arg_names)
        for name in list(block.desc.vars):
            if name not in used:
                del block.desc.vars[name]
                block.vars.pop(name, None)
        return pruned

    def to_json(self) -> str:
        return self.desc.to_json()

    @staticmethod
    def from_json(s: str) -> "Program":
        p = Program()
        p.desc = ProgramDesc.from_json(s)
        p.blocks = [Block(p, i) for i in range(len(p.desc.blocks))]
        for b in p.blocks:
            b._sync_ops()
        return p

    def __str__(self):
        lines = []
        for b in self.blocks:
            lines.append(f"block {b.idx} (parent {b.parent_idx}):")
            for name, v in sorted(b.desc.vars.items()):
                tag = " [persistable]" if v.persistable else ""
                lines.append(f"  var {name}: {v.dtype}{v.shape}{tag}")
            for op in b.ops:
                lines.append(f"  {op}")
        return "\n".join(lines)


class Parameter(Variable):
    """A persistable, trainable variable (reference framework.py:1118)."""

    def __init__(self, block: Block, shape=None, dtype=None, **kwargs):
        assert shape is not None, "Parameter requires a fully-known shape"
        assert all(s > 0 for s in shape), f"Parameter shape must be static, got {shape}"
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        super().__init__(block, shape=shape, dtype=dtype, persistable=True, **kwargs)


# --- default programs -------------------------------------------------------

_main_program = Program()
_startup_program = Program()


@contextlib.contextmanager
def in_block(program: Program, block_idx: int):
    """Temporarily build ops into `block_idx` of `program` — the shared
    idiom for control-flow builders that must emit setup ops into the
    PARENT block while the sub-block is current (DynamicRNN memory init,
    v2 beam_search boot state: those ops run before the loop op, which
    the enclosing context appends only on exit)."""
    cur = program.current_block_idx
    program.current_block_idx = block_idx
    try:
        yield program.current_block()
    finally:
        program.current_block_idx = cur


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(program: Program) -> Program:
    global _main_program
    old, _main_program = _main_program, program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program
    old, _startup_program = _startup_program, program
    return old


NAME_SCOPE_ATTR = "op_namescope"
_name_scopes: List[str] = []


@contextlib.contextmanager
def name_scope(prefix: str):
    """fluid.name_scope (reference framework.py name_scope): every op
    appended inside carries the attribute `op_namescope` = "/outer/inner/",
    and so does its gradient op, which copies its forward op's attributes.
    The executor lowers it as a named scope `pd_scope.<outer.inner>`
    between the op's role and its type, so a trace can book a `mul` to
    the layer that built it (benchmarks/rooflines.py scope_seconds)."""
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
