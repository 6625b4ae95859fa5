"""Executor: compiles program blocks to XLA and runs them on TPU/CPU.

TPU-native replacement for the reference's interpreting executor
(reference: paddle/fluid/framework/executor.cc:96-344 Executor::Run/Prepare,
python/paddle/fluid/executor.py:182-400). The reference walks a block op by
op, dispatching each to a CUDA kernel against a mutable Scope. On TPU that
per-op dispatch model wastes the compiler: here `Executor.run` *traces the
whole block's op lowerings into a single function* — feed vars and persistable
state in, fetch vars and updated state out — and `jax.jit`s it once per
(program, feed, fetch) signature. Parameters are donated so optimizer updates
alias in-place in HBM. An eager mode (`use_jit=False` or
PADDLE_TPU_EAGER=1) interprets op-by-op like the reference, for debugging and
NaN/Inf checks (reference FLAGS_check_nan_inf, executor.cc:325-333).

Scope semantics follow the reference (scope.h:38): persistable variables live
in the global scope across runs; block-local temporaries vanish after the run.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import os
import sys
import time
import warnings
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dynamics as dynamics_mod
from . import flags as flags_mod
from . import inspector as inspector_mod
from . import memory as memory_mod
from . import profiler as profiler_mod
from . import quant as quant_mod
from . import recompute as recompute_mod
from . import sentinel as sentinel_mod
from . import telemetry
from . import tracing as tracing_mod
from . import xplane as xplane_mod
from .backward import RECOMPUTE_ATTR
from .framework.desc import VarType
from .framework.framework import (NAME_SCOPE_ATTR, Program, Variable,
                                  default_main_program)
from .ops import registry
from .ops import sparse_ops as sparse_ops_mod
from .ops.sibling_products import OpenProducts

__all__ = [
    "CPUPlace", "TPUPlace", "CUDAPlace", "place_device",
    "LoDTensor", "Scope", "global_scope", "scope_guard", "Executor",
]


# ---------------------------------------------------------------------------
# Places (reference: platform/place.h:24,34,53 — CPUPlace/CUDAPlace variant).
# TPUPlace is the first-class accelerator place; CUDAPlace is accepted for
# source compatibility and maps to the same accelerator backend.
# ---------------------------------------------------------------------------

class Place:
    device_kind = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def __eq__(self, other):
        return (type(self) is type(other)) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    device_kind = "cpu"


class TPUPlace(Place):
    device_kind = "accelerator"


class CUDAPlace(TPUPlace):
    """Source-compat alias: scripts written for fluid.CUDAPlace(0) run on the
    TPU backend unchanged (BASELINE.json north star)."""


def _cpu_forced() -> bool:
    """jax was told to use the CPU and nothing else (JAX_PLATFORMS=cpu:
    the test harness, the CPU rehearsals of chip_smoke.py)."""
    return jax.config.jax_platforms == "cpu"


def place_device(place: Place):
    """Resolve a Place to a concrete jax.Device."""
    if isinstance(place, CPUPlace):
        cpus = [d for d in jax.devices("cpu")] if "cpu" in {
            d.platform for d in jax.local_devices()} else None
        if cpus is None:
            try:
                cpus = jax.devices("cpu")
            except RuntimeError:
                cpus = jax.local_devices()
        return cpus[min(place.device_id, len(cpus) - 1)]
    devs = jax.local_devices()
    accel = [d for d in devs if d.platform != "cpu"]
    if not accel:
        # an accelerator place runs on the CPU only where the platform was
        # forced to it (JAX_PLATFORMS=cpu: the test harness, the CPU
        # rehearsals) — never because the accelerator failed to show up
        if not _cpu_forced():
            raise RuntimeError(
                f"{place!r} asks for an accelerator and jax found none "
                f"(devices: {devs}); set JAX_PLATFORMS=cpu to rehearse "
                f"on the CPU, or use CPUPlace")
        accel = devs
    return accel[min(place.device_id, len(accel) - 1)]


# ---------------------------------------------------------------------------
# Runtime values
# ---------------------------------------------------------------------------

class LoDTensor:
    """Runtime tensor + level-of-detail sequence offsets
    (reference: framework/lod_tensor.h:55,107). The array is padded/dense; the
    LoD records per-sequence offsets so sequence ops can mask correctly."""

    def __init__(self, array=None, lod: Optional[List[List[int]]] = None):
        self._array = array
        self.lod = lod or []

    def set(self, array, place=None):
        self._array = np.asarray(array)

    def set_lod(self, lod):
        self.lod = lod

    def array(self):
        return self._array

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype is not None else a

    @property
    def shape(self):
        return tuple(self._array.shape)

    def recursive_sequence_lengths(self):
        return [[b - a for a, b in zip(lvl[:-1], lvl[1:])] for lvl in self.lod]


class Scope:
    """name -> runtime value, with parent chain (reference scope.h:38)."""

    def __init__(self, parent: Optional["Scope"] = None):
        self.parent = parent
        self.vars: Dict[str, Any] = {}
        self.kids: List[Scope] = []

    def new_scope(self) -> "Scope":
        s = Scope(self)
        self.kids.append(s)
        return s

    def var(self, name: str):
        if name not in self.vars:
            self.vars[name] = None
        return self.vars[name]

    def set_var(self, name: str, value):
        self.vars[name] = value

    def find_var(self, name: str):
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return s.vars[name]
            s = s.parent
        return None

    def has_var(self, name: str) -> bool:
        s: Optional[Scope] = self
        while s is not None:
            if name in s.vars:
                return True
            s = s.parent
        return False

    def local_var_names(self):
        return list(self.vars)

    def drop_kids(self):
        self.kids = []


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


# ---------------------------------------------------------------------------
# Lowering context handed to op kernels
# ---------------------------------------------------------------------------

class LoweringContext:
    def __init__(self, executor: "Executor", program: Program, rng_key,
                 lod_map: Dict[str, Any]):
        self.executor = executor
        self.program = program
        self.place = executor.place
        self._rng_key = rng_key
        self.lod_map = lod_map    # var name -> lod metadata (host-side)
        # mixed-precision compute dtype for MXU-bound ops (amp.py); None =
        # full precision. Read by ops.common.mxu_cast. Level O1 restores
        # f32 after each MXU op; O2 keeps activations bf16 end-to-end.
        self.amp_dtype = getattr(program, "_amp_dtype", None)
        self.amp_level = getattr(program, "_amp_level", "O1")
        # O3 quantization mode ("int8"/"fp8", amp.py) or None; read by
        # the matmul/conv lowerings to route through quant.py
        self.quant_mode = getattr(program, "_quant_mode", None)
        # live env of the block being traced; lowerings use it to read
        # sequence-length side channels (`<var>@SEQLEN`, see seq_len()).
        self.env: Dict[str, Any] = {}
        # out var name -> lengths array (or None to clear) set by sequence
        # lowerings to override the default SEQLEN propagation in _exec_op
        self.seq_overrides: Dict[str, Any] = {}
        # internal activation-layout tags (ops/layout.py): var name ->
        # "NHWC"/"NDHWC" for values held in the TPU-preferred layout;
        # absent = canonical NCHW. Aware lowerings set tags for their
        # outputs via set_layout (collected per-op like seq_overrides).
        from .ops import layout as layout_mod
        self.layout_opt = layout_mod.LAYOUT_OPT
        self.layouts: Dict[str, str] = {}
        self.layout_overrides: Dict[str, Any] = {}

    def layout_of(self, name: str):
        return self.layouts.get(name)

    def set_layout(self, name: str, tag):
        self.layout_overrides[name] = tag

    def seq_len(self, name: str):
        """Per-sequence valid lengths [batch] for a padded sequence var, or
        None. The TPU-native stand-in for the reference's LoD offset table
        (lod_tensor.h:55): LoDTensor feeds are padded dense and their lengths
        ride along the trace as an int32 array input."""
        return self.env.get(name + SEQLEN_SUFFIX)

    def seq_len2(self, name: str):
        """Inner lengths [batch, S] for a nested (lod_level=2) sequence var,
        or None (reference lod_tensor.h:55 second offset level)."""
        return self.env.get(name + SEQLEN2_SUFFIX)

    def set_seq_len(self, name: str, lengths):
        self.seq_overrides[name] = lengths

    def set_seq_len2(self, name: str, lengths):
        self.seq_overrides[name + SEQLEN2_SUFFIX] = lengths

    def next_rng(self, op=None):
        """Deterministic per-op PRNG key. Keyed on the op's first output name
        (stable identity), NOT a call counter: the generic vjp grad kernel
        re-traces the forward lowering, and a counter would hand the re-trace
        a different key than the forward pass saw (e.g. a dropout mask that
        differs between forward and backward). Per-step variation comes from
        the run counter folded into the base key (Executor.run)."""
        seed = int(op.attr("seed", 0) or 0) if op is not None else 0
        key = self._rng_key if not seed else jax.random.key(seed)
        ident = 0
        if op is not None:
            outs = op.desc.output_arg_names()
            if outs:
                import zlib
                ident = zlib.crc32(outs[0].encode("utf-8"))
        return jax.random.fold_in(key, ident)

    def run_block(self, block_idx: int, env: Dict[str, Any]) -> Dict[str, Any]:
        """Trace a sub-block's ops against `env` (for control-flow lowerings).
        Mutates and returns env."""
        block = self.program.block(block_idx)
        for op in block.ops:
            self.executor._exec_op(self, op, env)
        return env


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

_EAGER = os.environ.get("PADDLE_TPU_EAGER", "0") == "1"
_CHECK_NAN_INF = os.environ.get("PADDLE_TPU_CHECK_NAN_INF", "0") == "1"
_BENCHMARK = os.environ.get("PADDLE_TPU_BENCHMARK", "0") == "1"
_VLOG_LEVEL = int(os.environ.get("PADDLE_TPU_VLOG", "0") or 0)
# telemetry side-fetches (program._telemetry_fetch_extra, e.g. the clip
# pass's global-norm var): each one forces a device->host read per step to
# feed its gauge — PADDLE_TPU_TELEMETRY_FETCH=0 turns them off for
# latency-critical pipelined loops
_TELEMETRY_FETCH = os.environ.get("PADDLE_TPU_TELEMETRY_FETCH", "1") == "1"
# opt-in static verification at first compile (paddle_tpu.analysis): the
# reference's compile-time InferShape story — error-severity diagnostics
# raise errors.ProgramVerifyError BEFORE tracing, pointing at the op's
# Python creation site instead of a JAX traceback
_VERIFY = os.environ.get("PADDLE_TPU_VERIFY", "0") == "1"


_WARNED_CPU_SCAN_CONV = False


def _maybe_warn_cpu_scan_conv(device, program, steps):
    """Warn ONCE when a multi-step run_steps window is about to lower a
    conv backward inside lax.scan on the CPU backend: XLA:CPU runs
    grad-conv under scan ~60x slower than the same ops dispatched per
    step (the PR 5 windowed-dispatch caveat, previously documented only
    in CHANGES.md). Correctness is unaffected — tests stay on the
    windowed path — but a CPU training loop that cares about wall time
    should use per-step run() or a TPU backend."""
    global _WARNED_CPU_SCAN_CONV
    if _WARNED_CPU_SCAN_CONV or steps <= 1:
        return
    plat = getattr(device, "platform", None)
    if plat is None:
        plat = jax.default_backend()
    if plat != "cpu":
        return
    types = {o.type for o in program.global_block().ops}
    if not (types & {"conv2d_grad", "depthwise_conv2d_grad", "conv3d_grad",
                     "conv2d_transpose_grad"}):
        return
    _WARNED_CPU_SCAN_CONV = True
    import warnings
    warnings.warn(
        "run_steps is lowering a conv backward inside a lax.scan window "
        "on the XLA:CPU backend — known ~60x slower than per-step "
        "dispatch (see CHANGES.md, windowed dispatch caveat). Use "
        "exe.run() per step or steps=1 for CPU training wall time; TPU "
        "backends are unaffected.", RuntimeWarning, stacklevel=3)


def _vlog_level() -> int:
    """Live verbosity: the flags registry re-reads PADDLE_TPU_VLOG on every
    call, so flags.set("vlog", n) changes vlog() output at runtime (the
    import-time _VLOG_LEVEL snapshot is only the fallback if the registry
    is unavailable mid-interpreter-teardown)."""
    try:
        return int(flags_mod.get("vlog"))
    except Exception:
        return _VLOG_LEVEL


def vlog(level: int, msg: str):
    """glog-style leveled logging (reference VLOG; enable with
    PADDLE_TPU_VLOG=<level> or flags.set("vlog", n) at runtime)."""
    if level <= _vlog_level():
        import datetime
        ts = datetime.datetime.now().strftime("%H:%M:%S.%f")[:-3]
        print(f"V{level} {ts} paddle_tpu] {msg}", file=sys.stderr)

# FP-exception trapping (reference TrainerMain.cpp:49 feenableexcept
# FE_INVALID|FE_DIVBYZERO|FE_OVERFLOW): the XLA-world equivalent is
# jax's debug-nans mode — any op producing NaN/Inf raises at the op that
# made it (de-optimizes to op-by-op execution, debug only).
if os.environ.get("PADDLE_TPU_TRAP_FP", "0") == "1":
    jax.config.update("jax_debug_nans", True)
    jax.config.update("jax_debug_infs", True)

# op-coverage recorder: every executed op type lands in the in-process set
# (tests/test_zz_op_coverage.py asserts full-registry coverage at the end of
# a suite run); PADDLE_TPU_RECORD_OPS additionally appends to a file for
# cross-process reports (tools/op_coverage.py)
_RECORD_OPS_PATH = os.environ.get("PADDLE_TPU_RECORD_OPS")
_RECORDED_OPS = set()


def _record_op(op_type: str):
    if op_type not in _RECORDED_OPS:
        _RECORDED_OPS.add(op_type)
        if _RECORD_OPS_PATH:
            with open(_RECORD_OPS_PATH, "a") as f:
                f.write(op_type + "\n")

SEQLEN_SUFFIX = "@SEQLEN"
SEQLEN2_SUFFIX = "@SEQLEN2"   # inner lengths [B, S] of nested (level-2) LoD

# ops with a native SelectedRows (sparse-rows) kernel; everything else
# receives densified gradients (counted: sparse_densify_fallback_total).
# The reference registers SelectedRows variants for sum/sgd/adam
# (sum_op.cc, sgd_op.h, adam_op.h); momentum is a deliberate extension so
# the default CNN optimizer also keeps embedding grads sparse. The
# optimizer entries come from the sparse-capable table in
# ops/sparse_ops.py, which tools/check_registry.py pins against the
# actual lowerings. fused_sparse_* are the trace-time scatter-apply
# buckets (ops/fusion.py) — their Grad inputs must cross the boundary
# still sparse for the member kernels to re-execute.
_SPARSE_AWARE_OPS = frozenset(
    {"sum"} | set(sparse_ops_mod.SPARSE_APPLY_OPS)
    | {"fused_sparse_" + t for t in sparse_ops_mod.SPARSE_APPLY_OPS})


# ops that take an OpenProducts value as it is: `sum` folds it, and a
# fused chain (the one window kind that can hold a `sum`) runs its
# members through _exec_op, where each meets this same boundary
_OPEN_PRODUCT_AWARE_OPS = frozenset({"sum", "fused_chain"})


def _bucket_len(n: int) -> int:
    """Round a max sequence length up to a bucket boundary so XLA sees a
    small set of static shapes instead of one per batch (SURVEY.md §7:
    'bucketing + dense speed'): powers of two up to 64, multiples of 64 after."""
    if n <= 8:
        return 8
    b = 8
    while b < n and b < 64:
        b *= 2
    return b if b >= n else ((n + 63) // 64) * 64


def pack_to_padded(flat: np.ndarray, lod: List[List[int]]):
    """Packed [sum_len, ...] rows + LoD offsets -> padded dense + lengths:
    level-1 gives ([batch, T, ...], lengths [batch], None); level-2 nested
    sequences (reference lod_tensor.h:55, RecurrentGradientMachine.h:32)
    give ([batch, S, T, ...], outer lengths [batch], inner lengths
    [batch, S]). The dense/padded layout is the XLA-friendly equivalent of
    the reference's zero-padding-free packed LoDTensor."""
    assert len(lod) in (1, 2), "lod_level must be 1 or 2"
    if len(lod) == 1:
        offs = np.asarray(lod[0], dtype=np.int64)
        lengths = np.diff(offs).astype(np.int32)
        bsz = len(lengths)
        t = _bucket_len(int(lengths.max()) if bsz else 1)
        padded = np.zeros((bsz, t) + tuple(flat.shape[1:]), dtype=flat.dtype)
        if bsz and len(flat):
            # vectorized scatter: row r of flat lands at
            # [batch(r), r - start(batch(r))] — no per-sample Python loop in
            # the feed path
            batch_idx = np.repeat(np.arange(bsz), lengths)
            time_idx = np.arange(offs[-1]) - np.repeat(offs[:-1], lengths)
            padded[batch_idx, time_idx] = flat[: offs[-1]]
        return padded, lengths, None
    outer, inner = lod
    outer = np.asarray(outer, dtype=np.int64)
    inner = np.asarray(inner, dtype=np.int64)
    outer_lens = np.diff(outer).astype(np.int32)
    inner_lens_flat = np.diff(inner).astype(np.int32)
    bsz = len(outer_lens)
    s_max = _bucket_len(int(outer_lens.max()) if bsz else 1)
    t_max = _bucket_len(int(inner_lens_flat.max())
                        if len(inner_lens_flat) else 1)
    padded = np.zeros((bsz, s_max, t_max) + tuple(flat.shape[1:]),
                      dtype=flat.dtype)
    inner_lens = np.zeros((bsz, s_max), dtype=np.int32)
    if bsz and len(inner_lens_flat):
        n_seq = len(inner_lens_flat)
        seq_batch = np.repeat(np.arange(bsz), outer_lens)       # [n_seq]
        seq_pos = np.arange(n_seq) - np.repeat(outer[:-1], outer_lens)
        inner_lens[seq_batch, seq_pos] = inner_lens_flat
        total = int(inner[-1])
        if total:
            row_seq = np.repeat(np.arange(n_seq), inner_lens_flat)
            row_b = seq_batch[row_seq]
            row_s = seq_pos[row_seq]
            row_t = np.arange(total) - np.repeat(inner[:-1], inner_lens_flat)
            padded[row_b, row_s, row_t] = flat[:total]
    return padded, outer_lens, inner_lens


def padded_to_pack(padded: np.ndarray, lengths: np.ndarray,
                   inner_lengths: Optional[np.ndarray] = None):
    """Inverse of pack_to_padded: padded + lengths -> packed rows + LoD
    offsets (for fetch-side LoDTensor reconstruction); with inner_lengths
    the input is a nested [B, S, T, ...] batch and a 2-level LoD comes
    back."""
    lengths = np.asarray(lengths, dtype=np.int64)
    if inner_lengths is None:
        bsz = len(lengths)
        offs = np.concatenate([[0], np.cumsum(lengths)])
        if bsz == 0 or offs[-1] == 0:
            return padded[:0, 0], [offs.tolist()]
        batch_idx = np.repeat(np.arange(bsz), lengths)
        time_idx = np.arange(offs[-1]) - np.repeat(offs[:-1], lengths)
        return padded[batch_idx, time_idx], [offs.tolist()]
    inner_lengths = np.asarray(inner_lengths, dtype=np.int64)
    bsz = len(lengths)
    outer_offs = np.concatenate([[0], np.cumsum(lengths)])
    n_seq = int(outer_offs[-1])
    if n_seq == 0:
        return padded[:0, 0, 0], [outer_offs.tolist(), [0]]
    seq_batch = np.repeat(np.arange(bsz), lengths)
    seq_pos = np.arange(n_seq) - np.repeat(outer_offs[:-1], lengths)
    seq_lens = inner_lengths[seq_batch, seq_pos]                # [n_seq]
    inner_offs = np.concatenate([[0], np.cumsum(seq_lens)])
    total = int(inner_offs[-1])
    if total == 0:
        return padded[:0, 0, 0], [outer_offs.tolist(), inner_offs.tolist()]
    row_seq = np.repeat(np.arange(n_seq), seq_lens)
    row_t = np.arange(total) - np.repeat(inner_offs[:-1], seq_lens)
    return (padded[seq_batch[row_seq], seq_pos[row_seq], row_t],
            [outer_offs.tolist(), inner_offs.tolist()])


# Observers notified as (op, ins, outs) for every op lowered by _exec_op —
# ins/outs are {slot: [tracer|None]}. Installed only for the duration of an
# abstract trace (roofline.program_cost runs jax.eval_shape with one) so
# the analytic cost model sees concrete per-op shapes/dtypes instead of
# the ProgramDesc's -1 batch dims. Empty in normal execution: the per-op
# overhead is one falsy check at trace time, nothing at run time.
_op_observers: List = []


def _cost_supplier(executor, program, feed_avals, state_avals, window=False):
    """Zero-arg lazy supplier of the analytic per-op cost table
    (roofline.program_cost) for a compiled block: the REQUIRED operations
    beside the executed ones of its account. Holds only avals.
    window=True strips the leading [K] steps axis off each feed so the
    table is per-step."""
    if window:
        feed_avals = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                      for n, a in feed_avals.items()}

    def cost():
        from . import roofline
        return roofline.program_cost(executor, program, feed_avals,
                                     state_avals)

    return cost


@jax.jit
def _finite_all(leaves):
    """ONE fused finiteness reduction over every checked tensor of a step:
    the jit-path check_nan_inf used to `np.asarray` each fetch and state
    item — a device->host sync per tensor; this reduces them all on-device
    and costs a single scalar readback. Trace-cached per aval signature."""
    return functools.reduce(
        jnp.logical_and, (jnp.all(jnp.isfinite(x)) for x in leaves),
        jnp.asarray(True))


def _accepts_sparse_slots(reader) -> bool:
    """Whether a run_steps reader's next_window takes the emb_cache
    sparse_slots hook (reader.pipeline.DoubleBufferedFeeder does;
    user-supplied readers may predate it)."""
    import inspect
    try:
        return "sparse_slots" in inspect.signature(
            reader.next_window).parameters
    except (TypeError, ValueError):
        return False


class _WindowUnsupported(Exception):
    """Raised at trace time when a program feature (sequence/LoD fetches,
    shape-changing state) cannot ride through the lax.scan window; the
    executor falls back to the per-step path."""


class _CompiledBlock:
    def __init__(self, fn, state_names, feed_names, fetch_names, program):
        self.fn = fn
        self.state_names = state_names
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        # strong ref: the cache key uses id(program), which stays valid only
        # while the program object is alive
        self.program = program
        # feed (name, shape, dtype) signatures already traced by self.fn:
        # the executor's view of jax.jit's retrace cache, kept so telemetry
        # can name the signature that caused a cache miss (a retrace the
        # executor-level cache key — names only, no shapes — cannot see)
        self.seen_sigs: set = set()
        self.last_sig = None
        # the step's account by instruction: (HLO module name,
        # [xplane.Instr], XLA's FLOP count). Built once, from the text the
        # static memory analysis reads (memory.on_compile); where that
        # analysis is off or not this block's (a run_steps window),
        # lazily from `avals`, the first launch's argument avals (shapes,
        # dtypes, shardings: never the donated buffers)
        self.account = None
        self.avals = None
        self.cost_fn = None


_NO_WATCH = contextlib.nullcontext()
# op_role (backward.py, optimizer.py) -> the named scope that carries it
# into the HLO; an op without one is the forward pass
_ROLE_SCOPE = {None: "pd_role.forward", "forward": "pd_role.forward",
               "backward": "pd_role.backward",
               "optimize": "pd_role.optimize"}


def _step_id(scope):
    """The id a step's spans share: the scope's PRNG counter as the step
    starts. Looked up only while someone listens."""
    if not tracing_mod.active():
        return None
    return int(scope.find_var("__rng_counter__") or 0)


def _book_build(prog_label, seconds):
    """executor_build_seconds_total{program,phase}: once per block that
    compiled, never per step."""
    family = telemetry.counter(
        "executor_build_seconds_total",
        "first run of a compiled block, by phase: trace, lower, compile, "
        "analysis, execute", labels=("program", "phase"))
    for phase, secs in seconds.items():
        family.labels(program=prog_label, phase=phase).inc(max(secs, 0.0))


def _build_seconds(events, launch_s, plan_s):
    """{phase: seconds} of a call that built its block: jax's trace,
    lower and compile (or cache load) inside the launch, what is left of
    the launch as `execute`, and the executor's own walk from Program to
    step function (`plan_s`, before the launch) under `trace`."""
    seconds = dict.fromkeys(("trace", "lower", "compile"), 0.0)
    seconds.update(telemetry.build_phase_seconds(events))
    seconds["execute"] = launch_s - sum(seconds.values())
    seconds["trace"] += plan_s
    seconds["analysis"] = 0.0
    return seconds


def _span_build_events(events):
    """jax's trace / lower / compile events of a watched call as ring
    spans under the live span (launch), at the times jax measured. Only
    stretches of a millisecond or more: lowering a ResNet-50 step traces
    four thousand kernel bodies of 14 us each, which would push every
    other span out of the ring (the counter counts them all)."""
    if tracing_mod.enabled():
        parent = tracing_mod.current_span()
        if parent.sampled:
            for phase, start, end in telemetry.merge_build_events(events):
                if end - start >= 1e-3:
                    tracing_mod.record_span(phase, start, end, parent=parent)


class _Launch(NamedTuple):
    """What `_launch` hands to `_book`: the one timer's readings and what
    the call turned out to be."""
    t0: float                   # perf_counter as the call started
    run_dt: float               # the call, wall seconds
    compile_s: float            # of them, XLA's backend_compile
    cache: str                  # "miss" (this signature built), "hit", "n/a"
    cause: Optional[str] = None     # of a miss: first_compile|signature_change
    build_s: Optional[Dict[str, float]] = None   # {phase: seconds} of a miss
    sig: Optional[Tuple] = None     # the feed signature the block ran on


def _fetch_names(fetch_list) -> List[str]:
    return [v.name if isinstance(v, Variable) else str(v)
            for v in list(fetch_list or [])]


def _densify(into, lod_map, name, tensor):
    """A LoDTensor's dense value; its LoD goes to `lod_map`, and where it
    has one the value comes back padded, with the lengths filed in `into`
    under `<name>@SEQLEN` (and `@SEQLEN2` for a nested LoD)."""
    lod_map[name] = tensor.lod
    arr = np.asarray(tensor.array())
    if tensor.lod:
        arr, lengths, inner = pack_to_padded(arr, tensor.lod)
        into[name + SEQLEN_SUFFIX] = lengths
        if inner is not None:
            into[name + SEQLEN2_SUFFIX] = inner
    return arr


def _rebuild_fetches(fetch_names, fetch_vals, fetch_lens, return_numpy):
    """return: fetched sequence vars come back in the reference's packed
    layout ([sum_len, ...] rows): numpy mode returns the packed array,
    LoDTensor mode additionally carries the offsets."""
    rebuilt = []
    for n, v in zip(fetch_names, fetch_vals):
        lens = fetch_lens.get(n)
        inner = fetch_lens.get(n + SEQLEN2_SUFFIX)
        if lens is None and not return_numpy:
            # keep the fetch on-device: np.asarray would force a
            # device->host sync per step, which return_numpy=False
            # callers (benchmarks, pipelined training loops) avoid
            rebuilt.append(v)
            continue
        arr = np.asarray(v)
        if lens is not None:
            lens = np.asarray(lens)
            # ignore spuriously-tagged non-sequence fetches
            if arr.ndim < 2 or lens.shape[0] != arr.shape[0] or \
                    (lens.size and lens.max() > arr.shape[1]):
                lens = None
        if inner is not None and lens is not None:
            inner = np.asarray(inner)
            if arr.ndim < 3 or inner.shape[:2] != arr.shape[:2] or \
                    (inner.size and inner.max() > arr.shape[2]):
                inner = None
        if lens is not None:
            packed, lod = padded_to_pack(arr, lens, inner)
            rebuilt.append(np.asarray(packed) if return_numpy
                           else LoDTensor(packed, lod))
        else:
            rebuilt.append(arr if return_numpy else v)
    return rebuilt


class Executor:
    def __init__(self, place: Optional[Place] = None):
        self.place = place if place is not None else TPUPlace(0)
        self.device = place_device(self.place)
        self._cache: Dict[Tuple, _CompiledBlock] = {}
        self._analysis_cache: Dict[Tuple, Tuple] = {}
        # (id(program), version) -> what the last trace decided of the
        # program's recomputation segments (recompute.Plan), and the
        # programs whose compile ran out of memory with segments kept:
        # those are traced again with every segment replayed
        self._replay_plans: Dict[Tuple, recompute_mod.Plan] = {}
        self._replay_all: set = set()
        # telemetry side-fetches dispatched and not yet on the host
        self._side_pending: collections.deque = collections.deque()

    # --- public API ---------------------------------------------------------
    def run(self, program: Optional[Program] = None, feed: Optional[Dict] = None,
            fetch_list: Optional[Sequence] = None, feed_var_name: str = "feed",
            fetch_var_name: str = "fetch", scope: Optional[Scope] = None,
            return_numpy: bool = True, use_program_cache: bool = True,
            use_jit: Optional[bool] = None):
        program = program if program is not None else default_main_program()
        scope = scope if scope is not None else global_scope()
        # hang watchdog: a None fast-path unless sentinel.start() ran
        _tok = sentinel_mod.arm_dispatch(telemetry.program_label(program))
        try:
            # `step` covers the whole call; _dispatch tiles it with the
            # phases prepare / launch / bookkeep / writeback
            with tracing_mod.span("step", step=_step_id(scope)):
                return self._run_impl(
                    program, feed, fetch_list, scope, return_numpy,
                    use_program_cache, use_jit)
        except Exception as e:
            # flight-recorder crash hook: a no-op unless the recorder is
            # enabled (inspector.enable_flight_recorder or the
            # PADDLE_TPU_FLIGHT_RECORDER flag); writes the JSON crash report
            # before the exception propagates
            inspector_mod.notify_crash(self, program, e)
            raise
        finally:
            sentinel_mod.disarm_dispatch(_tok)

    def run_steps(self, program: Optional[Program] = None, feed_window=None,
                  *, reader=None, steps: Optional[int] = None,
                  fetch_list: Optional[Sequence] = None,
                  scope: Optional[Scope] = None, return_numpy: bool = True,
                  fetch_mode: str = "last", use_program_cache: bool = True,
                  use_jit: Optional[bool] = None):
        """Run K training steps in ONE host dispatch: the per-step compiled
        function wrapped in a jax.lax.scan over a device-stacked window of K
        batches, with persistable state donated across the whole window.
        One Python round-trip, one scope write-back, one telemetry record
        per K steps — the fused-loop answer to the reference's
        ParallelExecutor + double_buffer amortization
        (operators/reader/create_double_buffer_reader_op.cc).

        feed_window: a list of K per-step feed dicts, or a dict of arrays
        pre-stacked with a leading [K] axis. reader: an object with
        `next_window(k, device=...)` (reader.pipeline.DoubleBufferedFeeder)
        pulled instead of feed_window; requires `steps`. fetch_mode: 'last'
        (default) returns the final step's fetches, 'stack' a [K, ...] stack
        per fetch, 'mean' the window mean (e.g. for loss curves).

        Bitwise parity with K sequential run() calls is test-enforced
        (tests/test_run_steps.py): the scan carries the same uint32 rng
        counter the per-step path folds in, and `__rng_counter__` advances
        atomically by K only after the window succeeds.

        Falls back to K per-step run() calls — same results, per-step
        dispatch cost — in eager mode, when check_nan_inf or inspector
        probes need per-step attribution, and for LoD/sequence feeds or
        state (the padded repack is per-batch host work). Telemetry
        side-fetch gauges (_telemetry_fetch_extra) are skipped on the
        window path: they are a per-step observability feature."""
        program = program if program is not None else default_main_program()
        _tok = sentinel_mod.arm_dispatch(telemetry.program_label(program))
        try:
            return self._run_steps_impl(
                program, feed_window, reader, steps, fetch_list, scope,
                return_numpy, fetch_mode, use_program_cache, use_jit)
        except Exception as e:
            inspector_mod.notify_crash(self, program, e)
            raise
        finally:
            sentinel_mod.disarm_dispatch(_tok)

    def _run_steps_impl(self, program, feed_window, reader, steps,
                        fetch_list, scope, return_numpy, fetch_mode,
                        use_program_cache, use_jit):
        if fetch_mode not in ("last", "stack", "mean"):
            raise ValueError(f"fetch_mode must be last|stack|mean, "
                             f"got {fetch_mode!r}")
        scope = scope if scope is not None else global_scope()
        emb_cache = getattr(program, "_emb_cache", None)
        if reader is not None:
            if feed_window is not None:
                raise ValueError("pass feed_window or reader, not both")
            if steps is None:
                raise ValueError("reader windows need an explicit steps=K")
            # may raise StopIteration at end of pass — the drain signal.
            # With a hot-row cache active, ask the feeder to keep the
            # cached-table id slots host-side and hand back their
            # unique-id union (sparse_slots) — the ids remap to cache
            # slots below, so device_put-ing the raw ids would waste the
            # transfer and force a sync for the remap.
            if emb_cache is not None and _accepts_sparse_slots(reader):
                feed_window, _uniq = reader.next_window(
                    steps, device=self.device,
                    sparse_slots=emb_cache.feed_id_names())
            else:
                feed_window = reader.next_window(steps, device=self.device)
        if feed_window is None:
            raise ValueError("run_steps needs feed_window= or reader=")
        stacked, per_step, steps, lod_reason = self._normalize_window(
            feed_window, steps)
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")

        jit_mode = (not _EAGER) if use_jit is None else use_jit
        check_nan = _CHECK_NAN_INF or flags_mod.get("check_nan_inf")
        reason = lod_reason
        if steps == 1:
            reason = reason or "single_step"
        elif not jit_mode:
            reason = reason or "eager"
        elif check_nan:
            reason = reason or "check_nan_inf"
        elif getattr(program, "_probe_sites", None):
            reason = reason or "probes"
        if reason is None:
            # state-side LoD rejection: packed sequence state needs a
            # host-side repack per step
            fed = set(stacked)
            for n in self._external_inputs(program, fed, scope):
                v = scope.find_var(n)
                if isinstance(v, LoDTensor) and v.lod:
                    reason = "lod_state"
                    break
        if reason is None:
            _maybe_warn_cpu_scan_conv(self.device, program, steps)
            try:
                # emb_cache: remap the WHOLE window's ids to cache slots
                # in one residency transaction (every scanned step runs
                # against the same slab, so the union must be resident
                # at once). Done only on the window path: the per-step
                # fallback below re-derives feeds from the raw `stacked`
                # and each run() call remaps its own step — remapping
                # twice would read slot ids as global row ids.
                win_stacked = (emb_cache.prepare_feed(stacked)
                               if emb_cache is not None else stacked)
                # one `step` span for the K steps of the window, with
                # run()'s phases; its id is the first step's
                with tracing_mod.span("step", step=_step_id(scope)):
                    tracing_mod.phase("prepare")
                    return self._dispatch(
                        program, win_stacked, _fetch_names(fetch_list),
                        scope, return_numpy, use_program_cache, "window",
                        steps=steps, fetch_mode=fetch_mode)
            except _WindowUnsupported as e:
                reason = "trace_unsupported"
                vlog(1, f"run_steps window unsupported, falling back: {e}")
        if steps > 1:
            telemetry.counter(
                "executor_window_fallback_total",
                "run_steps calls served by the per-step path",
                labels=("program", "reason")).labels(
                    program=telemetry.program_label(program),
                    reason=reason).inc()
        if per_step is None:
            per_step = [{n: v[i] for n, v in stacked.items()}
                        for i in range(steps)]
        return self._run_steps_fallback(
            program, per_step, fetch_list, scope, return_numpy, fetch_mode,
            use_program_cache, use_jit)

    @staticmethod
    def _normalize_window(feed_window, steps):
        """-> (stacked feed dict or None-if-LoD, per-step feed list or None,
        K, lod-fallback reason or None). A list of per-step feed dicts
        stacks host-side; a pre-stacked dict (leading [K] axis on every
        leaf, e.g. from DoubleBufferedFeeder.next_window) passes through."""
        if isinstance(feed_window, dict):
            if not feed_window:
                raise ValueError("feed_window dict is empty")
            ks = set()
            for n, v in feed_window.items():
                if isinstance(v, LoDTensor):
                    raise ValueError(
                        f"pre-stacked feed_window entry '{n}' is a "
                        f"LoDTensor; pass a list of per-step feed dicts "
                        f"so the executor can fall back per-step")
                shape = getattr(v, "shape", None)
                if not shape:
                    raise ValueError(
                        f"feed_window entry '{n}' has no leading steps "
                        f"axis (shape {shape})")
                ks.add(int(shape[0]))
            if len(ks) != 1:
                raise ValueError(
                    f"feed_window leading dims disagree: {sorted(ks)}")
            k = ks.pop()
            if steps is not None and steps != k:
                raise ValueError(
                    f"steps={steps} but feed_window leading dim is {k}")
            return dict(feed_window), None, k, None
        per_step = list(feed_window)
        if not per_step:
            raise ValueError("feed_window list is empty")
        if steps is not None and steps != len(per_step):
            raise ValueError(
                f"steps={steps} but feed_window has {len(per_step)} entries")
        names = set(per_step[0])
        if any(set(f) != names for f in per_step[1:]):
            raise ValueError("per-step feed dicts must share the same keys")
        if any(isinstance(f[n], LoDTensor) and f[n].lod
               for f in per_step for n in names):
            return None, per_step, len(per_step), "lod_feed"
        stacked = {}
        for n in sorted(names):
            stacked[n] = np.stack([np.asarray(f[n]) for f in per_step])
        return stacked, per_step, len(per_step), None

    def _run_steps_fallback(self, program, per_step_feeds, fetch_list, scope,
                            return_numpy, fetch_mode, use_program_cache,
                            use_jit):
        """Per-step path: K sequential run() calls — identical results to
        the fused window, per-step dispatch cost. The rng counter advances
        +1 per completed step (a mid-window failure keeps the completed
        prefix, matching plain sequential training)."""
        outs = []
        for f in per_step_feeds:
            vals = self.run(program, feed=f, fetch_list=fetch_list,
                            scope=scope, return_numpy=return_numpy,
                            use_program_cache=use_program_cache,
                            use_jit=use_jit)
            if fetch_mode == "last":
                outs = vals
            else:
                outs.append(vals)
        if fetch_mode == "last":
            return outs
        cols = list(zip(*outs)) if outs else []
        if fetch_mode == "stack":
            return [np.stack([np.asarray(v) for v in col]) for col in cols]
        return [np.mean(np.stack([np.asarray(v) for v in col]), axis=0)
                for col in cols]

    def static_memory_analysis(self, program=None, feed=None,
                               fetch_list=None, scope=None, top_k=8):
        """Compile-only memory footprint of `program` under `feed`: the
        block is traced and compiled exactly as run() would (same
        donation, shardings and state gathering) but never executed, so
        no step runs and no real buffers are allocated — feed values may
        be jax.ShapeDtypeStructs, so the question can be asked of batch
        sizes that could never fit in host or device memory. Returns the
        memory.ProgramMemory record (also kept in memory.records())."""
        program = program if program is not None else default_main_program()
        compiled, feed_vals, state_vals, rng = self._aot_block(
            program, feed, fetch_list, scope)
        return memory_mod.analyze(
            compiled.fn, feed_vals, state_vals, rng,
            program=telemetry.program_label(program),
            place=f"{type(self.place).__name__}:{self.place.device_id}",
            top_k=top_k)

    def compiled_hlo(self, program=None, feed=None, fetch_list=None,
                     scope=None) -> str:
        """Optimized HLO text of the step as run() compiles it for this
        feed — what the device executes, custom calls included. Compile
        only, like static_memory_analysis; a recompile unless the
        persistent compilation cache covers it."""
        program = program if program is not None else default_main_program()
        compiled, feed_vals, state_vals, rng = self._aot_block(
            program, feed, fetch_list, scope)
        avals = jax.tree_util.tree_map(
            memory_mod.aval_of, (feed_vals, state_vals, np.uint32(rng)))
        return memory_mod.compile_from_avals(compiled.fn, avals).as_text()

    def step_account(self, program=None):
        """The account by instruction of `program`'s compiled step
        ([xplane.Instr]: name, opcode, heavy, flops, bytes, the program op
        instance it was lowered from, a collective's kind, bytes, groups
        and mesh axis), or None before its first run. Built once a block,
        where the static memory analysis reads the executable's text; for
        a block that analysis skipped (the `memory_analysis` flag off, a
        run_steps window), at the first call here, from the kept avals (a
        compile the persistent cache serves). The same list is what a
        trace is joined to (xplane.step_account)."""
        program = program if program is not None else default_main_program()
        blocks = [c for c in self._cache.values()
                  if c.program is program and c.seen_sigs]
        if not blocks:
            return None
        account = self._account_of(program, blocks[-1])
        return account and account[1]

    def _account_of(self, program, compiled):
        if compiled.account is None and compiled.avals is not None:
            try:
                with jax.default_device(self.device):
                    module, instrs, xla_flops = memory_mod.account_of(
                        memory_mod.compile_from_avals(compiled.fn,
                                                      compiled.avals),
                        getattr(program, "_mesh", None))
                self._keep_account(
                    compiled, telemetry.program_label(program), module,
                    xplane_mod.compact(instrs), xla_flops)
            except Exception as e:  # noqa: BLE001 - advisory
                telemetry.log_event(
                    "step_account_error",
                    program=telemetry.program_label(program),
                    error=f"{type(e).__name__}: {e}")
        return compiled.account

    def _keep_account(self, compiled, prog_label, module, instrs, xla_flops):
        """On the block, and under the HLO module name where a reader of
        a trace in this process finds it."""
        compiled.account = (module, instrs, xla_flops)
        xplane_mod.remember_account(
            module, instrs, program=prog_label, xla_flops=xla_flops,
            cost=compiled.cost_fn)

    def _commit_feeds(self, program, feed_vals, *, window=False):
        """On a mesh, move a feed that is committed elsewhere (a
        DoubleBufferedFeeder puts each batch on one device) to the
        step's in_sharding for it: jit refuses a committed argument
        whose sharding is not the one it was given, where it would
        transfer a numpy feed by itself. A chip-to-chip copy, queued
        like any other; feeds that already match, numpy feeds and
        feeds off-mesh pass through."""
        mesh = getattr(program, "_mesh", None)
        if mesh is None or mesh.is_multi_process:
            return feed_vals
        placed = [n for n, v in feed_vals.items()
                  if getattr(v, "committed", False)]
        if not placed:
            return feed_vals
        want = self._shardings(program, [], placed, window=window)[0]
        moved = {n: want[n] for n in placed
                 if not feed_vals[n].sharding.is_equivalent_to(
                     want[n], feed_vals[n].ndim)}
        if not moved:
            return feed_vals
        return {**feed_vals, **jax.device_put(
            {n: feed_vals[n] for n in moved}, moved)}

    def _commit_state(self, program, state_vals, feed_vals):
        """Place state where the step's outputs will live, before the
        compiled call: on this executor's device when a feed is committed
        to one, or per the step's in_shardings on a mesh. The state a step
        returns is committed as soon as one input was (a device_put feed
        is) and, on a mesh, carries the mesh in its type; state that
        starts out as numpy or as the startup program's uncommitted
        single-device outputs does neither, so the second call would see
        another argument mapping (off-mesh) or other avals (on a mesh)
        than the first, and the whole step would compile twice. jit would
        make the same transfer on the first call anyway. With nothing
        committed (numpy feeds) the step stays unplaced, call after call,
        and follows jax.default_device."""
        mesh = getattr(program, "_mesh", None)
        if mesh is None:
            if not any(getattr(v, "committed", False)
                       for v in feed_vals.values()):
                return state_vals
            loose = {n: v for n, v in state_vals.items()
                     if not getattr(v, "committed", False)}
            where = self.device
        elif mesh.is_multi_process:
            return state_vals    # every process holds only its shards
        else:
            loose = {n: v for n, v in state_vals.items()
                     if getattr(getattr(v, "sharding", None), "mesh",
                                None) != mesh}
            where = loose and self._shardings(program, sorted(loose), [])[1]
        if not loose:
            return state_vals
        return {**state_vals, **jax.device_put(loose, where)}

    def _aot_block(self, program, feed, fetch_list, scope):
        """(compiled block, feed_vals, state_vals, rng_counter) gathered
        as run() gathers them, for the compile-only entry points."""
        scope = scope if scope is not None else global_scope()
        feed_vals, state_vals, lod_map, rng_counter = self._gather(
            program, dict(feed or {}), scope)
        compiled = self._compile(
            program, sorted(state_vals), sorted(feed_vals),
            _fetch_names(fetch_list), self._persistable_outputs(program),
            lod_map)
        return compiled, feed_vals, state_vals, rng_counter

    def _run_impl(self, program, feed, fetch_list, scope, return_numpy,
                  use_program_cache, use_jit):
        """run()'s own part of `prepare`: what only a single step has (a
        reader to pull from, side-fetches, probes, the check_nan_inf
        scan), worked out and handed to _dispatch."""
        tracing_mod.phase("prepare")
        feed = dict(feed or {})
        # program-bound reader pipelines (layers.read_file): when the caller
        # gives no explicit feed for the reader vars, pull the next
        # (prefetched) batch — the executor-side half of the reference's
        # reader ops (operators/reader/*.cc). Raises EOFException when a
        # pass ends, matching the reference's drain loop idiom.
        for reader, names in getattr(program, "_pipeline_readers", []):
            fed = [n for n in names if n in feed]
            if fed:
                if len(fed) != len(names):
                    raise ValueError(
                        f"Reader variables {sorted(set(names) - set(fed))} "
                        f"are not in the feed but their sibling(s) {fed} "
                        f"are; feed all of a reader's outputs or none "
                        f"(pipeline pull is all-or-nothing)")
                continue
            batch_vals = reader.next_batch(self.device)
            feed.update(dict(zip(names, batch_vals)))
        # beyond-HBM hot-row cache (parallel/emb_cache.py): make the fed
        # ids of cached tables resident and remap them to cache-slot
        # indices, so lookup_table and the scatter-apply optimizers run
        # against the fixed-size device slab with static shapes
        emb_cache = getattr(program, "_emb_cache", None)
        if emb_cache is not None:
            feed = emb_cache.prepare_feed(feed)
        fetch_names = _fetch_names(fetch_list)
        jit_mode = (not _EAGER) if use_jit is None else use_jit
        # telemetry side-fetches (gauge name -> var name), e.g. the global
        # norm the clip pass marked: fetched alongside the user's list (so
        # they share the compiled block) and popped before values return
        side_fetches = []
        if _TELEMETRY_FETCH:
            marked = getattr(program, "_telemetry_fetch_extra", None)
            if marked:
                side_fetches = [(m, n) for m, n in sorted(marked.items())
                                if n not in fetch_names]
                fetch_names = fetch_names + [n for _, n in side_fetches]

        # inspector probes (inspector.instrument / GradientAudit): their
        # stat vectors are fetched with the user's list, so the probed step
        # stays one jitted computation and one device round-trip. Replay
        # programs built by the inspector itself (_inspector_internal) fetch
        # explicitly and skip all recording/raising to avoid recursion.
        internal_run = bool(getattr(program, "_inspector_internal", False))
        probe_sites = getattr(program, "_probe_sites", None) or None
        if probe_sites and not internal_run:
            fetch_names = fetch_names + [s.stat_var for s in probe_sites]
        else:
            probe_sites = None
        # check_nan_inf is live: the import-time snapshot (kept because
        # tests/tools monkeypatch it) OR the flag registry's current value,
        # so flags.set("check_nan_inf", True) takes effect mid-session
        check_nan = (_CHECK_NAN_INF or flags_mod.get("check_nan_inf")) \
            and not internal_run

        return self._dispatch(
            program, feed, fetch_names, scope, return_numpy,
            use_program_cache, "jit" if jit_mode else "eager",
            side_fetches=side_fetches, probe_sites=probe_sites,
            check_nan=check_nan, internal_run=internal_run)

    def _gather_state(self, program, fed: set, scope, lod_map):
        """The scope's half of gather -> (state names, state values): what
        the block reads beyond its feeds. State held as a LoDTensor with a
        LoD goes in padded, its lengths beside it under `<name>@SEQLEN`,
        the same convention as a LoD feed."""
        state_names = self._external_inputs(program, fed, scope)
        missing = [n for n in state_names if scope.find_var(n) is None]
        if missing:
            raise RuntimeError(
                f"Variables {missing} are read by the program but "
                f"absent from the scope — run the startup program first.")
        state_vals = {}
        for n in state_names:
            v = scope.find_var(n)
            state_vals[n] = _densify(state_vals, lod_map, n, v) \
                if isinstance(v, LoDTensor) else v
        return state_names, state_vals

    def _gather(self, program, feed, scope):
        """gather -> (feed_vals, state_vals, lod_map, rng_counter): the
        values one dispatch runs on, the same for run(), a run_steps
        window and the compile-only entry points. LoDTensor feeds with a
        LoD become padded dense arrays plus a `<name>@SEQLEN` lengths
        input (pack_to_padded) — the XLA-friendly LoD emulation; device
        arrays and avals pass through, never materialized.

        The per-step PRNG counter is read here and committed only after
        the step SUCCEEDS (past the compiled call, the check_nan_inf scan
        and the probe checks): a raising run must not advance it, or an
        OOM/NonFinite retry would replay the failed step under a
        different key."""
        feed_vals, lod_map = {}, {}
        for name, val in feed.items():
            if isinstance(val, LoDTensor):
                val = _densify(feed_vals, lod_map, name, val)
            elif not isinstance(val, (jax.Array, jax.ShapeDtypeStruct)):
                val = np.asarray(val)
            feed_vals[name] = val
        _names, state_vals = self._gather_state(
            program, set(feed_vals), scope, lod_map)
        return (feed_vals, state_vals, lod_map,
                scope.find_var("__rng_counter__") or 0)

    def _dispatch(self, program, feed, fetch_names, scope, return_numpy,
                  use_program_cache, mode, *, steps=1, fetch_mode=None,
                  side_fetches=(), probe_sites=None, check_nan=False,
                  internal_run=False):
        """The one way a program runs, inside its `step` span: gather ->
        lookup -> launch -> validate -> commit -> book -> return. `mode`
        is "jit" (the block compiled per step), "eager" (op by op, as the
        reference interprets) or "window" (`steps` steps in one lax.scan,
        every feed stacked on a leading [K] axis). `fetch_names` is the
        user's list, then the telemetry `side_fetches` ((gauge, var)
        pairs), then the probes' stat vars; only the user's come back."""
        window = mode == "window"
        prog_label = telemetry.program_label(program)
        place_label = f"{type(self.place).__name__}:{self.place.device_id}"
        n_fetch = len(fetch_names) - len(side_fetches) - len(probe_sites or ())
        with tracing_mod.span("sink.gather"):
            feed_vals, state_vals, lod_map, rng_counter = self._gather(
                program, feed, scope)
            persist_out = self._persistable_outputs(program)

        # lookup: the block compiled for these names, built on a miss
        compiled, key, plan_s = None, None, 0.0
        if mode == "eager":
            rng_key = jax.random.fold_in(
                jax.random.key(program.random_seed or 12345), rng_counter)
            call = lambda: self._run_eager(
                program, feed_vals, state_vals, fetch_names, persist_out,
                rng_key, lod_map, check_nan=check_nan)
        else:
            # every argument checked against where the block wants it, and
            # the key of the block compiled for these names
            with tracing_mod.span("sink.validate"):
                state_keys = sorted(state_vals)  # incl. @SEQLEN side channels
                feed_vals = self._commit_feeds(program, feed_vals,
                                               window=window)
                state_vals = self._commit_state(program, state_vals,
                                                feed_vals)
                key = (id(program), getattr(program, "_version", 0),
                       tuple(sorted(feed_vals)), tuple(fetch_names),
                       tuple(state_keys), self.place,
                       getattr(program, "_amp_dtype", None),
                       getattr(program, "_amp_level", "O1"),
                       # the seed folds into the compiled step (see
                       # _compile), so changing program.random_seed must
                       # recompile
                       program.random_seed,
                       *(("window", steps, fetch_mode) if window else ()),
                       dynamics_mod.cache_token(program),
                       quant_mod.cache_token(program))
                compiled = self._cache.get(key) if use_program_cache else None
            def build():
                args = (program, state_keys, sorted(feed_vals), fetch_names,
                        persist_out, lod_map)
                block = (self._compile_window(*args, steps, fetch_mode)
                         if window else self._compile(*args))
                if use_program_cache:
                    self._cache[key] = block
                return block

            if compiled is None:
                plan_t0 = time.perf_counter()
                compiled = build()
                plan_s = time.perf_counter() - plan_t0
            call = lambda: compiled.fn(feed_vals, state_vals,
                                       np.uint32(rng_counter))

        launch_args = (key, mode, steps, feed_vals, state_vals, rng_counter,
                       plan_s, prog_label, place_label)
        try:
            out, launch = self._launch(program, compiled, call, *launch_args)
        except Exception as e:
            if compiled is None or not self._kept_too_much(
                    program, state_vals, e):
                raise
            compiled = build()      # traced anew: every segment replayed
            out, launch = self._launch(program, compiled, call, *launch_args)
        # a window has no sequence fetches (_WindowUnsupported)
        fetch_vals, fetch_lens, new_state = \
            (out[0], {}, out[1]) if window else out
        # the dynamics stats row leaves new_state immediately: its
        # off-period NaN filler must never reach the check_nan scan or
        # the scope writeback (recorded only after the step commits)
        dyn_stats = new_state.pop(dynamics_mod.STATE_KEY, None)

        # validate: may raise, and then nothing below happens — a diverged
        # step never commits its state or its counter to the scope
        n_keep = n_fetch + len(side_fetches)
        self._validate(
            program, scope, feed, fetch_names, fetch_vals, new_state,
            rng_counter, prog_label, probe_sites, n_keep,
            scan=check_nan and mode == "jit")   # eager scans op by op

        # commit: the step is known-good. The counter (atomic for all K
        # steps of a window) and the state go back before any watcher
        # below runs or anything can wait on the device: the state's old
        # buffers were donated, so an interrupt in between would leave the
        # scope holding dead arrays
        tracing_mod.phase("writeback")
        scope.set_var("__rng_counter__", rng_counter + steps)
        for n, v in new_state.items():
            if n.endswith(SEQLEN_SUFFIX) or n.endswith(SEQLEN2_SUFFIX):
                continue
            if n + SEQLEN_SUFFIX in new_state:
                # sequence state goes back to the scope as a LoDTensor so the
                # next run re-packs it with its lengths intact (incl. the
                # inner lengths of nested lod_level=2 state)
                inner = new_state.get(n + SEQLEN2_SUFFIX)
                v, lengths, inner = telemetry.host_wait(
                    (v, new_state[n + SEQLEN_SUFFIX], inner), prog_label,
                    "lod_writeback")    # the repack is host work
                packed, lod = padded_to_pack(
                    np.asarray(v), np.asarray(lengths),
                    None if inner is None else np.asarray(inner))
                v = LoDTensor(packed, lod)
            scope.set_var(n, v)

        tracing_mod.phase("bookkeep")
        self._book(program, compiled, launch, mode, steps, feed_vals,
                   state_vals, rng_counter, dyn_stats, n_fetch,
                   list(zip(side_fetches, fetch_vals[n_fetch:n_keep])),
                   internal_run, prog_label, place_label)
        tracing_mod.phase("writeback")
        fetched = _rebuild_fetches(fetch_names[:n_fetch], fetch_vals[:n_fetch],
                                   fetch_lens, return_numpy)
        # on the host by now if the fetches are: a synchronous run records
        # its own step's values, a pipelined one waits for nothing
        with tracing_mod.span("sink.side_fetch"):
            self._publish_side_fetches()
        with tracing_mod.span("sink.dynamics"):
            dynamics_mod.drain()
        return fetched

    def _launch(self, program, compiled, call, key, mode, steps, feed_vals,
                state_vals, rng_counter, plan_s, prog_label, place_label):
        """launch -> (what `call` returned, _Launch): the call under the
        one timer every sink reads, then what even a step that fails
        validation has done — a signature seen, a block compiled or hit.
        `compiled` is None in eager mode, which builds nothing."""
        sig, new_sig, build_watch = None, False, _NO_WATCH
        if compiled is not None:
            if compiled.avals is None:
                # once per compiled block (building the aval pytree every
                # step would inflate the host timings being measured):
                # what a lazy account and the analytic cost table need
                compiled.avals = jax.tree_util.tree_map(
                    memory_mod.aval_of,
                    (feed_vals, state_vals, np.uint32(rng_counter)))
                compiled.cost_fn = _cost_supplier(
                    self, program, compiled.avals[0], compiled.avals[1],
                    window=mode == "window")
            if compiled.account is None and compiled.seen_sigs \
                    and profiler_mod.wants_device_table():
                # a traced session over a block that has run and has no
                # account yet (a run_steps window, or memory_analysis
                # off): build it here, before the timed call, so that
                # stop_profiler compiles nothing
                self._account_of(program, compiled)
            with tracing_mod.span("sink.signature"):
                sig = telemetry.signature_of(feed_vals)
                new_sig = sig not in compiled.seen_sigs
            if new_sig:
                # a signature not seen before builds: jax's own trace /
                # lower / compile events inside the call are collected
                # (cold path only)
                build_watch = telemetry.watch_build(prog_label)
        compile_before = telemetry.jax_compile_seconds()
        tracing_mod.phase("launch")
        run_t0 = time.perf_counter()
        try:
            with jax.default_device(self.device), \
                    build_watch as build_events:
                out = call()
                if profiler_mod.is_active():
                    # async dispatch returns futures; force execution
                    # inside the timed scope so the event measures the
                    # step, not the enqueue (only when profiling)
                    telemetry.host_wait(out, prog_label, "profiler_sync")
        except Exception as e:
            if mode == "window" and (
                    isinstance(e, _WindowUnsupported)
                    or isinstance(e, TypeError) and "carry" in str(e)):
                # the trace said so itself, or lax.scan rejected the
                # carry: the program changes a state aval across steps
                # (shape/dtype drift) — per-step territory
                self._cache.pop(key, None)
                if isinstance(e, _WindowUnsupported):
                    raise
                raise _WindowUnsupported(str(e)) from e
            # OOM forensics: a raw RESOURCE_EXHAUSTED becomes a structured
            # errors.OOMError (breakdown, top live buffers, donation
            # losses, suggestions) before the crash-report hook sees it
            oom = memory_mod.maybe_oom_error(
                self, program, prog_label, e, feed_vals, state_vals)
            if oom is not None:
                raise oom from e
            raise
        run_dt = time.perf_counter() - run_t0
        if new_sig:
            _span_build_events(build_events)
        tracing_mod.phase("bookkeep")
        # compile-vs-execute split: XLA's own backend_compile events
        # (jax.monitoring) accumulated across the call — catches the
        # jit retraces the executor cache key cannot see
        compile_s = telemetry.jax_compile_seconds() - compile_before
        if compiled is None:
            return out, _Launch(run_t0, run_dt, compile_s, "n/a")
        cause = build_s = None
        if new_sig:
            cause = ("first_compile" if not compiled.seen_sigs
                     else "signature_change")
            compiled.seen_sigs.add(sig)
            telemetry.counter(
                "executor_compiles_total", "block traces/compiles",
                labels=("program", "place")).labels(
                    program=prog_label, place=place_label).inc()
            telemetry.counter(
                "executor_compile_seconds_total",
                "XLA compile wall seconds spent inside Executor.run",
                labels=("program", "place")).labels(
                    program=prog_label, place=place_label).inc(compile_s)
            telemetry.log_event(
                "compile", program=prog_label, place=place_label,
                cause=cause, seconds=compile_s,
                **({"window_steps": steps} if mode == "window" else {}),
                signature=[list(s) for s in sig])
            build_s = _build_seconds(build_events, run_dt, plan_s)
            if cause == "signature_change":
                last = compiled.last_sig or ()
                telemetry.counter(
                    "executor_cache_misses_total",
                    "jit retraces caused by a changed feed signature",
                    labels=("program", "place")).labels(
                        program=prog_label, place=place_label).inc()
                telemetry.log_event(
                    "cache_miss", program=prog_label, place=place_label,
                    signature=[list(s) for s in sig],
                    changed=[list(s) for s in sig if s not in last])
        else:
            telemetry.counter(
                "executor_cache_hits_total",
                "runs served by an already-traced signature",
                labels=("program", "place")).labels(
                    program=prog_label, place=place_label).inc()
        compiled.last_sig = sig
        return out, _Launch(run_t0, run_dt, compile_s,
                            "miss" if new_sig else "hit", cause, build_s, sig)

    def _validate(self, program, scope, feed, fetch_names, fetch_vals,
                  new_state, rng_counter, prog_label, probe_sites, n_keep,
                  scan):
        """validate: the check_nan_inf scan of a jitted step and the
        inspector's probes. Either raises a structured NonFiniteError."""
        if scan:
            # jit-path equivalent of the reference FLAGS_check_nan_inf
            # per-op scan (executor.cc:325-333): inside one fused XLA
            # computation there is no per-op boundary, so the check runs
            # on every fetch and updated persistable after the step.
            # Probe stat vectors are exempt: their counts describe OTHER
            # tensors (record_probes inspects them below), and a stats
            # l2 that overflowed to inf must not masquerade as a hit.
            # ONE fused on-device reduction + ONE host sync for the
            # whole step (_finite_all); the per-tensor np.asarray walk
            # only runs on the failure path, to name the culprit
            with tracing_mod.span("sink.check_nan_inf"):
                checked = [
                    (name, val) for name, val in
                    list(zip(fetch_names[:n_keep], fetch_vals))
                    + list(new_state.items())
                    if jnp.issubdtype(getattr(val, "dtype", None)
                                      or np.asarray(val).dtype, jnp.inexact)]
                if checked and not bool(telemetry.host_wait(
                        _finite_all([v for _, v in checked]), prog_label,
                        "check_nan_inf")):
                    for name, val in checked:
                        arr = np.asarray(val)
                        if not np.isfinite(arr).all():
                            self._raise_nonfinite(
                                program, name, arr, feed, new_state,
                                rng_counter, scope, prog_label)
        if probe_sites:
            # the probe stat vectors (appended after the telemetry
            # extras) go to the inspector BEFORE state writeback: a
            # non-finite probe raises here
            with tracing_mod.span("sink.probes"):
                inspector_mod.record_probes(
                    self, program, scope, probe_sites, fetch_vals[n_keep:],
                    feed=feed, new_state=new_state, rng_counter=rng_counter,
                    prog_label=prog_label)

    def _book(self, program, compiled, launch, mode, steps, feed_vals,
              state_vals, rng_counter, dyn_stats, n_fetch, side_fetched,
              internal_run, prog_label, place_label):
        """book: every watcher and sink of a committed step, each called
        from this one place under a span of its own (`sink.<name>`, inside
        the `bookkeep` phase), so a trace says which of them held the
        host. None of them waits on the device by default: the dynamics
        row and the side-fetch gauges are queued in flight and published
        by a later step that finds them ready; the one that does wait
        (the flight recorder wants this step's norm) books it through
        telemetry.host_wait and finds the scope already whole. Between
        the modes only the labels, `steps` and the window's two extra
        fields differ; the static memory analysis belongs to the per-step
        block alone."""
        window = mode == "window"
        run_dt, compile_s = launch.run_dt, launch.compile_s
        window_fields = {"steps": steps, "per_step_seconds": run_dt / steps} \
            if window else {}
        # the profiler's host event is the launch, from the one timer
        profiler_mod.record_event(f"executor_run({mode})", run_dt,
                                  start=launch.t0)
        if launch.build_s is not None:
            with tracing_mod.span("sink.build"):
                self._book_first_run(program, compiled, launch, mode,
                                     feed_vals, state_vals, rng_counter,
                                     internal_run, prog_label, place_label)
        if dyn_stats is not None:
            with tracing_mod.span("sink.dynamics"):
                if window:
                    dynamics_mod.on_window(program, prog_label, dyn_stats,
                                           int(rng_counter), steps)
                else:
                    dynamics_mod.on_step(program, prog_label, dyn_stats,
                                         int(rng_counter))

        with tracing_mod.span("sink.counters"):
            telemetry.counter(
                "executor_runs_total", "Executor.run calls",
                labels=("program", "place", "mode")).labels(
                    program=prog_label, place=place_label, mode=mode).inc()
            telemetry.counter(
                "executor_steps_total",
                "training/eval steps executed (a run_steps window counts K)",
                labels=("program", "place")).labels(
                    program=prog_label, place=place_label).inc(steps)
            telemetry.histogram(
                "executor_run_seconds",
                "Executor.run wall seconds (dispatch-only unless profiling "
                "forces device sync)", labels=("program", "mode")).labels(
                    program=prog_label, mode=mode).observe(run_dt)
            telemetry.gauge(
                "executor_last_step_seconds",
                "wall seconds of the most recent executor step (per-step "
                "average for run_steps windows) — fleet skew input").set(
                    max(run_dt - compile_s, 0.0) / steps)
            if self._analysis(program)[3]:
                telemetry.counter(
                    "optimizer_steps_total",
                    "runs of programs carrying optimizer-role ops",
                    labels=("program",)).labels(
                        program=prog_label).inc(steps)
            telemetry.log_event(
                "run_window" if window else "run",
                program=prog_label, place=place_label, mode=mode,
                seconds=run_dt, compile_s=compile_s,
                execute_s=max(run_dt - compile_s, 0.0), cache=launch.cache,
                donated=len(state_vals) if compiled is not None else 0,
                feeds=len(feed_vals), fetches=n_fetch, **window_fields)
        if tracing_mod.enabled():
            step_span = tracing_mod.owner_span()    # no sink span is open
            if step_span.sampled:
                step_span.attrs.update(program=prog_label, place=place_label,
                                       mode=mode, cache=launch.cache)
                if window:
                    step_span.attrs["steps"] = steps

        hbm_sample = None
        if not internal_run:
            # live HBM accounting: one tracker sample per run (gauges +
            # flight-recorder fields below); byte counts come from avals
            # only, so the donated state arrays are safe to measure
            with tracing_mod.span("sink.memory"):
                try:
                    hbm_sample = memory_mod.on_run(
                        self, program, prog_label, feed_vals, state_vals)
                except Exception:
                    hbm_sample = None
        flight = not internal_run and inspector_mod.flight_enabled()
        with tracing_mod.span("sink.side_fetch"):
            # the flight recorder below wants this step's global norm: it
            # waits, and the wait is booked
            self._side_pending.extend(
                (metric, val, prog_label)
                for (metric, _n), val in side_fetched)
            self._publish_side_fetches(wait=flight)
        if flight:
            # flight recorder: one bounded ring record per step or window
            # (after the gauges above so the global norm is this step's; a
            # window skips the side-fetches and carries no norm)
            with tracing_mod.span("sink.flight"):
                record = {
                    "place": place_label, "mode": mode, "seconds": run_dt,
                    "compile_s": compile_s, "cache": launch.cache,
                    "feeds": len(feed_vals), "fetches": n_fetch,
                    "rng_counter": int(rng_counter),
                    "hbm_bytes_in_use":
                        (hbm_sample or {}).get("bytes_in_use"),
                    "hbm_peak_bytes": (hbm_sample or {}).get("peak_bytes"),
                    **window_fields}
                if not window:
                    record["global_norm"] = telemetry.read_gauge(
                        "optimizer_global_norm", program=prog_label)
                inspector_mod.record_step(program, prog_label, record)

    def _book_first_run(self, program, compiled, launch, mode, feed_vals,
                        state_vals, rng_counter, internal_run, prog_label,
                        place_label):
        """What a run that built its block books besides: the static
        memory analysis (once a per-step block) and the build's seconds
        by phase."""
        if mode == "jit" and launch.cause == "first_compile" \
                and not internal_run:
            # static memory analysis once per compiled block: an
            # extra AOT lower/compile from avals (the persistent
            # compilation cache absorbs the XLA work); advisory —
            # a failure must never fail the training step
            analysis_t0 = time.perf_counter()
            try:
                # under the launch's device context: jax keys its
                # trace and lowering caches on it, and outside it the
                # analysis traced and lowered the whole block again
                with tracing_mod.span("analysis"), \
                        jax.default_device(self.device), \
                        telemetry.watch_build(prog_label):
                    # (watched for the program's name alone: the cache's
                    # answer for the analysis' own compile is the block's)
                    rec = memory_mod.on_compile(
                        self, compiled, program, prog_label,
                        place_label, feed_vals, state_vals,
                        np.uint32(rng_counter), signature=launch.sig)
                    if rec is not None and rec.account is not None:
                        self._keep_account(
                            compiled, prog_label, rec.module,
                            rec.account, rec.xla_flops)
            except Exception as mem_e:
                telemetry.log_event(
                    "memory_analysis_error", program=prog_label,
                    error=f"{type(mem_e).__name__}: {mem_e}")
            launch.build_s["analysis"] = \
                time.perf_counter() - analysis_t0
        _book_build(prog_label, launch.build_s)

    def _publish_side_fetches(self, wait=False):
        """The telemetry side-fetches (program._telemetry_fetch_extra;
        PADDLE_TPU_TELEMETRY_FETCH=0 disables) queued by this step and by
        earlier ones, published without waiting on the device unless
        `wait` (booked under site `side_fetch`): a value still in flight
        stays queued until a later call finds it ready, so a pipelined
        loop keeps its steps in flight; a synchronous one (return_numpy)
        publishes its own step's values once its fetches are on the host.
        The dynamics rows follow the same rule in the observatory's own
        queue (dynamics.drain). A metric the catalog lists as a
        histogram takes a sample a step, any other is a gauge; one that
        carries a label beside `program` (`layer`, `exit`) takes one
        series per element of its vector. Each publication is also a
        `side_fetch` event of the step log (telemetry.recent_events), in
        step order: a histogram keeps no order, and a reader of a
        window's tail needs one."""
        while self._side_pending:
            metric, val, label = self._side_pending[0]
            if not (wait or telemetry.is_ready(val)):
                break
            self._side_pending.popleft()
            try:
                if wait:
                    telemetry.host_wait(val, label, "side_fetch")
                values = np.asarray(val, np.float64).ravel()
            except (TypeError, ValueError):
                continue
            telemetry.log_event("side_fetch", program=label, metric=metric,
                                values=values.tolist())
            spec = telemetry.METRIC_CATALOG.get(metric, {})
            labels = spec.get("labels", ("program",))
            by_element = [n for n in labels if n != "program"]
            hist = spec.get("kind") == "histogram"
            family = (telemetry.histogram if hist else telemetry.gauge)(
                metric, labels=labels)
            for i, v in enumerate(values if by_element else values[:1]):
                extra = {n: str(i) for n in by_element}
                child = family.labels(program=label, **extra)
                child.observe(float(v)) if hist else child.set(float(v))

    def _raise_nonfinite(self, program, name, arr, feed, new_state,
                         rng_counter, scope, prog_label):
        """Structured error for a fetch-level check_nan_inf hit: names the
        offending fetch var and dtype, counts the contamination, and (when
        the nonfinite_attribution flag is on) replays the step with
        bisection probes to name the first offending op."""
        from .errors import NonFiniteError
        telemetry.counter(
            "nonfinite_detections_total",
            "NaN/Inf values caught by check_nan_inf or inspector probes",
            labels=("program", "source")).labels(
                program=prog_label, source="fetch").inc()
        nan_c = int(np.isnan(arr).sum())
        inf_c = int(np.isinf(arr).sum())
        msg = (f"NaN/Inf detected in variable '{name}' (dtype {arr.dtype}, "
               f"shape {tuple(arr.shape)}, {nan_c} NaN / {inf_c} Inf) "
               f"after jitted step (check_nan_inf)")
        attribution = None
        if flags_mod.get("nonfinite_attribution"):
            try:
                attribution = inspector_mod.attribute_nonfinite(
                    self, program, feed, scope=scope, state=new_state,
                    rng_counter=rng_counter)
            except Exception:
                attribution = None
            if attribution is not None:
                msg += "\n  " + attribution.summary()
        raise NonFiniteError(msg, var_name=name, dtype=str(arr.dtype),
                             attribution=attribution,
                             feed_signature=inspector_mod.feed_signature(
                                 feed))

    def close(self):
        self._publish_side_fetches(wait=True)   # the last steps' values
        dynamics_mod.drain(wait=True)           # and their dynamics rows
        self._cache.clear()
        self._analysis_cache.clear()
        self._replay_plans.clear()

    # --- analysis -----------------------------------------------------------
    @staticmethod
    def _block_reads_writes(program, block, reads, writes, produced):
        for op in block.ops:
            if op.type in ("feed", "fetch"):
                continue
            for name in op.input_arg_names:
                if name not in produced:
                    reads.add(name)
            for a in op.desc.attrs.values():
                from .framework.desc import BlockRef, BlocksRef
                sub_idxs = []
                if isinstance(a, BlockRef):
                    sub_idxs = [a.idx]
                elif isinstance(a, BlocksRef):
                    sub_idxs = a.idxs
                for si in sub_idxs:
                    Executor._block_reads_writes(
                        program, program.block(si), reads, writes, set(produced))
            for name in op.output_arg_names:
                produced.add(name)
                writes.add(name)

    def _analysis(self, program):
        """Per-(program, version) cached read/write sets + persistable map +
        whether the block carries optimizer-role ops (telemetry's run-time
        train-step counter). The full block walk costs milliseconds on a
        ResNet-scale program and used to run twice per Executor.run — at
        TPU step rates that was a measurable host-side stall between
        steps."""
        key = (id(program), getattr(program, "_version", 0))
        hit = self._analysis_cache.get(key)
        if hit is not None and hit[0] is program:
            return hit[1], hit[2], hit[3], hit[4]
        reads, writes = set(), set()
        self._block_reads_writes(program, program.global_block(),
                                 reads, writes, set())
        persistable = {}
        for b in program.blocks:
            for name, v in b.desc.vars.items():
                if v.persistable:
                    persistable[name] = True
        has_optimize = any(
            op.desc.attrs.get("op_role") == "optimize"
            for op in program.global_block().ops)
        # keep a strong program ref: the cache key uses id(program)
        self._analysis_cache[key] = (program, reads, writes, persistable,
                                     has_optimize)
        return reads, writes, persistable, has_optimize

    def _external_inputs(self, program, fed: set, scope) -> List[str]:
        """Vars the block reads from the scope: already-present scope vars or
        declared persistables. Reads of undeclared/absent vars are optional
        inputs (grad cotangents never produced) and resolve to None.
        (Computing reads with an empty produced-set and subtracting `fed`
        is equivalent to seeding produced with `fed`: a fed var read before
        production lands in reads and is then subtracted.)"""
        reads, _writes, persistable, _ = self._analysis(program)
        out = []
        for n in sorted(reads - fed):
            if scope.has_var(n) and scope.find_var(n) is not None:
                out.append(n)
            elif persistable.get(n):
                out.append(n)
        return out

    def _persistable_outputs(self, program) -> List[str]:
        _reads, writes, persistable, _ = self._analysis(program)
        return [n for n in sorted(writes) if persistable.get(n)]

    # --- execution ----------------------------------------------------------
    def _exec_op(self, ctx: LoweringContext, op, env: Dict[str, Any]):
        if op.type in ("feed", "fetch"):
            return
        _record_op(op.type)
        try:
            opdef = registry.get(op.type)
        except KeyError as e:
            raise RuntimeError(
                f"Operator '{op.type}' is not registered "
                f"(outputs {op.output_arg_names}); available ops: "
                f"{len(registry.registered_ops())} registered") from e
        if opdef.lower is None:
            raise RuntimeError(
                f"Operator '{op.type}' has no kernel lowering "
                f"(inputs {dict(op.desc.inputs)}, "
                f"outputs {dict(op.desc.outputs)})")
        prev_env = ctx.env
        ctx.env = env
        ctx.seq_overrides = {}
        ctx.layout_overrides = {}
        propagate_tag = None
        if ctx.layout_opt:
            from .ops import layout as layout_mod
            propagate_tag = layout_mod.prepass(ctx.layouts, op, op.type, env)
        ins = {slot: [env.get(n) for n in names]
               for slot, names in op.desc.inputs.items()}
        if op.type not in _OPEN_PRODUCT_AWARE_OPS:
            # the gradient of one activation through sibling products,
            # left open by their gradient ops and folded by the program's
            # `sum` (ops/sibling_products.py), becomes an array where the
            # first other op reads it: one contraction, reduced once
            for slot, names in op.desc.inputs.items():
                for k, n in enumerate(names):
                    if isinstance(ins[slot][k], OpenProducts):
                        ins[slot][k] = env[n] = ins[slot][k].close()
        if op.type not in _SPARSE_AWARE_OPS:
            # SelectedRows grads (sparse embedding path) densify at the
            # boundary of any op without a sparse kernel — the analogue of
            # the reference's per-kernel SelectedRows dispatch. Counted:
            # this is the invisible perf cliff sparse_densify_fallback_total
            # exists to surface (a clip/regularizer/cast in the grad chain
            # silently turns O(rows) into O(table)).
            from .ops.common import SelectedRowsVal
            newins = {}
            hit = False
            for slot, vals in ins.items():
                conv = []
                for v in vals:
                    if isinstance(v, SelectedRowsVal):
                        hit = True
                        v = v.to_dense()
                    conv.append(v)
                newins[slot] = conv
            if hit:
                sparse_ops_mod.count_densify(op.type, "sparse_unaware_op")
            ins = newins
        t0 = time.perf_counter() if _BENCHMARK and _EAGER else None
        try:
            # the scopes land in every emitted HLO instruction's metadata
            # op_name ("jit(fn)/pd_role.<role>/pd.<type>/<prim>") — the hook
            # device time is booked to program ops by: the outermost
            # "pd.<type>" (xplane.provenance: the account's rows, the
            # profiler's device table) and the outermost "pd_role.<op_role>"
            # (benchmarks/program_trace.py; spelt so that no "pd." rule
            # sees it). A fused op's members keep the fused op's role. An
            # op built under fluid.name_scope also carries
            # "pd_scope.<outer.inner>" between the two, spelt likewise;
            # _trace_block puts the op's position, "pd_at.<n>", around
            # all three. A forward op replayed in the backward
            # (backward.RECOMPUTE_ATTR) carries "pd_recompute.<segment>"
            # inside its role (xplane.recompute_of).
            built_under = op.desc.attrs.get(NAME_SCOPE_ATTR)
            replayed_in = op.desc.attrs.get(RECOMPUTE_ATTR)
            with jax.named_scope(_ROLE_SCOPE.get(op.desc.attrs.get("op_role"),
                                                 _ROLE_SCOPE[None])), \
                    (jax.named_scope(f"{xplane_mod.RECOMPUTE_SCOPE}"
                                     f"{replayed_in}")
                     if replayed_in is not None
                     else contextlib.nullcontext()), \
                    (jax.named_scope("pd_scope." + built_under.strip(
                        "/").replace("/", ".")) if built_under
                     else contextlib.nullcontext()), \
                    jax.named_scope(f"pd.{op.type}"):
                outs = opdef.lower(ctx, op, ins)
        except (AssertionError, TypeError, ValueError, IndexError) as e:
            # PADDLE_ENFORCE-style context (reference platform/enforce.h +
            # utils/CustomStackTrace.h layer-stack dump): name the failing
            # operator, its variables, the live input shapes, and the user
            # line that built the op, instead of a bare JAX traceback
            from .errors import EnforceNotMet
            shapes = {slot: [getattr(v, "shape", None) for v in vals]
                      for slot, vals in ins.items()}
            site = getattr(op, "creation_site", None)
            raise EnforceNotMet(
                f"Operator {op.type} failed: {e}\n"
                f"  inputs: {dict(op.desc.inputs)}\n"
                f"  input shapes: {shapes}\n"
                f"  outputs: {dict(op.desc.outputs)}\n"
                f"  built at: {site or '<unknown>'}",
                op_type=op.type, creation_site=site) from e
        if _op_observers:
            for obs in _op_observers:
                obs(op, ins, outs)
        if t0 is not None:
            # FLAGS_benchmark parity (reference executor.cc:321): wait for
            # device completion per op and log wall time
            jax.block_until_ready(jax.tree.leaves(
                {k: [v for v in vs if v is not None]
                 for k, vs in outs.items()}))
            vlog(1, f"[benchmark] {op.type}: "
                    f"{(time.perf_counter() - t0) * 1e3:.3f} ms")
        # Default SEQLEN propagation mirrors the reference's LoD propagation
        # (most ops share LoD with their first sequence input); sequence
        # lowerings override via ctx.set_seq_len. Inheritance is restricted
        # to outputs that PRESERVE the carrier's [batch, time] leading dims —
        # an op that drops or reshapes the time axis (reductions, matmul
        # collapses) is no longer a sequence, and tagging it would make the
        # fetch path spuriously repack a dense tensor.
        inherited = None
        inherited2 = None
        carrier_shape = None
        for names in op.desc.inputs.values():
            for n in names:
                if n + SEQLEN_SUFFIX in env:
                    inherited = env[n + SEQLEN_SUFFIX]
                    inherited2 = env.get(n + SEQLEN2_SUFFIX)
                    carrier_shape = getattr(env.get(n), "shape", None)
                    break
            if inherited is not None:
                break
        for slot, names in op.desc.outputs.items():
            vals = outs.get(slot, [])
            for name, val in zip(names, vals):
                if val is not None:
                    env[name] = val
                    if name + SEQLEN2_SUFFIX in ctx.seq_overrides:
                        sl2 = ctx.seq_overrides[name + SEQLEN2_SUFFIX]
                        if sl2 is None:
                            env.pop(name + SEQLEN2_SUFFIX, None)
                        else:
                            env[name + SEQLEN2_SUFFIX] = sl2
                    if name in ctx.seq_overrides:
                        sl = ctx.seq_overrides[name]
                        if sl is None:
                            env.pop(name + SEQLEN_SUFFIX, None)
                        else:
                            env[name + SEQLEN_SUFFIX] = sl
                    elif inherited is not None and hasattr(val, "ndim") \
                            and getattr(val, "ndim", 0) >= 2 \
                            and carrier_shape is not None \
                            and len(carrier_shape) >= 2 \
                            and tuple(val.shape[:2]) == tuple(carrier_shape[:2]):
                        env[name + SEQLEN_SUFFIX] = inherited
                        if inherited2 is not None and \
                                name + SEQLEN2_SUFFIX not in ctx.seq_overrides:
                            env[name + SEQLEN2_SUFFIX] = inherited2
        if ctx.layout_opt and (ctx.layouts or propagate_tag
                               or ctx.layout_overrides):
            from .ops import layout as layout_mod
            layout_mod.tag_outputs(ctx.layouts, op, env, propagate_tag,
                                   ctx.layout_overrides)
        ctx.env = prev_env

    def recompute_plan(self, program) -> Optional[recompute_mod.Plan]:
        """What the last trace of `program` decided of its recomputation
        segments (recompute.Plan: kept or replayed a segment, why, and
        the bytes it was decided from); None before a trace, or where the
        program holds no segment."""
        return self._replay_plans.get(
            (id(program), getattr(program, "_version", 0)))

    def _plan_replay(self, program, env, state_vals) -> frozenset:
        """The block positions a kept segment's ops stand at: decided at
        the turn from forward to backward from the traced values' bytes
        and this device's limit (recompute.plan), booked, and remembered
        for recompute_plan()."""
        key = (id(program), getattr(program, "_version", 0))
        made = recompute_mod.plan(
            program, env,
            sum(memory_mod.nbytes_of(v) for v in state_vals.values()),
            memory_mod.device_limit(self.device),
            refused=key in self._replay_all)
        if len(self._replay_plans) > 64:
            self._replay_plans.clear()
        self._replay_plans[key] = made
        recompute_mod.book(program, made)
        return made.skipped

    def _kept_too_much(self, program, state_vals, error) -> bool:
        """The safety net under the estimate: did this launch run out of
        memory while COMPILING a step with segments kept? Then the
        program is marked to be traced with every segment replayed,
        counted and warned of; the caller compiles once more. A step that
        ran out while running has consumed its donated state and is not
        retried."""
        made = self.recompute_plan(program)
        if made is None or not made.skipped or not memory_mod.is_oom(error) \
                or any(getattr(v, "is_deleted", lambda: False)()
                       for v in state_vals.values()):
            return False
        self._replay_all.add((id(program), getattr(program, "_version", 0)))
        label = telemetry.program_label(program)
        telemetry.counter(
            "recompute_fallback_total",
            "compiles that ran out of device memory with recomputation "
            "segments kept and were made again with every segment replayed",
            labels=("program",)).labels(program=label).inc()
        warnings.warn(
            f"paddle_tpu recompute: program '{label}' did not fit with "
            f"{sum(d.kept for d in made.decisions.values())} "
            f"segment(s) kept ({made.kept_bytes} B; estimate "
            f"{made.estimate} of {made.limit} B); compiling again with "
            f"every segment replayed [recompute_fallback_total]",
            RuntimeWarning, stacklevel=4)
        return True

    def _trace_block(self, program, feed_vals, state_vals, fetch_names,
                     persist_out, rng_key, lod_map, grab_names=()):
        env: Dict[str, Any] = {}
        env.update(state_vals)
        env.update(feed_vals)
        ctx = LoweringContext(self, program, rng_key, lod_map)
        block = program.global_block()
        # trace-time fusion pass (ops/fusion.py, PADDLE_TPU_FUSION=1):
        # planned windows lower as one fused op at their anchor index;
        # everything else keeps the per-op path. The plan is fetch-
        # agnostic, so fold-mode elision re-checks against the names this
        # trace must materialize.
        from .ops import fusion as fusion_mod
        from .parallel import overlap as overlap_mod
        groups = fusion_mod.plan(program)
        # communication/compute overlap pass (parallel/overlap.py,
        # PADDLE_TPU_OVERLAP=1): dp gradient buckets flush — pin to the
        # replicated sharding under their pd.coll scope — right after
        # their last producing grad op, instead of resolving lazily at
        # the optimizer. Bitwise-neutral; only the sync point moves.
        oplan = overlap_mod.plan(program)
        # every op (or fused window) of the block is lowered under its
        # position in the block, "pd_at.<n>": the op INSTANCE in each
        # emitted instruction's op_name, beside the role, name scope and
        # type that _exec_op adds (spelt so that no "pd." / "pd_role." /
        # "pd_scope." rule sees it). Metadata only: the compiled step is
        # the same. The account's rows group by it (xplane.provenance):
        # which of 53 conv windows, which layer's mul_grad
        #
        # A program built with checkpoints says where the backward MAY
        # replay; which segments it does is decided once the first forward
        # is traced and every value's bytes are known (recompute.py). A
        # kept segment's barrier and replayed ops are bound to the first
        # forward's values and not lowered.
        turn = min((seg.barrier for seg in recompute_mod.segments(block)),
                   default=None)
        kept_ops: frozenset = frozenset()

        def lower(i, op):
            if i in kept_ops:
                recompute_mod.bind(op, env, ctx.layouts)
            else:
                self._exec_op(ctx, op, env)

        if not groups and oplan is None:
            for i, op in enumerate(block.ops):
                if i == turn:
                    kept_ops = self._plan_replay(program, env, state_vals)
                with jax.named_scope(f"{xplane_mod.AT_SCOPE}{i}"):
                    lower(i, op)
        else:
            protected = set(fetch_names) | set(persist_out)
            ops = block.ops
            groups = groups or {}
            i = 0
            while i < len(ops):
                if i == turn:
                    kept_ops = self._plan_replay(program, env, state_vals)
                g = groups.get(i)
                if g is not None and kept_ops.intersection(range(i, g.end)):
                    g = None    # a kept segment's ops are bound one by one
                with jax.named_scope(f"{xplane_mod.AT_SCOPE}{i}"):
                    if g is not None:
                        fusion_mod.execute_group(self, ctx, g, env,
                                                 protected)
                        nxt = g.end
                    else:
                        lower(i, ops[i])
                        nxt = i + 1
                if oplan is not None:
                    # anchors inside a fused window flush after the window
                    oplan.flush_range(ctx, env, i, nxt)
                i = nxt
        if ctx.layouts:
            # fetches and persistable state leave the trace in canonical
            # NCHW — the internal NHWC convention never escapes a run
            from .ops import layout as layout_mod
            layout_mod.canonicalize(ctx.layouts, env,
                                    list(fetch_names) + list(persist_out))
        from .ops.common import maybe_dense
        fetch = [maybe_dense(env[n], count_as="fetch") for n in fetch_names]
        # lengths side channel for fetched sequence vars, so run() can
        # rebuild LoDTensors (padded_to_pack) when return_numpy=False
        fetch_lens = {n: env[n + SEQLEN_SUFFIX] for n in fetch_names
                      if n + SEQLEN_SUFFIX in env}
        for n in fetch_names:
            if n + SEQLEN2_SUFFIX in env:
                fetch_lens[n + SEQLEN2_SUFFIX] = env[n + SEQLEN2_SUFFIX]
        new_state = {n: env[n] for n in persist_out if n in env}
        # state read but never written flows through unchanged
        for n in state_vals:
            if n not in new_state and not n.endswith(SEQLEN_SUFFIX):
                for b in program.blocks:
                    if b.desc.has_var(n) and b.desc.var(n).persistable:
                        new_state[n] = env[n]
                        break
        # lengths side channels for sequence-state write-back — only for vars
        # *declared* as sequences (lod_level>0): the default SEQLEN
        # propagation in _exec_op can spuriously tag non-sequence outputs
        # (e.g. a parameter updated from a sequence-derived gradient)
        for n in list(new_state):
            if n + SEQLEN_SUFFIX not in env:
                continue
            for b in program.blocks:
                if b.desc.has_var(n):
                    if b.desc.var(n).lod_level > 0:
                        new_state[n + SEQLEN_SUFFIX] = env[n + SEQLEN_SUFFIX]
                        if n + SEQLEN2_SUFFIX in env and \
                                b.desc.var(n).lod_level > 1:
                            new_state[n + SEQLEN2_SUFFIX] = \
                                env[n + SEQLEN2_SUFFIX]
                    break
        # raw trace values the dynamics reduction reads (grad vars): no
        # maybe_dense — SelectedRows grads reduce sparse — and no layout
        # canonicalize, the stats are layout-invariant reductions
        grabs = {n: env[n] for n in grab_names if n in env}
        return fetch, fetch_lens, new_state, grabs

    def _make_step_fn(self, program, fetch_names, persist_out, lod_map):
        """The pure per-step function `fn(feed_vals, state_vals, rng_counter)
        -> (fetch, lens, new_state)` both compile paths share: _compile jits
        it directly; _compile_window wraps it in a lax.scan over a stacked
        feed window."""
        mesh = getattr(program, "_mesh", None)
        param_specs = getattr(program, "_param_shardings", {})
        seed = program.random_seed or 12345
        dyn_plan = dynamics_mod.plan(program)

        def _state_spec(n):
            # accumulators of ANY sharded parameter inherit its sharding
            # (parallel/embedding.resolve_state_spec, generalized past
            # tables by the planner) so adam moments of a 1M-row table —
            # or an fsdp-sharded fc weight — never replicate per device
            spec = param_specs.get(n)
            if spec is None and (param_specs or
                                 getattr(program, "_sharded_tables", None)):
                from .parallel import embedding as embedding_mod
                spec = embedding_mod.resolve_state_spec(program, n)
            return spec

        def fn(feed_vals, state_vals, rng_counter):
            # key derivation INSIDE the jit: the per-step fold_in costs
            # nothing host-side (eagerly it was ~3ms/step of tiny
            # dispatches, measurable against a ~100ms ResNet step)
            rng_key = jax.random.fold_in(jax.random.key(seed), rng_counter)
            fetch, lens, new_state, grabs = self._trace_block(
                program, feed_vals, state_vals, fetch_names, persist_out,
                rng_key, lod_map,
                grab_names=dyn_plan.grab_names if dyn_plan else ())
            # fused dynamics reduction over pre-pin values (the stats are
            # scalars; pinning them replicated below would be a no-op
            # anyway, but the weights/grads must be the trace's own)
            dyn_stats = dynamics_mod.sampled_stats(
                dyn_plan, state_vals, new_state, grabs, rng_counter,
                telemetry.program_label(program))
            if mesh is not None:
                # pin state outputs to the same shardings the next run's
                # in_shardings expect (annotated params keep their spec,
                # everything else replicated) — otherwise XLA may choose a
                # sharded layout for an output and the donated round-trip
                # mismatches on the following step
                from jax.sharding import NamedSharding, PartitionSpec
                from .parallel._collectives import coll_scope
                pinned = {}
                for n, v in new_state.items():
                    spec = _state_spec(n)
                    sh = NamedSharding(mesh, PartitionSpec(*spec)) if spec \
                        else NamedSharding(mesh, PartitionSpec())
                    try:
                        if spec:
                            # annotated (tensor/ZeRO-sharded) params: the
                            # resharding collectives GSPMD inserts here get
                            # a pd.coll site so fleet.py attributes them;
                            # replicated pins stay untagged (usually no-ops)
                            with coll_scope("tp_state_pin"):
                                pinned[n] = \
                                    jax.lax.with_sharding_constraint(v, sh)
                        else:
                            pinned[n] = \
                                jax.lax.with_sharding_constraint(v, sh)
                    except (TypeError, ValueError):
                        pinned[n] = v
                new_state = pinned
            if dyn_stats is not None:
                # rides new_state through the donated round-trip; the
                # executor pops it before check_nan and scope writeback
                new_state[dynamics_mod.STATE_KEY] = dyn_stats
            return fetch, lens, new_state

        return fn

    def _shardings(self, program, state_names, feed_names, *, window=False):
        """SPMD in_shardings for the compiled step, or None off-mesh: feeds
        sharded along batch over the 'dp' axis, state (parameters /
        accumulators) replicated unless annotated. XLA GSPMD inserts the
        gradient AllReduce over ICI — the TPU-native replacement for the
        reference's pserver/NCCL paths (SURVEY.md §2.5). With window=True
        each feed gains a leading steps axis, so its per-step spec shifts
        right by one (the scan axis is never sharded)."""
        mesh = getattr(program, "_mesh", None)
        if mesh is None:
            return None
        param_specs = getattr(program, "_param_shardings", {})
        from jax.sharding import NamedSharding, PartitionSpec
        repl = NamedSharding(mesh, PartitionSpec())

        # per-parameter PartitionSpec annotations (tensor / ZeRO
        # sharding, parallel/tensor_parallel.py); sharded-table optimizer
        # accumulators inherit their table's row sharding
        # (parallel/embedding.resolve_state_spec); everything else is
        # replicated and XLA GSPMD partitions the consumers
        state_shardings = {}
        has_specs = bool(param_specs) or \
            bool(getattr(program, "_sharded_tables", None))
        if has_specs:
            from .parallel import embedding as embedding_mod
        for n in state_names:
            spec = param_specs.get(n)
            if spec is None and has_specs:
                spec = embedding_mod.resolve_state_spec(program, n)
            state_shardings[n] = repl if spec is None else \
                NamedSharding(mesh, PartitionSpec(*spec))

        # Feed sharding rule: an explicit per-feed override
        # (program._feed_shardings[name] = spec tuple, see
        # parallel.shard_feed) wins; otherwise feeds batch-shard on
        # the axis named 'dp' when the mesh has one, and replicate on
        # meshes without a data axis (sp/ep/mp-only meshes must opt
        # in via shard_feed). @SEQLEN sidecars are [batch] vectors
        # and follow their base feed's batch (dim-0) axis.
        feed_specs = getattr(program, "_feed_shardings", {})
        dp_axis = "dp" if "dp" in mesh.axis_names else None
        default_spec = (dp_axis,) if dp_axis else ()

        def _feed_spec(n):
            if n.endswith(SEQLEN2_SUFFIX):
                base = n[: -len(SEQLEN2_SUFFIX)]
            elif n.endswith(SEQLEN_SUFFIX):
                base = n[: -len(SEQLEN_SUFFIX)]
            else:
                base = None
            if base is not None:
                bspec = feed_specs.get(base)
                if bspec is not None:
                    return (bspec[0] if bspec else None,)
                return default_spec
            spec = feed_specs.get(n)
            if spec is not None:
                return tuple(spec)
            return default_spec

        def _feed_sharding(n):
            spec = _feed_spec(n)
            if window:
                spec = (None,) + spec
            return NamedSharding(mesh, PartitionSpec(*spec))

        feed_shardings = {n: _feed_sharding(n) for n in feed_names}
        return feed_shardings, state_shardings, repl

    def _jit_compile(self, program, fn, sh):
        """The ONE jax.jit call site for both compile paths (per-step and
        the run_steps scan window). Consolidated so compiler options — the
        overlap pass's async-collective + latency-hiding-scheduler set
        today, anything else tomorrow — reach EVERY path; before this the
        four duplicated call sites each had to be patched in step.
        tools/check_registry.py lints this file down to exactly one
        direct jit call site, so a new path can't silently skip it.
        `compiler_options()` returns None (plain compile) off-mesh, off-
        gate, on non-TPU backends, or when the probe rejects the set.

        State is donated only off-CPU: XLA CPU never aliases donated
        buffers (the donation audit's alias=0B warning), so donation buys
        nothing there — and it is actively unsafe when a state entry is a
        scope-held numpy array, because CPU device_put may zero-copy an
        aligned host buffer and donating memory jax does not own corrupts
        the heap (flaky SIGSEGV/garbage reads, alignment- and therefore
        allocation-order-dependent)."""
        from .parallel import overlap as overlap_mod
        plat = getattr(self.device, "platform", None) or jax.default_backend()
        kwargs: Dict[str, Any] = {}
        if plat != "cpu":
            kwargs["donate_argnums"] = (1,)
        if sh is not None:
            feed_shardings, state_shardings, repl = sh
            kwargs["in_shardings"] = (feed_shardings, state_shardings, repl)
        opts = overlap_mod.compiler_options(program)
        if opts:
            kwargs["compiler_options"] = opts
        return jax.jit(fn, **kwargs)

    def _maybe_verify(self, program, feed_names, fetch_names):
        """PADDLE_TPU_VERIFY=1: run the static analyzer once per program
        version on the cache-miss path (so a verified program costs
        nothing on later steps) and refuse to trace a program with
        error-severity diagnostics."""
        if not _VERIFY:
            return
        key = (id(program), getattr(program, "_version", 0))
        seen = getattr(self, "_verified_programs", None)
        if seen is None:
            seen = self._verified_programs = set()
        if key in seen:
            return
        from .analysis import analyze_program
        report = analyze_program(program, feeds=list(feed_names),
                                 fetches=list(fetch_names))
        if report.errors:
            from .errors import ProgramVerifyError
            raise ProgramVerifyError(
                report.errors, program_name=getattr(program, "name", None))
        seen.add(key)

    def _compile(self, program, state_names, feed_names, fetch_names,
                 persist_out, lod_map) -> _CompiledBlock:
        self._maybe_verify(program, feed_names, fetch_names)
        fn = self._make_step_fn(program, fetch_names, persist_out, lod_map)
        sh = self._shardings(program, state_names, feed_names)
        jitted = self._jit_compile(program, fn, sh)
        return _CompiledBlock(jitted, state_names, feed_names, fetch_names,
                              program)

    def prepare_serving(self, program, feed_names, fetch_names, scope):
        """Compile one inference program for the serving engine and return
        (compiled_block, state_names, persist_out). This is the stable
        seam between serving/ and the executor: the engine AOT-lowers
        per-bucket executables from compiled_block.fn (jit's .lower() on
        explicit avals) instead of re-implementing tracing, sharding
        resolution, or the donation contract. Raises the same
        missing-state error as Executor.run when a persistable the block
        reads has no value in `scope` (startup never ran / load_persistables
        skipped a file)."""
        feed_names = sorted(feed_names)
        state_names, _vals = self._gather_state(
            program, set(feed_names), scope, {})
        persist_out = self._persistable_outputs(program)
        compiled = self._compile(program, state_names, feed_names,
                                 fetch_names, persist_out, lod_map={})
        return compiled, state_names, persist_out

    def _compile_window(self, program, state_names, feed_names, fetch_names,
                        persist_out, lod_map, steps, fetch_mode) \
            -> _CompiledBlock:
        """Compile a K-step fused window: the per-step fn wrapped in a
        jax.lax.scan whose carry is (persistable state, rng counter) and
        whose xs is a feed dict with a leading [K] axis. One Python
        dispatch, one donation, one write-back per K steps; per-step rng
        parity comes from carrying the same uint32 counter the per-step
        path folds in (step i of the window uses counter+i, bitwise what K
        sequential runs would use)."""
        self._maybe_verify(program, feed_names, fetch_names)
        step_fn = self._make_step_fn(program, fetch_names, persist_out,
                                     lod_map)

        def fnK(window_feed, state_vals, rng_counter):
            def body(carry, feed_slice):
                state, counter = carry
                fetch, lens, new_state = step_fn(feed_slice, state, counter)
                if lens:
                    raise _WindowUnsupported(
                        f"sequence fetches {sorted(lens)} need per-batch "
                        f"LoD reconstruction")
                # persistables written by the step ride the carry; state
                # that is read but never written flows through unchanged;
                # written-but-never-read persistables (no feedback edge)
                # leave as per-step outputs and the last slice wins —
                # exactly K sequential runs' write-back order
                carry_state = {n: new_state.get(n, state[n]) for n in state}
                extras = {n: v for n, v in new_state.items()
                          if n not in state}
                return (carry_state, counter + jnp.uint32(1)), (fetch, extras)

            init = (state_vals, jnp.uint32(rng_counter))
            (final_state, _), (fetch_seq, extra_seq) = jax.lax.scan(
                body, init, window_feed)
            if fetch_mode == "stack":
                fetch = list(fetch_seq)
            elif fetch_mode == "mean":
                fetch = [jnp.mean(f, axis=0) for f in fetch_seq]
            else:  # "last"
                fetch = [f[-1] for f in fetch_seq]
            new_state = dict(final_state)
            for n, v in extra_seq.items():
                # the dynamics stats row keeps its full [K, ...] stack —
                # the observatory picks the period-boundary slices out
                new_state[n] = v if n == dynamics_mod.STATE_KEY else v[-1]
            return fetch, new_state

        sh = self._shardings(program, state_names, feed_names, window=True)
        jitted = self._jit_compile(program, fnK, sh)
        return _CompiledBlock(jitted, state_names, feed_names, fetch_names,
                              program)

    def _run_eager(self, program, feed_vals, state_vals, fetch_names,
                   persist_out, rng_key, lod_map, check_nan=False):
        env: Dict[str, Any] = {}
        env.update({k: jnp.asarray(v) for k, v in state_vals.items()})
        env.update({k: jnp.asarray(v) for k, v in feed_vals.items()})
        ctx = LoweringContext(self, program, rng_key, lod_map)
        block = program.global_block()
        for op in block.ops:
            # per-op host events in the interpreter path (reference
            # RecordEvent around each kernel launch, operator.cc:486)
            with profiler_mod.record(op.type):
                self._exec_op(ctx, op, env)
            if check_nan and op.type != "tensor_stats":
                # per-op scan (reference executor.cc:325 FLAGS_check_nan_inf
                # semantics); in eager mode the op boundary IS available, so
                # the error names the producing op directly — no bisection
                # replay needed. tensor_stats outputs are exempt for the
                # same reason as in the jit path.
                for name in op.output_arg_names:
                    v = env.get(name)
                    if v is not None and jnp.issubdtype(
                            jnp.asarray(v).dtype, jnp.inexact):
                        if not bool(jnp.all(jnp.isfinite(v))):
                            from .errors import NonFiniteError
                            raise NonFiniteError(
                                f"NaN/Inf in output '{name}' of op "
                                f"{op.type}",
                                var_name=name, op_type=op.type,
                                dtype=str(jnp.asarray(v).dtype))
        if ctx.layouts:
            from .ops import layout as layout_mod
            layout_mod.canonicalize(ctx.layouts, env,
                                    list(fetch_names) + list(persist_out)
                                    + list(state_vals))
        from .ops.common import maybe_dense
        fetch = [maybe_dense(env[n], count_as="fetch") for n in fetch_names]
        fetch_lens = {n: env[n + SEQLEN_SUFFIX] for n in fetch_names
                      if n + SEQLEN_SUFFIX in env}
        for n in fetch_names:
            if n + SEQLEN2_SUFFIX in env:
                fetch_lens[n + SEQLEN2_SUFFIX] = env[n + SEQLEN2_SUFFIX]
        new_state = {}
        for n in set(persist_out) | set(state_vals):
            if n.endswith(SEQLEN_SUFFIX):
                continue
            if n in env:
                for b in program.blocks:
                    if b.desc.has_var(n) and b.desc.var(n).persistable:
                        new_state[n] = env[n]
                        break
        for n in list(new_state):
            if n + SEQLEN_SUFFIX not in env:
                continue
            for b in program.blocks:
                if b.desc.has_var(n):
                    if b.desc.var(n).lod_level > 0:
                        new_state[n + SEQLEN_SUFFIX] = env[n + SEQLEN_SUFFIX]
                        if n + SEQLEN2_SUFFIX in env and \
                                b.desc.var(n).lod_level > 1:
                            new_state[n + SEQLEN2_SUFFIX] = \
                                env[n + SEQLEN2_SUFFIX]
                    break
        return fetch, fetch_lens, new_state
