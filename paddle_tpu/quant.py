"""Quantized MXU compute: the third AMP level ("O3").

The reference framework dispatches kernels by OpKernelType place/dtype/
library (reference: framework/op_kernel_type.h) — fp32 vs fp16 vs MKLDNN
int8 builds of the same op. On TPU the analogous axis is the MXU input
type: bf16 (AMP O1/O2) and, one level down, int8 / fp8 — the MXU runs
int8 dots at 2x the bf16 rate, and serving qps-per-chip comes from
exactly that. `amp.decorate(..., level="O3")` tags the program with a
quant mode ("int8" default, PADDLE_TPU_QUANT_MODE=fp8 to switch) and the
matmul/conv lowerings route eligible compute through this module:

  * weights are quantized symmetrically per output channel
    (scale = max|w| / 127 per column / per Co), activations per row,
    dynamically at each call — no calibration pass;
  * the integer dot accumulates in int32 (`preferred_element_type`) and
    dequantizes by the outer product of the two scale vectors, so the
    stored output is the same bf16 the O2 path would produce;
  * the whole quantized op is a `jax.custom_vjp`: backward is the plain
    bf16 matmul/conv math (straight-through estimator). `jnp.round` has
    a zero gradient a.e. and integer dots are not differentiable, so
    letting the generic vjp grad path retrace the quantized forward
    would silently produce zero weight gradients;
  * eligibility is a trace-time gate (`ineligible_matmul` /
    `ineligible_conv`) with counted per-reason fallbacks
    (quant_fallback_total{op,reason}), mirroring attention's
    pallas_fallback_total discipline — including a quantization
    error-bound check against PADDLE_TPU_QUANT_TOL;
  * serving (`ServingEngine(quantize="int8")`) pre-quantizes persistable
    weights ONCE at admission (`prequantize`, with a measured per-weight
    parity gate on the dequantization error) and bakes the int8 tensors
    into the AOT bucket executables as constants; activations still
    scale per call.

Gate-off story: with PADDLE_TPU_QUANT=0 every gate returns "disabled",
the lowerings take their plain O2 route, and O3 numerics equal O2
bitwise.

Error model for the trace-time bound: symmetric uniform quantization
adds relative noise of RMS step/sqrt(12) per operand element (int8:
1/(127*sqrt(12)) ~ 0.23%; fp8 e4m3, 3 mantissa bits: 2^-3/sqrt(12) ~
3.6%). Quantization noise on a K-term dot product is zero-mean and
independent per term, so the *relative* RMS error of the output stays
~sqrt(eps_x^2 + eps_w^2) independent of K. Ops whose estimate exceeds
PADDLE_TPU_QUANT_TOL (default 0.06 — passes int8 and fp8; tighten to
force the "error_bound" fallback) fall back to bf16.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = [
    "FALLBACK_REASONS", "QUANT", "QUANT_OPS", "count_fallback",
    "count_hit", "error_estimate", "fp8_supported", "gate_for_op",
    "ineligible_conv", "ineligible_matmul", "prequantize",
    "prequantized", "qconv2d",
    "qmatmul", "quantize_channelwise",
    "weight_qparams",
]

QUANT = os.environ.get("PADDLE_TPU_QUANT", "1") == "1"
QUANT_TOL = float(os.environ.get("PADDLE_TPU_QUANT_TOL", "0.06"))

_LANE = 128

# Every reason the gates can return (pinned by check_quant_table — a
# reason produced but not listed here would ship an unlabelled fallback
# counter, exactly the pallas FALLBACK_REASONS contract).
FALLBACK_REASONS = frozenset(
    {"disabled", "mode", "rank", "dtype", "shape", "kernel",
     "error_bound"})

# RMS relative quantization noise per operand element (module
# docstring); bf16 operands arrive already rounded, so these are the
# *additional* noise of the int8/fp8 step.
_EPS_RMS = {"int8": 1.0 / (127.0 * math.sqrt(12.0)),
            "fp8": 2.0 ** -3 / math.sqrt(12.0)}

# int8 full-scale / fp8 e4m3 max-normal
_QMAX = {"int8": 127.0, "fp8": 448.0}

_FLOAT_IN = (jnp.bfloat16, jnp.float32)

# Registered op types that route through this module, and the quantized
# entry point each uses. check_quant_table pins it against ops/registry
# and the gate/lowering sources — an op listed here whose lowering never
# consults the gate (or vice versa) silently loses quantization, so the
# lint fails instead.
QUANT_OPS = {
    "mul": "qmatmul",
    "matmul": "qmatmul",
    "conv2d": "qconv2d",
    "depthwise_conv2d": "qconv2d",   # groups gate: always falls back
}


def cache_token(program):
    """The quant part of the executor's compile-cache key: everything
    that changes how lowerings route, beyond the program itself."""
    return (getattr(program, "_quant_mode", None), QUANT, QUANT_TOL)


_FP8_OK = None


def fp8_supported() -> bool:
    """Whether the current backend executes float8_e4m3fn dots — probed
    once per process with a tiny real dot (an eval_shape would not catch
    a backend that traces but cannot compile fp8)."""
    global _FP8_OK
    if _FP8_OK is None:
        try:
            a = jnp.ones((8, 8), jnp.float8_e4m3fn)
            out = jax.jit(lambda u, v: lax.dot_general(
                u, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))(a, a)
            jax.block_until_ready(out)
            _FP8_OK = True
        except Exception:  # noqa: BLE001 - any failure means "no fp8"
            _FP8_OK = False
    return _FP8_OK


def error_estimate(k: int, mode: str) -> float:
    """Trace-time relative-RMS error estimate for a quantized K-deep
    matmul/conv contraction (module docstring's model): both operands
    carry one quantization step of noise."""
    eps = _EPS_RMS.get(mode, 1.0)
    del k  # zero-mean noise: relative output error is depth-independent
    return math.sqrt(2.0) * eps


# --- trace-time gates ---------------------------------------------------

def ineligible_matmul(x, y, mode="int8"):
    """None when the quantized matmul applies to x [M, K] @ y [K, N],
    else the fallback reason. Operands are post-mxu_cast (bf16 under
    O3). Shared by the lowering, the preflight dry-run and the serving
    admission pass, so it must stay a pure shape/dtype predicate."""
    if not QUANT:
        return "disabled"
    if mode not in _QMAX:
        return "mode"
    if mode == "fp8" and not fp8_supported():
        return "mode"
    if getattr(x, "ndim", 0) != 2 or getattr(y, "ndim", 0) != 2:
        return "rank"
    if getattr(x, "dtype", None) not in _FLOAT_IN or \
            getattr(y, "dtype", None) not in _FLOAT_IN:
        return "dtype"
    k = x.shape[1]
    if k < 32 or k % 8:
        # too shallow to amortize the quantize/dequantize sweeps, or
        # misaligned for the int8 MXU tile (32 sublanes)
        return "shape"
    if error_estimate(k, mode) > QUANT_TOL:
        return "error_bound"
    return None


def ineligible_conv(x, w, strides, paddings, dilations, groups=1,
                    mode="int8", mesh=None):
    """None when the quantized conv applies (NHWC x, OIHW w, both
    post-mxu_cast), else the reason. The int8 conv is the Pallas kernel
    pallas_conv.conv2d_q8, so that kernel's tiling gate
    (pallas_conv.ineligible: 128-lane channels, groups 1, the VMEM row
    budget, no mesh over more than one device) is a hard prerequisite,
    reason "kernel". The backward never meets this gate: conv2d_grad
    transposes the bf16 lax conv whatever the forward ran."""
    if not QUANT:
        return "disabled"
    if mode not in _QMAX:
        return "mode"
    if mode == "fp8":
        return "mode"    # the Pallas quant conv kernel is int8-only
    from .ops import pallas_conv
    if pallas_conv.ineligible(x, w, strides, paddings, dilations,
                              groups, mesh) is not None:
        return "kernel"
    co, ci, kh, kw = w.shape
    if error_estimate(ci * kh * kw, mode) > QUANT_TOL:
        return "error_bound"
    return None


def _pair2(v):
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1])) if len(v) > 1 else (int(v[0]),) * 2
    return (int(v), int(v))


def gate_for_op(op_type, ins, attrs, mode, nhwc=False):
    """Dry-run the lowering-time eligibility gate for ONE op instance on
    aval-like inputs (.shape/.dtype suffice — jax.ShapeDtypeStruct or
    real arrays). `ins` maps slot name -> list of values shaped the way
    the lowering receives them; `attrs` is the op's attr dict. For convs
    `nhwc` says Input is already channels-minor (the layout convention
    tags it so mid-stack); with nhwc=False the user-visible NCHW shape
    is rotated first, mirroring _conv2d's transpose.

    Shared by the roofline cost model (int8 peak factor) and the
    preflight quant pass so their verdicts replay the executor's actual
    routing without tracing. Returns None (would quantize) or the
    fallback reason string."""
    def _aval(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(int(d) for d in shape), dtype)

    assert op_type in QUANT_OPS, op_type
    if op_type in ("conv2d", "depthwise_conv2d"):
        x, w = ins["Input"][0], ins["Filter"][0]
        if not nhwc and getattr(x, "ndim", 0) == 4:
            s = x.shape
            x = _aval((s[0], s[2], s[3], s[1]), x.dtype)
        return ineligible_conv(
            x, w, _pair2(attrs.get("strides", [1, 1])),
            _pair2(attrs.get("paddings", [0, 0])),
            _pair2(attrs.get("dilations", [1, 1])),
            attrs.get("groups", 1) or 1, mode)
    x, y = ins["X"][0], ins["Y"][0]
    if op_type == "mul":
        def _flat(v, n):
            shp = tuple(int(d) for d in v.shape)
            rows = int(np.prod(shp[:n])) if n else 1
            cols = int(np.prod(shp[n:])) if n < len(shp) else 1
            return _aval((rows, cols), v.dtype)
        x = _flat(x, int(attrs.get("x_num_col_dims", 1)))
        y = _flat(y, int(attrs.get("y_num_col_dims", 1)))
    else:  # matmul: gate sees post-transpose operands
        if attrs.get("transpose_X", False) and getattr(x, "ndim", 0) > 1:
            s = x.shape
            x = _aval(s[:-2] + (s[-1], s[-2]), x.dtype)
        if attrs.get("transpose_Y", False) and getattr(y, "ndim", 0) > 1:
            s = y.shape
            y = _aval(s[:-2] + (s[-1], s[-2]), y.dtype)
    return ineligible_matmul(x, y, mode)


# --- counters -----------------------------------------------------------

# Both are silent in a gradient op's re-trace of a forward lowering
# (ops/kernel_choice.py): the forward op counted itself on its own trace.

def count_fallback(op: str, reason: str):
    from . import telemetry
    from .ops import kernel_choice
    if kernel_choice.in_retrace():
        return
    telemetry.counter(
        "quant_fallback_total",
        "O3 lowerings that fell back from the quantized path to bf16, "
        "by op and gating reason",
        labels=("op", "reason")).labels(op=op, reason=reason).inc()


def count_hit(op: str):
    from . import telemetry
    from .ops import kernel_choice
    if kernel_choice.in_retrace():
        return
    telemetry.counter(
        "quant_kernel_total",
        "lowerings served by the quantized (int8/fp8) path, by op",
        labels=("op",)).labels(op=op).inc()


# --- quantize helpers ---------------------------------------------------

def quantize_channelwise(x, axis: int, mode: str = "int8"):
    """Symmetric per-channel quantization: reduce max|x| over every dim
    EXCEPT `axis`, scale to the mode's full range, round. Returns
    (q, scale) with scale shaped like x reduced to size 1 everywhere but
    `axis` — so `q * scale` (or the int32 accumulator times the scale
    product) dequantizes by broadcast."""
    x32 = x.astype(jnp.float32)
    red = tuple(d for d in range(x32.ndim) if d != axis % x32.ndim)
    amax = jnp.max(jnp.abs(x32), axis=red, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / _QMAX[mode]
    if mode == "fp8":
        q = (x32 / scale).astype(jnp.float8_e4m3fn)
    else:
        q = jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def weight_qparams(w: np.ndarray, axis: int, mode: str = "int8"):
    """Host-side quantize_channelwise for serving admission: numpy in,
    (q, scale, rel_rms_err) out. The error term is the measured parity
    number the admission gate checks against QUANT_TOL — a real
    dequantize-and-compare, not the analytic estimate."""
    w32 = np.asarray(w, np.float32)
    red = tuple(d for d in range(w32.ndim) if d != axis % w32.ndim)
    amax = np.max(np.abs(w32), axis=red, keepdims=True)
    scale = np.maximum(amax, 1e-12) / _QMAX[mode]
    if mode == "fp8":
        q = (w32 / scale).astype(jnp.float8_e4m3fn)
    else:
        q = np.clip(np.rint(w32 / scale), -127, 127).astype(np.int8)
    deq = q.astype(np.float32) * scale
    denom = float(np.sqrt(np.mean(w32 * w32))) or 1.0
    err = float(np.sqrt(np.mean((deq - w32) ** 2))) / denom
    return q, scale.astype(np.float32), err


# --- quantized compute --------------------------------------------------

def _int_dot(xq, yq, mode):
    acc_t = jnp.float32 if mode == "fp8" else jnp.int32
    return lax.dot_general(xq, yq, (((1,), (0,)), ((), ())),
                           preferred_element_type=acc_t)


def _qmm_fwd_impl(x, y, mode, pre):
    xq, sx = quantize_channelwise(x, axis=0, mode=mode)   # [M,1] rows
    if pre is None:
        yq, sy = quantize_channelwise(y, axis=1, mode=mode)  # [1,N] cols
    else:
        yq, sy = jnp.asarray(pre[0]), jnp.asarray(pre[1])
    acc = _int_dot(xq, yq, mode).astype(jnp.float32)
    return (acc * (sx * sy)).astype(x.dtype)


def _make_qmm(mode: str, pre):
    @jax.custom_vjp
    def qmm(x, y):
        return _qmm_fwd_impl(x, y, mode, pre)

    def fwd(x, y):
        return qmm(x, y), (x, y)

    def bwd(res, g):
        # straight-through: the bf16 matmul vjp, exactly what the O2
        # path's generic grad would compute
        x, y = res
        gx = jnp.matmul(g, jnp.swapaxes(y, -1, -2)).astype(x.dtype)
        gy = jnp.matmul(jnp.swapaxes(x, -1, -2), g).astype(y.dtype)
        return gx, gy

    qmm.defvjp(fwd, bwd)
    return qmm


def qmatmul(x, y, mode: str = "int8", pre=None):
    """Quantized x [M, K] @ y [K, N] -> [M, N] in x.dtype. Per-row
    activation scales, per-column weight scales, int32 (fp8: f32)
    accumulation, straight-through bf16 backward. `pre` optionally
    supplies admission-time (q, scale) for y (ServingEngine) — y itself
    still flows in for the (never-taken at serve time) backward."""
    return _make_qmm(mode, pre)(x, y)


def _qconv_fwd_impl(x, w, strides, paddings, dilations, pre):
    from .ops import pallas_conv
    # conv activations scale per-tensor: the MXU contraction mixes every
    # input channel and tap, so only a scalar scale factors out of the
    # int32 accumulator
    x32 = x.astype(jnp.float32)
    sx = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-12) / _QMAX["int8"]
    xq = jnp.clip(jnp.round(x32 / sx), -127, 127).astype(jnp.int8)
    if pre is None:
        wq, sw = quantize_channelwise(w, axis=0, mode="int8")  # per-Co
    else:
        wq, sw = jnp.asarray(pre[0]), jnp.asarray(pre[1])
    dq = (sx * sw.reshape(-1)).astype(jnp.float32)             # [Co]
    return pallas_conv.conv2d_q8(xq, wq, strides, paddings, dilations,
                                 dq, out_dtype=x.dtype)


def _make_qconv(strides, paddings, dilations, pre):
    @jax.custom_vjp
    def qconv(x, w):
        return _qconv_fwd_impl(x, w, strides, paddings, dilations, pre)

    def fwd(x, w):
        return qconv(x, w), (x, w)

    def bwd(res, g):
        # straight-through via the bf16 reference conv's vjp. The
        # explicit conv2d_grad lowering does the same without meeting
        # this rule; it exists for direct jax.grad through the lowering
        # (preflight probes, fused windows).
        x, w = res
        s, p, d = strides, paddings, dilations

        def ref(xv, wv):
            return lax.conv_general_dilated(
                xv, jnp.transpose(wv, (2, 3, 1, 0)),
                window_strides=s, padding=[(p[0], p[0]), (p[1], p[1])],
                rhs_dilation=d,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        _, vjp = jax.vjp(ref, x, w)
        gx, gw = vjp(g.astype(x.dtype))
        return gx.astype(x.dtype), gw.astype(w.dtype)

    qconv.defvjp(fwd, bwd)
    return qconv


def qconv2d(x, w, strides, paddings, dilations, mode: str = "int8",
            pre=None):
    """Quantized NHWC conv (x [N,H,W,Ci], w [Co,Ci,KH,KW]) through the
    Pallas int8 kernel: per-tensor activation scale, per-Co weight
    scales, int32 VMEM accumulation, dequantized on the output row while
    it is still in VMEM. Caller must have passed ineligible_conv."""
    del mode  # the conv kernel is int8-only (gate returns "mode" on fp8)
    return _make_qconv(tuple(strides), tuple(paddings), tuple(dilations),
                       pre)(x, w)


# --- serving admission --------------------------------------------------

# weight slot per quantizable op type: the persistable operand the
# engine pre-quantizes (activations are per-call by definition)
_WEIGHT_SLOTS = {"mul": "Y", "matmul": "Y", "conv2d": "Filter",
                 "depthwise_conv2d": "Filter"}


def prequantized(ctx, name: str):
    """The admission-time (q, scale) for weight var `name`, or None —
    read by the matmul/conv lowerings during the serving trace."""
    cache = getattr(ctx.program, "_quant_weights", None)
    return cache.get(name) if cache else None


def prequantize(program, scope, mode: str = "int8") -> dict:
    """Quantize every eligible persistable weight of `program` once,
    host-side, and stash the (q, scale) pairs on the program for the
    serving trace to bake into the AOT bucket executables as constants.

    Per-weight parity gate: the measured relative RMS dequantization
    error must stay within QUANT_TOL, or the weight is left dynamic
    (counted as quant_fallback_total{op,reason="error_bound"}). Returns
    {"quantized": [names], "skipped": {name: reason}} for the engine's
    admission report."""
    cache = {}
    skipped = {}
    block = program.global_block()
    for op_ in block.ops:
        slot = _WEIGHT_SLOTS.get(op_.type)
        if slot is None:
            continue
        names = op_.desc.inputs.get(slot, [])
        if not names:
            continue
        name = names[0]
        if name in cache or name in skipped:
            continue
        if op_.type == "matmul" and op_.attr("transpose_Y", False):
            skipped[name] = "shape"   # cache stores [K, N] orientation
            continue
        var = block.desc.vars.get(name)
        if var is None or not var.persistable:
            continue
        w = scope.find_var(name)
        if w is None:
            skipped[name] = "shape"
            continue
        w = np.asarray(w)
        if w.dtype not in (np.float32, np.dtype(jnp.bfloat16)):
            skipped[name] = "dtype"
            count_fallback(op_.type, "dtype")
            continue
        axis = 0 if slot == "Filter" else -1
        use_mode = "int8" if slot == "Filter" else mode
        q, scale, err = weight_qparams(w, axis, use_mode)
        if err > QUANT_TOL:
            skipped[name] = "error_bound"
            count_fallback(op_.type, "error_bound")
            continue
        cache[name] = (q, scale)
    program._quant_weights = cache
    return {"quantized": sorted(cache), "skipped": skipped}
