"""Roofline performance attribution: per-op FLOPs/bytes, achieved TF/s,
and compute-/memory-bound verdicts (ISSUE 6 tentpole).

Joins three sources into one per-op table:

1. **Analytic cost model** — per-IR-op FLOPs and HBM bytes derived from
   concrete shapes/dtypes. The ProgramDesc's VarDesc shapes carry -1
   batch dims, so `program_cost` re-traces the executor's step fn under
   `jax.eval_shape` with an op observer installed
   (executor._op_observers): every op's lowering reports its actual
   input/output avals, and `_op_cost` maps (op_type, shapes) -> (flops,
   bytes). Cross-checked against XLA's own `compiled.cost_analysis()`.

2. **Measured device time and executed work** — the trace's `XLA Ops`
   events joined, instruction by instruction, to the account the
   executor keeps of each compiled block (`xplane.step_account`: op
   instance, executed FLOPs and bytes, the floor `max(flops / peak,
   bytes / hbm)`), folded by program op instance; device time no account
   names pools under "(unattributed)" so fractions sum to the true device
   total. The analytic model of (1) stays beside it as the REQUIRED
   FLOPs: what the executed ones exceed it by is recomputation, and for
   an op that runs on Pallas kernels (kda_scan, the attention ops,
   moe_experts, ssd_scan, causal_conv1d, each with its gradient) the work
   the implementation adds. There the executed side is what each Mosaic
   call declares of itself (`Instr.declared_*`, ops/kernel_cost.py: the
   work as implemented) plus the account's count of the op's XLA
   instructions; the report's kernels table sets each kernel's declared
   floor beside its device time. executed / required says how much work
   an implementation adds, declared floor / time how fast it does it.
   `xplane.timeline_dir` (XLine.timestamp_ns + XEvent.offset_ps)
   supplies the step-time waterfall: device compute vs infeed vs
   collectives vs host gap, plus the device duty cycle.

3. **Two-point measured roofline** — a sustained-matmul TF/s probe and
   an HBM-bandwidth probe (both cached per process; env-overridable via
   PADDLE_TPU_SUSTAINED_TFLOPS / PADDLE_TPU_HBM_GBPS for hermetic CI).
   Their ratio is the ridge intensity (flops/byte): ops whose arithmetic
   intensity sits right of the ridge are compute-bound, left of it
   memory-bound, and ops with no cost info are "unattributed".

The report also publishes continuous `mfu_nominal`, `mfu_vs_sustained`
and `device_duty_cycle` gauges through telemetry.py. Consumers:
`profiler.stop_profiler` (printed table) and `python -m paddle_tpu perf`
(CLI) via `capture()`.
"""

from __future__ import annotations

import os
import re
import shutil
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["program_cost", "op_cost", "matmul_probe",
           "hbm_probe", "ici_probe", "ensure_probes", "ensure_ici",
           "nominal_tflops", "collect_report", "format_report", "capture",
           "waterfall", "UNATTRIBUTED"]

UNATTRIBUTED = "(unattributed)"


# --- analytic per-op cost model ---------------------------------------------

def _nelems(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _aval_list(slot_dict) -> List[Tuple[tuple, Any]]:
    """{slot: [tracer|None]} -> [(shape, dtype), ...] skipping Nones and
    valueless entries."""
    out = []
    for vals in (slot_dict or {}).values():
        for v in vals:
            shape = getattr(v, "shape", None)
            dtype = getattr(v, "dtype", None)
            if shape is not None and dtype is not None:
                out.append((tuple(shape), dtype))
    return out


def _slot_shape(slot_dict, slot) -> Optional[tuple]:
    for v in (slot_dict or {}).get(slot, []):
        shape = getattr(v, "shape", None)
        if shape is not None:
            return tuple(shape)
    return None


def _suffix_shape(slot_dict, suffix) -> Optional[tuple]:
    """First concrete shape whose slot is `suffix` or ends in `:suffix` —
    fused window ops prefix member slots as "<idx>:<slot>"."""
    for slot in (slot_dict or {}):
        if slot == suffix or slot.endswith(":" + suffix):
            s = _slot_shape(slot_dict, slot)
            if s is not None:
                return s
    return None


def _suffix_attr(attrs, suffix, default=None):
    for k, v in (attrs or {}).items():
        if k == suffix or k.endswith(":" + suffix):
            return v
    return default


def _bytes_of(avals) -> int:
    total = 0
    for shape, dtype in avals:
        total += _nelems(shape) * np.dtype(dtype).itemsize
    return total


# Multipliers: a backward op roughly doubles the forward work (dX and dW
# are each one forward-shaped contraction for matmul/conv families).
_GRAD_FACTOR = 2.0

# flops per element for the "roughly k ops per element" families; the
# model is deliberately coarse (roofline verdicts need the right order of
# magnitude and the matmul/conv terms dominate any real step).
_ELEMWISE_COST = {
    "softmax": 5.0, "log_softmax": 5.0, "batch_norm": 5.0,
    "layer_norm": 5.0, "group_norm": 5.0, "sigmoid": 4.0, "tanh": 4.0,
    "exp": 2.0, "gelu": 8.0, "swish": 5.0, "dropout": 2.0,
    "cross_entropy": 4.0, "softmax_with_cross_entropy": 8.0,
    "rms_norm": 5.0, "relu2": 2.0, "silu": 5.0,
    # two multiply-adds a rotated element and its sine or cosine
    "rotary_embedding": 6.0,
}


def _scan_flops(ins, outs, attrs):
    """ssd_scan: the chunked form's four products per token: scores C B^T
    per group and their masked product with x (chunk wide each), the chunk
    states and the entering state's read-out (H P N each). The useful
    work, whichever of hybrid_ops.ssd_scan_chunked and the kernels of
    ops/pallas_scan.py runs it: the kernels issue a head narrower than
    128 lanes 128 wide behind a lane mask and their gradient computes the
    forward's products again, which a trace's time holds and this count
    does not."""
    x, b = _slot_shape(ins, "X"), _slot_shape(ins, "B")
    if x is None or b is None:
        return None
    chunk = int(attrs.get("chunk_size", 128))
    tokens, hp = _nelems(x[:2]), _nelems(x[2:])
    return 2.0 * tokens * (chunk * _nelems(b[2:]) + chunk * hp
                           + 2 * hp * b[3])


def _kda_flops(ins, outs, attrs):
    """kda_scan: the recurrence's three products of a head's [K, V] state
    a token (k^T S, the rank-one correction, q^T S). The useful work,
    which no chunk length moves: the chunked form's within-chunk products
    and its triangular inverse are how hybrid_ops.kda_chunked reaches it,
    which a trace's time holds and this count does not."""
    k, v = _slot_shape(ins, "K"), _slot_shape(ins, "V")
    if k is None or v is None:
        return None
    return 3 * 2.0 * _nelems(k) * v[3]


def _experts_flops(ins, outs, attrs):
    """moe_experts: the up and down products (and the gate product of
    gated experts, given as WGate) over the EXPECTED rows routed to the
    held experts, top_k x held / experts of a token's. Around them the op
    moves rows and multiplies nothing: the handled pairs' rows of X
    gathered into sorted order and N x top_k rows of the down product
    gathered back to their tokens (the same two gathers in the gradient;
    no [N, D] is filled and added into since PR 38). Those rows are
    temporaries, so op_cost's bytes (X, the matrices and Out once) leave
    them out, as they leave out every op's."""
    x, w1 = _slot_shape(ins, "X"), _slot_shape(ins, "W1")
    if x is None or w1 is None:
        return None
    held = int(attrs.get("experts_held", w1[0]))
    share = held / float(attrs.get("num_experts", held))
    products = 3 if _slot_shape(ins, "WGate") is not None else 2
    return (2.0 * products * x[0] * int(attrs.get("top_k", 1)) * share
            * w1[1] * w1[2])


def _router_flops(ins, outs, attrs):
    x, w = _slot_shape(ins, "X"), _slot_shape(ins, "W")
    return None if x is None or w is None else 2.0 * x[0] * _nelems(w)


def _conv1d_flops(ins, outs, attrs):
    x, w = _slot_shape(ins, "X"), _slot_shape(ins, "Filter")
    return None if x is None or w is None else (2.0 * w[1] + 4) * _nelems(x)


def _sdpa_flops(ins, outs, attrs):
    """scaled_dot_product_attention over Q [B, T, H, D] and K [B, Tk, H_kv,
    D]: the two products, scores and weighted sum, over the (query, key)
    pairs the mask keeps (`causal`, and under it a `window` of keys), 4 D
    a pair and query head. The useful work, whichever of the einsum and
    the flash kernels of ops/pallas_attention.py runs it: masked pairs a
    tile computes all the same, lanes of padding and the backward's
    recomputed scores are time and not this count. A replayed op that was
    handed its first run's outputs (registry.handed_on) requires none."""
    q, k = _slot_shape(ins, "Q"), _slot_shape(ins, "K")
    if q is None or k is None or len(q) != 4 or len(k) != 4:
        return None
    if _slot_shape(ins, "KeptOut") is not None:
        return 0.0      # replayed and handed Out and LSE: it runs nothing
    tq, tk = q[1], k[1]
    pairs = tq * tk
    if attrs.get("causal", False):
        window = int(attrs.get("window", 0) or 0)
        seen = np.minimum(np.arange(tq, dtype=np.int64) + 1 + (tk - tq), tk)
        if window:
            seen = np.minimum(seen, window)
        pairs = int(np.maximum(seen, 0).sum())
    return 4.0 * q[0] * q[2] * q[3] * pairs


def _bd_attention_flops(ins, outs, attrs):
    """block_diffusion_attention over Q [2 B, L, H, D], the noised
    streams then the clean ones: a clean query sees the clean keys of
    its block and of every block before it, a noised one its own noised
    block and the clean blocks strictly before, so each of the two sees
    (q // block + 1) x block keys; 4 D a pair and head, as `_sdpa_flops`,
    and nothing where a replay was handed the outputs."""
    q = _slot_shape(ins, "Q")
    if q is None or len(q) != 4:
        return None
    if _slot_shape(ins, "KeptOut") is not None:
        return 0.0
    block = int(attrs.get("block_length", 1) or 1)
    pairs = int(((np.arange(q[1], dtype=np.int64) // block + 1)
                 * block).sum())
    return 4.0 * q[0] * q[2] * q[3] * pairs


def _hc_maps_flops(ins, outs, attrs):
    """hyper_connection_maps: the projection of a token's n C numbers
    onto the 2 n + n^2 maps, and the norm's sum of squares."""
    x, phi = _slot_shape(ins, "X"), _slot_shape(ins, "Phi")
    if x is None or phi is None:
        return None
    return 2.0 * _nelems(x) * (phi[1] + 1)


def _hc_mix_flops(ins, outs, attrs):
    """hc_pre_mix: n multiply-adds a channel of x_in; hc_post_res_mix:
    n + 1 a channel of each of the n rows of X'."""
    x = _slot_shape(ins, "X")
    if x is None:
        return None
    post = _slot_shape(ins, "Post") is not None
    return 2.0 * _nelems(x) * (x[-2] + 1 if post else 1)


# ops/hybrid_ops.py and ops/hyper_connection_ops.py: forward flops from
# the op's concrete shapes
_HYBRID_COST = {"hyper_connection_maps": _hc_maps_flops,
                "hc_pre_mix": _hc_mix_flops,
                "hc_post_res_mix": _hc_mix_flops,
                "ssd_scan": _scan_flops, "moe_experts": _experts_flops,
                "moe_router": _router_flops, "causal_conv1d": _conv1d_flops,
                "kda_scan": _kda_flops,
                "scaled_dot_product_attention": _sdpa_flops,
                "block_diffusion_attention": _bd_attention_flops}

# flops per parameter element for the bucketed fused optimizer applies
# (ops/fusion.py): sgd = mul+sub; momentum adds the velocity update;
# adam adds two moment EMAs, the bias-corrected lr and the rsqrt-divide.
_FUSED_OPT_COST = {"fused_sgd": 2.0, "fused_momentum": 5.0,
                   "fused_adam": 12.0}


def _fused_cost(op_type: str, ins, outs, attrs) -> Tuple[float, float]:
    """Cost of a fused window/bucket op (ops/fusion.py). Window ops carry
    member slots prefixed "<idx>:<slot>" and member attrs prefixed
    "<idx>:<attr>"; optimizer buckets use natural multi-value slots."""
    in_avals = _aval_list(ins)
    out_avals = _aval_list(outs)
    bytes_ = float(_bytes_of(in_avals) + _bytes_of(out_avals))
    out_elems = sum(_nelems(s) for s, _ in out_avals)
    in_elems = sum(_nelems(s) for s, _ in in_avals)
    if op_type == "fused_conv_bn_act":
        filt = _suffix_shape(ins, "Filter")
        y = _suffix_shape(outs, "Y") or _suffix_shape(outs, "Output")
        if filt is not None and y is not None:
            flops = (2.0 * _nelems(y) * filt[1] * filt[-2] * filt[-1]
                     + 10.0 * _nelems(y))     # bn stats + normalize + act
        else:
            flops = float(out_elems)
    elif op_type == "fused_bn_act":
        y = _suffix_shape(outs, "Y")
        flops = 6.0 * float(_nelems(y) if y is not None
                            else max(in_elems, out_elems))
    elif op_type in _FUSED_OPT_COST:
        p_elems = sum(_nelems(getattr(v, "shape", ()))
                      for v in (ins or {}).get("Param", [])
                      if getattr(v, "shape", None) is not None)
        flops = _FUSED_OPT_COST[op_type] * float(p_elems or out_elems)
    else:
        # fused_fc_act (matmul + bias + act) or fused_chain (one-ish flop
        # per produced element; XLA DCEs the unread member outputs)
        x = _suffix_shape(ins, "X")
        out_shape = _suffix_shape(outs, "Out")
        ncol = int(_suffix_attr(attrs, "x_num_col_dims", 1) or 1)
        if op_type == "fused_fc_act" and x is not None \
                and out_shape is not None:
            flops = (2.0 * _nelems(out_shape) * _nelems(x[ncol:])
                     + 2.0 * _nelems(out_shape))
        else:
            flops = float(out_elems)
    return flops, bytes_


def op_cost(op_type: str, ins: Dict[str, list], outs: Dict[str, list],
            attrs=None) -> Tuple[float, float]:
    """(flops, hbm_bytes) for one lowered op given its concrete avals.
    Bytes are the unfused lower bound: every input read once + every
    output written once (XLA fusion only shrinks this, so intensity is a
    floor and the memory-bound verdict conservative)."""
    attrs = attrs or {}
    if op_type.startswith("fused_"):
        return _fused_cost(op_type, ins, outs, attrs)
    in_avals = _aval_list(ins)
    out_avals = _aval_list(outs)
    bytes_ = float(_bytes_of(in_avals) + _bytes_of(out_avals))
    out_elems = sum(_nelems(s) for s, _ in out_avals)
    in_elems = sum(_nelems(s) for s, _ in in_avals)

    grad = op_type.endswith("_grad")
    base = op_type[:-5] if grad else op_type
    flops: float

    if base in ("conv2d", "depthwise_conv2d", "conv2d_transpose"):
        filt = _slot_shape(ins, "Filter")
        out_shape = (_slot_shape(outs, "Output") or _slot_shape(outs, "Out"))
        if grad and out_shape is None:
            # grad op outputs are dX/dW; the conv-shaped tensor is the
            # Output@GRAD input
            out_shape = _slot_shape(ins, "Output@GRAD")
        if filt is not None and out_shape is not None:
            # filter [Cout, Cin/groups, kh, kw]: grouped and depthwise
            # convs already carry the per-group Cin in dim 1
            cin_per_group, kh, kw = filt[1], filt[-2], filt[-1]
            flops = 2.0 * _nelems(out_shape) * cin_per_group * kh * kw
        else:
            flops = float(out_elems)
    elif base in ("mul", "matmul", "matmul_v2", "fc"):
        x = _slot_shape(ins, "X") or _slot_shape(ins, "Input")
        out_shape = _slot_shape(outs, "Out")
        if grad and out_shape is None:
            out_shape = _slot_shape(ins, "Out@GRAD")
        if x is not None and out_shape is not None and len(x) >= 1:
            if base == "mul":
                ncol = int(attrs.get("x_num_col_dims", 1) or 1)
                k = _nelems(x[ncol:])
            else:
                tx = bool(attrs.get("transpose_X",
                                    attrs.get("trans_x", False)))
                k = x[-2] if (tx and len(x) >= 2) else x[-1]
            flops = 2.0 * _nelems(out_shape) * int(k)
        else:
            flops = float(out_elems)
    elif base in _HYBRID_COST:
        flops = _HYBRID_COST[base](ins, outs, attrs)
        if flops is None:
            flops = float(out_elems)
    elif "attention" in base:
        # scores + weighted sum: 2 * (2 * B*H*T^2*D) = 4*T*q_elems
        q = (_slot_shape(ins, "Q") or _slot_shape(ins, "Query")
             or _slot_shape(ins, "X"))
        if q is not None and len(q) >= 2:
            t = q[-2] if len(q) >= 3 else q[0]
            flops = 4.0 * _nelems(q) * int(t)
        else:
            flops = float(out_elems)
    elif base.startswith("reduce_") or base in ("mean", "sum"):
        flops = float(in_elems)
    elif base.startswith("pool"):
        ksize = attrs.get("ksize") or []
        win = _nelems(ksize) if ksize else 1
        flops = float(out_elems * max(win, 1))
    elif base in ("lookup_table", "lookup_table_v2", "embedding", "gather",
                  "reshape", "reshape2", "transpose", "transpose2",
                  "concat", "split", "fill_constant", "assign", "cast",
                  "shape", "slice", "squeeze", "squeeze2", "unsqueeze",
                  "unsqueeze2", "flatten", "flatten2"):
        flops = 0.0     # pure data movement: bytes dominate
    elif base in _ELEMWISE_COST:
        flops = _ELEMWISE_COST[base] * float(max(in_elems, out_elems))
    else:
        # default: one flop per output element (elementwise family)
        flops = float(out_elems)

    if grad:
        flops *= _GRAD_FACTOR
    return flops, bytes_


def _shape_sig(ins, outs):
    """Shape tag for one op instance: the largest output's dims, plus the
    filter kernel dims for convs ("4x56x56x128|k3x3")."""
    best = None
    for vals in (outs or {}).values():
        for v in vals or []:
            shp = getattr(v, "shape", None)
            if shp is not None and (
                    best is None or np.prod(shp) > np.prod(best)):
                best = tuple(int(x) for x in shp)
    if best is None:
        return None
    sig = "x".join(str(x) for x in best)
    for v in (ins or {}).get("Filter", []) or []:
        shp = getattr(v, "shape", None)
        if shp is not None and len(shp) >= 2:
            sig += "|k" + "x".join(str(int(x)) for x in shp[-2:])
            break
    return sig


def program_cost(executor, program, feed_avals: Dict[str, Any],
                 state_avals: Dict[str, Any]) -> Dict[str, Any]:
    """Analytic per-op-type cost table for ONE step of `program`:
    {"ops": {op_type: {"flops","bytes","count"}}, "total_flops",
    "total_bytes"}. Traces the executor's step fn under jax.eval_shape —
    abstract, nothing executes, but every op observer callback sees the
    concrete shapes the ProgramDesc cannot provide (-1 batch dims)."""
    import jax
    from . import executor as executor_mod
    from . import quant

    table: Dict[str, Dict[str, float]] = {}
    qmode = getattr(program, "_quant_mode", None)

    def _peak_factor(op, ins, attrs):
        """2.0 when this instance routes through the int8/fp8 path (the
        MXU's int8 peak is 2x its bf16 peak, so the compute roofline
        doubles), else 1.0. Replays the lowering gate on the observed
        avals; convs are probed in both layout interpretations because
        the observer cannot see the trace-time layout tags — a shape
        that gates in under either is counted quantized. Best-effort by
        design: any gate error reads as the conservative 1.0."""
        if not qmode or op.type not in quant.QUANT_OPS:
            return 1.0
        try:
            if quant.gate_for_op(op.type, ins, attrs, qmode,
                                 nhwc=True) is None:
                return 2.0
            if op.type in ("conv2d", "depthwise_conv2d") and \
                    quant.gate_for_op(op.type, ins, attrs, qmode,
                                      nhwc=False) is None:
                return 2.0
        except Exception:  # noqa: BLE001
            pass
        return 1.0

    def observe(op, ins, outs):
        try:
            attrs = dict(getattr(op.desc, "attrs", {}) or {})
        except Exception:  # noqa: BLE001
            attrs = {}
        flops, bytes_ = op_cost(op.type, ins, outs, attrs)
        acc = table.setdefault(op.type,
                               {"flops": 0.0, "bytes": 0.0, "count": 0,
                                "max_flops": 0.0, "shape": None,
                                "peak_factor": None})
        factor = _peak_factor(op, ins, attrs)
        acc["peak_factor"] = factor if acc["peak_factor"] is None \
            else min(acc["peak_factor"], factor)
        acc["flops"] += flops
        acc["bytes"] += bytes_
        acc["count"] += 1
        if flops >= acc["max_flops"]:
            # the kernel_efficiency scoreboard tags each op type with its
            # heaviest instance's shape, so the table names a workload
            acc["max_flops"] = flops
            acc["shape"] = _shape_sig(ins, outs)

    persist_out = executor._persistable_outputs(program)
    fn = executor._make_step_fn(program, [], persist_out, {})
    rng_aval = jax.ShapeDtypeStruct((), np.uint32)
    executor_mod._op_observers.append(observe)
    try:
        jax.eval_shape(fn, dict(feed_avals), dict(state_avals), rng_aval)
    finally:
        executor_mod._op_observers.remove(observe)
    return {"ops": table,
            "total_flops": sum(d["flops"] for d in table.values()),
            "total_bytes": sum(d["bytes"] for d in table.values())}


# --- two-point measured roofline --------------------------------------------

_PROBES: Dict[str, float] = {}


def _platform() -> str:
    import jax
    try:
        return jax.devices()[0].platform
    except Exception:  # noqa: BLE001
        return "cpu"


def matmul_probe(n: Optional[int] = None, iters: Optional[int] = None,
                 repeats: int = 3) -> float:
    """Sustained matmul TF/s: a jitted lax.scan chain of data-dependent
    [n,n] matmuls (nothing elidable), best of `repeats`, scalar readback
    as the fence. Sized down automatically on CPU so tier-1 CI stays
    fast."""
    import jax
    import jax.numpy as jnp

    tpu = _platform() == "tpu"
    n = n or (4096 if tpu else 256)
    iters = iters or (32 if tpu else 4)
    dtype = jnp.bfloat16 if tpu else jnp.float32

    a = jnp.asarray(np.random.default_rng(0).standard_normal((n, n)) * 0.01,
                    dtype)

    @jax.jit
    def chain(x):
        def body(c, _):
            return jnp.matmul(c, x), None
        c, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.float32(c[0, 0])

    float(chain(a))            # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(chain(a))        # scalar readback fences the whole chain
        best = min(best, time.perf_counter() - t0)
    return (2.0 * n ** 3 * iters) / best / 1e12


def hbm_probe(mbytes: Optional[int] = None, iters: Optional[int] = None,
              repeats: int = 3) -> float:
    """Sustained HBM bandwidth in GB/s: a jitted lax.scan of
    `c = c * s + x` over a large array — each iteration reads x, reads c,
    writes c (3x the array's bytes of traffic; XLA aliases c in place)."""
    import jax
    import jax.numpy as jnp

    tpu = _platform() == "tpu"
    mb = mbytes or (256 if tpu else 16)
    iters = iters or (16 if tpu else 4)
    elems = mb * (1 << 20) // 4
    x = jnp.ones((elems,), jnp.float32)

    @jax.jit
    def sweep(x):
        def body(c, _):
            return c * jnp.float32(0.999) + x, None
        c, _ = jax.lax.scan(body, x, None, length=iters)
        return jnp.float32(c[0])

    float(sweep(x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(sweep(x))
        best = min(best, time.perf_counter() - t0)
    return (3.0 * elems * 4 * iters) / best / 1e9


def ici_probe(mbytes: Optional[int] = None, repeats: int = 3) \
        -> Optional[float]:
    """Sustained interconnect bus bandwidth in GB/s: a jitted all-reduce
    (psum) of a large array over every local device, timed end to end and
    converted with the nccl-tests 2(n-1)/n bus-bandwidth factor. On a TPU
    slice this measures ICI; on the CPU backend with forced host devices
    it measures the memcpy fabric — either way it is the link roofline
    per-collective busbw is judged against. None with < 2 devices."""
    import jax
    import jax.numpy as jnp

    n = jax.device_count()
    if n < 2:
        return None
    tpu = _platform() == "tpu"
    mb = mbytes or (64 if tpu else 8)
    elems = mb * (1 << 20) // 4
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("probe",))
    spec = jax.sharding.PartitionSpec("probe")

    @jax.jit
    def ar(x):
        y = jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(mesh, spec))
        # reduce ONLY the sharded axis: the [elems] result is replicated,
        # forcing an all-reduce of the full payload (a scalar-producing
        # y.sum() would let XLA all-reduce just partial scalars)
        return y.sum(0)

    x = jnp.ones((n, elems), jnp.float32)
    ar(x).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        ar(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    nbytes = elems * 4    # the all-reduced buffer
    return nbytes * 2.0 * (n - 1) / n / best / 1e9


def ensure_ici(probe: bool = True) -> Optional[float]:
    """Cached ICI/DCN bus bandwidth in GB/s, PADDLE_TPU_ICI_GBPS override
    first (mirrors ensure_probes). Separate from ensure_probes so the
    existing matmul/HBM callers don't pay an all-reduce probe."""
    if "ici_gbps" not in _PROBES:
        env = os.environ.get("PADDLE_TPU_ICI_GBPS")
        if env:
            _PROBES["ici_gbps"] = float(env)
        elif probe:
            try:
                _PROBES["ici_gbps"] = ici_probe()
            except Exception:  # noqa: BLE001 - probe is advisory
                _PROBES["ici_gbps"] = None
        else:
            return None
    return _PROBES.get("ici_gbps")


def ensure_probes(probe: bool = True) -> Dict[str, Optional[float]]:
    """{"sustained_tflops","hbm_gbps","ridge"} — measured once per process
    and cached; PADDLE_TPU_SUSTAINED_TFLOPS / PADDLE_TPU_HBM_GBPS env
    overrides skip the measurement entirely (hermetic CI, or reusing the
    numbers a previous run measured on the same host)."""
    if "sustained_tflops" not in _PROBES:
        env = os.environ.get("PADDLE_TPU_SUSTAINED_TFLOPS")
        if env:
            _PROBES["sustained_tflops"] = float(env)
        elif probe:
            try:
                _PROBES["sustained_tflops"] = matmul_probe()
            except Exception:  # noqa: BLE001 - probe is advisory
                _PROBES["sustained_tflops"] = None
    if "hbm_gbps" not in _PROBES:
        env = os.environ.get("PADDLE_TPU_HBM_GBPS")
        if env:
            _PROBES["hbm_gbps"] = float(env)
        elif probe:
            try:
                _PROBES["hbm_gbps"] = hbm_probe()
            except Exception:  # noqa: BLE001
                _PROBES["hbm_gbps"] = None
    tf = _PROBES.get("sustained_tflops")
    bw = _PROBES.get("hbm_gbps")
    ridge = (tf * 1e12) / (bw * 1e9) if tf and bw else None
    return {"sustained_tflops": tf, "hbm_gbps": bw, "ridge": ridge}


def nominal_tflops() -> Optional[float]:
    """Datasheet bf16 peak for mfu_nominal, from the one table keyed by
    device_kind (chip.PEAKS; an accelerator that is not in it raises);
    None on CPU (no meaningful nominal — mfu_vs_sustained is the honest
    number there)."""
    from . import chip
    row = chip.peaks()
    return row.bf16_tflops if row else None


# --- waterfall / timeline ---------------------------------------------------

_COLLECTIVE_PAT = ("all-reduce", "allreduce", "all-gather", "allgather",
                   "reduce-scatter", "reducescatter", "collective",
                   "all-to-all", "alltoall", "permute", "send", "recv")
_INFEED_PAT = ("infeed", "outfeed", "copy", "transfer", "memcpy", "h2d",
               "d2h", "host-to-device", "device-to-host", "dynamic-update")


def _bucket(event_name: str) -> str:
    low = event_name.lower()
    if any(p in low for p in _COLLECTIVE_PAT):
        return "collective"
    if any(p in low for p in _INFEED_PAT):
        return "infeed"
    return "compute"


def waterfall(trace_dir) -> Optional[Dict[str, Any]]:
    """Step-time waterfall from the xplane timeline: per device plane,
    pick the busiest XLine (the raw XLA-op line; derived step/module
    lines duplicate it), bucket its events into compute / infeed /
    collectives, and call everything between first-event-start and
    last-event-end that no event covers the host gap. Sums across device
    planes (per-core time adds up); falls back to host planes on
    CPU-backend traces."""
    from . import xplane

    records = xplane.timeline_dir(trace_dir)
    if not records:
        return None
    by_plane: Dict[str, list] = {}
    for r in records:
        by_plane.setdefault(r["plane"], []).append(r)
    planes = {p: rs for p, rs in by_plane.items()
              if p.startswith("/device:")}
    if not planes:
        # host-plane fallback (CPU backend): keep only instruction-like
        # events so the busiest-line pick lands on the XLA execution
        # thread, not the python line whose events span the whole session
        planes = {}
        for p, rs in by_plane.items():
            filtered = []
            for line in rs:
                evs = [e for e in line["events"]
                       if xplane.instr_like(e[0])]
                if evs:
                    filtered.append({**line, "events": evs})
            if filtered:
                planes[p] = filtered
        if not planes:
            return None
    out = {"compute_ps": 0, "infeed_ps": 0, "collective_ps": 0,
           "collective_exposed_ps": 0, "host_gap_ps": 0, "span_ps": 0,
           "planes": len(planes)}
    for _, lines in planes.items():
        best = None
        best_busy = -1
        for line in lines:
            busy = sum(d for _, _, d in line["events"])
            if busy > best_busy:
                best_busy, best = busy, line
        if not best or not best["events"]:
            continue
        start = min(off for _, off, _ in best["events"])
        end = max(off + d for _, off, d in best["events"])
        span = max(end - start, best_busy)
        for name, _, dur in best["events"]:
            out[_bucket(name) + "_ps"] += dur
        # exposed = collective time hidden under NO concurrent compute;
        # refines the single collectives bucket into hidden vs blocking
        out["collective_exposed_ps"] += sum(
            xplane.exposed_in_line(best["events"]).values())
        out["span_ps"] += span
        out["host_gap_ps"] += max(span - best_busy, 0)
    if not out["span_ps"]:
        return None
    out["device_duty_cycle"] = min(
        (out["compute_ps"] + out["infeed_ps"] + out["collective_ps"])
        / out["span_ps"], 1.0)
    return out


# --- the joined report ------------------------------------------------------

def _required_costs(infos, notes):
    """The analytic per-op-type table summed over the compiled blocks a
    trace ran (their `cost` suppliers, executor._cost_supplier)."""
    cost: Dict[str, Dict[str, float]] = {}
    total_flops = total_bytes = 0.0
    have = False
    for info in infos:
        cost_fn = info.get("cost")
        if cost_fn is None:
            continue
        try:
            t = cost_fn()
        except Exception as e:  # noqa: BLE001
            notes.append(f"cost model unavailable: {type(e).__name__}: {e}")
            continue
        for op_type, d in t["ops"].items():
            acc = cost.setdefault(
                op_type, {"flops": 0.0, "bytes": 0.0, "max_flops": 0.0,
                          "shape": None, "peak_factor": None})
            pf = d.get("peak_factor")
            if pf is not None:
                acc["peak_factor"] = pf if acc["peak_factor"] is None \
                    else min(acc["peak_factor"], pf)
            acc["flops"] += d["flops"]
            acc["bytes"] += d["bytes"]
            if d.get("max_flops", 0.0) >= acc["max_flops"]:
                acc["max_flops"] = d.get("max_flops", 0.0)
                acc["shape"] = d.get("shape")
        total_flops += t["total_flops"]
        total_bytes += t["total_bytes"]
        have = True
    return cost, total_flops, total_bytes, have


def _build_table(snapshot) -> Dict[str, Dict[str, Any]]:
    """{program: {"phases": {phase: seconds}, "cache": {"hit", "miss"}}}
    of the blocks this process built: the first run's seconds by phase
    (executor_build_seconds_total) and, beside them, how many executables
    jax's persistent cache loaded and how many it had to compile
    (executor_compile_cache_total): a `compile` phase of a hit is a load.
    Empty read away from the process that ran the program."""
    out: Dict[str, Dict[str, Any]] = {}
    for name, field, key in (("executor_build_seconds_total", "phases",
                              "phase"),
                             ("executor_compile_cache_total", "cache",
                              "result")):
        for labels, value in (snapshot["counters"].get(name) or {}).items():
            said = dict(pair.split("=", 1) for pair in labels.split(","))
            row = out.setdefault(said.get("program", "-"),
                                 {"phases": {}, "cache": {}})
            row[field][said.get(key, "-")] = value
    return out


def _kernel_tables(steps, cost):
    """(kernels, kernel-backed ops) of the main module's traced `steps`,
    each a step and chip (the mean over them).

    kernels: one row a Mosaic kernel that declared its work, by name
    (`heavy`): `calls`, `ms`, `floor_ms` (the declared floor: max(declared
    FLOPs / peak, declared bytes / HBM bandwidth) a call, summed),
    `share` = floor over time, `bound`, `declared_by` ("kernel": the
    package's own, the work as implemented; "jax": megablox gmm / tgmm,
    every row of the buffer, an upper bound), `gflop`, `mb`.

    kernel-backed ops: one row a program op type some declared kernel
    runs under (`kda_scan`, `kda_scan_grad`, ...): `ms` under the op,
    `executed_flops` = its Mosaic calls' declared FLOPs (`declared_flops`)
    + the account's FLOPs of its XLA instructions, `required_flops` from
    the analytic table (`op_cost`; None read away from the process that
    traced unless it left the table beside the trace), `added` = executed
    over required: the work the implementation adds."""
    if not steps:
        return [], []
    n = float(len(steps))
    kernels: Dict[str, Dict[str, Any]] = {}
    ops: Dict[str, Dict[str, Any]] = {}
    backed = {r["label"] for st in steps for r in st["rows"]
              if r["joined"] and r.get("declared_by")}
    for st in steps:
        for r in st["rows"]:
            if not r["joined"]:
                continue
            declared = r.get("declared_by")
            if declared:
                k = kernels.setdefault(r["heavy"], {
                    "kernel": r["heavy"], "calls": 0.0, "ms": 0.0,
                    "floor_ms": None, "gflop": 0.0, "mb": 0.0,
                    "by_flops": 0.0, "declared_by": declared})
                k["calls"] += r["count"] / n
                k["ms"] += r["ms"] / n
                k["gflop"] += (r["declared_flops"] or 0.0) * r["count"] / n / 1e9
                k["mb"] += (r["declared_bytes"] or 0) * r["count"] / n / 1e6
                if r.get("kernel_floor_ms") is not None:
                    k["floor_ms"] = (k["floor_ms"] or 0.0) \
                        + r["kernel_floor_ms"] / n
                    if r["kernel_bound"] == "flops":
                        k["by_flops"] += r["kernel_floor_ms"] / n
            if r["label"] in backed:
                o = ops.setdefault(r["label"], {
                    "op": r["label"], "ms": 0.0, "declared_flops": 0.0,
                    "xla_flops": 0.0})
                o["ms"] += r["ms"] / n
                o["declared_flops" if declared else "xla_flops"] += (
                    (r["declared_flops"] if declared else r["flops"])
                    or 0.0) * r["count"] / n
    for k in kernels.values():
        floor, by_flops = k["floor_ms"], k.pop("by_flops")
        k["share"] = floor / k["ms"] if floor is not None and k["ms"] else None
        # the kernel's bound is the one most of its floor is made of
        k["bound"] = None if floor is None else \
            "flops" if 2 * by_flops >= floor else "bytes"
    for o in ops.values():
        o["executed_flops"] = o["declared_flops"] + o["xla_flops"]
        required = (cost.get(o["op"]) or {}).get("flops")
        o["required_flops"] = required
        o["added"] = o["executed_flops"] / required if required else None
    return tuple(sorted(table.values(), key=lambda d: -d["ms"])
                 for table in (kernels, ops))


def collect_report(trace_dir, steps: Optional[int] = None,
                   probe: bool = True, accounts=None
                   ) -> Optional[Dict[str, Any]]:
    """Join measured device time, the executed work of each instruction
    and the two-point roofline into one report dict (see format_report
    for the printed form), one row a program op INSTANCE (`op`, `at` =
    its position in the block). `accounts` are (instructions, info) pairs
    as `xplane.known_accounts()` gives them (the default: what the
    executor kept in this process, else what an earlier reader saved
    beside the trace); `info["cost"]` supplies the analytic table, the
    REQUIRED FLOPs beside the executed ones. `steps` is how many executor
    steps ran inside the trace (the required totals scale by it). Never
    raises on a missing piece — each absent source just blanks its
    columns. Nothing is compiled here."""
    from . import telemetry, xplane

    notes: List[str] = []
    pairs = xplane.known_accounts() if accounts is None else list(accounts)
    acct = xplane.step_account(
        trace_dir, accounts=[instrs for instrs, _ in pairs]
        if pairs or accounts is not None else None)
    if acct is None or not acct["steps"]:
        return None
    used = [pairs[i] for i in acct.get("used", ()) if i < len(pairs)]
    cost, total_flops, total_bytes, have_cost = _required_costs(
        [info for _, info in used], notes)
    if have_cost:
        xplane.save_required(trace_dir, cost)
    else:
        # read away from the process that traced: what it left beside the
        # trace (per-op REQUIRED flops; no totals, so no MFU from here)
        cost = xplane.saved_required(trace_dir) or cost
    xla_flops = sum(info.get("xla_flops") or 0.0 for _, info in used)
    executed_flops = sum(i.flops or 0.0 for instrs, _ in used
                         for i in instrs if i.entry)

    probes = ensure_probes(probe)
    ridge = probes["ridge"]
    sustained = probes["sustained_tflops"]
    nominal = nominal_tflops() or sustained
    # the floor's peaks: the chip's published ones where the account knows
    # the chip (xplane.step_account), else the probes'
    peak = acct["peak_flops"] or (sustained * 1e12 if sustained else None)
    hbm = acct["hbm_bytes_per_s"] or (
        probes["hbm_gbps"] * 1e9 if probes["hbm_gbps"] else None)

    agg: Dict[tuple, Dict[str, Any]] = {}
    total_ps = 0.0
    for st in acct["steps"]:
        for r in st["rows"]:
            ps = r["ms"] * 1e9
            total_ps += ps
            key = (r["label"], r["at"]) if r["joined"] or r["op"] \
                else (UNATTRIBUTED, None)
            a = agg.setdefault(key, {
                "ps": 0.0, "work_flops": 0.0, "work_bytes": 0.0,
                "instrs": {}, "joined": False,
                "scope": r["scope"], "role": r["role"],
                "recompute": r.get("recompute")})
            a["ps"] += ps
            if r["joined"]:
                a["joined"] = True
                # a Mosaic call's work is what it declares of itself (its
                # FLOPs as implemented, the bytes its pipeline moves): the
                # account's own count stops at the call
                flops, nbytes = (r["flops"], r["bytes"]) \
                    if r.get("declared_by") is None \
                    else (r["declared_flops"], r["declared_bytes"])
                a["work_flops"] += (flops or 0.0) * r["count"]
                a["work_bytes"] += (nbytes or 0) * r["count"]
                a["instrs"][r["name"]] = (flops, nbytes, r["shape"])
    if not agg:
        return None

    rows = []
    for (name, at), a in sorted(agg.items(), key=lambda kv: -kv[1]["ps"]):
        ps = a["ps"]
        c = cost.get(name)
        flops = bytes_ = tflops = intensity = None
        shape = c.get("shape") if c else None
        if a["joined"]:
            flops = sum(f or 0.0 for f, _, _ in a["instrs"].values())
            bytes_ = float(sum(b or 0 for _, b, _ in a["instrs"].values()))
            if ps:
                tflops = a["work_flops"] / (ps / 1e12) / 1e12
            if bytes_:
                intensity = flops / bytes_
            if shape is None:
                shape = xplane._plain(max(
                    a["instrs"].values(), key=lambda v: v[1] or 0)[2]) \
                    or None
        if name == UNATTRIBUTED or not a["joined"]:
            bound = "unattributed"
        elif intensity is not None and ridge is not None:
            bound = "compute" if intensity >= ridge else "memory"
        elif intensity is not None:
            # no bandwidth probe: fall back to the classic "MXU-shaped or
            # not" split so the verdict column never silently disappears
            bound = "compute" if intensity >= 100 else "memory"
        else:
            bound = "unattributed"
        # per-kernel scoreboard: the least the device could take for the
        # work this op instance executed (the larger of the compute and
        # the bandwidth floor) vs measured — the floor share attributes
        # the remaining MFU gap instance by instance. int8/fp8: an op
        # type whose every instance routes through the quantized path
        # computes against the MXU's doubled peak (peak_factor from
        # program_cost)
        min_ps = efficiency = None
        factor = (c.get("peak_factor") if c else None) or 1.0
        if a["joined"] and ps:
            floors = []
            if a["work_flops"] and peak:
                floors.append(a["work_flops"] / (peak * factor))
            if a["work_bytes"] and hbm:
                floors.append(a["work_bytes"] / hbm)
            if floors:
                min_ps = max(floors) * 1e12
                if min_ps > 0:
                    efficiency = min_ps / ps
        rows.append({"op": name, "at": at, "role": a["role"],
                     "scope": a["scope"], "recompute": a["recompute"],
                     "ps": ps, "frac": ps / total_ps,
                     "flops": flops, "bytes": bytes_, "tflops": tflops,
                     "intensity": intensity, "bound": bound,
                     "shape": shape,
                     "required_flops": c["flops"] if c else None,
                     "peak_factor": factor if c else None,
                     "min_ps": min_ps, "efficiency": efficiency})

    wf = None
    try:
        wf = waterfall(trace_dir)
    except Exception as e:  # noqa: BLE001
        notes.append(f"waterfall unavailable: {type(e).__name__}: {e}")

    colls = None
    try:
        from . import fleet
        colls = fleet.collective_table(trace_dir, steps=steps, probe=probe,
                                       account=acct)
    except Exception as e:  # noqa: BLE001
        notes.append(
            f"collective attribution unavailable: {type(e).__name__}: {e}")

    report: Dict[str, Any] = {
        "trace_dir": str(trace_dir), "steps": steps,
        "device_total_ps": total_ps, "rows": rows,
        "mapped": any(a["joined"] for a in agg.values()),
        "joined": acct["joined"], "waterfall": wf,
        "collectives": colls,
        "device_duty_cycle": (wf or {}).get("device_duty_cycle"),
        "sustained_tflops": sustained, "hbm_gbps": probes["hbm_gbps"],
        "ridge_intensity": ridge, "nominal_tflops": nominal,
        "total_flops_per_step": total_flops if have_cost else None,
        "total_bytes_per_step": total_bytes if have_cost else None,
        # fraction of the analytic flops that ride the int8/fp8 roofline
        # (peak_factor 2.0 on every instance of the op type)
        "quant_flops_fraction": (
            sum(d["flops"] for d in cost.values()
                if (d.get("peak_factor") or 1.0) > 1.0) / total_flops
            if have_cost and total_flops else None),
        # entry instructions that take time on the device, and the
        # fusions among them (≈ device kernels that aren't library calls):
        # the per-step kernel-count proxy the fusion pass is judged by
        "kernel_counts": {
            "modules": len(used),
            "instructions": sum(len(instrs) for instrs, _ in used),
            "fusions": sum(1 for instrs, _ in used for i in instrs
                           if i.opcode == "fusion")} if used else None,
        "mfu_nominal": None, "mfu_vs_sustained": None, "notes": notes,
        "build": _build_table(telemetry.snapshot()),
    }
    report["kernels"], report["kernel_ops"] = _kernel_tables(
        xplane.main_steps(acct), cost)
    report["kernel_efficiency"] = [
        {"op": r["op"], "at": r["at"], "shape": r["shape"],
         "ms": round(r["ps"] / 1e9, 4),
         "min_ms": round(r["min_ps"] / 1e9, 4),
         "efficiency": round(r["efficiency"], 4)}
        for r in rows if r["efficiency"] is not None]
    # input-bound verdict: the waterfall blames the host input path when
    # the device idles more than it computes and infeed+host-gap dominate
    duty = report["device_duty_cycle"]
    report["input_bound"] = None
    if wf and duty is not None:
        report["input_bound"] = bool(
            duty < 0.6
            and wf["infeed_ps"] + wf["host_gap_ps"] > wf["compute_ps"])
        if report["input_bound"]:
            report["input_bound_remedy"] = (
                "step time is input-bound: raise the feeder's "
                "window_prefetch and/or drive the loop through "
                "Executor.run_steps so a window amortizes host dispatch")
    if xla_flops > 0 and (have_cost or executed_flops):
        # required (the analytic model) beside executed (the account) and
        # XLA's own count of the compiled step: executed over required is
        # recomputation, executed against XLA holds the account's
        # arithmetic to the compiler's
        report["cost_crosscheck"] = {
            "analytic_flops": total_flops if have_cost else None,
            "executed_flops": executed_flops, "xla_flops": xla_flops,
            "rel_err": (abs(total_flops - xla_flops) / xla_flops
                        if have_cost else None),
            "executed_rel_err": abs(executed_flops - xla_flops) / xla_flops}
    span_ps = (wf or {}).get("span_ps") or 0
    if have_cost and steps and span_ps:
        achieved = total_flops * steps / (span_ps / 1e12) / 1e12
        report["achieved_tflops"] = achieved
        if nominal:
            report["mfu_nominal"] = achieved / nominal
        if sustained:
            report["mfu_vs_sustained"] = achieved / sustained

    # continuous telemetry: the gauges the MFU campaign watches between
    # traced sessions, plus the per-op counters the table already fed
    for row in rows:
        telemetry.counter(
            "device_op_seconds_total",
            "device time attributed to IR ops across traced sessions",
            labels=("op",)).labels(op=row["op"]).inc(row["ps"] / 1e12)
    for row in rows:
        if row["efficiency"] is not None:
            telemetry.gauge(
                "kernel_efficiency",
                "measured device time vs analytic roofline minimum "
                "(achieved fraction), by op and heaviest shape",
                labels=("op", "shape")).labels(
                op=row["op"], shape=row["shape"] or "?").set(
                row["efficiency"])
    for gname in ("mfu_nominal", "mfu_vs_sustained", "device_duty_cycle"):
        if report.get(gname) is not None:
            telemetry.gauge(
                gname, f"{gname} from the latest roofline report").set(
                    report[gname])
    # per-trace collective wait: fleet.local_snapshot and the goodput
    # ledger read these instead of re-parsing the trace. The fleet table
    # is the better source (it finds collectives on CPU traces' thread
    # lines, which the waterfall's busiest-line pick misses).
    if colls and colls.get("rows"):
        total_ms = sum(r["time_ms"] for r in colls["rows"])
        exposed_ms = sum(r["exposed_ms"] for r in colls["rows"])
    elif wf:
        total_ms = wf["collective_ps"] / 1e9
        exposed_ms = wf["collective_exposed_ps"] / 1e9
    else:
        total_ms = exposed_ms = None
    if total_ms is not None:
        telemetry.gauge(
            "collective_time_seconds",
            "total collective device time in the latest traced session"
        ).set(total_ms / 1e3)
        telemetry.gauge(
            "collective_exposed_seconds",
            "collective time not hidden under compute in the latest "
            "traced session").set(exposed_ms / 1e3)
    return report


def _fmt(v, scale=1.0, prec=2, width=9) -> str:
    if v is None:
        return f"{'-':>{width}s}"
    return f"{v / scale:{width}.{prec}f}"


def format_report(report: Dict[str, Any]) -> List[str]:
    """Render a report dict as the printed device table + waterfall +
    roofline + MFU summary lines (profiler.stop_profiler and the perf
    CLI share this). Row format keeps `[device] <op> ...` so existing
    log scrapers (and tests) still find the op in field 2."""
    lines = [f"{'Device op (jit)':40s} {'Total(ms)':>12s} {'Frac':>8s} "
             f"{'GFLOPs':>9s} {'MB':>9s} {'TF/s':>9s} {'AI':>9s}  "
             f"{'Bound':12s} {'Floor':>6s}  At"]
    for row in report["rows"]:
        floor = ("{:6.1%}".format(row["efficiency"])
                 if row.get("efficiency") is not None else "     -")
        at = "" if row.get("at") is None else f"  @{row['at']}"
        if row.get("recompute") is not None:   # a replayed forward op
            at += f"  replay {row['recompute']}"
        lines.append(
            f"[device] {row['op']:31s} {row['ps'] / 1e9:12.4f} "
            f"{row['frac']:8.1%} {_fmt(row['flops'], 1e9)} "
            f"{_fmt(row['bytes'], 1e6)} {_fmt(row['tflops'])} "
            f"{_fmt(row['intensity'], 1.0, 1)}  {row['bound']:12s} "
            f"{floor}{at}")
    wf = report.get("waterfall")
    if wf:
        span = wf["span_ps"]
        coll_txt = "{:.1%}".format(wf["collective_ps"] / span)
        if wf.get("collective_exposed_ps") is not None \
                and wf["collective_ps"]:
            coll_txt += " ({:.0%} exposed)".format(
                wf["collective_exposed_ps"] / wf["collective_ps"])
        lines.append(
            "[waterfall] compute {:.1%} | infeed {:.1%} | collectives "
            "{} | host gap {:.1%}  (span {:.3f} ms)".format(
                wf["compute_ps"] / span, wf["infeed_ps"] / span,
                coll_txt, wf["host_gap_ps"] / span,
                span / 1e9))
    colls = report.get("collectives")
    if colls and colls.get("rows"):
        lines.append(
            f"{'Collective':20s} {'Call site':22s} {'MB':>9s} "
            f"{'busbw GB/s':>11s} {'% link':>7s} {'Exposed(ms)':>12s}"
            f"  Axis")
        for r in colls["rows"]:
            pct = ("{:6.1%}".format(r["pct_link"])
                   if r.get("pct_link") is not None else "     -")
            lines.append(
                "[coll] {:13s} {:22s} {:9.2f} {:>11s} {} {:12.3f}  {}".format(
                    r["kind"], r["site"], r["bytes"] / 1e6,
                    _fmt(r.get("busbw_gbps"), 1.0, 2, 11).strip().rjust(11),
                    pct, r["exposed_ms"], r.get("axis") or "-"))
        if colls.get("ici_gbps"):
            lines.append(
                "[coll] link roofline {:.1f} GB/s ({} participants)".format(
                    colls["ici_gbps"], colls.get("participants") or "?"))
    if report.get("sustained_tflops") or report.get("hbm_gbps"):
        ridge = report.get("ridge_intensity")
        lines.append(
            "[roofline] sustained {} TF/s | hbm {} GB/s | ridge {} "
            "flops/byte".format(
                _fmt(report.get("sustained_tflops"), width=1),
                _fmt(report.get("hbm_gbps"), width=1),
                _fmt(ridge, 1.0, 1, 1)))
    ke = report.get("kernel_efficiency")
    if ke:
        lines.append(
            f"{'Kernel scoreboard':40s} {'Meas(ms)':>10s} {'Min(ms)':>10s}"
            f" {'Achieved':>9s}")
        for r in ke:
            shape = f" [{r['shape']}]" if r.get("shape") else ""
            op = r["op"] if r.get("at") is None else f"{r['op']}@{r['at']}"
            lines.append(
                f"[kernel] {op:24s}{shape:14s} {r['ms']:10.4f} "
                f"{r['min_ms']:10.4f} {r['efficiency']:9.1%}")
    ops = report.get("kernel_ops")
    if ops:
        lines.append(
            f"{'Kernel-backed op (a step)':34s} {'ms':>9s} {'Required':>10s} "
            f"{'Executed':>10s} {'Added':>7s}  GFLOP: Mosaic declared + XLA")
        for o in ops:
            added = "      -" if o["added"] is None \
                else "{:6.2f}x".format(o["added"])
            lines.append(
                f"[work] {o['op']:27s} {o['ms']:9.3f} "
                f"{_fmt(o['required_flops'], 1e9, 2, 10)} "
                f"{_fmt(o['executed_flops'], 1e9, 2, 10)} {added}  "
                f"{o['declared_flops'] / 1e9:.2f} + "
                f"{o['xla_flops'] / 1e9:.2f}")
    kernels = report.get("kernels")
    if kernels:
        lines.append(
            f"{'Mosaic kernel (a step)':34s} {'Calls':>6s} {'ms':>9s} "
            f"{'Floor ms':>9s} {'Share':>7s} {'Bound':6s} {'By':7s} "
            f"{'GFLOP':>9s} {'MB':>9s}")
        for k in kernels:
            share = "      -" if k["share"] is None \
                else "{:7.1%}".format(k["share"])
            lines.append(
                f"[mosaic] {k['kernel']:25s} {k['calls']:6.1f} "
                f"{k['ms']:9.3f} {_fmt(k['floor_ms'], 1.0, 3, 9)} {share} "
                f"{k['bound'] or '-':6s} {k['declared_by']:7s} "
                f"{k['gflop']:9.2f} {k['mb']:9.1f}")
    if report.get("input_bound"):
        lines.append("[verdict] input-bound: " +
                     report.get("input_bound_remedy", ""))
    for program, row in sorted((report.get("build") or {}).items()):
        phases = " | ".join(
            f"{phase} {row['phases'][phase]:.2f} s" for phase in
            ("trace", "lower", "compile", "analysis", "execute")
            if phase in row["phases"])
        cache = row["cache"]
        if cache:
            phases += (" | " if phases else "") + (
                "cache: {:.0f} loaded, {:.0f} compiled".format(
                    cache.get("hit", 0), cache.get("miss", 0)))
        lines.append(f"[build] {program}: {phases}")
    hc = report.get("kernel_counts")
    if hc:
        lines.append(
            "[hlo] {} instructions | {} fusion kernels | {} modules"
            .format(hc["instructions"], hc["fusions"], hc["modules"]))
    cc = report.get("cost_crosscheck")
    if cc:
        bits = []
        if cc.get("analytic_flops") is not None:
            bits.append(f"analytic {cc['analytic_flops'] / 1e9:.3f} GFLOPs "
                        f"(rel err {cc['rel_err']:.1%})")
        bits.append(f"executed {cc['executed_flops'] / 1e9:.3f} GFLOPs "
                    f"(rel err {cc['executed_rel_err']:.1%})")
        lines.append("[crosscheck] " + " | ".join(bits)
                     + f" vs XLA {cc['xla_flops'] / 1e9:.3f} GFLOPs")
    mfu_bits = []
    if report.get("mfu_nominal") is not None:
        mfu_bits.append(f"nominal {report['mfu_nominal']:.3f}")
    if report.get("mfu_vs_sustained") is not None:
        mfu_bits.append(f"vs sustained {report['mfu_vs_sustained']:.3f}")
    if report.get("device_duty_cycle") is not None:
        mfu_bits.append(f"duty cycle {report['device_duty_cycle']:.3f}")
    if mfu_bits:
        lines.append("[mfu] " + " | ".join(mfu_bits))
    for note in report.get("notes", []):
        lines.append(f"[device] ({note})")
    return lines


def capture(run, steps: int = 3, probe: bool = True) \
        -> Optional[Dict[str, Any]]:
    """Run `run()` `steps` times inside a silent traced profiling session
    and return the roofline report (None on any failure). Nothing is
    printed. The temp trace dir is deleted afterwards."""
    from . import profiler as profiler_mod

    tmp = tempfile.mkdtemp(prefix="pd_roofline_")
    report = None
    try:
        profiler_mod.start_profiler(trace_dir=tmp)
        try:
            for _ in range(steps):
                run()
        finally:
            report = profiler_mod.finish_trace_report(probe=probe)
    except Exception:  # noqa: BLE001 - attribution must never kill the run
        report = None
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return report
