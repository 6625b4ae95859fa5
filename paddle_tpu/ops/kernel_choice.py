"""Whether a lowering took its Pallas kernel or kept the XLA path, and
why: the one place that is booked, and the one place that knows a
lowering is a gradient's re-trace of a forward and books nothing.

A kernel's shape rule stays beside the kernel it describes (GATES). A
lowering asks it and hands the answer to `book`, which counts lowerings:
a step traced again (a new feed shape) counts again. chip_smoke.py and
PERF.md section 3 read the two counters to say whether the kernels
engaged; tools/check_registry.py::check_pallas_table pins REASONS
against the sources of GATES, both ways, and this module as the
counters' only creator.
"""

from __future__ import annotations

import contextlib

from .. import telemetry

__all__ = ["GATES", "REASONS", "WITHIN", "book", "in_retrace", "retrace"]

_ATTENTION = frozenset({"shape", "seq", "heads", "head_dim", "block"})

# op -> every reason its gate may give for keeping the XLA path.
REASONS = {
    # the int8 kernel under AMP O3, the only conv route; quant reports a
    # miss here as quant_fallback_total{reason="kernel"}
    "conv2d": frozenset({"mesh", "rank", "groups", "dtype", "channels",
                         "attrs", "geometry"}),
    # one gate, asked with the block length by block-diffusion attention
    "scaled_dot_product_attention": _ATTENTION,
    "block_diffusion_attention": _ATTENTION,
    "ssd_scan": frozenset({"chunk", "state", "heads"}),
    # the delta rule (ops/pallas_kda.py, forward and gradient): a head
    # one lane block of K and whole ones of V, the chunk a power of two
    # of 16-row sub-blocks, a group of value heads under one key head no
    # wider than a grid step (and only under a decay a head); else
    # hybrid_ops.kda_scan_chunked keeps the op
    "kda_scan": frozenset({"width", "chunk", "group"}),
    # the mixers' short convolution (ops/pallas_conv1d.py, forward and
    # gradient): whole 128-wide blocks of time and of channels, bf16 or
    # float32, the taps' reach inside a tile; else
    # hybrid_ops.causal_conv1d_reference keeps the op
    "causal_conv1d": frozenset({"dtype", "taps", "time", "channels"}),
    "moe_experts": frozenset({"rows", "width"}),
    # moe_experts' second choice, the token side's kernel (WITHIN)
    "pair_sum": frozenset({"width", "tokens", "rows", "experts"}),
}

# op -> the functions of paddle_tpu.ops whose `return "<reason>"` lines
# are its gate (the lint reads their source).
_ATTENTION_GATE = ("pallas_attention.ineligible",
                   "pallas_attention._lane_block")
GATES = {
    "conv2d": ("pallas_conv.ineligible",),
    "scaled_dot_product_attention": _ATTENTION_GATE,
    "block_diffusion_attention": _ATTENTION_GATE,
    "ssd_scan": ("hybrid_ops.ssd_scan_ineligible",),
    "kda_scan": ("hybrid_ops.kda_scan_ineligible",),
    "causal_conv1d": ("pallas_conv1d.ineligible",),
    "moe_experts": ("hybrid_ops.gmm_ineligible",),
    "pair_sum": ("pallas_pair_sum.ineligible",),
}

# a kernel that is one of several choices of an op's lowering is booked
# under its own name: kernel -> the registered op whose lowering books it
WITHIN = {"pair_sum": "moe_experts"}

_RETRACE = False


@contextlib.contextmanager
def retrace():
    """registry.generic_grad_lower traces a forward lowering again under
    jax.vjp in here: the forward op counted itself on its own trace, so
    `book` is silent, as are the other counters of forward lowerings,
    which ask `in_retrace()` (quant_kernel_total, quant_fallback_total,
    activation_kept_total)."""
    global _RETRACE
    prev = _RETRACE
    _RETRACE = True
    try:
        yield
    finally:
        _RETRACE = prev


def in_retrace() -> bool:
    return _RETRACE


def book(op: str, reason):
    """Book the choice of one lowering of a forward `op`: `reason` None
    books pallas_kernel_total{op}, the gate's reason for the XLA path
    books pallas_fallback_total{op, reason}, and a reason REASONS does
    not hold for the op is an error, not an unlabelled series."""
    declared = REASONS[op]
    if reason is not None and reason not in declared:
        raise ValueError(
            f"{op}: fallback reason {reason!r} is not declared in "
            f"kernel_choice.REASONS ({sorted(declared)})")
    if _RETRACE:
        return
    if reason is None:
        telemetry.counter(
            "pallas_kernel_total",
            "lowerings of a forward op served by a Pallas kernel, by op "
            "(kernel_choice.REASONS lists them); a gradient op books "
            "nothing",
            labels=("op",)).labels(op=op).inc()
    else:
        telemetry.counter(
            "pallas_fallback_total",
            "lowerings that asked for a Pallas kernel and kept the XLA "
            "path, by op and the gate's reason",
            labels=("op", "reason")).labels(op=op, reason=reason).inc()
