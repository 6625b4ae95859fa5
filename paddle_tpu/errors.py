"""Typed error hierarchy (reference: paddle/fluid/platform/enforce.h
EnforceNotMet + pybind/exception.cc mapping C++ exceptions onto Python
types). The executor raises EnforceNotMet for op execution failures — it
carries the failing operator, its declared inputs/outputs, the live input
shapes, and the op's Python creation site (CustomStackTrace parity,
reference paddle/utils/CustomStackTrace.h layer-stack dump)."""

from __future__ import annotations

__all__ = ["EnforceNotMet", "EOFException", "NonFiniteError", "NotFoundError",
           "OOMError", "ProgramVerifyError", "ServingOverloadError"]


class EnforceNotMet(RuntimeError):
    """An operator's runtime contract failed (reference PADDLE_ENFORCE)."""

    def __init__(self, message, op_type=None, creation_site=None):
        super().__init__(message)
        self.op_type = op_type
        self.creation_site = creation_site


class NonFiniteError(FloatingPointError, RuntimeError):
    """A NaN/Inf was detected in a tensor (reference FLAGS_check_nan_inf,
    executor.cc:325 CheckTensorNANOrInf). Subclasses both FloatingPointError
    (the eager per-op check's historical type) and RuntimeError (the jit
    fetch-level check's), so existing handlers keep working.

    Structured fields localize the origin: `var_name`/`dtype` name the tensor
    the detection fired on, `op_type`/`op_index` the producing op when known,
    `stats` its inspector.TensorStats, and `attribution` the full
    inspector.Attribution from the bisection re-run (None when attribution is
    disabled or inconclusive)."""

    def __init__(self, message, var_name=None, dtype=None, op_type=None,
                 op_index=None, stats=None, attribution=None,
                 feed_signature=None):
        super().__init__(message)
        self.var_name = var_name
        self.dtype = dtype
        self.op_type = op_type
        self.op_index = op_index
        self.stats = stats
        self.attribution = attribution
        self.feed_signature = feed_signature

    def to_dict(self):
        """JSON-serializable view (flight-recorder crash reports)."""
        return {
            "type": type(self).__name__,
            "message": str(self),
            "var_name": self.var_name,
            "dtype": self.dtype,
            "op_type": self.op_type,
            "op_index": self.op_index,
            "stats": self.stats.to_dict() if self.stats is not None else None,
            "attribution": (self.attribution.to_dict()
                            if self.attribution is not None else None),
            "feed_signature": ([list(s) for s in self.feed_signature]
                               if self.feed_signature else None),
        }


class OOMError(MemoryError, RuntimeError):
    """The device ran out of HBM (XLA RESOURCE_EXHAUSTED). jax surfaces
    this as a bare XlaRuntimeError whose message names the failed
    allocation but nothing about WHAT is occupying the chip; the executor
    (memory.maybe_oom_error) replaces it with this structured error.
    Subclasses MemoryError (the natural Python type) and RuntimeError (so
    handlers catching the raw jax error's base type keep working); the
    message retains the RESOURCE_EXHAUSTED marker for text-matching retry
    loops.

    Fields: `breakdown` maps byte classes (params/opt_state/feeds plus
    device bytes_in_use/bytes_limit when memory_stats is available),
    `top_buffers` lists the largest live arrays (named when they map back
    to scope/feed vars), `donation_lost_bytes` counts donated state XLA
    failed to alias in place, `analysis` is the block's static
    memory.ProgramMemory view, and `suggestions` are concrete next steps
    (donate, AMP, remat, a smaller batch)."""

    def __init__(self, message, program=None, breakdown=None,
                 top_buffers=None, donation_lost_bytes=0, analysis=None,
                 suggestions=None, device=None):
        super().__init__(message)
        self.program = program
        self.breakdown = dict(breakdown or {})
        self.top_buffers = list(top_buffers or [])
        self.donation_lost_bytes = donation_lost_bytes
        self.analysis = analysis
        self.suggestions = list(suggestions or [])
        self.device = device

    def to_dict(self):
        """JSON-serializable view (flight-recorder crash reports)."""
        return {
            "type": type(self).__name__,
            "message": str(self),
            "program": self.program,
            "breakdown": self.breakdown,
            "top_buffers": self.top_buffers,
            "donation_lost_bytes": self.donation_lost_bytes,
            "analysis": self.analysis,
            "suggestions": self.suggestions,
            "device": self.device,
        }


class ProgramVerifyError(RuntimeError):
    """The static analyzer (paddle_tpu.analysis) found error-severity
    diagnostics in a program about to compile. Raised by the executor
    under PADDLE_TPU_VERIFY=1 *before* tracing, so the message points at
    the op's Python creation site instead of a JAX traceback — the
    compile-time InferShape story of the reference, restored.

    `diagnostics` holds the analysis.Diagnostic objects (error severity
    only); the message numbers them with op index, source site and hint."""

    def __init__(self, diagnostics, program_name=None):
        self.diagnostics = list(diagnostics)
        self.program_name = program_name
        head = (f"program verification failed: "
                f"{len(self.diagnostics)} error(s)")
        if program_name:
            head += f" in {program_name}"
        body = "\n".join(f"  [{i + 1}] {d.format()}"
                         for i, d in enumerate(self.diagnostics))
        super().__init__(head + ("\n" + body if body else "") +
                         "\n(set PADDLE_TPU_VERIFY=0 to skip verification, "
                         "or run `python -m paddle_tpu analyze` for the "
                         "full report)")

    def to_dict(self):
        """JSON-serializable view (flight-recorder crash reports)."""
        return {
            "type": type(self).__name__,
            "message": str(self),
            "program_name": self.program_name,
            "diagnostics": [d.to_dict() for d in self.diagnostics],
        }


class NotFoundError(KeyError):
    """A variable/operator lookup failed (reference NotFound error code)."""


class ServingOverloadError(RuntimeError):
    """A serving request was rejected by overload control (serving/batcher):
    either the bounded request queue was full at submit time, or the
    request's deadline expired before its batch reached the device.
    Shedding with a typed error keeps the accepted requests' latency bounded
    instead of letting the queue collapse under 2x load — the caller is
    expected to retry against another replica or surface the rejection.

    `reason` is the shed cause ("queue_full" | "deadline" | "shutdown"),
    `queue_depth` the depth observed at rejection."""

    def __init__(self, message, reason=None, queue_depth=None):
        super().__init__(message)
        self.reason = reason
        self.queue_depth = queue_depth

    def to_dict(self):
        """JSON-serializable view (flight-recorder crash reports)."""
        return {
            "type": type(self).__name__,
            "message": str(self),
            "reason": self.reason,
            "queue_depth": self.queue_depth,
        }


def __getattr__(name):
    # canonical home of EOFException is layers.io (it predates this
    # module); lazily re-exported so the typed hierarchy is one import
    # away without an import cycle
    if name == "EOFException":
        from .layers.io import EOFException
        return EOFException
    raise AttributeError(name)
