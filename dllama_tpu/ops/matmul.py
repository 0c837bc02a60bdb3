"""Quantized matmul dispatch: Pallas TPU kernels or XLA fallback.

The reference routes each matmul through a per-(op, quant-triple) kernel table
(nn-cpu-ops.cpp:1296-1355, llamafile sgemm for batch>1). Here the "dispatch
table" is two backends:

* ``xla``    — dequantize-then-dot in one jit; XLA fuses the dequant into the
               matmul epilogue. Correctness reference, and the only path on
               CPU and on sharded (GSPMD) engines: ``pallas_call`` has no
               partitioning rule, so under a mesh the Pallas path would
               all-gather sharded weights per call.
* ``pallas`` — fused Q40 dequant-matmul kernels (ops/pallas/q40_matmul.py)
               that stream packed nibbles HBM->VMEM (~3x less HBM traffic
               than bf16 weights) and address layer-stacked weights by
               scalar-prefetch index (no per-layer slice copies). Inside, a
               decode-shaped (m<=16) and a prefill-shaped (m>16) kernel split
               mirrors the reference's GEMV/sgemm tiering.

Backend resolution: an explicit ``backend=`` argument wins (the engine passes
one resolved at construction — per-engine, not global), then the module-level
``BACKEND`` switch (CLI ``--kernels``), then platform auto-detection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dllama_tpu.ops.quant import Q8Tensor, QTensor, slice_leaf

# module-level backend switch; the CLI sets this once at startup.
BACKEND = "auto"

# prefill GEMM routing (VERDICT r2 #4 / reference's llamafile sgemm tier,
# nn-cpu-ops.cpp:1003-1019): at or above this flattened batch*seq, a Pallas-
# backed matmul routes to the XLA dequant-dot instead — prefill is FLOPs-bound
# and the plain MXU GEMM beats in-kernel unpacking once the packed-bytes
# saving stops mattering. None = always fused: the route at prefill sizes
# is not measured on the chip (ROADMAP Speed #5, experiments/kbench.py).
XLA_PREFILL_MIN_M: int | None = None


def device_platform() -> str:
    """Platform of the device the kernels will run on — the ONE place the
    package asks. A backend that fails to initialise raises here instead of
    reading as "cpu" (which would silently turn kernels=auto into the XLA
    dequant path). Compile-only rehearsals for a described chip (no device
    attached: experiments/aot_check.py, tests/test_chip_compile.py) steer
    every platform-derived choice by replacing this function."""
    return jax.devices()[0].platform


def resolve_backend(backend: str | None = None, sharded: bool = False) -> str:
    """'pallas' or 'xla'. Sharded engines force 'xla' unless explicitly
    overridden (pallas_call under GSPMD would gather the sharded weights)."""
    b = backend or BACKEND
    if b == "auto":
        if sharded:
            return "xla"
        return "pallas" if device_platform() == "tpu" else "xla"
    return b


def engine_matmul(kernels: str, shardings) -> "functools.partial":
    """The single place engines turn their (kernels flag, shardings) pair
    into a bound matmul — InferenceEngine and BatchEngine share this so the
    resolution rule can never diverge between tiers."""
    import functools

    backend = resolve_backend(
        None if kernels == "auto" else kernels, sharded=shardings is not None
    )
    return functools.partial(matmul, backend=backend)


def _route_xla_prefill(x: jax.Array) -> bool:
    """Prefill-GEMM routing rule, shared by the Q40 and Q80 fused paths.

    Prefill-shaped only (ADVICE r3): model activations are [b, t, d], so
    t > 1 distinguishes prefill from batched decode — a 64-slot decode step
    must NOT lose the packed-weights bandwidth win just because its
    flattened m crosses the threshold. 2-D calls (no seq axis) are
    decode-shaped by construction."""
    if XLA_PREFILL_MIN_M is None or not (x.ndim >= 3 and x.shape[-2] > 1):
        return False
    m = 1
    for d in x.shape[:-1]:
        m *= d
    return m >= XLA_PREFILL_MIN_M


def matmul(x: jax.Array, w, layer=None, backend: str | None = None) -> jax.Array:
    """``x @ w`` (or ``x @ w[layer]``) where ``w`` is a QTensor/Q8Tensor or
    dense array.

    x: [..., k] activations (bf16/f32); returns [..., n] in x.dtype.
    ``layer``: traced index into a layer-stacked weight ([L, k, n] logical) —
    the Pallas path indexes the stack via DMA, the XLA path slices it.
    """
    if isinstance(w, (QTensor, Q8Tensor)):
        if resolve_backend(backend) == "pallas":
            # Q80 gets the same fused treatment as Q40 (1.0625 B/weight
            # streamed vs 2 for the dense-bf16 fallback), same routing rule
            if isinstance(w, QTensor):
                from dllama_tpu.ops.pallas.q40_matmul import q40_matmul as kernel
                from dllama_tpu.ops.pallas.q40_matmul import supported
            else:
                from dllama_tpu.ops.pallas.q80_matmul import q80_matmul as kernel
                from dllama_tpu.ops.pallas.q80_matmul import supported

            if supported(x.shape, w) and not _route_xla_prefill(x):
                return kernel(x, w, layer,
                              interpret=device_platform() != "tpu")
        if layer is not None and len(w.shape) == 3:
            w = slice_leaf(w, layer)
        wd = w.dequantize(x.dtype)
    else:
        if layer is not None and jnp.ndim(w) == 3:
            w = slice_leaf(w, layer)
        wd = w.astype(x.dtype)
    return jnp.dot(x, wd, preferred_element_type=jnp.float32).astype(x.dtype)
