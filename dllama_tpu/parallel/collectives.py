"""Quantized collectives: the reference's Q80 activation exchange on ICI.

The reference never moves f32 activations between nodes — every
SYNC_NODE_SLICES rides the Q80-quantized ZQ pipe, and the col-matmul
"all-reduce" is an all-gather of quantized partial sums + local merge-add
(SURVEY.md §3.4, nn-network.cpp:521-554, nn-cpu-ops.cpp:838-875). These are
the shard_map-level equivalents, for use when bf16 collectives are
bandwidth-bound (measure before enabling — ICI is fast enough that bf16 is
the default; Q80 halves the payload at ~1e-2 relative error).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from dllama_tpu.ops.quant import dequantize_q80_jnp, quantize_q80_jnp

_F16_MAX = 65504.0


def _f16_wire(scales: jax.Array) -> jax.Array:
    """f32 block scales -> f16 for the wire, saturation-safe: a block with
    absmax > ~8.3e6 would otherwise overflow f16 to inf and poison the whole
    reduced tensor. Clamping to f16-max keeps the block merely coarser."""
    return jnp.clip(scales, -_F16_MAX, _F16_MAX).astype(jnp.float16)


def q80_all_gather(x: jax.Array, axis_name: str, axis: int = 0, tiled: bool = True) -> jax.Array:
    """all_gather(x) with the payload quantized to Q80 (codes i8 + f16 block
    scales, the reference's own NnBlockQ80 wire format) — ~1/2 the bytes of
    bf16, ~1/4 of f32 on the wire."""
    codes, scales = quantize_q80_jnp(x)
    codes_g = jax.lax.all_gather(codes, axis_name, axis=axis, tiled=tiled)
    scales_g = jax.lax.all_gather(_f16_wire(scales), axis_name, axis=axis, tiled=tiled)
    return dequantize_q80_jnp(codes_g, scales_g.astype(jnp.float32), x.dtype)


def q80_all_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """The reference's all-reduce: all-gather Q80 partial sums, reduce locally
    (all-gather + merge-add ≡ all-reduce, SURVEY.md §3.4). Payload is the
    quantized partials with f16 scales (NnBlockQ80's wire dtype; the f32→f16
    scale rounding is ~5e-4 relative, far inside Q80's ~1e-2 step); the
    reduction itself is f32 on-chip."""
    codes, scales = quantize_q80_jnp(x)
    codes_g = jax.lax.all_gather(codes, axis_name, axis=0, tiled=False)
    scales_g = jax.lax.all_gather(_f16_wire(scales), axis_name, axis=0, tiled=False)
    parts = dequantize_q80_jnp(codes_g, scales_g.astype(jnp.float32), jnp.float32)
    return jnp.sum(parts, axis=0).astype(x.dtype)


def resolve_sync(sync: str, shardings) -> str:
    """Resolve the tp activation-exchange payload ('auto' -> 'bf16'|'q80').

    The data-earned policy (VERDICT r4 next #3), from the committed
    collective-bytes record (COLLECTIVES.md). The DEFAULT stays 'bf16'
    everywhere — sync payloads are <0.1% of a decode step's HBM traffic, so
    an unmeasured latency win does not buy a lossy default — but 'auto'
    encodes the recommendation for users who want it:

    * tp=2 — q80 wins on BOTH accountings: measured post-SPMD HLO bytes
      (8b: 544 vs 1024 KB/tok/chip) AND the analytic wire model (522 vs
      762). 'auto' takes the quantized exchange.
    * tp>=4 — the accountings DISAGREE: the q80 all-gather formulation
      materializes more HLO bytes than the bf16 all-reduce (8b tp8: 2176
      vs 1024 KB) while the wire model still favors q80 (586 vs 1006).
      Real ICI has not been timed, so 'auto' stays on the conservative
      bf16 all-reduce until a four-chip run re-measures; explicit
      '--sync q80' remains available.
    * pp meshes — the q80 col_fn is not supported there; 'auto' degrades
      to bf16 instead of raising.

    Reference analog: `--buffer-float-type q80` (app.cpp:204-205),
    recommended unconditionally there; the XLA lowering earns a narrower
    recommendation."""
    if sync not in ("auto", "bf16", "q80"):
        raise ValueError(f"sync must be 'auto', 'bf16' or 'q80', got {sync!r}")
    if sync != "auto":
        return sync
    if shardings is None:
        return "bf16"
    shape = shardings.mesh.shape
    if shape.get("pp", 1) > 1:
        return "bf16"
    return "q80" if shape["tp"] == 2 else "bf16"


def make_q80_col_matmul(mesh):
    """`--sync q80`: the runtime caller of :func:`q80_all_reduce`.

    Returns a drop-in for the wo/w2 col-sharded matmuls in models/llama._layer:
    a shard_map manual over 'tp' only (dp/sp stay GSPMD-auto) that computes the
    local partial product and exchanges it Q80-quantized — the reference's
    load-bearing ZQ-pipe trick (nn-network.cpp:521-554) as an ICI option.
    Output error is the Q80 step (~1e-2 relative), identical to the
    reference's `--buffer-float-type q80` accuracy contract.
    """
    from jax.sharding import PartitionSpec as P

    from dllama_tpu.ops.matmul import matmul
    from dllama_tpu.ops.quant import QTensor

    def body(xl, wl):
        return q80_all_reduce(matmul(xl, wl), "tp")

    def col_matmul(x, w):
        w_spec = P("tp", None)  # [in, out] with the contraction dim tp-sharded
        if isinstance(w, QTensor):
            w_spec = QTensor(w_spec, w_spec)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P(None, None, "tp"), w_spec),
            out_specs=P(),
            axis_names=frozenset({"tp"}),
            check_vma=False,
        )(x, w)

    return col_matmul
