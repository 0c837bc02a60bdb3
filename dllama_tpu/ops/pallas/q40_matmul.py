"""Fused Q40 dequant-matmul Pallas kernels — the decode/prefill hot loop.

The reference's equivalent is matmul_Q80_Q40 (nn-cpu-ops.cpp:225-446) for
decode plus llamafile sgemm (sgemm.cpp:819-1010) for prefill; on TPU the win
is HBM bandwidth: the kernel streams the *packed* 4-bit weights (0.5625
bytes/weight incl. f16 scales) from HBM into VMEM and dequantizes on-chip
right before the MXU dot — ~3x less HBM traffic than bf16 weights, which is
the whole game for small-batch decode.

Two TPU-specific design points beyond the reference's scheme:

1. **Layer-stacked weights with scalar-prefetch indexing.** The model keeps
   every layer's weights stacked as one ``[L, k/2, n]`` array (the scanned
   forward needs that layout). Feeding ``lax.dynamic_slice`` output to a
   custom call would make XLA materialize a full HBM copy of every weight,
   every layer, every token — tripling decode traffic. Instead the kernels
   take the whole stacked array plus the layer index as a scalar-prefetch
   argument; the Pallas DMA pipeline indexes the layer directly in HBM
   (``PrefetchScalarGridSpec``), so no copy ever exists.

2. **Two dequant schemes, split by batch size** (the reference's decode
   GEMV / prefill sgemm split, nn-cpu-ops.cpp:1003-1019):

   * ``deq`` (m > 16): classic in-kernel dequant — unpack nibbles, one
     fused multiply per weight, bf16 dot. Dequant cost amortizes over the m
     rows, so prefill is MXU-bound.
   * ``blockdot`` (m <= 16): decode is HBM/VPU-bound and per-element dequant
     arithmetic is the bottleneck, so this kernel never builds the dequantized
     matrix. Nibbles become *exact* signed codes ``q - 8`` via an
     exponent-trick bitcast (OR into the mantissa of 2^23 where the float ulp
     is 1, subtract 2^23 + 8 — exact by Sterbenz), the codes are lossless in
     bf16 (|q-8| <= 8), the MXU computes per-block partial dots
     y[kb] = x_kb @ codes_kb, and the f32 block scales touch only the tiny
     [k/32, m, n-tile] partials:  out = sum_kb s[kb] * y[kb].
     Per-weight VPU work drops to the ~2-op unpack; the scale math is
     O(m/32) per weight element and the per-element dequant multiply is gone.

Layout (see ops/quant.QTensor): ``packed: u8[(L,) k/2, n]`` where packed row
``16*b + j`` holds codes for input dims ``32*b + j`` (low nibble) and
``32*b + j + 16`` (high nibble); ``scales: f16[(L,) k/32, n]`` (streamed as
raw u16 bits, widened in-register by ``_scales_f32``).

Grid is (m_tiles, n_tiles, k_tiles) with k innermost: the f32 accumulator
block stays VMEM-resident across the k sweep and is written back once per
(m, n) tile. Inputs are double-buffered by the Pallas pipeline automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile
from dllama_tpu.ops.quant import Q_BLOCK, QTensor

# f32 bit pattern of 2^23 = 8388608.0; mantissa ulp there is exactly 1, so
# OR-ing a nibble q into the low bits gives the exact float 2^23 + q, and
# subtracting (2^23 + 8) yields the exact signed code q - 8 (the subtraction
# of nearby floats is exact by Sterbenz' lemma) — int->float conversion and
# the -8 offset in two cheap VPU ops, no convert instruction.
_EXP_BITS = 0x4B000000
_V_OFFSET = 8388608.0 + 8.0

# kernel-style override for benchmarks:
# 'auto' | 'deq' | 'blockdot' | 'maskdot' | 'loopdot'
# ('maskdot' = blockdot's math with the per-block partial dots expressed as
# ONE plain dot on a block-masked activation matrix — a fallback in case
# Mosaic rejects the batched dot_general; MXU does nb x redundant zero MACs,
# irrelevant while decode is HBM/VPU-bound. 'loopdot' = the same math as a
# STATICALLY UNROLLED sequence of plain [m,32]x[32,tn] dots — no batched
# dot_general, no masking, no redundant MACs; the most lowering-conservative
# fallback, at the cost of nb tiny MXU launches per grid step.)
STYLE = "auto"

# decode-kernel tile overrides for on-hardware autotuning (experiments/
# kbench.py sweeps these): None = the pick_tile defaults. tk/tn must divide
# the op's k/n; out-of-range overrides fall back to the default pick.
BLOCKDOT_TK: int | None = None
BLOCKDOT_TN: int | None = None


def _unpack_codes(packed_block, tk: int, tn: int):
    """u8[tk/2, tn] nibbles -> f32[tk/32, 32, tn] of exact codes q - 8."""
    p = packed_block.astype(jnp.int32)
    lo = (p & 0x0F) | _EXP_BITS
    hi = (p >> 4) | _EXP_BITS
    nb = tk // Q_BLOCK
    half = Q_BLOCK // 2
    codes = jnp.concatenate(
        [lo.reshape(nb, half, tn), hi.reshape(nb, half, tn)], axis=1
    )
    return jax.lax.bitcast_convert_type(codes, jnp.float32) - _V_OFFSET


# 2^112: shifts an f16 exponent (bias 15) into the f32 field (bias 127) after
# the mantissa/exponent bits are placed at f32 positions.
_F16_WIDEN = 2.0 ** 112


def _scales_f32(s):
    """Widen a scales tile to f32 in-register.

    QTensor scales live as f16 in HBM (half the scale bytes — ~10% of Q40
    decode traffic) and reach the kernel bitcast to u16 (the dispatcher does
    the bitcast; Mosaic support for f16 vectors is not assumed). The widening
    places sign/exponent/mantissa at their f32 offsets and rescales by 2^112 —
    exact for all normal AND subnormal f16 values (the classic half->float
    exponent-scaling identity; the only mismatch would be f16 inf/nan, which
    the Q40 quantizer never produces). Note: if the VPU flushes f32
    subnormals, a subnormal f16 scale (<6.1e-5) decodes to 0 — affected
    weights are < 5e-4 in magnitude, far below quantization noise.

    f32 tiles pass through untouched (hand-built QTensors)."""
    if s.dtype == jnp.uint16:
        u = s.astype(jnp.uint32)
        bits = ((u & 0x8000) << 16) | ((u & 0x7FFF) << 13)
        return jax.lax.bitcast_convert_type(bits, jnp.float32) * _F16_WIDEN
    return s.astype(jnp.float32)


def _deq_kernel(layer_ref, x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, tk, tn):
    del layer_ref  # consumed by the index maps
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    c = _unpack_codes(packed_ref[:], tk, tn)  # [nb, 32, tn] exact q - 8
    s = _scales_f32(scales_ref[:])[:, None, :]
    w = (c * s).reshape(tk, tn).astype(x_ref.dtype)
    acc_ref[:] += jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _blockdot_kernel(
    layer_ref, xb_ref, packed_ref, scales_ref, out_ref, acc_ref, *, tk, tn
):
    del layer_ref
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # codes q-8 are EXACT in the activation dtype (|q-8| <= 8, integral —
    # lossless even in bf16), so the MXU block-dot on raw codes is exact; the
    # f32 scales touch only the [nb, m, tn] partials — per-weight VPU work is
    # just the unpack, no per-element dequant multiply.
    c = _unpack_codes(packed_ref[:], tk, tn).astype(xb_ref.dtype)  # [nb, 32, tn]
    y = jax.lax.dot_general(
        xb_ref[:], c, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [nb, m, tn]
    s = _scales_f32(scales_ref[:])[:, None, :]  # [nb, 1, tn]
    acc_ref[:] += jnp.sum(y * s, axis=0)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _deq_call(layer, x, packed, scales, *, interpret: bool = False):
    """x[m, k] @ dequant(packed[layer], scales[layer]) -> f32[m, n]."""
    m, k = x.shape
    n = packed.shape[-1]
    # every m tile streams and dequantises the WHOLE weight again, so a batch
    # up to 512 rows is one tile whatever it divides by: m = 48 (48 serving
    # slots) split as 3 x 16 cost three passes, 124 us a 2048 x 8192 call
    # where m = 64 cost 59 (my chip run, PR 29, experiments/q40_decode_bench.py)
    tm = m if m <= 512 else _pick_tile(m, (512, 256, 128, 64, 32, 16, 8))
    tn = _pick_tile(n, (512, 256, 128))
    tk = _pick_tile(k, (512, 256, 128, 64, 32))
    grid = (m // tm, n // tn, k // tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kb, L: (i, kb)),
            pl.BlockSpec((None, tk // 2, tn), lambda i, j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, tk // Q_BLOCK, tn), lambda i, j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kb, L: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_deq_kernel, tk=tk, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * x.dtype.itemsize
            + k * n // 2
            + (k // Q_BLOCK) * n * scales.dtype.itemsize
            + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, x, packed, scales)


def _maskdot_kernel(
    layer_ref, x_ref, packed_ref, scales_ref, out_ref, acc_ref, *, tk, tn
):
    del layer_ref
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    m = x_ref.shape[0]
    nb = tk // Q_BLOCK
    w = _unpack_codes(packed_ref[:], tk, tn).astype(x_ref.dtype).reshape(tk, tn)
    # x replicated per block row, masked to that block's 32 lanes: one big dot
    # then computes every per-block partial y[b] = x_b @ codes_b at once
    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, m, tk), 2)
    blk = jax.lax.broadcasted_iota(jnp.int32, (nb, m, tk), 0)
    xaug = jnp.where(lane // Q_BLOCK == blk, x_ref[:][None], 0).reshape(nb * m, tk)
    y = jnp.dot(xaug, w, preferred_element_type=jnp.float32).reshape(nb, m, tn)
    acc_ref[:] += jnp.sum(y * _scales_f32(scales_ref[:])[:, None, :], axis=0)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _loopdot_kernel(
    layer_ref, xb_ref, packed_ref, scales_ref, out_ref, acc_ref, *, tk, tn
):
    del layer_ref
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # blockdot's exact math (codes q-8 lossless in the activation dtype, f32
    # scales applied to the per-block partials) with the nb-batched dot
    # unrolled into nb PLAIN dots at static indices — nothing here that a
    # Mosaic build supporting jnp.dot can reject
    c = _unpack_codes(packed_ref[:], tk, tn).astype(xb_ref.dtype)  # [nb, 32, tn]
    s = _scales_f32(scales_ref[:])  # [nb, tn]
    acc = acc_ref[:]
    for b in range(tk // Q_BLOCK):  # static unroll
        y = jnp.dot(xb_ref[b], c[b], preferred_element_type=jnp.float32)
        acc = acc + y * s[b][None, :]
    acc_ref[:] = acc

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _loopdot_call(layer, x, packed, scales, *, interpret: bool = False):
    """blockdot fallback #2: same math, statically-unrolled plain dots. Small
    tk keeps the unroll count (tk/32 dots per grid step) bounded."""
    m, k = x.shape
    n = packed.shape[-1]
    nb = k // Q_BLOCK
    tn = _pick_tile(n, (512, 256, 128))
    tk = _pick_tile(k, (256, 128, 64, 32))
    grid = (n // tn, k // tk)
    xb = x.reshape(m, nb, Q_BLOCK).transpose(1, 0, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk // Q_BLOCK, m, Q_BLOCK), lambda j, kb, L: (kb, 0, 0)),
            pl.BlockSpec((None, tk // 2, tn), lambda j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, tk // Q_BLOCK, tn), lambda j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, kb, L: (0, j)),
        scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_loopdot_kernel, tk=tk, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * 4 + k * n // 2 + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, xb, packed, scales)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _maskdot_call(layer, x, packed, scales, *, interpret: bool = False):
    """blockdot fallback: same math, plain-dot-only lowering (m <= 16)."""
    m, k = x.shape
    n = packed.shape[-1]
    tn = _pick_tile(n, (512, 256, 128))
    tk = _pick_tile(k, (512, 256, 128, 64, 32))
    grid = (n // tn, k // tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, tk), lambda j, kb, L: (0, kb)),
            pl.BlockSpec((None, tk // 2, tn), lambda j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, tk // Q_BLOCK, tn), lambda j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, kb, L: (0, j)),
        scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_maskdot_kernel, tk=tk, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k * (tk // Q_BLOCK),  # nb-masked redundant MACs
            bytes_accessed=m * k * x.dtype.itemsize + k * n // 2 + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, x, packed, scales)


@functools.partial(jax.jit, static_argnames=("interpret", "tk", "tn"))
def _blockdot_call(layer, x, packed, scales, *, interpret: bool = False,
                   tk: int | None = None, tn: int | None = None):
    """Decode-shaped path: x[m<=16, k] against stacked Q40 weights.
    tk/tn are static tile overrides (from the module knobs, validated by the
    dispatcher) — part of the jit key so an autotune sweep actually recompiles."""
    m, k = x.shape
    n = packed.shape[-1]
    nb = k // Q_BLOCK
    tn = tn or _pick_tile(n, (512, 256, 128))
    tk = tk or _pick_tile(k, (2048, 1024, 512, 256, 128, 64, 32))
    grid = (n // tn, k // tk)
    # pre-shaped outside the kernel: Mosaic can't split the lane dim in-kernel
    xb = x.reshape(m, nb, Q_BLOCK).transpose(1, 0, 2)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tk // Q_BLOCK, m, Q_BLOCK), lambda j, kb, L: (kb, 0, 0)),
            pl.BlockSpec((None, tk // 2, tn), lambda j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, tk // Q_BLOCK, tn), lambda j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, kb, L: (0, j)),
        scratch_shapes=[pltpu.VMEM((m, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_blockdot_kernel, tk=tk, tn=tn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * 4 + k * n // 2 + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, xb, packed, scales)


def supported(x_shape: tuple[int, ...], w: QTensor) -> bool:
    """Tileability check used by the ops.matmul dispatcher."""
    k, n = w.shape[-2], w.shape[-1]
    return k % Q_BLOCK == 0 and n % 128 == 0 and k >= 128


def q40_matmul(
    x: jax.Array, w: QTensor, layer=None, *, interpret: bool = False
) -> jax.Array:
    """``x @ w[layer]`` for any leading batch dims; returns x.dtype.

    ``w`` may be a 2-D weight (``layer=None``) or a layer-stacked
    ``[L, k, n]`` weight addressed by the traced scalar ``layer`` — the
    stacked form is indexed by the DMA engine, never sliced by XLA.
    """
    *lead, k = x.shape
    assert k % Q_BLOCK == 0 and k >= 128 and w.shape[-1] % 128 == 0, (
        f"untileable Q40 matmul: k={k}, n={w.shape[-1]} (see supported())"
    )
    m = 1
    for d in lead:
        m *= d
    if w.packed.ndim == 2:
        packed, scales = w.packed[None], w.scales[None]
        layer = 0
    else:
        packed, scales = w.packed, w.scales
        assert layer is not None, "stacked QTensor needs a layer index"
    n = packed.shape[-1]
    if scales.dtype == jnp.float16:
        # kernels take raw u16 bits (see _scales_f32); the bitcast is free
        scales = jax.lax.bitcast_convert_type(scales, jnp.uint16)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    x2 = x.reshape(m, k)
    # pad rows up to the f32 sublane (8) so tiny decode batches still tile
    pad = (-m) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    mp = m + pad
    style = STYLE
    if style == "auto":
        style = "blockdot" if mp <= 16 else "deq"
    elif style in ("blockdot", "maskdot", "loopdot") and mp > 16:
        # forced decode-shaped styles apply only to decode-shaped calls; a
        # forced style is a DECODE-kernel selector, prefill always uses deq
        # (callers labeling results must report per-m paths)
        style = "deq"
    if style == "blockdot":
        tk_o = BLOCKDOT_TK if (
            BLOCKDOT_TK and k % BLOCKDOT_TK == 0 and BLOCKDOT_TK % Q_BLOCK == 0
        ) else None
        tn_o = BLOCKDOT_TN if (BLOCKDOT_TN and n % BLOCKDOT_TN == 0) else None
        out = _blockdot_call(layer_arr, x2, packed, scales, interpret=interpret,
                             tk=tk_o, tn=tn_o)
    elif style == "maskdot":
        out = _maskdot_call(layer_arr, x2, packed, scales, interpret=interpret)
    elif style == "loopdot":
        out = _loopdot_call(layer_arr, x2, packed, scales, interpret=interpret)
    else:
        out = _deq_call(layer_arr, x2, packed, scales, interpret=interpret)
    if pad:
        out = out[:m]
    return out.reshape(*lead, n).astype(x.dtype)


def q40_matmul_2d(
    x: jax.Array, packed: jax.Array, scales: jax.Array, *, interpret: bool = False
) -> jax.Array:
    """Back-compat wrapper: x[m, k] @ dequant(packed, scales) -> f32[m, n]."""
    if scales.dtype == jnp.float16:
        scales = jax.lax.bitcast_convert_type(scales, jnp.uint16)
    layer = jnp.zeros((1,), jnp.int32)
    return _deq_call(layer, x, packed[None], scales[None], interpret=interpret)
