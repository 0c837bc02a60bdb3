"""Fused Q80 dequant-matmul Pallas kernels.

The reference runs Q80-weight models through the same kernel table as Q40
(matmul_Q80_Q80 rows, nn-cpu-ops.cpp:448-540); here the win is again HBM
bandwidth: int8 codes + f16 block scales stream 1.0625 bytes/weight from
HBM — ~1.9x less than the dense-bf16 fallback Q80 files previously loaded
as. Structure mirrors ops/pallas/q40_matmul.py (layer-stacked weights via
scalar-prefetch indexing, (m, n, k)/(n, k) grids with the k sweep
innermost, f32 VMEM accumulator), minus the nibble unpack — int8 codes
convert exactly to the activation dtype (|q| <= 127 is integral and exact
even in bf16), so the decode scheme is the same scale-the-partials
blockdot: y[kb] = x_kb @ codes_kb on the MXU, out = sum_kb s[kb] * y[kb].
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.pallas.q40_matmul import _scales_f32
from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile
from dllama_tpu.ops.quant import Q_BLOCK, Q8Tensor


def _deq_kernel(layer_ref, x_ref, codes_ref, scales_ref, out_ref, acc_ref, *, tk, tn):
    del layer_ref
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    c = codes_ref[:].astype(jnp.float32).reshape(tk // Q_BLOCK, Q_BLOCK, tn)
    s = _scales_f32(scales_ref[:])[:, None, :]
    w = (c * s).reshape(tk, tn).astype(x_ref.dtype)
    acc_ref[:] += jnp.dot(x_ref[:], w, preferred_element_type=jnp.float32)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        out_ref[:] = acc_ref[:]


def _blockdot_kernel(layer_ref, xb_ref, codes_ref, scales_ref, out_ref, acc_ref, *, tk, tn):
    del layer_ref
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # int8 codes are exact in the activation dtype; per-weight VPU work is
    # one convert, the f32 scales touch only the [nb, m, tn] partials
    c = codes_ref[:].astype(xb_ref.dtype).reshape(tk // Q_BLOCK, Q_BLOCK, tn)
    y = jax.lax.dot_general(
        xb_ref[:], c, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [nb, m, tn]
    acc_ref[:] += jnp.sum(y * _scales_f32(scales_ref[:])[:, None, :], axis=0)

    @pl.when(kb == pl.num_programs(1) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _deq_call(layer, x, codes, scales, *, interpret: bool = False):
    m, k = x.shape
    n = codes.shape[-1]
    tm = _pick_tile(m, (512, 256, 128, 64, 32, 16, 8))
    tn = _pick_tile(n, (512, 256, 128))
    tk = _pick_tile(k, (512, 256, 128, 64, 32))
    grid = (m // tm, n // tn, k // tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kb, L: (i, kb)),
            pl.BlockSpec((None, tk, tn), lambda i, j, kb, L: (L[0], kb, j)),
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
            bytes_accessed=m * k * x.dtype.itemsize + k * n
            + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, x, codes, scales)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _blockdot_call(layer, x, codes, scales, *, interpret: bool = False):
    m, k = x.shape
    n = codes.shape[-1]
    tn = _pick_tile(n, (1024, 512, 256, 128))
    tk = _pick_tile(k, (2048, 1024, 512, 256, 128, 64, 32))
    nb = tk // Q_BLOCK
    # x pre-blocked [nb_total, m, 32]: block b of the k axis sits at row b
    xb = x.reshape(m, k // Q_BLOCK, Q_BLOCK).transpose(1, 0, 2)
    grid = (n // tn, k // tk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((nb, m, Q_BLOCK), lambda j, kb, L: (kb, 0, 0)),
            pl.BlockSpec((None, tk, tn), lambda j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, nb, tn), lambda j, kb, L: (L[0], kb, j)),
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
            bytes_accessed=m * k * x.dtype.itemsize + k * n
            + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, xb, codes, scales)


def supported(x_shape: tuple[int, ...], w: Q8Tensor) -> bool:
    """Tileability gate, mirroring q40_matmul.supported."""
    k, n = w.shape[-2], w.shape[-1]
    return x_shape[-1] == k and k % Q_BLOCK == 0 and n % 128 == 0 and k >= 128


def q80_matmul(x: jax.Array, w: Q8Tensor, layer=None, *, interpret: bool = False) -> jax.Array:
    """``x[..., k] @ dequant(w[layer])`` -> [..., n] in x.dtype.

    Same decode/prefill split as q40_matmul: m <= 16 rides the
    scale-the-partials blockdot (no dequantized matrix is ever built),
    larger m the classic in-kernel dequant GEMM.
    """
    *lead, k = x.shape
    assert k % Q_BLOCK == 0 and k >= 128 and w.shape[-1] % 128 == 0, (
        f"untileable Q80 matmul: k={k}, n={w.shape[-1]} (see supported())"
    )
    m = 1
    for d in lead:
        m *= d
    codes, scales = w.codes, w.scales
    if codes.ndim == 2:
        codes, scales = codes[None], scales[None]
        layer = 0
    else:
        assert layer is not None, "stacked Q8Tensor needs a layer index"
    n = codes.shape[-1]
    if scales.dtype == jnp.float16:
        scales = jax.lax.bitcast_convert_type(scales, jnp.uint16)
    lay = jnp.asarray(layer, jnp.int32).reshape(1)
    x2 = x.reshape(m, k)
    pad = (-m) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if m + pad <= 16:
        out = _blockdot_call(lay, x2, codes, scales, interpret=interpret)
    else:
        out = _deq_call(lay, x2, codes, scales, interpret=interpret)
    if pad:
        out = out[:m]
    return out.reshape(*lead, n).astype(x.dtype)
