"""One decode step of a gated delta-rule (KDA) layer's matrix state, in
place, on the layer-stacked state — Pallas TPU kernel, the sibling of
``ssm_step.py`` (ops/delta.py has the equations):

    S' = exp(g) (.) S        S in R^{H x K x V}, one per slot; g per (head, key)
    u  = v - S'^T k          a reduction over the KEY axis
    S  = S' + (beta k) u^T   then a rank-one update
    o  = S^T q               and a second reduction over the key axis

The state of every delta-rule layer lives in ONE array ``[L, slots, H, K, V]``
float32 (models/llama.RecurrentState): a decode step reads and writes each
advancing slot's ``H*K*V*4`` bytes once a layer (2 MB at H=32, K=V=128) and
nothing else of it, so the kernel takes the whole stack, the layer rides as
scalar prefetch into the BlockSpec index maps, and the stack aliases its
output (no layer's slice is cut out or put back by XLA).

Grid: one step a slot; its block is the slot's whole layer state, ``H``
``[K, V]`` tiles (keys on sublanes, values on lanes). What multiplies along
the key axis (exp(g), k, beta*k, q) comes transposed ``[slots, 4, K, H]`` so
that a head's vector is a static lane slice ``[K, 1]`` that broadcasts along
V; v comes and o leaves as ``[slots, H, V]`` rows. Both reductions run over
sublanes.

``mode[slot]`` (SMEM) as in ``ssm_step``: 0 = leave the slot (its block is
copied through, bit-equal), 1 = advance, 2 = advance from a ZERO state (a
row at position 0 has no history; a select, so a stale non-finite state
cannot leak into a new request).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def supported(state_shape: tuple[int, ...], dtype) -> bool:
    """[L, slots, H, K, V]: whole 128-lane rows of V, whole sublane tiles of
    K, 32-bit state (a narrower state is the jnp path's)."""
    _, _, h, k, v = state_shape
    return v % 128 == 0 and k % 8 == 0 and h % 8 == 0 and jnp.dtype(dtype).itemsize == 4


def _kernel(layer_ref, mode_ref,  # scalar prefetch (SMEM)
            s_ref, kq_ref, v_ref,  # VMEM blocks
            o_ref, so_ref, *, heads: int):
    b = pl.program_id(0)
    mode = mode_ref[b]

    @pl.when(mode == 0)
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(mode != 0)
    def _():
        keep = mode == 1
        for h in range(heads):
            col = lambda i: kq_ref[i, :, h:h + 1]  # [K, 1]
            s = jnp.where(keep, s_ref[h], 0.0) * col(0)  # exp(g) S  [K, V]
            u = v_ref[h:h + 1, :] - jnp.sum(s * col(1), axis=0, keepdims=True)
            s = s + col(2) * u
            so_ref[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * col(3), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step(layer, mode, state, kq, v, *, interpret: bool = False):
    """layer i32[1], mode i32[slots], state f32[L, slots, H, K, V], kq
    f32[slots, 4, K, H] (exp(g), k, beta*k, q, transposed), v
    f32[slots, H, V] -> (o f32[slots, H, V], state).

    The name, and the 5-D state in the result, are what the benchmark's
    trace reader finds this call by (benchmark/costs/kda_step.py)."""
    _, slots, heads, kd, vd = state.shape
    slot_bytes = heads * kd * vd * 4
    state_spec = pl.BlockSpec((None, None, heads, kd, vd),
                              lambda b, L, *_: (L[0], b, 0, 0, 0))
    row_spec = pl.BlockSpec((None, heads, vd), lambda b, *_: (b, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots,),
        in_specs=[
            state_spec,
            pl.BlockSpec((None, 4, kd, heads), lambda b, *_: (b, 0, 0, 0)),
            row_spec,
        ],
        out_specs=[row_spec, state_spec],
    )
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, heads, vd), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # after the 2 scalar-prefetch args: state=2 aliases output 1
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state block in and out, double-buffered, + the small operands
            vmem_limit_bytes=4 * slot_bytes + 8 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=7 * slots * heads * kd * vd,
            bytes_accessed=2 * slots * slot_bytes,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, mode, state, kq, v)
    return o, state


def kda_step(state, layer, q, k, v, decay, beta, mode, *,
             interpret: bool = False):
    """The decode step of layer `layer` of the stacked state, in place.

    state f32[L, slots, H, K, V]; q, k, decay f32[slots, H, K] (decay =
    exp(g)); v f32[slots, H, V]; beta f32[slots, H]; mode i32[slots] (module
    docstring). Returns (o f32[slots, H, V] = S_new^T q, state)."""
    f32 = jnp.float32
    kq = jnp.stack([decay, k, beta[..., None] * k, q], axis=1).astype(f32)
    return _kda_step(
        jnp.asarray(layer, jnp.int32).reshape(1), mode.astype(jnp.int32),
        state, kq.transpose(0, 1, 3, 2), v.astype(f32), interpret=interpret)

