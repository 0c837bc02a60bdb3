"""One decode step of a state-space (Mamba-2, SSD) layer's recurrent state,
in place, on the layer-stacked state — Pallas TPU kernel.

    S <- a * S + (dt * x) (outer) B        S in R^{H x P x N}, one per slot
    y  = S C                                (the new S)

The state of every state-space layer lives in ONE array
``[L, slots, H, P, N]`` float32 (models/llama.RecurrentState). A decode step
must read and write each advancing slot's ``H*P*N*4`` bytes once a layer
(2 MB at H=64, P=64, N=128) — at 48 slots that is four fifths of the bytes
of a whole step — and nothing else of that array: so, like
``paged_decode_attention(layer=)`` and the Q40 matmuls, the kernel takes the
whole stack, the layer index rides as scalar prefetch into the BlockSpec
index maps, and the stack aliases its output (the layer scan carries one
buffer; no layer's slice is cut out or put back by XLA).

Grid: one step a slot; its block is the slot's whole layer state, ``H``
``[P, N]`` tiles, lane-dense in N (2 MB, double-buffered in and out).
Per-head scalars (the decay ``a``) ride in SMEM beside the layer; ``dt * x``
comes transposed ``[slots, P, H]`` so that a head's column is a static lane
slice that broadcasts along N (Mosaic has no cheap lanes-to-sublanes move),
and ``y`` leaves the same way.

``mode[slot]`` (SMEM) says what the step does to a slot: 0 = leave it (an
inactive or frozen slot: its block is copied through, bit-equal), 1 =
advance, 2 = advance from a ZERO state (a row at position 0 has no history,
whatever the slot held before: a select, not a multiply, so a stale
non-finite state cannot leak into a new request).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def supported(state_shape: tuple[int, ...], dtype) -> bool:
    """[L, slots, H, P, N]: whole 128-lane rows of N, whole sublane tiles of
    P, 32-bit state (a narrower state is the XLA path's)."""
    _, _, h, p, n = state_shape
    return n % 128 == 0 and p % 8 == 0 and h % 8 == 0 and jnp.dtype(dtype).itemsize == 4


def _kernel(layer_ref, mode_ref, a_ref,  # scalar prefetch (SMEM)
            s_ref, x_ref, bc_ref,  # VMEM blocks
            y_ref, o_ref, *, heads: int):
    b = pl.program_id(0)
    mode = mode_ref[b]

    @pl.when(mode == 0)
    def _():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(mode != 0)
    def _():
        bm = bc_ref[0:1, :]  # [1, N]
        cm = bc_ref[1:2, :]
        keep = mode == 1
        for h in range(heads):
            a = a_ref[b * heads + h]
            s = jnp.where(keep, s_ref[h], 0.0)  # [P, N]
            s = a * s + x_ref[:, h:h + 1] * bm
            o_ref[h] = s
            y_ref[:, h:h + 1] = jnp.sum(s * cm, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_step(layer, mode, a, state, xt, bc, *, interpret: bool = False):
    """layer i32[1], mode i32[slots], a f32[slots * H] (per-head decay),
    state f32[L, slots, H, P, N], xt f32[slots, P, H] (dt * x, transposed),
    bc f32[slots, 2, N] (rows B, C) -> (yT f32[slots, P, H], state).

    The name, and the 5-D state in the result, are what the benchmark's
    trace reader finds this call by (benchmark/costs/ssm_step.py)."""
    _, slots, heads, p, n = state.shape
    slot_bytes = heads * p * n * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(slots,),
        in_specs=[
            pl.BlockSpec((None, None, heads, p, n),
                         lambda b, L, *_: (L[0], b, 0, 0, 0)),
            pl.BlockSpec((None, p, heads), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((None, 2, n), lambda b, *_: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, p, heads), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec((None, None, heads, p, n),
                         lambda b, L, *_: (L[0], b, 0, 0, 0)),
        ],
    )
    yt, state = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((slots, p, heads), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # after the 3 scalar-prefetch args: state=3 aliases output 1
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the state block in and out, double-buffered, + the small operands
            vmem_limit_bytes=4 * slot_bytes + 8 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=6 * slots * heads * p * n,
            bytes_accessed=2 * slots * slot_bytes,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, mode, a, state, xt, bc)
    return yt, state


def ssm_step(state, layer, x, dt, decay, bmat, cmat, mode, *,
             interpret: bool = False):
    """The decode step of layer `layer` of the stacked state, in place.

    state f32[L, slots, H, P, N]; x f32[slots, H, P]; dt, decay f32[slots, H]
    (the softplus'd step and exp(-exp(A_log) * dt)); bmat, cmat
    f32[slots, N]; mode i32[slots] (module docstring).
    Returns (y f32[slots, H, P] = S_new C, state)."""
    xt = (x * dt[..., None]).transpose(0, 2, 1)  # [slots, P, H]
    bc = jnp.stack([bmat, cmat], axis=1)
    yt, state = _ssm_step(
        jnp.asarray(layer, jnp.int32).reshape(1), mode.astype(jnp.int32),
        decay.reshape(-1).astype(jnp.float32), state, xt.astype(jnp.float32),
        bc.astype(jnp.float32), interpret=interpret)
    return yt.transpose(0, 2, 1), state
