"""One decode step of a power-retention layer's state, in place, on the
layer-stacked state — Pallas TPU kernel, the sibling of ``kda_step.py``
(ops/power.py has the equations, the diagonal order of the expanded dims and
why the state is held in whole tiles):

    S = exp(gamma) S + [v; 1] phi(k)^T      S in R^{(D + 1) x R} a kv head
    [y~; n] = S phi(q)                      for each of the kv head's J
                                            query heads, from the ONE pass

The state of every retention layer lives in ONE array ``[L, slots, G, rows,
lanes]`` float32 (models/llama.RecurrentState; rows = D + 1 and lanes = R =
D (D + 1) / 2 rounded up to whole tiles): a decode step reads and writes each
advancing slot's ``G rows lanes 4`` bytes once a layer (36.2 MB at G = 8,
D = 128) and nothing else of it, so the kernel takes the whole stack, the
layer rides as scalar prefetch into the BlockSpec index maps, and the stack
aliases its output. A slot's state does not fit VMEM:

Grid: (slots, kv heads); a block is one kv head's ``[rows, lanes]`` (4.5 MB).
The expanded dims lie on LANES, a diagonal of D of them a lane tile: the
diagonal g of phi(u) is u times u rotated by g lanes, so phi of k and of
the J query heads is built in VMEM from one row each (6 x 65 rotations and
products a block, against 17 x 65 tiles of state), and never exists in
HBM. The walk then takes 8 value rows at a time across the lanes, diagonal
after diagonal (the last one ragged: D / 2 of its D lanes are pairs, the
rest is the state's zero padding, and phi is zero there): decay, add v (a
column, broadcast along lanes) times phi(k) (a row, equal on all sublanes),
write back, and accumulate the tile times each query head's phi(q) into that
head's [8, D] accumulator, which is reduced over lanes once a row group. The
normaliser's row rides in the last row group, whose other rows are padding.

q, k and the decay come as rows ``[slots, G, J + 2 (+ pad), D]`` (the decay
on every lane of its row), v as a column ``[slots, G, rows, 1]`` with its 1;
the outputs leave as ``[slots, G, rows, 128]``, query head j of the group in
lane j.

``mode[slot]`` (SMEM) as in ``kda_step``: 0 = leave the slot (its blocks are
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

from dllama_tpu.ops.power import state_dims, with_one

OUT_LANES = 128  # query head j of a group leaves in lane j


def supported(state_shape: tuple[int, ...], dtype, head_size: int) -> bool:
    """[L, slots, G, rows, lanes] as `ops/power.state_dims` gives them for
    the head size, whole 128-lane diagonals, and a 32-bit state (a narrower
    state is the jnp path's)."""
    return (head_size % 128 == 0 and jnp.dtype(dtype).itemsize == 4
            and tuple(state_shape[-2:]) == state_dims(head_size))


def _kernel(layer_ref, mode_ref,  # scalar prefetch (SMEM)
            s_ref, qk_ref, v_ref,  # VMEM blocks
            o_ref, so_ref, phi_ref, *, d: int, j: int):
    mode = mode_ref[pl.program_id(0)]
    half = d // 2

    @pl.when(mode == 0)
    def _():
        so_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(mode != 0)
    def _():
        keep = mode == 1
        # phi of the J query heads (rows 0 .. J - 1) and of k (row J), a
        # diagonal a [8, D] tile whose sublanes are equal
        pairs = jax.lax.broadcasted_iota(jnp.int32, (8, d), 1) < half
        for r in range(j + 1):
            u = jnp.broadcast_to(qk_ref[r:r + 1, :], (8, d)) * 2.0 ** 0.25
            phi_ref[r, 0] = u * u * 0.5 ** 0.5
            for g in range(1, half):
                phi_ref[r, g] = u * pltpu.roll(u, d - g, 1)
            phi_ref[r, half] = jnp.where(pairs, u * pltpu.roll(u, half, 1), 0.0)
        decay = jnp.broadcast_to(qk_ref[j + 1:j + 2, :], (8, d))
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, OUT_LANES), 1)

        def group(i, carry):
            """Value rows [8 i, 8 i + 8) across every diagonal."""
            at = pl.ds(pl.multiple_of(i * 8, 8), 8)
            vb = jnp.broadcast_to(v_ref[at, :], (8, d))
            acc = [jnp.zeros((8, d), jnp.float32) for _ in range(j)]
            for g in range(half + 1):
                lanes = pl.ds(g * d, d)
                s = (jnp.where(keep, s_ref[at, lanes], 0.0) * decay
                     + vb * phi_ref[j, g])
                so_ref[at, lanes] = s
                for r in range(j):
                    acc[r] = acc[r] + s * phi_ref[r, g]
            out = jnp.zeros((8, OUT_LANES), jnp.float32)
            for r in range(j):
                out = jnp.where(lane == r,
                                jnp.sum(acc[r], axis=1, keepdims=True), out)
            o_ref[at, :] = out
            return carry

        jax.lax.fori_loop(0, s_ref.shape[0] // 8, group, 0)


@functools.partial(jax.jit, static_argnames=("j", "interpret"))
def _retention_step(layer, mode, state, qk, v, *, j: int,
                    interpret: bool = False):
    """layer i32[1], mode i32[slots], state f32[L, slots, G, rows, lanes],
    qk f32[slots, G, 8 n, D] (the group's `j` query heads, k, the decay), v
    f32[slots, G, rows, 1] -> (o f32[slots, G, rows, 128], state).

    The name, and the 5-D state in the result, are what the benchmark's
    trace reader finds this call by (benchmark/costs/retention_step.py)."""
    _, slots, groups, rows, lanes = state.shape
    d = qk.shape[-1]
    block_bytes = rows * lanes * 4
    state_spec = pl.BlockSpec((None, None, None, rows, lanes),
                              lambda b, g, L, *_: (L[0], b, g, 0, 0))
    per = lambda *tail: pl.BlockSpec((None, None, *tail),
                                     lambda b, g, *_: (b, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, groups),
        in_specs=[state_spec, per(qk.shape[2], d), per(rows, 1)],
        out_specs=[per(rows, OUT_LANES), state_spec],
        scratch_shapes=[pltpu.VMEM((j + 1, d // 2 + 1, 8, d), jnp.float32)],
    )
    o, state = pl.pallas_call(
        functools.partial(_kernel, d=d, j=j),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((slots, groups, rows, OUT_LANES), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # after the 2 scalar-prefetch args: state=2 aliases output 1
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state block in and out, double-buffered, + phi and the
            # small operands
            vmem_limit_bytes=4 * block_bytes + 12 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=(4 + 2 * j) * slots * groups * rows * lanes,
            bytes_accessed=2 * slots * groups * block_bytes,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, mode, state, qk, v)
    return o, state


def retention_step(state, layer, q, k, v, decay, mode, *,
                   interpret: bool = False):
    """The decode step of layer `layer` of the stacked state, in place.

    state f32[L, slots, G, rows, lanes]; q [slots, H, D] (query heads g J ..
    g J + J - 1 read kv head g); k, v [slots, G, D]; decay f32[slots, G]
    (= exp(gamma)); mode i32[slots] (module docstring). Returns ([y~; n; ...]
    f32[slots, H, rows] = S_new phi(q), state)."""
    f32 = jnp.float32
    slots, h, d = q.shape
    g, rows = k.shape[1], state.shape[-2]
    j = h // g
    assert j <= OUT_LANES, "a group's query heads leave in a lane each"
    pad = jnp.zeros((slots, g, -(j + 2) % 8, d), f32)
    qk = jnp.concatenate([
        q.astype(f32).reshape(slots, g, j, d), k.astype(f32)[:, :, None],
        jnp.broadcast_to(decay.astype(f32)[..., None, None], (slots, g, 1, d)),
        pad], axis=2)
    o, state = _retention_step(
        jnp.asarray(layer, jnp.int32).reshape(1), mode.astype(jnp.int32),
        state, qk, with_one(v, rows)[..., None], j=j, interpret=interpret)
    return o[..., :j].transpose(0, 1, 3, 2).reshape(slots, h, rows), state
