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
   * ``blockdot`` (m <= 16, bf16 activations): decode streams every weight
     once for a handful of rows, so what counts is bytes a grid step and
     vector ops a weight. The kernel never builds the dequantized matrix and
     never widens a byte: a tile of packed rows is read as 32-bit words
     (``pltpu.bitcast``), and shift / and / or on whole words leave two bf16
     codes a word, ``16 + q`` exactly (``_unpack_words``: 1.5 vector ops a
     vreg of weights, where the byte-wise unpack cost 4.5). The MXU takes
     128 rows at a time against x masked to each Q40 block's lanes, four
     blocks stacked along the rows (``_group_dot``), which gives the
     per-block partial dots y[b] = x_b @ codes_b; the f32 block scales meet
     only those partials, out = sum_b s[b] * y[b], and the codes' constant
     offset leaves as 24 * sum_b s[b] * xsum[b], a small exact dot a grid
     step. x stays in VMEM for the whole call; the kernel lays it out in its
     row order itself, at its first grid step (``_layout_constants``: no XLA
     op prepares anything); tiles come from the weight's shape by a cost of
     bytes, grid steps and loop passes (``_blockdot_tiles``): a grid step
     under 256 KB of packed bytes costs more than it moves (PR 32 measured
     64 KB steps at 22% of the byte roofline).

Layout (see ops/quant.QTensor): ``packed: u8[(L,) k/2, n]`` where packed row
``16*b + j`` holds codes for input dims ``32*b + j`` (low nibble) and
``32*b + j + 16`` (high nibble); ``scales: f16[(L,) k/32, n]`` (streamed as
raw u16 bits, widened in-register by ``_scales_f32``).

Grid is ((m_tiles,) n_tiles, k_tiles) with k innermost: the f32 accumulator
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

# kernel-style override for the chip benches (experiments/kbench.py,
# q40_decode_bench.py): 'auto' | 'deq' | 'blockdot'. 'auto' is what serves:
# blockdot for m <= 16, deq above; a forced 'blockdot' still applies only to
# decode-shaped calls.
STYLE = "auto"


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


# bf16 16.0 in both halves of a 32-bit word; the float ulp at [16, 32) is 1/8,
# so a nibble q placed at mantissa bits 3..6 reads 16 + q exactly
_W_EXP = 0x41804180
_W_MASK = 0x00780078
_W_OFFSET = 24.0  # (16 + q) - 24 = q - 8


def _unpack_words(w):
    """u32[r, tn] words of four packed bytes -> four bf16[2r, tn] arrays of
    exact codes 16 + q, whole words at a time (shift, and, or: 12 vector ops
    a word of eight weights, no widening of a byte). Word row s holds packed
    rows 4s..4s+3 (`pltpu.bitcast` of the u8 tile); array i takes from it the
    low (i even) or high (i odd) nibble of bytes i // 2 and i // 2 + 2, which
    land in bf16 rows 2s and 2s + 1: `_position` is that map."""
    mask, exp = jnp.uint32(_W_MASK), jnp.uint32(_W_EXP)
    words = (w << 3, w >> 1, w >> 5, w >> 9)
    return [pltpu.bitcast((v & mask) | exp, jnp.bfloat16) for v in words]


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


#: what the kernel walks k by: eight Q40 blocks, one f32 tile of their scales
_SUB_K = 256
#: rows the MXU takes a pass: four Q40 blocks, whose codes `_unpack_words`
#: leaves in the order `_position` gives
_GROUP = 128
#: groups whose block sums share one 128-lane row (a block a lane): 4096 k rows
_CHUNK = 32


def _position(src):
    """Where input dim `src` of a 128-row group lands among the rows
    `_unpack_words` unpacks it to: arrays i = 2*i1 + nib of 32 rows each,
    in them block b, packed row pair jh, half h (a permutation of the bits of
    the index: src = 32*b + 16*nib + 4*jh + 2*h + i1)."""
    i1, h, jh, nib, b = src & 1, (src >> 1) & 1, (src >> 2) & 3, (src >> 4) & 1, src >> 5
    return 64 * i1 + 32 * nib + 8 * b + 2 * jh + h


def _layout_constants(groups: int):
    """The two 0/1 matrices that lay x out for the kernel, through the MXU
    (exact: one 1 a column): `place[src, 128*b + p]` moves dim src of a
    group to position p, on the copy kept for block b alone (x masked to a
    block's lanes, the four blocks side by side); `sums[g, src, 4*g + b]`
    adds dim src of group g into lane 4*g + b of its chunk's row of block
    sums."""
    src = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 4 * _GROUP), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 4 * _GROUP), 1)
    place = ((col & 127) == _position(src)) & ((col >> 7) == (src >> 5))
    g = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 0)
    src = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 2)
    sums = lane == 4 * g + (src >> 5)
    # (selected as f32, then narrowed: a mask has the 32-bit layout)
    as_bf16 = lambda mask: jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)
    return as_bf16(place), as_bf16(sums)


def _group_dot(xa, codes):
    """[g, 4m, 128] x [g, 128, lanes] -> f32[g, 4m, lanes], a 128-row group
    a batch: rows b*m.. of a group are its block b's partial dot, since `xa`
    holds x on block b's lanes there and 0 elsewhere."""
    return jax.lax.dot_general(xa, codes, (((2,), (1,)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _scaled(y, sb):
    """sum_b y[b] * sb[b] over the blocks of a pass: the f32 scales meet the
    partial dots (y [blocks, m, lanes], sb [blocks, lanes])."""
    return (y * sb[:, None, :]).sum(axis=0)


def _bf16_parts(v, n: int):
    """f32 -> n f32 arrays that each fit bf16 (the top 16 bits of what is
    left, cut by a mask: a rounding convert and its way back is a pair the
    compiler may drop) and sum to v exactly once n x 8 bits cover it: 3 for
    any f32, 2 for an f16's 11 bits."""
    parts = []
    for _ in range(n - 1):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32) & jnp.uint32(0xFFFF0000)
        parts.append(jax.lax.bitcast_convert_type(bits, jnp.float32))
        v = v - parts[-1]
    return parts + [v]


def _blockdot_kernel(
    layer_ref, x_ref, packed_ref, scales_ref, out_ref, xa_ref, xs_ref, s_ref,
    *, tk, tn, lanes, rows
):
    del layer_ref
    _blockdot_body(pl.program_id(0), pl.program_id(1), x_ref, packed_ref,
                   scales_ref, out_ref, xa_ref, xs_ref, s_ref,
                   tk=tk, tn=tn, lanes=lanes, rows=rows)


def _expert_kernel(
    layer_ref, expert_ref, src_ref, live_ref, x_ref, packed_ref, scales_ref,
    out_ref, xa_ref, xs_ref, s_ref, *, tk, tn, lanes, rows
):
    """The block-dot tier with an expert index beside the layer index: grid
    step (t, j, kb) is tile t of the rows in expert order against tile
    (kb, j) of that tile's expert. The inner loop is `_blockdot_body`'s; x
    is laid out again at each tile's first step (its rows are another
    expert's). Tiles behind the last live one do nothing and, their block
    indices frozen by the index maps, move nothing."""
    del layer_ref, expert_ref, src_ref
    t, j, kb = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(t < live_ref[0])
    def _():
        _blockdot_body(j, kb, x_ref, packed_ref,
                       scales_ref, out_ref, xa_ref, xs_ref, s_ref,
                       tk=tk, tn=tn, lanes=lanes, rows=rows)


def _blockdot_body(j, kb, x_ref, packed_ref, scales_ref, out_ref, xa_ref,
                   xs_ref, s_ref, *, tk, tn, lanes, rows):
    """Grid step (j, kb) of x[m, k] against one Q40 weight: what
    `_blockdot_kernel` (one weight a call) and `_expert_kernel` (one weight a
    tile of rows) both run."""
    m = out_ref.shape[0]
    nb = tk // Q_BLOCK
    per_chunk = min(_CHUNK, xa_ref.shape[0])  # groups a chunk

    @pl.when((j == 0) & (kb == 0))
    def _():
        # Once a call, for every grid step to read: x in the kernel's row
        # order and masked to each Q40 block's lanes, the four blocks stacked
        # along rows (one 128-deep pass then gives the four blocks' partial
        # dots), and x's block sums as three bf16 parts (and a zero one, to
        # whole tiles) for the codes' offset. Both through the MXU, 4096 k
        # rows a pass: no XLA op prepares anything for this call.
        place, sums = _layout_constants(per_chunk)

        def chunk(c, carry):
            base = pl.multiple_of(c * (per_chunk * _GROUP), per_chunk * _GROUP)
            xc = jnp.concatenate(
                [x_ref[:, pl.ds(base + g * _GROUP, _GROUP)] for g in range(per_chunk)],
                axis=0)  # [groups * m, 128], a group's m rows together
            z = jnp.dot(xc, place, preferred_element_type=jnp.float32)
            xa = jnp.concatenate(
                [z[:, _GROUP * b:_GROUP * (b + 1)].reshape(per_chunk, m, _GROUP)
                 for b in range(4)], axis=1)  # [groups, 4m, 128]
            xa_ref[pl.ds(c * per_chunk, per_chunk)] = xa.astype(xa_ref.dtype)
            xsum = _group_dot(xc.reshape(per_chunk, m, _GROUP), sums).sum(axis=0)
            parts = _bf16_parts(xsum, 3) + [jnp.zeros_like(xsum)]
            xs_ref[c] = jnp.concatenate(parts, axis=0).astype(xs_ref.dtype)
            return carry

        jax.lax.fori_loop(0, xs_ref.shape[0], chunk, 0)

    @pl.when(kb == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    if s_ref.shape[0] != nb:  # rows past the tile's blocks meet zero sums
        s_ref[:] = jnp.zeros_like(s_ref)
    s_ref[0:nb, :] = _scales_f32(scales_ref[:])

    def lane_step(c, carry):
        l0 = pl.multiple_of(c * lanes, lanes)

        def sweep(at, rows, acc):
            """`rows` k rows from row `at(1)` of the tile, as whole-array
            ops: a loop pass waits for its MXU results once (0.15 us), so it
            is made as long as `_inner` allows and nothing is unrolled by
            hand. `at(d)` is that row over d: the offsets of the packed rows
            (2), the scales (32) and the groups (128), whole tiles each."""
            groups, blocks = rows // _GROUP, rows // Q_BLOCK
            w = pltpu.bitcast(packed_ref[pl.ds(at(2), rows // 2), pl.ds(l0, lanes)],
                              jnp.uint32)  # [rows / 8, lanes]
            # the pass's 128-row groups, one batch each of the dot
            codes = jnp.concatenate(
                [t.reshape(groups, 32, lanes) for t in _unpack_words(w)], axis=1)
            xa = xa_ref[pl.ds(kb * (tk // _GROUP) + at(_GROUP), groups)]
            sb = s_ref[pl.ds(at(Q_BLOCK), blocks), pl.ds(l0, lanes)]
            y = _group_dot(xa, codes)
            return acc + _scaled(y.reshape(blocks, m, lanes), sb)

        acc = jax.lax.fori_loop(
            0, tk // rows,
            lambda i, acc: sweep(lambda d: pl.multiple_of(i * (rows // d), _SUB_K // d),
                                 rows, acc),
            jnp.zeros((m, lanes), jnp.float32))
        if tk % rows:  # what the whole passes leave of a k like 43 x 256
            acc = sweep(lambda d: tk // rows * rows // d, tk % rows, acc)
        # the codes read 16 + q: take 24 * sum_b xsum[b] * s[b] off, exactly
        # (an f16 scale is two bf16 parts, a block sum three; f32 sums), a
        # chunk of 128 blocks a dot
        chunks = s_ref.shape[0] // _GROUP
        off = sum(jnp.dot(xs_ref[kb * chunks + c],
                          part[c * _GROUP:(c + 1) * _GROUP].astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
                  for part in _bf16_parts(s_ref[:, pl.ds(l0, lanes)], 2)
                  for c in range(chunks))
        off = off[0:m] + off[m:2 * m] + off[2 * m:3 * m]
        out_ref[:, pl.ds(l0, lanes)] += acc - _W_OFFSET * off
        return carry

    jax.lax.fori_loop(0, tn // lanes, lane_step, 0)


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


#: What the chip charges beyond a call's bytes, in KB of HBM time at the 600
#: GB/s a weight stream sustains (fitted to `experiments/kbench.py q40`'s
#: tile sweep, my chip runs, PR 32): a grid step 0.17 us, a pass of the
#: kernel's inner loop (whose MXU results it waits for) 0.15 us, and the
#: first tile's copy, which hides behind nothing.
_STEP_KB, _PASS_KB, _FIRST_TILE = 100, 90, 0.65
#: weights one pass of the inner loop covers (what hides its latency)
_PASS_WEIGHTS = 1 << 21
#: packed bytes a grid step moves at least where the weight allows it, and
#: what a step's buffers may take of VMEM
_STEP_FLOOR, _VMEM_BUDGET = 256 * 1024, 24 * 1024 * 1024


def _inner(tk: int, tn: int) -> tuple[int, int]:
    """(lanes, rows) of a pass of the kernel's inner loops for a tile: the
    widest of 512 / 256 / 128 lanes dividing tn, and the k rows (whole loop
    steps of 256) that make the pass cover `_PASS_WEIGHTS`."""
    lanes = next(w for w in (512, 256, 128) if tn % w == 0)
    return lanes, min(tk, _PASS_WEIGHTS // lanes // _SUB_K * _SUB_K)


@functools.lru_cache(maxsize=None)
def _blockdot_tiles(k: int, n: int) -> tuple[int, int]:
    """(tk, tn) for the m <= 16 kernel from the weight's shape alone: the
    tile that costs least by the figures above, among tn a multiple of 128
    dividing n and tk the whole of k or whole chunks of 4096 dividing it (a
    tile's block sums are then whole rows, its scales whole u16 tiles), within
    the VMEM budget, no tile under `_STEP_FLOOR` and no weight of two floors
    or more in one step, where its divisors leave another choice."""
    best = None
    for tn in (t for t in range(128, n + 1, 128) if n % t == 0):
        for tk in [k] + [t for t in range(_CHUNK * _GROUP, k, _CHUNK * _GROUP) if k % t == 0]:
            nb = tk // Q_BLOCK
            # two buffers of packed rows and u16 scales, the scales as f32
            vmem = 2 * (tk * tn // 2 + nb * tn * 2) + -(-nb // _GROUP) * _GROUP * tn * 4
            if vmem > _VMEM_BUDGET:
                continue
            steps = (k // tk) * (n // tn)
            # kept for a weight whose divisors leave nothing else: a tile
            # under the floor, one step that overlaps no copy with any work
            odd = (tk * tn // 2 < min(_STEP_FLOOR, k * n // 4)
                   or steps == 1 and k * n // 2 >= 2 * _STEP_FLOOR)
            lanes, rows = _inner(tk, tn)
            passes = steps * (tn // lanes) * -(-tk // rows)
            cost = (steps * _STEP_KB + passes * _PASS_KB
                    + _FIRST_TILE * tk * tn / 2048)
            if best is None or (odd, cost, -tk) < best[0]:
                best = ((odd, cost, -tk), tk, tn)
    return best[1], best[2]


def _x_and_scratch(x, tk: int, tn: int, m: int | None = None):
    """What `_blockdot_body` works on besides the weight, for rows of `m`
    (default: all of x's): x as it goes in (padded to whole chunks of 4096
    dims where k has a part chunk: the kernel lays it out itself at a
    tile's first grid step) and the three VMEM scratches: x by group and
    block, x's block sums, the tile's scales as f32 in whole chunks."""
    m = m or x.shape[0]
    k = x.shape[1]
    groups = k // _GROUP
    per_chunk = min(_CHUNK, groups)
    chunks = -(-groups // per_chunk)
    kp = chunks * per_chunk * _GROUP
    if kp != k:
        x = jnp.pad(x, ((0, 0), (0, kp - k)))
    nbp = -(-(tk // Q_BLOCK) // _GROUP) * _GROUP
    return x, [pltpu.VMEM((kp // _GROUP, 4 * m, _GROUP), x.dtype),
               pltpu.VMEM((chunks, 4 * m, _GROUP), x.dtype),
               pltpu.VMEM((nbp, tn), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("interpret", "tk", "tn", "lanes", "rows"))
def _blockdot_call(layer, x, packed, scales, *, interpret: bool = False,
                   tk: int | None = None, tn: int | None = None,
                   lanes: int | None = None, rows: int | None = None):
    """Decode-shaped path: bf16 x[m<=16, k] against stacked Q40 weights ->
    f32[m, n]. tk / tn / lanes / rows are the chip sweep's overrides
    (`experiments/kbench.py q40`); serving passes none and runs
    `_blockdot_tiles`."""
    m, k = x.shape
    n = packed.shape[-1]
    dtk, dtn = _blockdot_tiles(k, n)
    tk, tn = tk or dtk, tn or dtn
    assert m % 16 == 0 and k % _SUB_K == 0 and (tk == k or tk % (_CHUNK * _GROUP) == 0)
    nb = tk // Q_BLOCK
    dlanes, drows = _inner(tk, tn)
    lanes, rows = lanes or dlanes, rows or drows
    x, scratch = _x_and_scratch(x, tk, tn)
    kp = x.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // tn, k // tk),
        in_specs=[
            pl.BlockSpec((m, kp), lambda j, kb, L: (0, 0)),
            pl.BlockSpec((None, tk // 2, tn), lambda j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, nb, tn), lambda j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((m, tn), lambda j, kb, L: (0, j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_blockdot_kernel, tk=tk, tn=tn, lanes=lanes, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the first grid step builds what every later one reads
            dimension_semantics=("arbitrary", "arbitrary"),
            # all but a quarter of VMEM is this call's to claim, so XLA's
            # memory-space assignment cannot park a whole stacked scales
            # array there (it copied 115 MB a decode step in slices, waits
            # included: `slice-done`, PERF.md section 6, PR 32)
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * 2 + k * n // 2 + (k // Q_BLOCK) * n * scales.dtype.itemsize + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, x, packed, scales)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _expert_call(layer, tile_expert, tile_src, n_live, x, packed, scales, *,
                 tm: int, interpret: bool = False):
    """bf16 x[T*tm, k], rows in expert order and padded to whole tiles of tm,
    against the stacked experts packed u8[L, E, k/2, n] -> f32[T*tm, n]: tile t
    meets expert tile_expert[t] of layer `layer`. The name and the 4-D packed
    operand are what the benchmark's trace reader finds this call by
    (benchmark/costs/moe_experts.py)."""
    rows_total, k = x.shape
    n = packed.shape[-1]
    tiles = rows_total // tm
    tk, tn = _blockdot_tiles(k, n)
    assert tm % 16 == 0 and k % _SUB_K == 0 and (tk == k or tk % (_CHUNK * _GROUP) == 0)
    nb = tk // Q_BLOCK
    lanes, rows = _inner(tk, tn)
    x, scratch = _x_and_scratch(x, tk, tn, m=tm)
    kp = x.shape[1]
    nj, nkb = n // tn, k // tk

    def weight_map(t, j, kb, L, E, S, N):
        # a dead tile keeps the last live step's block: nothing is copied
        dead = t >= N[0]
        return (L[0], E[t], jnp.where(dead, nkb - 1, kb),
                jnp.where(dead, nj - 1, j))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer[1], tile_expert[T], tile_src[T], n_live[1]
        grid=(tiles, nj, nkb),
        in_specs=[
            pl.BlockSpec((tm, kp), lambda t, j, kb, L, E, S, N: (S[t], 0)),
            pl.BlockSpec((None, None, tk // 2, tn), weight_map),
            pl.BlockSpec((None, None, nb, tn), weight_map),
        ],
        out_specs=pl.BlockSpec(
            (tm, tn), lambda t, j, kb, L, E, S, N: (
                S[t], jnp.where(t >= N[0], nj - 1, j))),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        functools.partial(_expert_kernel, tk=tk, tn=tn, lanes=lanes, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_total, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows_total * n * k,
            # at most one expert a tile; the trace's reader counts the
            # experts really touched (benchmark/costs/moe_experts.py)
            bytes_accessed=rows_total * k * 2 + rows_total * n * 4
            + min(tiles, packed.shape[1]) * (k * n // 2 + (k // Q_BLOCK) * n * 2),
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, tile_expert, tile_src, n_live, x, packed, scales)


def expert_supported(w, dtype) -> bool:
    """Whether `q40_expert_matmul` takes an expert stack (QTensor [.., E, k,
    n]) with activations of `dtype`: the block-dot tier's own terms."""
    k, n = w.shape[-2], w.shape[-1]
    return (isinstance(w, QTensor) and jnp.dtype(dtype) == jnp.bfloat16
            and k % _SUB_K == 0 and n % 128 == 0)


def q40_expert_matmul(x: jax.Array, w: QTensor, *, layer, tile_expert,
                      tile_src, n_live, tm: int,
                      interpret: bool = False) -> jax.Array:
    """Rows in expert order (ops/layers.expert_groups) x the experts' Q40
    weights -> f32[T*tm, n]. `w` is the layer-stacked [L, E, k, n] expert
    weight with `layer` a traced index, or one layer's [E, k, n]; either is
    indexed by the DMA engine, never sliced or dequantised by XLA."""
    assert expert_supported(w, x.dtype), (w.shape, x.dtype)
    packed, scales = w.packed, w.scales
    if packed.ndim == 3:
        packed, scales, layer = packed[None], scales[None], 0
    if scales.dtype == jnp.float16:
        scales = jax.lax.bitcast_convert_type(scales, jnp.uint16)
    return _expert_call(
        jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, tile_src,
        jnp.asarray(n_live, jnp.int32).reshape(1), x, packed, scales, tm=tm,
        interpret=interpret)


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
        # kernels take raw u16 bits (see _scales_f32; Mosaic takes no f16
        # argument); the bitcast is free
        scales = jax.lax.bitcast_convert_type(scales, jnp.uint16)
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    x2 = x.reshape(m, k)
    # the block-dot kernel carries its codes in bf16, walks k by eight blocks
    # and takes 16 rows (a whole bf16 tile): other activations, depths and
    # batches take the dequantising tier, padded to the f32 sublane (8)
    blockdot = (STYLE != "deq" and m <= 16 and k % _SUB_K == 0
                and x2.dtype == jnp.bfloat16)
    pad = (-m) % (16 if blockdot else 8)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if blockdot:
        out = _blockdot_call(layer_arr, x2, packed, scales, interpret=interpret)
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
