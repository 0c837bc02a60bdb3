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

   * ``deq`` (m > 16): dequantise a weight, then one plain dot of m rows a
     pass. What bound the byte-wise form of this tier was its grid (512 x
     512 tiles: 32-128 KB a step, the copies alone 70% of a call; PR 37),
     then vector work a weight. A grid step now moves 0.5-2 MB, the result's
     whole width where a pass holds it (``_deq_tiles``); inside it the packed
     rows are read as 32-bit words and every nibble becomes the f32 product
     (q - 8) * s in four vector ops (``_dequant_words``: xor, shift, mask, an
     int32 convert that reads (q - 8) * 2^28, one multiply by the scale
     times 2^-28), rounded once to the activation dtype as before. x stays
     in VMEM for the call, laid out in the planes' row order by the kernel
     itself at an m tile's first step (``_deq_position``, through the MXU).
     Dequantising costs the same for any m, so prefill is MXU-bound.
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

3. **The grouped expert call has a tile walk of its own** (`_expert_call`:
   rows in expert order against the stacked experts ``[L, E, k/2, n]``, the
   expert of a rows tile scalar-prefetched beside the layer). The arithmetic
   is the two tiers'; what differs is what a tile pays beyond its expert's
   bytes, because a decode step's tile holds 1-3 real rows against a 1.1-1.3
   MB expert where a dense call holds 16 against 9-26 MB (priced part by
   part on the chip, PERF.md section 6, PR 39: the dense body a tile read
   3.3-4.3 us a touched expert of which 1.4 were the bytes):

   * the grid is ``(n_live, n tiles)``, its first bound the traced count of
     tiles that hold a row: a dead tile costs nothing (a frozen grid step
     cost 0.3-0.5 us, 20-44 of them a call), and its rows are never written;
   * a grid step holds the WHOLE depth of an expert, the whole expert where
     4 MB of packed bytes hold it (`_expert_inner`): one step a touched
     expert, not two or three, the next expert's copy running behind it;
     two tiles of one expert follow each other and the second finds the
     weight's block in place;
   * a 16-row tile (`_expert_kernel`) runs the block-dot arithmetic in one
     pass of the whole depth and as many columns as `_EXPERT_PASS_WEIGHTS`
     allows (a 768 x 2560 expert was ten passes of 0.2 M weights, each
     waiting for its MXU results), writes its result once (nothing zeroed,
     nothing accumulated across steps), lays its rows out with two dots
     against 0/1 matrices the call builds ONCE in VMEM (`_expert_constants`;
     rebuilt a tile they were 0.1-0.3 us of vector work), and zeroes the
     scales scratch's spare rows once a call;
   * a taller tile (`_expert_deq_kernel`: a prefill slice, tiles of 32 rows
     and more by `ops/layers.expert_tile_rows`) runs the dequantising
     tier's body, m rows through the MXU a pass where the block-dot body
     streams 4 m. The choice is static (by the tile's height alone).

Layout (see ops/quant.QTensor): ``packed: u8[(L,) k/2, n]`` where packed row
``16*b + j`` holds codes for input dims ``32*b + j`` (low nibble) and
``32*b + j + 16`` (high nibble); ``scales: f16[(L,) k/32, n]`` (streamed as
raw u16 bits, widened in-register by ``_scales_f32``).

Grid of the dense calls is ((m_tiles,) n_tiles, k_tiles) with k innermost:
the f32 result block stays VMEM-resident across the k sweep and is written
back once per (m, n) tile. Inputs are double-buffered by the Pallas pipeline
automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.quant import Q_BLOCK, QTensor

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


#: k rows whose eight nibble planes are one bf16 tile (16 word rows) each:
#: what the dequantising tier lays x out by, and what its k must be whole in
_DEQ_GROUP = 128
#: groups of x laid out a pass of the first grid step's loop
_DEQ_LAY = 8
#: (q - 8) * 2^28 is what a nibble reads as at the top of an int32 word
_TOP_NIBBLE = 2.0 ** -28


def _deq_position(src):
    """Where input dim `src` of a 128-dim group lands among the rows
    `_dequant_words` leaves: plane e = 2 * byte + nibble of the group's 16
    word rows, in it block b's packed row quad j4 (src = 32*b + 16*nibble +
    4*j4 + byte: word row 4*b + j4 holds packed rows 16*b + 4*j4 + byte)."""
    byte, j4, nib, b = src & 3, (src >> 2) & 3, (src >> 4) & 1, src >> 5
    return 16 * (2 * byte + nib) + 4 * b + j4


def _scale_rows(s):
    """f32[nb, tn] block scales -> f32[4 nb, tn], a block's row under each
    of its four word rows: whole tiles of two blocks, chosen by sublane."""
    nb, tn = s.shape
    pair = jnp.broadcast_to(s[:, None, :], (nb, 8, tn)).reshape(nb // 2, 2, 8, tn)
    first = jax.lax.broadcasted_iota(jnp.int32, (nb // 2, 8, tn), 1) < 4
    return jnp.where(first, pair[:, 0], pair[:, 1]).reshape(4 * nb, tn)


def _nibble_planes(w):
    """u32[r, tn] words of whole 128-dim groups -> f32[r / 16, 8, 16, tn],
    (q - 8) * 2^28 exactly, a group's eight nibble planes side by side. No
    byte is widened: one xor a word turns every nibble into q - 8 in two's
    complement, a shift and a mask put it at the top of the word, where an
    int32 convert reads it. The planes are one array's leading axis, so the
    kernel's traced size does not grow with them (a kernel is lowered again
    at every call site of every program)."""
    r, tn = w.shape
    w = (w ^ jnp.uint32(0x88888888)).reshape(r // 16, 1, 16, tn)
    shift = 28 - 4 * jax.lax.broadcasted_iota(jnp.uint32, (r // 16, 8, 16, tn), 1)
    v = (w << shift) & jnp.uint32(0xF0000000)
    return jax.lax.bitcast_convert_type(v, jnp.int32).astype(jnp.float32)


def _dequant_words(w, sb, dtype):
    """u32[r, tn] words and f32[r, tn] scale rows (`_scale_rows`, times
    2^-28) -> dtype[8 r, tn] dequantised weights (q - 8) * s, a group's rows
    in `_deq_position` order: the f32 product of q - 8 and s (a power of two
    apart), rounded once to `dtype`: four vector ops and the rounding a vreg
    of weights."""
    r, tn = w.shape
    planes = _nibble_planes(w) * sb.reshape(r // 16, 1, 16, tn)
    return planes.astype(dtype).reshape(8 * r, tn)


def _deq_dot(xa, w):
    """[m, rows] x [rows, tn] -> f32[m, tn]: m rows through the MXU a pass."""
    return jnp.dot(xa, w, preferred_element_type=jnp.float32)


def _deq_kernel(layer_ref, x_ref, packed_ref, scales_ref, out_ref, xa_ref, s_ref,
                *, rows):
    """Grid step (i, j, kb): rows tile i of x against tile (kb, j) of one
    layer's weight."""
    del layer_ref  # consumed by the index maps
    _deq_body(pl.program_id(1), pl.program_id(2), x_ref, packed_ref, scales_ref,
              out_ref, xa_ref, s_ref, rows=rows)


def _expert_deq_kernel(layer_ref, expert_ref, src_ref, live_ref, x_ref, packed_ref,
                       scales_ref, out_ref, xa_ref, s_ref, *, rows):
    """Grid step (t, j) of the grouped expert call where a tile is taller
    than 16 rows (a prefill slice): rows tile t against the whole depth of
    columns tile j of that tile's expert, through the dequantising tier's
    body (m rows through the MXU a pass, where the block-dot body streams
    4 m: from 32 rows a tile on that binds, PR 37). Its needs are the dense
    call's: x laid out once a rows tile, at the tile's first step."""
    del layer_ref, expert_ref, src_ref, live_ref  # the index maps' and the grid's
    _deq_body(pl.program_id(1), 0, x_ref, packed_ref, scales_ref, out_ref, xa_ref,
              s_ref, rows=rows)


def _deq_body(j, kb, x_ref, packed_ref, scales_ref, out_ref, xa_ref, s_ref, *, rows):
    """Step (j, kb) of a rows tile of x against tile (kb, j) of one Q40
    weight, dequantised `rows` k rows at a time as whole-array ops and fed
    to one plain dot a pass (m rows through the MXU, not 4 m): what
    `_deq_kernel` (one weight a call) and `_expert_deq_kernel` (one expert
    a tile of rows) both run."""
    tm, k = x_ref.shape
    tk = 2 * packed_ref.shape[0]
    nb = tk // Q_BLOCK
    # a float32 x keeps its bits through the 0/1 matrix only at full precision
    exact = jax.lax.Precision.HIGHEST if x_ref.dtype == jnp.float32 else None

    @pl.when((j == 0) & (kb == 0))
    def _():
        # Once an m tile, for every grid step to read: x with each 128-dim
        # group in the order the planes dequantise to, through the MXU
        # (exact: one 1 a column), a few groups stacked along the rows a dot.
        src = jax.lax.broadcasted_iota(jnp.int32, (_DEQ_GROUP, _DEQ_GROUP), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (_DEQ_GROUP, _DEQ_GROUP), 1)
        place = jnp.where(col == _deq_position(src), 1.0, 0.0).astype(x_ref.dtype)

        def lay(base, groups):
            at = lambda g: pl.ds(base + g * _DEQ_GROUP, _DEQ_GROUP)
            xc = jnp.concatenate([x_ref[:, at(g)] for g in range(groups)], axis=0)
            z = jnp.dot(xc, place, preferred_element_type=jnp.float32,
                        precision=exact).astype(xa_ref.dtype)
            for g in range(groups):
                xa_ref[:, at(g)] = z[g * tm:(g + 1) * tm]

        span = _DEQ_LAY * _DEQ_GROUP

        def chunk(c, carry):
            lay(pl.multiple_of(c * span, span), _DEQ_LAY)
            return carry

        jax.lax.fori_loop(0, k // span, chunk, 0)
        if k % span:
            lay(k // span * span, k % span // _DEQ_GROUP)

    @pl.when(kb == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    s_ref[0:nb, :] = _scales_f32(scales_ref[:]) * _TOP_NIBBLE

    def sweep(at, rows):
        """`rows` k rows from row `at(1)` of the tile; `at(d)` is that row
        over d: the offsets of the packed rows (2) and the scales (32)."""
        w = pltpu.bitcast(packed_ref[pl.ds(at(2), rows // 2), :], jnp.uint32)
        sb = _scale_rows(s_ref[pl.ds(at(Q_BLOCK), rows // Q_BLOCK), :])
        xa = xa_ref[:, pl.ds(pl.multiple_of(kb * tk, _DEQ_GROUP) + at(1), rows)]
        out_ref[:] += _deq_dot(xa, _dequant_words(w, sb, xa_ref.dtype))

    if rows == tk:
        sweep(lambda d: 0, tk)
    else:
        def one(i, carry):
            sweep(lambda d: pl.multiple_of(i * (rows // d), _SUB_K // d), rows)
            return carry

        jax.lax.fori_loop(0, tk // rows, one, 0)


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
    place = _place_mask()
    g = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 0)
    src = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (groups, _GROUP, _GROUP), 2)
    sums = lane == 4 * g + (src >> 5)
    return _as_bf16(place), _as_bf16(sums)


def _place_mask():
    """`place` of `_layout_constants` as a mask [128, 512]."""
    src = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 4 * _GROUP), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, 4 * _GROUP), 1)
    return ((col & 127) == _position(src)) & ((col >> 7) == (src >> 5))


def _as_bf16(mask):
    """A mask as 0 / 1 in bf16 (selected as f32, then narrowed: a mask has
    the 32-bit layout)."""
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)


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
    """Grid step (j, kb) of x[m, k] against tile (kb, j) of one layer's Q40
    weight."""
    del layer_ref  # consumed by the index maps
    j, kb = pl.program_id(0), pl.program_id(1)
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


def _expert_constants(k: int, nbp: int):
    """The 0/1 matrices that lay a tile's rows out, through the MXU (exact:
    one 1 a column): `place` as `_layout_constants` gives it, and
    `sums[src, b]`, which adds input dim src into its Q40 block's lane b of
    the row of block sums (nbp lanes: whole 128-lane tiles)."""
    src = jax.lax.broadcasted_iota(jnp.int32, (k, nbp), 0)
    blk = jax.lax.broadcasted_iota(jnp.int32, (k, nbp), 1)
    return _as_bf16(_place_mask()), _as_bf16(blk == (src >> 5))


def _lay_rows(x_ref, place_ref, sums_ref, xa_ref, xs_ref):
    """A tile's rows for the grouped expert kernel, two dots against the 0/1
    matrices the call keeps in VMEM: x in the kernel's row order and masked
    to each Q40 block's lanes, the four blocks stacked along rows (one
    128-deep pass then gives the four blocks' partial dots), and x's block
    sums as three bf16 parts (and a zero one, to whole tiles) for the codes'
    offset."""
    m, k = x_ref.shape
    groups = k // _GROUP
    xc = jnp.concatenate(
        [x_ref[:, pl.ds(g * _GROUP, _GROUP)] for g in range(groups)], axis=0)
    z = jnp.dot(xc, place_ref[:], preferred_element_type=jnp.float32)
    xa_ref[:] = jnp.concatenate(
        [z[:, _GROUP * b:_GROUP * (b + 1)].reshape(groups, m, _GROUP)
         for b in range(4)], axis=1).astype(xa_ref.dtype)  # [groups, 4m, 128]
    xsum = jnp.dot(x_ref[:], sums_ref[:], preferred_element_type=jnp.float32)
    parts = _bf16_parts(xsum, 3) + [jnp.zeros_like(xsum)]
    xs_ref[:] = jnp.concatenate(parts, axis=0).astype(xs_ref.dtype)


def _expert_kernel(
    layer_ref, expert_ref, src_ref, live_ref, x_ref, packed_ref, scales_ref,
    out_ref, place_ref, sums_ref, xa_ref, xs_ref, s_ref, *, lanes
):
    """Grid step (t, j) of the grouped expert call at 16 rows a tile: tile t
    of the rows in expert order against the WHOLE depth of columns tile j of
    that tile's expert (a 1-1.3 MB expert is one step: j has one value). The
    arithmetic is the block-dot tier's (`_unpack_words`, `_group_dot`,
    `_scaled`, the offset's exact dot); the walk is this kernel's own (the
    module's docstring, point 3, says what a tile of 1-3 real rows paid in
    the dense call's body): the 0/1 layout matrices and the scales scratch's
    zero rows are built at the call's first step and kept; a tile lays its
    rows out at its first step (`_lay_rows`); a pass covers the whole depth
    and `lanes` columns; the result is written once."""
    del layer_ref, expert_ref, src_ref, live_ref  # the index maps' and the grid's
    t, j = pl.program_id(0), pl.program_id(1)
    m, k = x_ref.shape
    tn = out_ref.shape[1]
    groups, nb = k // _GROUP, k // Q_BLOCK

    @pl.when((t == 0) & (j == 0))
    def _():
        place_ref[:], sums_ref[:] = _expert_constants(*sums_ref.shape)
        if s_ref.shape[0] != nb:  # rows past the blocks meet zero sums
            s_ref[:] = jnp.zeros_like(s_ref)

    @pl.when(j == 0)
    def _():  # once a tile: its rows are another expert's
        _lay_rows(x_ref, place_ref, sums_ref, xa_ref, xs_ref)

    s_ref[0:nb, :] = _scales_f32(scales_ref[:])

    def lane_step(c, carry):
        l0 = pl.multiple_of(c * lanes, 128)
        w = pltpu.bitcast(packed_ref[:, pl.ds(l0, lanes)], jnp.uint32)
        codes = jnp.concatenate(
            [v.reshape(groups, 32, lanes) for v in _unpack_words(w)], axis=1)
        y = _group_dot(xa_ref[:], codes)
        acc = _scaled(y.reshape(nb, m, lanes), s_ref[0:nb, pl.ds(l0, lanes)])
        # the codes read 16 + q: take 24 * sum_b xsum[b] * s[b] off, exactly
        # (an f16 scale is two bf16 parts, a block sum three; f32 sums)
        off = sum(jnp.dot(xs_ref[:], part.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
                  for part in _bf16_parts(s_ref[:, pl.ds(l0, lanes)], 2))
        off = off[0:m] + off[m:2 * m] + off[2 * m:3 * m]
        out_ref[:, pl.ds(l0, lanes)] = acc - _W_OFFSET * off
        return carry

    jax.lax.fori_loop(0, tn // lanes, lane_step, 0)


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


#: weights a pass of the dequantising tier's loop covers at most: its f32
#: planes are whole-array values, about 10 B a weight of VMEM while they live
_DEQ_PASS_WEIGHTS = 9 << 18
#: what a step's buffers and a pass's planes may take of VMEM, and what x
#: (its block's two buffers and the laid-out copy) may take beside them
_DEQ_VMEM, _DEQ_X_BYTES = 40 * 1024 * 1024, 36 * 1024 * 1024


def _deq_pass(tk: int, tn: int) -> int:
    """k rows a pass of the dequantising tier's loop over a tile: all of
    them, or the most whole 256-row steps dividing tk that keep the pass
    within `_DEQ_PASS_WEIGHTS` (0: the tile is too wide for any)."""
    if tk * tn <= _DEQ_PASS_WEIGHTS:
        return tk
    return max((r for r in range(_SUB_K, tk, _SUB_K)
                if tk % r == 0 and r * tn <= _DEQ_PASS_WEIGHTS), default=0)


@functools.lru_cache(maxsize=None)
def _deq_tiles(m: int, k: int, n: int, itemsize: int = 2) -> tuple[int, int, int, int]:
    """(tm, tk, tn, rows a pass) for the m > 16 tier from the call's shape
    alone. tm: the whole batch up to 512 rows (every m tile streams and
    dequantises the whole weight again: m = 48 split as 3 x 16 cost three
    passes, PR 29), less where x would not fit beside its laid-out copy.
    (tk, tn): the tile that costs least by `_blockdot_tiles`' figures (a
    grid step, a pass of the inner loop, the first tile's copy) and a grid
    step more a tile of the result, among tn a multiple of 128 dividing n
    and tk the whole of k or whole 256-row steps dividing it, within the
    VMEM budget with the m tile's f32 result; no tile under `_STEP_FLOOR`
    and no weight of two floors or more in one step, where its divisors
    leave another choice. A pass is the tile's whole width and as many of
    its rows as `_DEQ_PASS_WEIGHTS` allows."""
    fits = lambda t: 3 * t * k * itemsize <= _DEQ_X_BYTES
    tm = m if m <= 512 and fits(m) else next(
        t for t in (512, 256, 128, 64, 32, 16, 8) if m % t == 0 and (fits(t) or t == 8))
    best = None
    depths = [k] + [t for t in range(_SUB_K, k, _SUB_K) if k % t == 0]
    for tn in (t for t in range(128, n + 1, 128) if n % t == 0):
        for tk in depths:
            nb = tk // Q_BLOCK
            rows = _deq_pass(tk, tn)
            # two buffers of packed rows, u16 scales and the f32 result, the
            # scales as f32, a pass's planes
            vmem = (2 * (tk * tn // 2 + nb * tn * 2 + tm * tn * 4) + nb * tn * 4
                    + 10 * rows * tn)
            if not rows or vmem > _DEQ_VMEM:
                continue
            steps = (k // tk) * (n // tn)
            odd = (tk * tn // 2 < min(_STEP_FLOOR, k * n // 4)
                   or steps == 1 and k * n // 2 >= 2 * _STEP_FLOOR)
            # a tile of the result costs a grid step more: its zeroing and
            # write-back (0.14-0.20 us a tile over `kbench.py deq`'s sweep,
            # my chip run, PR 37: whole-width tiles read 1.2-1.4 us under
            # whole-depth ones on Granite's 16 M-weight shapes)
            cost = ((steps + n // tn) * _STEP_KB + steps * (tk // rows) * _PASS_KB
                    + _FIRST_TILE * tk * tn / 2048)
            if best is None or (odd, cost, -tk) < best[0]:
                best = ((odd, cost, -tk), tk, tn, rows)
    return (tm,) + best[1:]


@functools.partial(jax.jit, static_argnames=("interpret", "tk", "tn", "rows"))
def _deq_call(layer, x, packed, scales, *, interpret: bool = False,
              tk: int | None = None, tn: int | None = None, rows: int | None = None):
    """x[m, k] @ dequant(packed[layer], scales[layer]) -> f32[m, n], k whole
    128-dim groups. The name, the f32[m, n] result and the packed array as
    the first u8 operand are what the benchmark's trace reader finds and
    prices this call by (benchmark/costs/q40_matmul.py). tk / tn / rows are
    the chip sweep's overrides (`experiments/kbench.py deq`); serving passes
    none and runs `_deq_tiles`."""
    m, k = x.shape
    n = packed.shape[-1]
    tm, dtk, dtn, _ = _deq_tiles(m, k, n, x.dtype.itemsize)
    tk, tn = tk or dtk, tn or dtn
    rows = min(rows or _deq_pass(tk, tn), tk)
    assert k % _DEQ_GROUP == 0 and tk % rows == 0 and (rows == tk or rows % _SUB_K == 0)
    nb = tk // Q_BLOCK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m // tm, n // tn, k // tk),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i, j, kb, L: (i, 0)),
            pl.BlockSpec((None, tk // 2, tn), lambda i, j, kb, L: (L[0], kb, j)),
            pl.BlockSpec((None, nb, tn), lambda i, j, kb, L: (L[0], kb, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kb, L: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, k), x.dtype),
                        pltpu.VMEM((-(-nb // 8) * 8, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_deq_kernel, rows=rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # an m tile's first grid step lays x out for its later ones
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # all but 8 MB of VMEM is this call's to claim: as for
            # `_blockdot_call`, XLA's memory-space assignment then cannot
            # park a stacked scales array there (`slice-done`, 1.3 ms of a
            # Granite decode step until PR 37; beside a claim of 96 MB the
            # 19 MB of out_proj's scales still fitted)
            vmem_limit_bytes=120 * 1024 * 1024,
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=m * k * x.dtype.itemsize
            + (m // tm) * (k * n // 2 + (k // Q_BLOCK) * n * scales.dtype.itemsize)
            + m * n * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(layer, x, packed, scales)


def _x_and_scratch(x, tk: int, tn: int):
    """What `_blockdot_kernel` works on besides the weight: x as it goes in
    (padded to whole chunks of 4096 dims where k has a part chunk: the
    kernel lays it out itself at its first grid step) and the three VMEM
    scratches: x by group and block, x's block sums, the tile's scales as
    f32 in whole chunks."""
    m, k = x.shape
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


#: packed bytes of an expert's columns tile a grid step holds at most (two
#: buffers of it, the scales and a pass's values stay far inside VMEM)
_EXPERT_STEP_BYTES = 4 * 1024 * 1024
#: weights a pass of the expert kernel's loop covers at most: one pass for
#: Kimi-Linear's 2304 x 1024 read 125 us a call where two read 139
#: (`kbench.py expert`, my chip run, PR 39)
_EXPERT_PASS_WEIGHTS = 9 << 18


@functools.lru_cache(maxsize=None)
def _expert_inner(k: int, n: int) -> tuple[int, int]:
    """(tn, lanes) of the grouped expert kernel from an expert's shape alone:
    a grid step takes the whole depth and the widest columns tile (a
    multiple of 128 dividing n) within `_EXPERT_STEP_BYTES`, the whole
    expert where it fits; a pass of the inner loop the whole depth and the
    widest part of the tile that keeps it within `_EXPERT_PASS_WEIGHTS`."""
    widths = [w for w in range(n, 0, -128) if n % w == 0]
    tn = next((w for w in widths if k * w // 2 <= _EXPERT_STEP_BYTES), 128)
    lanes = next((w for w in widths if tn % w == 0 and k * w <= _EXPERT_PASS_WEIGHTS), 128)
    return tn, lanes


@functools.lru_cache(maxsize=None)
def _expert_deq_tn(k: int, n: int) -> int:
    """The columns tile of the grouped expert kernel's tall tiles: the widest
    (a multiple of 128 dividing n) whose whole depth one pass of
    `_DEQ_PASS_WEIGHTS` dequantises, the whole expert where it fits; a depth
    no pass holds at 128 columns is walked by `_deq_pass`' steps."""
    return next((w for w in range(n, 0, -128)
                 if n % w == 0 and k * w <= _DEQ_PASS_WEIGHTS), 128)


@functools.partial(jax.jit, static_argnames=("tm", "interpret", "tn", "lanes"))
def _expert_call(layer, tile_expert, tile_src, n_live, x, packed, scales, *,
                 tm: int, interpret: bool = False, tn: int | None = None,
                 lanes: int | None = None):
    """bf16 x[T*tm, k], rows in expert order and padded to whole tiles of tm,
    against the stacked experts packed u8[L, E, k/2, n] -> f32[T*tm, n]: tile t
    meets expert tile_expert[t] of layer `layer`, for the n_live tiles that
    hold a row (the grid's first bound: the rows of the tiles behind them
    are never written). The name and the 4-D packed operand are what the
    benchmark's trace reader finds this call by
    (benchmark/costs/moe_experts.py). tn / lanes are the chip sweep's
    overrides (`experiments/kbench.py expert`); serving passes none and
    runs `_expert_inner` / `_expert_deq_tn`."""
    rows_total, k = x.shape
    n = packed.shape[-1]
    tiles = rows_total // tm
    nb = k // Q_BLOCK
    assert tm % 16 == 0 and k % _SUB_K == 0
    if tm > 16:  # a slice's tiles: the dequantising body (static: by tm alone)
        tn = tn or _expert_deq_tn(k, n)
        kernel = functools.partial(_expert_deq_kernel, rows=_deq_pass(k, tn))
        scratch = [pltpu.VMEM((tm, k), x.dtype),  # x in the planes' row order
                   pltpu.VMEM((-(-nb // 8) * 8, tn), jnp.float32)]  # the tile's scales
    else:
        dtn, dlanes = _expert_inner(k, n)
        tn = tn or dtn
        lanes = lanes or (dlanes if tn % dlanes == 0 else tn)
        assert tn % lanes == 0
        nbp = -(-nb // _GROUP) * _GROUP
        kernel = functools.partial(_expert_kernel, lanes=lanes)
        scratch = [
            pltpu.VMEM((_GROUP, 4 * _GROUP), x.dtype),  # place
            pltpu.VMEM((k, nbp), x.dtype),  # sums
            pltpu.VMEM((k // _GROUP, 4 * tm, _GROUP), x.dtype),  # x by group and block
            pltpu.VMEM((4 * tm, nbp), x.dtype),  # x's block sums, in parts
            pltpu.VMEM((nbp, tn), jnp.float32),  # the tile's scales
        ]
    assert n % tn == 0 and tn % 128 == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # layer[1], tile_expert[T], tile_src[T], n_live[1]
        grid=(n_live[0], n // tn),
        in_specs=[
            pl.BlockSpec((tm, k), lambda t, j, L, E, S, N: (S[t], 0)),
            pl.BlockSpec((None, None, k // 2, tn), lambda t, j, L, E, S, N: (L[0], E[t], 0, j)),
            pl.BlockSpec((None, None, nb, tn), lambda t, j, L, E, S, N: (L[0], E[t], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda t, j, L, E, S, N: (S[t], j)),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_total, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            # the call's first step builds what every later one reads, a
            # tile's first step what its later ones read
            dimension_semantics=("arbitrary", "arbitrary"),
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
    """Tileability check used by the ops.matmul dispatcher: whole 128-lane
    tiles of n, and k in whole 128-dim groups (what the dequantising tier
    lays x out by; the block-dot tier asks for 256 on top)."""
    k, n = w.shape[-2], w.shape[-1]
    return k % _DEQ_GROUP == 0 and n % 128 == 0


def q40_matmul(
    x: jax.Array, w: QTensor, layer=None, *, interpret: bool = False
) -> jax.Array:
    """``x @ w[layer]`` for any leading batch dims; returns x.dtype.

    ``w`` may be a 2-D weight (``layer=None``) or a layer-stacked
    ``[L, k, n]`` weight addressed by the traced scalar ``layer`` — the
    stacked form is indexed by the DMA engine, never sliced by XLA.
    """
    *lead, k = x.shape
    assert k % _DEQ_GROUP == 0 and w.shape[-1] % 128 == 0, (
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
    # the block-dot kernel carries its codes in bf16 and walks k by eight
    # blocks: other activations, depths and batches take the dequantising
    # tier; both take whole tiles of rows (16 of bf16, 8 of f32)
    blockdot = m <= 16 and k % _SUB_K == 0 and x2.dtype == jnp.bfloat16
    pad = (-m) % (32 // x2.dtype.itemsize)
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    if blockdot:
        out = _blockdot_call(layer_arr, x2, packed, scales, interpret=interpret)
    else:
        out = _deq_call(layer_arr, x2, packed, scales, interpret=interpret)
    if pad:
        out = out[:m]
    return out.reshape(*lead, n).astype(x.dtype)
