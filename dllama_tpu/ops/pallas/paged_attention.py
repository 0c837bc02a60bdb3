"""General paged flash-decode attention — any-page-size Pallas TPU kernel.

PR 5's block-table flash variant (ops/pallas/flash_attention.paged_*) rides
the automatic BlockSpec pipeline, which constrains a page to hold whole
64-row kv tiles (`page_size % 64 == 0`); every other page size fell back to
``ops/layers.paged_gqa_attention`` — a jnp gather that re-materializes the
ENTIRE paged KV through XLA every step, the exact memory-traffic blowup
PagedAttention (Kwon et al., 2023, vLLM) exists to avoid.

This kernel drops the tileability requirement by driving the KV pipeline
manually (the jax-ml TPU paged_attention pattern, exemplified in
SNIPPETS.md [1]/[2]'s `pltpu.PrefetchScalarGridSpec` scalar-prefetch idiom):

* the page pools stay in HBM (``memory_space=ANY``) — the kernel, not the
  BlockSpec machinery, owns their movement;
* ``(pos, tables)`` ride as scalar-prefetch arguments, so the kernel walks
  each slot's block table and issues ``make_async_copy`` DMAs of one PAGE of
  a BLOCK of kv heads at a time into a ring of VMEM landing buffers — any
  page size, the partial last page masked by the same absolute-position
  causal mask the dense kernel uses. The pool's layout is ``[N, Hkv, page,
  lanes]``, so a page's ``hb`` consecutive heads are ONE contiguous run
  (``pool.at[pg, pl.ds(h0, hb)]``: 1 MB at 32 heads x 128 rows x 128 lanes
  of bf16), not ``hb`` separate 32 KB copies that each pay a round trip;
* the two ENDS of a walk move the rows the step reads, not the pages it
  touches (PR 53; the kernel is HBM-bound, and at a few pages a slot the
  ends are a third of its bytes): the walk's LAST page is copied in by
  units of ``sub`` rows from row 0 to the unit that holds row
  ``(pos + t - 1) % page``, a windowed walk's FIRST page from the unit that
  holds row ``(pos - W + 1) % page`` on (one page that is both: the
  intersection), every page between them stays ONE copy.
  :func:`walk_ends` is the one definition of those rows: the kernel sizes
  its copies from it (the successor's too: its ``pos`` is scalar prefetch)
  and the host counts them from it (:func:`rows_moved`). ``sub`` is a
  function of the page, the dtype and the head block's bytes
  (:func:`_row_tiles`: a page whose copy is under 256 KB comes whole, its
  pass is bound by its fixed cost and a branch a page buys nothing). Rows of a ring
  slot that no copy filled hold an older page's rows: the mask gives them
  p = 0 and 0 x finite = 0, so the value ring is zeroed once a call;
* the grid is one step per (slot, block of ``hb`` kv heads, q tile); the
  page run is a dynamic ``fori_loop`` bounded by the slot's LIVE page count
  (``pos``-derived), so decode cost scales with the live context exactly
  like the dense kernel's tile pruning. ``hb`` and the ring's depth come
  from :func:`_plan`: the largest divisor of ``Hkv`` whose ring,
  accumulator and per-page temporaries fit a VMEM budget — a function of
  the call's shapes and dtype, no flag; ``hb = 1`` at depth 2 is the same
  code and what `paged_decode_supported` admits. The dots are batched over
  the head block; each head's online softmax runs page by page;
* the ring does not drain between grid steps: while a step's last pages are
  in the MXU the copies of the NEXT step's first pages (next q tile, head
  block or slot: its ``pos`` and table are scalar prefetch too) are already
  in flight, and the ring slot of a step's first page is carried in SMEM —
  the jax-ml kernel's ``buffer_index`` idea. Every grid axis is therefore
  ``arbitrary`` (one TensorCore on v5e: nothing is lost);
* the new token's KV rows are scatter-written into the pool INSIDE the same
  launch (``input_output_aliases`` keeps the pool update in place) and read
  nothing back: a row's target page is one of the sweep's own last pages,
  so when that page's head block has landed the rows are blended into the
  landed copy (an f32 ``where`` over the sublane tile that holds the row),
  the sweep reads the blended copy, and the TILES that received rows are
  written back under that page's dots (PR 53: ``hb`` runs of ``win`` rows,
  2 x 128 KB a slot at 32 heads where the two head blocks were 2 MB; a chunk
  of t rows writes the tiles from its first row's to its last row's of
  each page; a page that is not whole tiles of its dtype is one tile). An
  inactive slot's rows, routed to the trash page no table holds, find that
  page riding as one more page behind the slot's sweep (masked out of the
  softmax): the same blend, the same tiles in and out. The separate
  `_paged_cache_update` dispatch decode used to pay per layer is gone;
* q and K enter the q.k product as the bfloat16 they are stored as where
  both are (a product of two bfloat16 values is exact in float32, so it is
  the same sum in one MXU pass); the scale, mask, exp, p, l, the
  accumulator and the p.v product stay float32, as do both operands
  whenever q or the pool is float32;
* the pool the kernel walks is the WHOLE layer-stacked array, viewed as one
  run of L*P pages, and the layer is data: its first page is added to the
  prefetched page indices (``paged_decode_attention(layer=)``). The decoder's
  layer scan carries that one buffer through every layer's aliased call, so
  no layer's slice is cut out of the stack before its call or written back
  after it — the same reason the matmuls DMA-index the weight stacks. A
  per-layer pool (``layer=None``) is the same call with first page 0;
* a LATENT pool (one row a token, key and value of every head, ``Hkv = 1``)
  is swept several pages a PASS (PR 48): a page's pass there is a
  [tq, page] score tile against 160 KB of rows, and its fixed cost (the
  wait, two products, the lane reductions, the rescale: a serial chain that
  does not overlap the next page's) took 0.63 us a page against 0.20 us of
  bytes. The copies stay a page each (a slot's pages are scattered), ``pp``
  of them land side by side in one ring slot, and the pass is ONE score
  product, ONE softmax update and ONE value product over ``pp * page``
  keys; dead pages of a slot's last pass are not copied and are masked.
  ``pp`` comes from :func:`_plan`; every other call takes a page a pass and
  is the program it was.

Numerics are the same online-softmax (flash) formulation as
``flash_attention._kernel``: f32 accumulation, large-finite mask fill, one
running (m, l, acc) state per head and q tile.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dllama_tpu.ops.pallas.tiling import pick_tile as _pick_tile

_NEG_INF = -1e30  # large-finite: keeps fully-masked pages NaN-free

#: Chunks longer than this scatter their KV rows with a single XLA scatter
#: before the kernel launches instead of blending them into the sweep's
#: landed pages: a 256-token prefill chunk would otherwise unroll 2*T row
#: blends into every (slot, head block) program. Decode (t=1) and batched
#: spec verify (t=k+1) sit far below it and always fuse.
FUSED_SCATTER_MAX_T = 16

#: The capability floor: two (k, v) pairs of ONE head's page — the ring at
#: its shallowest (depth 2) and narrowest (one kv head a grid step) — must
#: fit here. Pages above it route to the gather fallback instead of risking
#: a Mosaic VMEM overflow at compile time.
_PAGE_VMEM_BYTES = 4 * 1024 * 1024

#: What :func:`_plan` sizes the head block and the ring's depth into: the
#: landing ring, the f32 accumulator with m/l, and the sweep's per-page
#: temporaries (scores, probabilities, the widened V). Read at call time,
#: so a test can shrink it to force ``hb = 1``.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
#: Mosaic's scoped-VMEM ceiling for this kernel (v5e: 128 MiB physical, 16
#: MiB by default): the budget above plus the auto-pipelined q / new-row /
#: out blocks and the compiler's own temporaries.
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: The ring is as deep as the budget allows up to this many (k, v) pairs:
#: depth - 1 pairs are in flight while one is in the MXU, and past a few
#: hundred KB in flight the HBM is covered; every slot of the ring costs an
#: unrolled conditional DMA start in the prologue.
_MAX_DEPTH = 4
#: Pages one pass of a LATENT sweep takes where the budget holds them
#: (`_plan`): the smallest within 5% of the best row of the chip's sweep at
#: A.X-K1's decode shape (32 slots x 64 heads, 24-72 pages a slot; ms a
#: call at 1 / 2 / 3 / 4 / 6 / 8 / 10 / 12 / 16 pages: 1.004 / 0.680 /
#: 0.555 / 0.506 / 0.463 / 0.436 / 0.432 / 0.426 / 0.434), which is also
#: the best row at Kimi-Linear's (48 x 32 heads, 1-9 pages: 0.168 / 0.140 /
#: 0.125 / 0.119 / 0.122 / 0.116 / 0.116 / 0.123 / 0.139) and within 4% of
#: the best of a 512-row slice's q tiles of 128 (4.85 / 3.58 / 3.16 / 2.80
#: / 2.81 / 2.64 / 2.69 / 2.54 / 3.01), so tq and the table's width need no
#: say in it (`experiments/kbench.py paged --latent`, PERF.md section 6,
#: PR 48). The pass's pages are walked by loops, so the kernel's traced size
#: (warm-up time) does not grow with this number.
_LATENT_PASS_PAGES = 8
_Q_TILE_MAX = 128  # folded q rows one grid step takes (and one MXU pass)
#: Rows one copy of a walk's END page carries where the page holds several
#: (`_row_tiles`): the smallest within 5% of the best row at EVERY shape of
#: the chip's sweep (`experiments/kbench.py paged --decode --sub`, PR 53,
#: ms a fused decode call at 16 / 32 / 64 rows and the whole page, two
#: runs: DeepSeek 12 slots x 32 heads, 2-4 pages: 0.1353-0.1356 /
#: 0.1360-0.1397 / 0.1414-0.1424 / 0.1482-0.1486; Granite 48 x 8, 1-8:
#: 0.1763-0.1768 / 0.1774 / 0.1787-0.1810 / 0.1854-0.1874; Laguna's window
#: 24 x 8, 5 pages of which two are ends: 0.1194-0.1198 / 0.1141-0.1153 /
#: 0.1125-0.1138 / 0.1202-0.1205, where 16 rows read 6% over the best;
#: Laguna's global 4-33 pages: 0.3552-0.3559 / 0.3556-0.3564 / 0.3573-0.3575
#: / 0.3616-0.3622).
_END_COPY_ROWS = 32
#: A page's head block (one pool's copy) below this many bytes comes whole:
#: a pass that small is bound by its fixed cost, not its bytes (PR 48), and
#: what an end saves (3/8 of a page's copy on average: 0.06 us at 4 heads)
#: is less than the choice costs, which is a branch for EVERY page of the
#: walk, at its start and at its wait. SmallThinker's calls (4 kv heads: 128 KB; walks of 33-76 pages)
#: read 0.2935-0.2960 ms by units and 0.2835-0.2841 whole (the parent
#: 0.2875); Granite's and Laguna's (8 heads: 256 KB) and DeepSeek's (1 MB)
#: gain (PR 53, the same sweep).
_END_MIN_PAGE_BYTES = 256 * 1024

_LANES = 128  # TPU vector lane count: the minor-dim tile of every memref


def pool_lanes(head_size: int) -> int:
    """Minor-dim width of a page pool this kernel can DMA-walk: the head
    size rounded up to whole 128-lane rows. Mosaic slices an HBM memref only
    at tile granularity, so a [P, Hkv, page, 64] pool (Llama-3.2-1B's
    head_size) is refused at compile time ("Slice shape along dimension 3
    must be aligned to tiling (128), but is 64") and XLA would hold it
    page-minor, a whole-pool relayout away from what a Mosaic call takes.
    Engines on the kernel route allocate their pool this wide; the pad
    lanes stay zero (zero q lanes score 0, zero v lanes are sliced off)."""
    return -(-head_size // _LANES) * _LANES


def _row_tiles(page: int, itemsize: int, block_bytes: int | None = None,
               end_copy: tuple[int, int] | None = None) -> tuple[int, int]:
    """(win, sub) of a call, functions of its shapes and dtype alone:
    ``win`` is the rows of one sublane tile of the dtype (16 of bfloat16, 8
    of float32), what a new row is blended into and written back as; ``sub``
    is the rows one copy of a walk's END page carries: the largest whole
    number of tiles up to ``_END_COPY_ROWS`` that divides the page, or the
    page where its head block (``block_bytes`` a pool) is under
    ``_END_MIN_PAGE_BYTES`` (``end_copy`` = those two, read at call time).
    A page that is not whole tiles is one tile and one unit: every copy of
    it is the whole page, as before PR 53."""
    end_rows, min_bytes = end_copy or (_END_COPY_ROWS, _END_MIN_PAGE_BYTES)
    win = 32 // itemsize
    if page % win:
        return page, page
    if block_bytes is not None and block_bytes < min_bytes:
        return win, page
    return win, max(s for s in range(win, max(end_rows, win) + 1, win)
                    if page % s == 0)


def decode_tiles(n_heads: int, n_kv_heads: int, page: int, lanes: int,
                 itemsize: int) -> tuple[int, int]:
    """(win, sub) of an engine's fused decode call (t = 1), for the host's
    count of the rows it moves (`rows_moved`): the head block is `_plan`'s
    for the call's shapes."""
    tq = _q_tile(-(-(n_heads // n_kv_heads) // 8) * 8)
    hb = _plan(n_kv_heads, page, lanes, itemsize, tq, 1, _VMEM_BUDGET_BYTES)[0]
    return _row_tiles(page, itemsize, hb * page * lanes * itemsize)


class _Rows:
    """Row arithmetic on TRACED scalars, as `numpy` spells it on the host's
    arrays: `walk_ends` runs on either. Every operand is a row count >= 0,
    so the truncating division is the floor; the primitives are bound
    directly because the kernel's lowering time is every warm program's
    set-up time: `jnp.where` / `minimum` wrap theirs in a nested jit, and
    `//` and `%` lower through a traced `sign` (3 ms an operator on the CPU
    where a bound `div` takes 0.3)."""

    _i32 = staticmethod(lambda x: jnp.int32(x) if isinstance(x, int) else x)
    where = staticmethod(lambda c, a, b: jax.lax.select(
        c, _Rows._i32(a), _Rows._i32(b)))
    minimum = staticmethod(lambda a, b: jax.lax.min(a, _Rows._i32(b)))
    maximum = staticmethod(lambda a, b: jax.lax.max(a, _Rows._i32(b)))
    floor_divide = staticmethod(lambda a, b: jax.lax.div(a, _Rows._i32(b)))
    remainder = staticmethod(lambda a, b: jax.lax.rem(a, _Rows._i32(b)))


def walk_ends(xp, pos, first_q, last_q, page: int, nb: int,
              window: int | None):
    """(lo, hi, r0, r1) of one walk: the blocks [lo, hi) of the slot's table
    that queries at ``pos + first_q .. pos + last_q`` read, the first live
    row of block ``lo`` and one past the last live row of block ``hi - 1``.
    THE definition of which rows a walk needs from its two end pages: the
    kernel sizes its copies from it and the host counts them from it
    (``xp`` is :class:`_Rows` there and `numpy` here). A walk clipped at
    the table's end (a horizon past ``nb`` pages) takes both end pages
    whole: a clipped chunk's rows wrap around the last page."""
    last_key = pos + last_q
    last_blk = xp.floor_divide(last_key, page)
    hi = xp.minimum(last_blk + 1, nb)
    inside = last_blk < nb
    r1 = xp.where(inside, xp.remainder(last_key, page) + 1, page)
    if window is None:
        return 0, hi, 0, r1
    first_key = xp.maximum(pos + first_q - window + 1, 0)
    first_blk = xp.floor_divide(first_key, page)
    lo = xp.minimum(first_blk, hi - 1)
    r0 = xp.where(inside & (first_blk == lo), xp.remainder(first_key, page), 0)
    return lo, hi, r0, r1


def rows_moved(pos, page: int, nb: int, win: int, sub: int,
               window: int | None = None):
    """KV rows (of one pool: k and v move the same) the DMAs of ONE layer's
    fused decode call move for a live slot whose new row lands at ``pos``
    (numpy, any shape): the walk's pages copied in, its two end pages by
    live units of ``sub`` rows, and the ``win``-row tile written back
    (``win``, ``sub``: `decode_tiles`). What the call NEEDS is ``pos + 1``
    rows (``min(pos + 1, window)``)."""
    import numpy as np

    lo, hi, r0, r1 = walk_ends(np, np.asarray(pos, np.int64), 0, 0, page, nb,
                               window)
    first, last = r0 // sub * sub, ((r1 - 1) // sub + 1) * sub
    return (hi - lo - 1) * page + last - first + win  # one page is both ends


def paged_decode_supported(q_shape: tuple[int, ...], page_size: int,
                           kv_dtype=jnp.bfloat16) -> bool:
    """Capability check for the engine's paged-attention dispatcher — the
    explicit (dtype / head-dim / page-geometry) contract that replaced the
    old `paged_supported` whole-64-row-tile gate:

    * any page size that is a whole number of 8-row sublanes (the DMA
      granularity of the VMEM landing buffers); no power-of-two or 64-row
      requirement — 8, 24, 120 all route to the kernel;
    * head_size >= 8 (same floor as the dense flash kernel);
    * 16- or 32-bit kv elements (bf16 / f32 pools). f8 pools route to the
      gather fallback: Mosaic rejects the f8->f32 in-register extension
      (`arith.extf` is 16->32-bit only — the same rejection the DENSE f8
      flash path now hits in the AOT gate, a libtpu-level pre-existing
      condition, so paged matches dense f8 behavior rather than extending
      the breakage);
    * a ring of two (k, v) pairs of one head's page — at the pool's
      lane-padded row width (:func:`pool_lanes`) — must fit the floor
      (:func:`_plan` falls back to exactly that: one head a grid step,
      depth 2).

    Ragged tables need no capability: unallocated entries are clamped to
    the last live page by the kernel and masked by position, so any
    ``max_blocks`` works.
    """
    hd = q_shape[-1]
    el = jnp.dtype(kv_dtype).itemsize
    return (
        page_size >= 8
        and page_size % 8 == 0
        and hd >= 8
        and el in (2, 4)
        and 4 * page_size * pool_lanes(hd) * el <= _PAGE_VMEM_BYTES
    )


def _q_tile(rows: int) -> int:
    """Folded q rows one grid step takes: all of them up to one MXU pass
    (a spec-verify chunk of 9 tokens x group 4 is ONE sweep of the pages,
    not five), else the largest power-of-two tile that divides them."""
    return rows if rows <= _Q_TILE_MAX else _pick_tile(rows, (128, 64, 32, 16, 8))


def _fuses(t: int, rows: int) -> bool:
    """Whether a chunk of ``t`` new rows (``rows`` folded q rows) is
    scattered inside the kernel: the blend needs the slot's ONE sweep of its
    pages, so the folded rows must be one q tile, and ``t`` small enough to
    loop over (longer chunks scatter through XLA first, identical result)."""
    return t <= FUSED_SCATTER_MAX_T and _q_tile(rows) == rows


def _plan(hkv: int, page: int, lanes: int, itemsize: int, tq: int, t: int,
          budget: int, latent: bool = False) -> tuple[int, int, int, int]:
    """(hb, depth, pp, bytes): the kv heads one grid step serves, the landing
    ring's depth in passes, the pages one pass of the sweep consumes, and
    the VMEM that plan needs — functions of the call's shapes and dtype
    alone.

    A head costs ``depth`` passes of ring, its f32 accumulator row block
    with m and l, the f32 copies of its ``t`` new rows, and the sweep's
    per-pass temporaries. ``hb`` is the largest divisor of ``hkv`` whose
    ring of three passes fits the budget (two in flight behind the one in
    the MXU); the depth then grows into what is left, up to ``_MAX_DEPTH``.
    A page too large for any of it gets ``hb = 1`` at depth 2, which is
    what `paged_decode_supported` admits.

    A pass is one page of (k, v) pairs, except for a LATENT sweep (one
    pool, ``hkv = 1``): its page is a [tq, page] score tile against 160 KB
    of rows, so the pass's fixed cost (a wait, two products, the lane
    reductions and the rescale, none of which overlaps the next pass's)
    is paid for every 128 rows; there a pass takes
    ``_LATENT_PASS_PAGES`` pages (fewer if the budget holds no ring of two
    such passes), and the ring is two passes deep: one pass's copies in
    flight behind the one in the MXU, which is ``pp`` pages where a page a
    pass keeps ``depth - 1``."""
    state = tq * (lanes + 2 * _LANES) + 2 * t * lanes  # acc, m, l, new rows
    if latent:
        one = page * lanes * itemsize  # a latent row lands once, k and v
        need = lambda pp: 2 * pp * one + (  # the ring; s, p, widened rows
            state + 2 * tq * pp * page + pp * page * lanes) * 4
        pp = max((p for p in range(2, _LATENT_PASS_PAGES + 1)
                  if need(p) <= budget), default=1)
        if pp > 1:
            return 1, 2, pp, need(pp)
    pair = 2 * page * lanes * itemsize
    fixed = (state + 2 * tq * page + 2 * page * lanes) * 4  # s, p, widened k / v
    need = lambda hb, depth: hb * (depth * pair + fixed)
    hb = max((d for d in range(1, hkv + 1)
              if hkv % d == 0 and need(d, 3) <= budget),
             default=1)
    depth = max((d for d in range(2, _MAX_DEPTH + 1) if need(hb, d) <= budget),
                default=2)
    return hb, depth, 1, need(hb, depth)


def latent_plan(heads: int, row: int, page: int, itemsize: int,
                chunk: int) -> dict:
    """What :func:`_plan` gives an engine's two latent calls, for the
    program's own report (`/debug/perf`): the decode step's (t = 1, the
    heads one q tile) and a prefill slice's of `chunk` rows (q tiles of
    `_q_tile`, scattered by XLA first)."""
    def one(t):
        rows = -(-t * heads // 8) * 8
        _, depth, pp, nbytes = _plan(1, page, pool_lanes(row), itemsize,
                                     _q_tile(rows), t if _fuses(t, rows) else 1,
                                     _VMEM_BUDGET_BYTES, True)
        return {"pages_per_pass": pp, "ring_passes": depth, "vmem_bytes": nbytes}

    return {"decode": one(1), "slice": one(chunk)}


def _kernel(pos_ref, tables_ref, wpages_ref, woffs_ref,  # scalar prefetch
            q_ref, newk_ref, newv_ref,  # VMEM blocks [hb, tq | t, lanes]
            kpool_in, vpool_in,  # HBM (ANY) — aliased to outputs
            out_ref, kpool_ref, vpool_ref,  # out block + aliased pools
            kbuf, vbuf, newk32, newv32, acc_ref, m_ref, l_ref, base_ref,
            copy_sems, write_sems,
            *, scale, page, group, t, tq, rows_live, nb, fused, hb, depth,
            mxu_dtype, sub, window=None, latent=False, pp=1):
    b, hblk, iq = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nbatch, nhb, nq = pl.num_programs(0), pl.num_programs(1), pl.num_programs(2)
    lanes = kbuf.shape[-1]
    # A PASS of the sweep is `pp` consecutive pages of the run: one wait,
    # one score product against `span` keys, one softmax update, one value
    # product. The ring, the run indices and `base` count passes; a pass's
    # page k lands in rows k*page.. of its ring slot. pp = 1 (every call
    # but a latent one, `_plan`) is a page a pass and the program it always
    # was: each `pp == 1` below keeps that program's equations as they were.
    assert pp == 1 or (latent and window is None), (pp, latent, window)
    span = pp * page
    passes = lambda pages: pages if pp == 1 else (pages + (pp - 1)) // pp
    # A page a pass copies the two END pages of a walk by live units of
    # `sub` rows (`walk_ends`); `sub = page` (a page that is not whole tiles,
    # or no larger than a unit) is one copy a page and the program it was.
    ends = pp == 1 and sub < page

    def sweep_pages(bb, qq):
        # live-page horizon of q tile qq of slot bb (mirrors
        # flash_attention's kv-tile clamp: pad rows must not widen it),
        # clamped to the table: the logical view is exactly nb*page rows
        last_row = jnp.minimum(qq * tq + tq - 1, rows_live - 1)
        return jnp.minimum((pos_ref[bb] + last_row // group) // page + 1, nb)

    def sweep_first(bb, qq, hi):
        # a windowed layer's walk starts at the block that holds the oldest
        # row the tile's FIRST query still sees (pos + first_offset - W + 1);
        # blocks before it may have been handed back to the pool already
        lo = jnp.maximum(pos_ref[bb] + (qq * tq) // group - window + 1, 0) // page
        return jnp.minimum(lo, hi - 1)

    def sweep_rows(bb, qq):
        # the first live row of the walk's first page, one past the last
        # live row of its last page (the blocks are sweep_first / _pages')
        return walk_ends(
            _Rows, pos_ref[bb], _Rows.floor_divide(qq * tq, group),
            _Rows.floor_divide(_Rows.minimum(qq * tq + tq - 1, rows_live - 1),
                               group), page, nb, window)[2:]

    # ---- fused KV scatter, addressing. A live slot's rows pos .. pos+t-1
    # land in table pages blk(0) .. blk(t-1), the LAST pages of its sweep
    # (the sweep covers pos+t-1; a block past the table is clipped to the
    # last entry, which the sweep ends on too): they are blended into the
    # sweep's own landed copy of the page and read nothing back. An
    # inactive slot's rows were routed to the trash page, which no table
    # holds: that page rides as ONE more page behind the slot's sweep,
    # receives the rows the same way, and is masked out of the softmax.
    pos_b = pos_ref[b]
    blk = lambda tt: jnp.minimum((pos_b + tt) // page, nb - 1)
    n_sweep = sweep_pages(b, iq)
    if window is None:
        lo = 0
    else:
        lo = sweep_first(b, iq, n_sweep)
        n_sweep = n_sweep - lo  # pages of the run; run page i is block lo + i
    n_pass = passes(n_sweep)  # the sweep's passes; the last may be part dead
    if fused:
        live = wpages_ref[b, 0] == tables_ref[b, blk(0)]
        n = n_pass + jnp.where(live, 0, 1)
        # the block a row lands in, as an index of this step's page run;
        # the trash page is page 0 of a pass of its own behind the sweep
        run_of = (lambda blk_: blk_) if window is None else (lambda blk_: blk_ - lo)
        trash = n_sweep if pp == 1 else n_pass * pp
        target = lambda tt: jnp.where(live, run_of(blk(tt)), trash)
        # Mosaic cannot DMA a dynamically-offset single sublane row, so a
        # row is blended in VMEM: an f32 `where` (sub-32-bit sublane
        # broadcasts don't lower; bf16<->f32 round-trips exactly) over the
        # one sublane tile that holds the row, or over the whole page where
        # a page is not whole tiles of its dtype
        win = 32 // jnp.dtype(kbuf.dtype).itemsize
        win = page if page % win else win
        if pp == 1:
            # the rows [lo, hi) a chunk routed to the trash page lands in:
            # (pos + tt) % page, which wraps where the chunk crosses a page
            tr_lo = woffs_ref[b, 0]
            tr_hi = _Rows.where(tr_lo + t <= page, tr_lo + t, page)
            tr_lo = _Rows.where(tr_lo + t <= page, tr_lo, 0)
    else:
        n = n_pass

    # ---- the run of passes this step consumes, and the successor's: the
    # ring does not drain between grid steps. Run index v < n is this step's
    # pass v; n <= v < n + n2 is sweep pass v - n of the NEXT grid step
    # (next q tile, head block or slot: its pos and block table are scalar
    # prefetch, so they are known; never its trash page, which this step
    # may still be writing). Run pass v lands in ring slot
    # (base + v) % depth; `base` is carried across steps in SMEM.
    first = (b == 0) & (hblk == 0) & (iq == 0)

    @pl.when(first)
    def _():
        base_ref[0] = 0
        if pp > 1:
            # a dead page of a part-filled pass is never copied: what its
            # rows of the ring hold meets p = 0 in the value product, and
            # must be finite (afterwards: an older pass's rows)
            kbuf[...] = jnp.zeros_like(kbuf)
        if ends:
            # rows of a ring slot that no copy has filled meet p = 0 in the
            # value product: finite (afterwards: an older page's rows)
            vals = kbuf if latent else vbuf
            vals[...] = jnp.zeros_like(vals)

    base = base_ref[0]
    wrap_q = iq == nq - 1
    wrap_h = wrap_q & (hblk == nhb - 1)
    iq2 = jnp.where(wrap_q, 0, iq + 1)
    hblk2 = jnp.where(wrap_h, 0, jnp.where(wrap_q, hblk + 1, hblk))
    b2 = jnp.minimum(jnp.where(wrap_h, b + 1, b), nbatch - 1)
    last_step = wrap_h & (b == nbatch - 1)  # no successor to fetch for
    n2_sweep = sweep_pages(b2, iq2)
    if window is None:
        lo2 = 0
    else:
        lo2 = sweep_first(b2, iq2, n2_sweep)
        n2_sweep = n2_sweep - lo2
    n2 = jnp.where(last_step, 0, passes(n2_sweep))
    if ends:
        (r0, r1), (r0_2, r1_2) = sweep_rows(b, iq), sweep_rows(b2, iq2)

    def page_id(bb, i, own):
        # defensive clamp like _paged_cache_update: a horizon past the
        # allocated table reads the last entry (its rows are masked anyway)
        blk_i = i if window is None else i + jnp.where(own, lo, lo2)
        pg = tables_ref[bb, jnp.minimum(blk_i, nb - 1)]
        if fused:  # only this step's own run reaches past its sweep
            pg = jnp.where(own & (i >= n_sweep), wpages_ref[b, 0], pg)
        return pg

    # a latent row is key AND value: one pool, one copy a page
    pairs = (((kpool_ref, kbuf),) if latent
             else ((kpool_ref, kbuf), (vpool_ref, vbuf)))

    def copies(pg, hh, slot, k=0, back=False):
        heads = pl.ds(hh * hb, hb)  # [hb, page, lanes]: contiguous in HBM
        # where page k of a pass lands, and the semaphores of its copies
        land = lambda buf: buf.at[slot] if pp == 1 else buf.at[
            slot, :, pl.ds(pl.multiple_of(k * page, page), page), :]
        if back:
            return [pltpu.make_async_copy(land(buf), pool.at[pg, heads],
                                          write_sems.at[k * 2 + j])
                    for j, (pool, buf) in enumerate(pairs)]
        sem = slot if pp == 1 else slot * pp + k
        return [pltpu.make_async_copy(pool.at[pg, heads], land(buf),
                                      copy_sems.at[sem, j])
                for j, (pool, buf) in enumerate(pairs)]

    def row_block(pg, hh, slot, r, rows, back=False):
        # rows r .. r + rows of a page's head block: `hb` runs, one copy
        heads = pl.ds(hh * hb, hb)
        at = pl.ds(pl.multiple_of(r, rows), rows)
        if back:
            return [pltpu.make_async_copy(buf.at[slot, :, at, :],
                                          pool.at[pg, heads, at, :],
                                          write_sems.at[j])
                    for j, (pool, buf) in enumerate(pairs)]
        return [pltpu.make_async_copy(pool.at[pg, heads, at, :],
                                      buf.at[slot, :, at, :],
                                      copy_sems.at[slot, j])
                for j, (pool, buf) in enumerate(pairs)]

    def end_units(i, mine, pg, hh, slot, do):
        """do(copy) for the copies that bring run page i of this step's walk
        (`mine`) or of the successor's into its ring slot: ONE copy of the
        page, or, where the page is an END of the walk (its first page
        under a window, its last, the trash page behind it), a copy a unit
        of `sub` rows from the first live unit to the last. A page between
        the ends pays one compare and the branch; the units are a loop: the
        traced size is warm-up time. A wait mirrors its start."""
        pick = (lambda a, b: a) if mine is True else (
            lambda a, b: _Rows.where(mine, a, b))
        last = pick(n_sweep, n2_sweep) - 1
        at_end = i >= last if window is None else (i >= last) | (i == 0)

        def whole():
            for cp in copies(pg, hh, slot):
                do(cp)

        def units():
            lo_r = 0 if window is None else _Rows.where(
                i == 0, pick(r0, r0_2), 0)
            hi_r = _Rows.where(i == last, pick(r1, r1_2), page)
            if fused:  # (the trash page: the units its rows land in)
                lo_r = _Rows.where(i > last, tr_lo, lo_r)
                hi_r = _Rows.where(i > last, tr_hi, hi_r)

            def unit(u, _):
                for cp in row_block(pg, hh, slot, u * sub, sub):
                    do(cp)
                return 0

            jax.lax.fori_loop(_Rows.floor_divide(lo_r, sub),
                              _Rows.floor_divide(hi_r - 1, sub) + 1, unit, 0)

        jax.lax.cond(at_end, units, whole)

    def each_page(i, pages, fn, first_page=lambda: 0):
        """fn(k, page index) for the pages of pass i of a sweep of `pages()`
        pages, from `first_page()` on: page 0 of a pass always is (a sweep's
        pass holds a page; the trash pass is its page 0), a later one where
        the sweep reaches it: a dead page costs no copy. A loop, not an
        unrolled run of conditionals: the traced size is warm-up time and
        must not grow with `pp`. (The bounds are thunks: a page a pass has
        the one page and traces neither.)"""
        if pp == 1:
            return fn(0, i)

        def one(k, _):
            fn(k, i * pp + k)
            return 0

        jax.lax.fori_loop(first_page(), jnp.clip(pages() - i * pp, 1, pp),
                          one, 0)

    def start(v):
        mine = v < n

        @pl.when(v < n + n2)
        def _():
            bb, i = jnp.where(mine, b, b2), jnp.where(mine, v, v - n)

            def go(k, ix):
                pg = page_id(bb, ix, mine)
                if ends:
                    return end_units(ix, mine, pg, jnp.where(mine, hblk, hblk2),
                                     jax.lax.rem(base + v, depth),
                                     lambda cp: cp.start())
                for cp in copies(pg, jnp.where(mine, hblk, hblk2),
                                 jax.lax.rem(base + v, depth), k):
                    cp.start()

            each_page(i, lambda: jnp.where(mine, n_sweep, n2_sweep), go)

    def prologue(v, _):  # what no predecessor started for this step
        @pl.when(first | (v >= n_pass))
        def _():
            start(v)
        return 0

    jax.lax.fori_loop(0, depth - 1, prologue, 0)

    mxu = lambda x: x if x.dtype == mxu_dtype else x.astype(mxu_dtype)
    q = mxu(q_ref[...])  # [hb, tq, lanes]
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    if fused:
        # f32 copies of the new rows: the blend below takes one row at a
        # dynamic sublane offset, which Mosaic serves for 32-bit rows only
        newk32[...] = newk_ref[...].astype(jnp.float32)
        if not latent:
            newv32[...] = newv_ref[...].astype(jnp.float32)
    # causal mask against absolute cache positions: query row r of tile iq
    # holds token offset (iq*tq + r) // group (t-major GQA fold)
    qpos = pos_b + (iq * tq + jax.lax.broadcasted_iota(
        jnp.int32, (tq, span), 0)) // group
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, span), 1)

    def body(i, _):
        slot = jax.lax.rem(base + i, depth)
        start(i + depth - 1)  # into the slot pass i-1 just left

        if pp == 1:
            here = page_id(b, i, True)
        pid = lambda ix: here if pp == 1 else page_id(b, ix, True)

        def landed(k, ix):
            if ends:
                return end_units(ix, True, pid(ix), hblk, slot,
                                 lambda cp: cp.wait())
            for cp in copies(pid(ix), hblk, slot, k):
                cp.wait()

        each_page(i, lambda: n_sweep, landed)

        if fused:
            # rows are blended in order, so a duplicate (page, offset)
            # target — a clipped table, or trash collisions when
            # t > page_size — resolves last-row-wins
            first_target = target(0)
            if pp == 1:
                wrote = i >= first_target
                # the rows of this page that received rows, first to last:
                # the chunk's own (one row: its offset), those of a chunk
                # clipped at the table's end (they wrap: the page), the
                # trash page's
                if t == 1:
                    w_lo = woffs_ref[b, 0]
                else:
                    at = pos_b - (lo + i) * page  # the chunk's first row
                    clipped = (lo + i == nb - 1) & (pos_b + t > nb * page)
                    w_lo = _Rows.where(live, _Rows.where(
                        clipped, 0, _Rows.maximum(at, 0)), tr_lo)
                    w_hi = _Rows.where(live, _Rows.where(
                        clipped, page - 1,
                        _Rows.minimum(at + t - 1, page - 1)), tr_hi - 1)
            else:  # the pass holds a page at or behind the first target
                wrote = i >= first_target // pp

            def each_back(do):
                if pp == 1:
                    # the TILES of the page that received rows: `hb` runs
                    # of `win` rows each, not the head block
                    def tile(w, _):
                        for wr in row_block(here, hblk, slot, w * win, win,
                                            back=True):
                            do(wr)
                        return 0

                    tile_of = lambda r: _Rows.floor_divide(r, win)
                    if t == 1:
                        return tile(tile_of(w_lo), 0)
                    return jax.lax.fori_loop(tile_of(w_lo), tile_of(w_hi) + 1,
                                             tile, 0)

                # ONE write a page that received rows: every page of the
                # run from the first target on (the sweep ends on the last)
                def go(k, ix):
                    for wr in copies(pid(ix), hblk, slot, k, back=True):
                        do(wr)

                each_page(i, lambda: n_sweep, go,
                          lambda: jnp.clip(first_target - i * pp, 0, pp))

            def blend(tt, _):
                @pl.when((target(tt) if pp == 1 else target(tt) // pp) == i)
                def _():
                    off = woffs_ref[b, tt]
                    if pp > 1:  # the target page's rows of the pass
                        off = off + jax.lax.rem(target(tt), pp) * page
                    r0 = pl.multiple_of(off // win * win, win)
                    window = (slot, slice(None), pl.ds(r0, win), slice(None))
                    sel = jax.lax.broadcasted_iota(
                        jnp.int32, (win, lanes), 0) == off - r0
                    for rows, buf in (((newk32, kbuf),) if latent else
                                      ((newk32, kbuf), (newv32, vbuf))):
                        buf[window] = jnp.where(
                            sel[None], rows[:, pl.ds(tt, 1), :],
                            buf[window].astype(jnp.float32)).astype(buf.dtype)
                return 0

            @pl.when(wrote)
            def _():
                jax.lax.fori_loop(0, t, blend, 0)
                # under this pass's dots
                each_back(lambda wr: wr.start())

        k = kbuf[slot]  # [hb, span, lanes]
        v = k if latent else vbuf[slot]  # the landed page read ONCE
        # q and K enter the MXU as the bfloat16 they are stored as where
        # both are (a product of two bfloat16 values is exact in float32);
        # a float32 q or pool keeps float32 operands
        s = jax.lax.dot_general(q, mxu(k), (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32)
        s = s * scale  # [hb, tq, span]
        if pp > 1:
            # the sweep's live pages alone: a part-filled pass's dead pages
            # and the trash pass lie at or past n_sweep * page
            key = i * span + col
            mask = (key <= qpos) & (key < n_sweep * page)
        elif window is None:
            mask = i * page + col <= qpos
        else:
            key = (lo + i) * page + col
            mask = (key <= qpos) & (key > qpos - window)
        if fused and pp == 1:  # the trash page behind an inactive slot's sweep
            mask = mask & (i < n_sweep)
        mask = mask[None]
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, :, :1]
        l_prev = l_ref[...][:, :, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_cur = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

        if fused:
            @pl.when(wrote)  # before the ring hands this slot out again
            def _():
                each_back(lambda wr: wr.wait())
        return 0

    jax.lax.fori_loop(0, n, body, 0)
    l = l_ref[...][:, :, :1]
    out_ref[...] = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
    base_ref[0] = jax.lax.rem(base + n, depth)


def _paged_call(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                new_v, *, group: int, interpret: bool, rows_live: int,
                fused: bool, scale: float,
                vmem_budget: int = _VMEM_BUDGET_BYTES,
                end_copy: tuple[int, int] | None = None,
                window: int | None = None, latent: bool = False):
    """qf[B, Hkv, rows_pad, hd] x pool[N, Hkv, page, hd] ->
    (out f32 [B, Hkv, rows_pad, hd], k_pool, v_pool).

    The pools ride in HBM (ANY memory space) and alias their outputs, so the
    fused scatter is an in-place update at the XLA level; the kernel DMA-
    walks them through the prefetched block tables. N is one layer's P
    pages, or all L*P of the layer-merged stack with ``tables``/``wpages``
    already offset to the layer (the kernel cannot tell, and need not). The
    name and the 4-D pool in the result are what the benchmark's trace
    reader finds this call by (benchmark/costs/paged_attention.py).

    ``latent``: the k pool's row is key and value at once (one latent a
    token, Hkv = 1, every query head in the one head block's q rows): the
    sweep lands each page once and uses it for the scores and the mix; the
    v pool is a placeholder nothing reads or writes, and the output's lanes
    are the whole row's (the caller keeps the latent's)."""
    b, hkv, rows, hd = qf.shape
    npool, _, page, _ = k_pool.shape
    nb = tables.shape[1]
    t = new_k.shape[2]
    tq = _q_tile(rows)
    assert not fused or _fuses(t, rows), (t, group, rows)
    hb, depth, pp, _ = _plan(hkv, page, hd, k_pool.dtype.itemsize, tq, t,
                             vmem_budget, latent)
    sub = _row_tiles(page, k_pool.dtype.itemsize,
                     hb * page * hd * k_pool.dtype.itemsize, end_copy)[1]
    grid = (b, hkv // hb, rows // tq)
    bf16 = jnp.dtype(jnp.bfloat16)
    mxu_dtype = bf16 if qf.dtype == bf16 == k_pool.dtype else jnp.dtype(
        jnp.float32)
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    by_q = lambda b, h, iq, *_: (b, h, iq, 0)
    by_head = lambda b, h, iq, *_: (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # pos[B], tables[B, nb], wpages/woffs[B, t]
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, hb, tq, hd), by_q),
            pl.BlockSpec((None, hb, t, hd), by_head),
            pl.BlockSpec((None, hb, t, hd), by_head),
            any_spec,  # k pool (HBM)
            any_spec,  # v pool (HBM)
        ],
        out_specs=[
            pl.BlockSpec((None, hb, tq, hd), by_q),
            any_spec,
            any_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((depth, hb, pp * page, hd), k_pool.dtype),  # k landing ring
            pltpu.VMEM((1, 1, 8, _LANES) if latent else (depth, hb, page, hd),
                       v_pool.dtype),
            pltpu.VMEM((hb, t, hd), jnp.float32),  # the new k rows, widened
            pltpu.VMEM((hb, t, hd), jnp.float32),
            pltpu.VMEM((hb, tq, hd), jnp.float32),  # acc
            pltpu.VMEM((hb, tq, _LANES), jnp.float32),  # m
            pltpu.VMEM((hb, tq, _LANES), jnp.float32),  # l
            pltpu.SMEM((1,), jnp.int32),  # ring slot of this step's page 0
            pltpu.SemaphoreType.DMA((depth * pp, 2)),  # (landed page, k/v) copies
            pltpu.SemaphoreType.DMA((2 * pp,)),  # k/v scatter write-backs a page
        ],
    )
    out, k_pool, v_pool = pl.pallas_call(
        functools.partial(_kernel, scale=scale, page=page,
                          group=group, t=t, tq=tq, rows_live=rows_live,
                          nb=nb, fused=fused, hb=hb, depth=depth,
                          mxu_dtype=mxu_dtype, window=window, latent=latent,
                          pp=pp, sub=sub),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
        ],
        # after the 4 scalar-prefetch args: qf=4, newk=5, newv=6, kpool=7,
        # vpool=8; the pools alias outputs 1 and 2 (in-place update)
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            # every axis carries the ring into the next step: one
            # TensorCore (v5e) walks them in order, nothing is lost
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hkv * rows * nb * page * hd,
            bytes_accessed=(b * hkv * rows * hd * 2) * qf.dtype.itemsize
            + 2 * b * hkv * nb * page * hd * k_pool.dtype.itemsize,
            transcendentals=b * hkv * rows * nb * page,
        ),
        interpret=interpret,
    )(pos, tables, wpages, woffs, qf, new_k, new_v, k_pool, v_pool)
    return out, k_pool, v_pool


_STATIC = ("group", "interpret", "rows_live", "fused", "scale", "vmem_budget",
           "end_copy")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_folded(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                  new_v, *, group: int, interpret: bool, rows_live: int,
                  fused: bool, scale: float,
                  vmem_budget: int = _VMEM_BUDGET_BYTES,
                  end_copy: tuple[int, int] | None = None):
    """`_paged_call` for a layer whose queries see the whole context, under
    the name the benchmark's trace reader finds it by (benchmark/costs/
    paged_attention.py)."""
    return _paged_call(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                       new_v, group=group, interpret=interpret,
                       rows_live=rows_live, fused=fused, scale=scale,
                       vmem_budget=vmem_budget, end_copy=end_copy)


@functools.partial(jax.jit, static_argnames=_STATIC + ("window",))
def _paged_window(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                  new_v, *, window: int, **kw):
    """The same sweep for a layer whose queries see `window` rows: a call of
    its own name on the device plane, so a trace tells the clipped walk of
    the window pool from the global one (benchmark/costs/
    paged_attention_window.py)."""
    return _paged_call(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                       new_v, window=window, **kw)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _paged_latent(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                  new_v, **kw):
    """The same sweep over a pool of LATENT rows (one a token, key and value
    of every head), a call of its own name on the device plane
    (benchmark/costs/paged_attention_latent.py)."""
    return _paged_call(qf, k_pool, v_pool, pos, tables, wpages, woffs, new_k,
                       new_v, latent=True, **kw)


def _scatter_rows_by_page(pool, new, nb, pos, wpages, woffs, trash):
    """``pool.at[wpages, :, woffs, :].set(rows)`` for a chunk of T
    consecutive rows per slot, done page by page so that the pool itself is
    only ever updated by whole pages at its leading dim.

    The direct form scatters at dims 0 and 2 of [N, Hkv, page, lanes], and
    XLA:TPU serves it by re-laying-out the WHOLE operand before and after
    (three copies of whatever it is handed: a layer's 70 MB slice when the
    pool was sliced per layer, the 2.1 GB stack once it is not). A chunk
    touches at most ceil(T / page) + 1 pages a slot, so: gather those
    pages (a few MB), scatter the rows into that small buffer with the same
    (page, offset) addressing, and put each page back with a
    dynamic_update_slice at the leading dim, which is in place. Pages of
    the buffer that received no row (the chunk ended before them) go back
    to the trash page, never over a page another entry writes.

    pool [N, Hkv, page, lanes]; new [B, Hkv, T, lanes]; wpages/woffs i32
    [B, T] from ``paged_write_targets`` (same block clipping, inactive slots
    already routed to ``trash``)."""
    b, _, t, _ = new.shape
    page = pool.shape[2]
    n_local = min(-(-t // page) + 1, nb)  # pages one slot's chunk can touch
    rows = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    blk = jnp.clip(rows // page, 0, nb - 1)  # as paged_write_targets clips
    local = blk - blk[:, :1]  # [B, T] which of the slot's pages a row hits
    ahead = blk[:, :1] + jnp.arange(n_local, dtype=jnp.int32)[None]
    # page id of local page j = the target of the first row that hits it
    first_row = jnp.clip(ahead * page - pos[:, None], 0, t - 1)
    ids = jnp.where(ahead <= blk[:, -1:],
                    jnp.take_along_axis(wpages, first_row, axis=1),
                    trash).reshape(-1)
    buf = jnp.take(pool, ids, axis=0, mode="clip")  # [B*n_local, Hkv, page, lanes]
    slot_base = jnp.arange(b, dtype=jnp.int32)[:, None] * n_local
    buf = buf.at[slot_base + local, :, woffs, :].set(
        new.transpose(0, 2, 1, 3).astype(pool.dtype))

    def put(i, pool):
        pg = jax.lax.dynamic_slice_in_dim(buf, i, 1, axis=0)
        return jax.lax.dynamic_update_slice(pool, pg, (ids[i], 0, 0, 0))

    return jax.lax.fori_loop(0, b * n_local, put, pool)


def paged_decode_attention(
    q: jax.Array,  # [B, T, Hq, hd]
    k_pool: jax.Array,  # [P, Hkv, page, hd or pool_lanes(hd)] (one layer),
    # or with `layer` the stored stack [L, P, Hkv, page, lanes]
    v_pool: jax.Array,
    tables: jax.Array,  # i32 [B, max_blocks]
    pos_base: jax.Array,  # i32 scalar or [B] per-row positions
    new_k: jax.Array | None = None,  # [B, Hkv, T, hd] rows to scatter first
    new_v: jax.Array | None = None,
    active: jax.Array | None = None,  # [B] bool: inactive rows -> trash page
    *,
    layer: jax.Array | None = None,  # i32 scalar: the pools are the stack
    interpret: bool = False,
    window: int | None = None,  # rows a query sees, itself included; None
    # = the whole context (and today's program, to the instruction)
    latent: int = 0,  # > 0: the k pool holds ONE row a token for all heads
    # (Hkv = 1), key and value at once; the output is the mix of the rows'
    # first `latent` dims, [B, T, Hq, latent]; v_pool is a placeholder
    scale: float | None = None,  # score scale; None = 1/sqrt(hd)
) -> jax.Array | tuple[jax.Array, jax.Array, jax.Array]:
    """Block-table paged attention over the HBM page pool, any page size.

    Without ``new_k``/``new_v`` this is a drop-in for
    ``ops.layers.paged_gqa_attention`` (returns the [B, T, Hq, hd] output
    only). With them, the call is the FUSED decode step: the new rows are
    scatter-written at their block-table positions (``active=False`` rows
    to the trash page) and the attention sweep reads them — returns
    ``(out, k_pool, v_pool)`` with the pools updated in place
    (input/output aliased). Chunks longer than ``FUSED_SCATTER_MAX_T``
    scatter via XLA before the launch instead (identical result; prefill
    chunks should not unroll a blend per row into the kernel).

    With ``layer`` the pools are the whole layer-stacked arrays as
    ``PagedKVCache`` stores them and the call reads and writes that layer's
    pages IN the stack: the stack is viewed as one pool of L*P pages (a
    merge of the two leading dims — a bitcast, no bytes move) and the
    layer's first page, ``layer * P``, is added to every page index that
    rides in as scalar prefetch (block tables, write targets; the layer's
    trash page is its own last page). The kernel body, the rows written
    and their order are the per-layer call's; what goes is the layer's
    slice being cut out of the stack before the call and put back after
    it. Returns the pools at the stacked shape."""
    b, t, hq, hd = q.shape
    stack_shape = k_pool.shape
    if layer is not None:
        k_pool = k_pool.reshape(-1, *stack_shape[2:])
        if not latent:
            v_pool = v_pool.reshape(-1, *stack_shape[2:])
    n_pool, hkv, page, lanes = stack_shape[-4:]
    group = hq // hkv
    if lanes != hd:
        # lane-padded pool (pool_lanes): zero-pad the head dim of every row
        # that meets it — scores and outputs are unchanged (exact zeros)
        pad_hd = lambda x: None if x is None else jnp.pad(
            x, ((0, 0),) * 3 + ((0, lanes - hd),))
        q, new_k, new_v = pad_hd(q), pad_hd(new_k), pad_hd(new_v)
    qf = (
        q.reshape(b, t, hkv, group, lanes)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, hkv, t * group, lanes)
    )
    rows = t * group
    pad = (-rows) % 8
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos_base, jnp.int32)),
                           (b,))
    tables = jnp.asarray(tables, jnp.int32)
    # page indices of the layer-merged view: the layer's own first page on
    # top of what the per-layer call computes
    first_page = 0 if layer is None else jnp.asarray(layer, jnp.int32) * n_pool

    write = new_k is not None
    if write:
        # the ONE definition of paged write addressing (shared with
        # _paged_cache_update — the fused scatter is write-for-write
        # identical to the separate dispatch it replaces)
        from dllama_tpu.ops.layers import paged_write_targets

        wpages, woffs = paged_write_targets(tables, pos, t, page, n_pool,
                                            active)
        wpages = wpages + first_page
        if not _fuses(t, qf.shape[2]):
            # prefill-sized chunk (or one whose folded rows take several q
            # tiles, so several sweeps): scattered by XLA, then a
            # read-only sweep
            scatter = lambda pool, new: _scatter_rows_by_page(
                pool, new, tables.shape[1], pos, wpages, woffs,
                first_page + n_pool - 1)
            k_pool = scatter(k_pool, new_k)
            if not latent:
                v_pool = scatter(v_pool, new_v)
            write = False
    if not write:
        # dummy single-row write of what the trash page already gets —
        # the kernel skips the scatter entirely (fused=False)
        wpages = jnp.zeros((b, 1), jnp.int32)
        woffs = jnp.zeros((b, 1), jnp.int32)
        nk = jnp.zeros((b, hkv, 1, lanes), k_pool.dtype)
        nv = jnp.zeros((b, hkv, 1, lanes), v_pool.dtype)
    else:
        nk = new_k.astype(k_pool.dtype)
        nv = jnp.zeros_like(nk) if latent else new_v.astype(v_pool.dtype)

    call = (_paged_latent if latent else _paged_folded if window is None
            else functools.partial(_paged_window, window=int(window)))
    out, k_pool, v_pool = call(
        qf, k_pool, v_pool, pos, tables + first_page, wpages, woffs, nk, nv,
        group=group, interpret=interpret, rows_live=rows, fused=write,
        scale=1.0 / math.sqrt(hd) if scale is None else float(scale),
        vmem_budget=_VMEM_BUDGET_BYTES,
        end_copy=(_END_COPY_ROWS, _END_MIN_PAGE_BYTES))
    vd = latent or hd
    out = (
        out[:, :, :rows, :vd].reshape(b, hkv, t, group, vd)
        .transpose(0, 2, 1, 3, 4)
        .reshape(b, t, hq, vd)
        .astype(q.dtype)
    )
    if new_k is None:
        return out
    return (out, k_pool.reshape(stack_shape),
            v_pool if latent else v_pool.reshape(stack_shape))
